"""Forward and backward substitution on packed band factors of half-bandwidth
bw for k right-hand sides: row i meets min(bw, i) entries of L and
min(bw, n-1-i) of U, each a multiply-add, so 4·n·bw·k FLOPs less the end
triangles; the factors read once, the right-hand sides read and the
solutions written once."""


def count(n: int, bw: int, k: int, itemsize: int) -> tuple[float, float]:
    k = max(k, 1)
    s = min(bw, n)
    per_sweep = (n - s) * bw + s * (s - 1) / 2.0  # Σ_i min(bw, i)
    return 4.0 * per_sweep * k, itemsize * (n * (2 * bw + 1) + 2.0 * n * k)
