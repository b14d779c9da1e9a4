"""Band LU without pivoting of an n x n operand of half-bandwidth bw, held
row-aligned as (n, 2bw+1): pivot p meets r = min(bw, n-1-p) rows below it
and r columns right of it, so r divisions and r² multiply-subtracts, in all
Σ (r + 2r²) ≈ 2n·bw² FLOPs; the band read once and the packed factors
written once."""


def count(n: int, bw: int, k: int, itemsize: int) -> tuple[float, float]:
    s = min(bw, n)  # the last s pivots meet s-1, ..., 0 rows
    full = n - s  # pivots that meet bw rows
    sum_r = full * bw + s * (s - 1) / 2.0
    sum_r2 = full * bw * bw + (s - 1) * s * (2 * s - 1) / 6.0
    return sum_r + 2.0 * sum_r2, 2.0 * itemsize * n * (2 * bw + 1)
