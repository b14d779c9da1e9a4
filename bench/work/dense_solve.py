"""Forward and backward substitution on packed n x n factors for k
right-hand sides: 2n²k FLOPs; the factors read once, the right-hand sides
read and the solutions written once."""


def count(n: int, bw: int, k: int, itemsize: int) -> tuple[float, float]:
    k = max(k, 1)
    return 2.0 * n * n * k, itemsize * (n * n + 2.0 * n * k)
