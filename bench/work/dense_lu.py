"""Dense LU without pivoting of an n x n operand: 2n³/3 FLOPs; the operand
read once and the packed factors written once."""


def count(n: int, bw: int, k: int, itemsize: int) -> tuple[float, float]:
    return 2.0 * n**3 / 3.0, 2.0 * itemsize * n * n
