"""Host float64 residuals that decide ``correct``.

The number compared is taken over the answers checked in a run: the
largest relative residual ``‖Ax − b‖₂ / ‖b‖₂``.  Operands and answers are
widened to float64 on the host; nothing of the program is used.
"""
from __future__ import annotations

import numpy as np


def residuals(a, b, x, rows: int = 2048) -> np.ndarray:
    """Per column, the relative residual of ``r = Ax - b``.  ``a`` (n, n);
    ``b`` and ``x`` (n,) or (n, k).  Widened a block of rows at a time, so
    that a 1 GiB operand needs no 2 GiB copy."""
    a = np.asarray(a)
    b64 = np.asarray(b, np.float64).reshape(a.shape[0], -1)
    x64 = np.asarray(x, np.float64).reshape(b64.shape)
    r = np.empty_like(b64)
    for i in range(0, a.shape[0], rows):
        r[i : i + rows] = a[i : i + rows].astype(np.float64) @ x64 - b64[i : i + rows]
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.linalg.norm(r, axis=0) / np.linalg.norm(b64, axis=0)


def judge(readings: dict[str, float], limits: dict[str, float]) -> tuple[bool, list[str]]:
    """``correct`` when every reading is finite and within its limit; the
    lines name each number beside its limit."""
    ok, lines = True, []
    for name, limit in limits.items():
        value = float(readings.get(name, float("nan")))
        good = bool(np.isfinite(value) and value <= limit)
        ok = ok and good
        lines.append(f"{name}={value!r} limit={limit!r} {'ok' if good else 'FAIL'}")
    return ok, lines
