"""Host float64 residuals that decide ``correct``.

The number compared is taken over the answers checked in a run: the
largest relative residual ``‖Ax − b‖₂ / ‖b‖₂``.  Operands and answers are
widened to float64 on the host; nothing of the program is used.
"""
from __future__ import annotations

import numpy as np
from numpy.lib.stride_tricks import sliding_window_view


def residuals(a, b, x, *, bw: int = 0, rows: int = 2048) -> np.ndarray:
    """Per column, the relative residual of ``r = Ax - b``.  ``a`` (n, n),
    or with ``bw`` the row-aligned band (n, 2bw+1), ``a[i, t] = A[i, i -
    bw + t]``; ``b`` and ``x`` (n,) or (n, k).  Widened a block of rows at a
    time, so that a 1 GiB operand needs no 2 GiB copy."""
    if bw:
        return _band_residuals(a, b, x, bw, rows)
    a = np.asarray(a)
    b64 = np.asarray(b, np.float64).reshape(a.shape[0], -1)
    x64 = np.asarray(x, np.float64).reshape(b64.shape)
    r = np.empty_like(b64)
    for i in range(0, a.shape[0], rows):
        r[i : i + rows] = a[i : i + rows].astype(np.float64) @ x64 - b64[i : i + rows]
    return _relative(r, b64)


def _relative(r, b64) -> np.ndarray:
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.linalg.norm(r, axis=0) / np.linalg.norm(b64, axis=0)


def _row_blocks(a):
    """``(first row, rows on the host)`` of ``a``: one block per row shard of
    a sharded ``jax.Array`` (replicas read once), else the whole of it."""
    shards = getattr(a, "addressable_shards", None)
    if not shards or len(shards) == 1:
        yield 0, np.asarray(a)
        return
    seen = set()
    for s in sorted(shards, key=lambda s: s.index[0].start or 0):
        start = s.index[0].start or 0
        if start not in seen:
            seen.add(start)
            if s.data.shape[1:] != a.shape[1:]:
                raise ValueError(f"operand sharded across its columns: shard {s.data.shape} of {a.shape}")
            yield start, np.asarray(s.data)


def _band_residuals(a, b, x, bw: int, rows: int) -> np.ndarray:
    n = a.shape[0]
    b64 = np.asarray(b, np.float64).reshape(n, -1)
    x64 = np.asarray(x, np.float64).reshape(b64.shape)
    xpad = np.zeros((n + 2 * bw, x64.shape[1]))
    xpad[bw : bw + n] = x64  # row i of A meets xpad[i : i + 2bw + 1]
    r = np.empty_like(b64)
    for start, block in _row_blocks(a):
        for i in range(0, block.shape[0], rows):
            band = block[i : i + rows].astype(np.float64)
            g, m = start + i, band.shape[0]
            win = sliding_window_view(xpad[g : g + m + 2 * bw], 2 * bw + 1, axis=0)
            r[g : g + m] = np.einsum("mt,mkt->mk", band, win) - b64[g : g + m]
    return _relative(r, b64)


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
