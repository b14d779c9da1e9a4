"""Blocked (rank-k) EbV LU — the TPU-adapted fast path.

The paper's rank-1 updates have O(1) FLOP/byte arithmetic intensity: fine for
a 2008 GPU's scalar ALUs, hopeless against an MXU.  The adaptation keeps the
paper's two invariants while blocking for the MXU:

* **bi-vectorization** → the *fused panel step*: the pivot-scaled L-column
  block and the trsm-produced U-row block of the same step are computed
  together and consumed by one rank-``b`` GEMM update (one pass over the
  trailing matrix instead of the paper's two vector passes per step).
* **equalization** → the tile/owner schedules exported here
  (:func:`ebv_folded_owners`) pair wide early panels with narrow late panels
  so per-executor work is equal — the r ↔ n-2-r pairing at block granularity.

Shapes shrink statically (Python loop under ``jit``), so no masking waste.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .solve import unit_lower_solve_packed

__all__ = [
    "panel_factor",
    "blocked_lu",
    "fused_blocked_lu",
    "fused_lu_steps",
    "fused_block_size",
    "FUSED_VMEM_MAX_N",
    "sub_block_width",
    "strip_trsm",
    "strip_utrsm",
    "row_at",
    "col_at",
    "set_rows",
    "dot_f32",
    "ValueRef",
    "factor_diag_strip",
    "solve_below_strip",
    "pad_identity_tail",
    "ebv_folded_owners",
    "cyclic_owners",
]


def sub_block_width(block: int) -> int:
    """Strip width of the two-level (axpy-in-strip, GEMM-retire) panel/trsm
    scheme.  Shared by :func:`fused_blocked_lu` and the Pallas megakernel
    (:func:`repro.kernels.ebv_lu.lu_fused`) so both trace identical op
    shapes — the basis of their bitwise equality."""
    return next((c for c in (32, 16, 8) if block % c == 0), block)


def pad_identity_tail(a: jax.Array, n_to: int) -> jax.Array:
    """Embed square ``a`` in an (n_to, n_to) array with an identity tail —
    inert under no-pivot elimination and substitution (unit pivots, zero
    coupling).  Shared by the fused LU drivers and the tiled solve."""
    n = a.shape[-1]
    if n_to == n:
        return a
    pad_ix = jnp.arange(n, n_to)
    one = jnp.ones((), a.dtype)
    return jnp.zeros((n_to, n_to), a.dtype).at[:n, :n].set(a).at[pad_ix, pad_ix].set(one)


# Mosaic (the TPU Pallas compiler) lowers neither value-level
# ``dynamic_slice`` nor ``dynamic_update_slice``, so the step bodies below
# read a traced row/column of a value with an iota mask and a reduction and
# write one back with a masked select.  Exactly one term of each reduction
# is non-zero, so the result equals the old slice bit for bit.
def row_at(x: jax.Array, k) -> jax.Array:
    """Row ``k`` (traced or static) of a 2-D value, as ``(1, w)``."""
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.sum(jnp.where(rows == k, x, 0), axis=0, keepdims=True)


def col_at(x: jax.Array, k) -> jax.Array:
    """Column ``k`` (traced or static) of a 2-D value, as ``(m, 1)``."""
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(cols == k, x, 0), axis=1, keepdims=True)


def set_rows(x: jax.Array, piece: jax.Array, r0: int) -> jax.Array:
    """``x`` with rows ``r0 : r0 + piece.shape[0]`` replaced (static offset;
    a concatenation, which Mosaic lowers where ``dynamic_update_slice``
    does not)."""
    parts = [x[:r0], piece, x[r0 + piece.shape[0]:]]
    return jnp.concatenate([p for p in parts if p.shape[0]], axis=0)


class ValueRef:
    """Ref-style view of a (possibly traced) array for the pure-jnp mirrors.

    The shared step bodies are written against Pallas refs (``ref[i:j, k:l]``
    reads and ``ref[...] = v`` writes, all offsets static), which is what
    Mosaic lowers.  The mirrors hand the same body this wrapper instead:
    reads slice ``value``, writes rebind it through ``.at[].set``.  Unlike
    ``pl.run_state`` it composes with ``jax.vmap`` (the batched mirrors)."""

    def __init__(self, value: jax.Array):
        self.value = value

    @staticmethod
    def _index(idx):
        def one(i):
            if isinstance(i, pl.Slice):  # pl.ds(start, size) with a static start
                return slice(int(i.start), int(i.start) + i.size)
            return i

        return tuple(one(i) for i in idx) if isinstance(idx, tuple) else one(idx)

    def __getitem__(self, idx):
        return self.value[self._index(idx)]

    def __setitem__(self, idx, v):
        self.value = self.value.at[self._index(idx)].set(v)


def dot_f32(a: jax.Array, b: jax.Array) -> jax.Array:
    """f32-accurate GEMM for the exact tier: on TPU a DEFAULT-precision f32
    dot runs in bf16 passes, so every solver dot states HIGHEST."""
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def strip_trsm(ldiag: jax.Array, rhs: jax.Array) -> jax.Array:
    """Unit-lower solve of a ``(C2, w)`` strip against the ``(C2, C2)``
    diagonal block, as a short sequential masked-axpy recurrence on an array
    carry.  Shared verbatim by the megakernel and its mirror — both sides
    trace this exact jaxpr, so their bitwise equality holds by construction."""
    c2 = ldiag.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (c2, 1), 0)

    def body(k, u):
        lk = jnp.where(rows > k, col_at(ldiag, k), 0.0)
        return u - lk * row_at(u, k)

    return jax.lax.fori_loop(0, c2 - 1, body, rhs)


def strip_utrsm(udiag: jax.Array, rhs: jax.Array) -> jax.Array:
    """Upper-triangular solve (diagonal division included) of a ``(C2, w)``
    strip against the ``(C2, C2)`` diagonal block, as a short backward
    masked-axpy recurrence on an array carry — the backward-sweep twin of
    :func:`strip_trsm`.  Shared verbatim by the banded solve kernel and its
    pure-jnp mirror, so their bitwise equality holds by construction."""
    c2 = udiag.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (c2, 1), 0)

    def body(kk, x):
        k = c2 - 1 - kk
        ucol = col_at(udiag, k)
        pivot = row_at(ucol, k)
        xk = row_at(x, k) / pivot
        x = jnp.where(rows == k, xk, x)
        uk = jnp.where(rows < k, ucol, 0.0)
        return x - uk * xk

    return jax.lax.fori_loop(0, c2, body, rhs)


def factor_diag_strip(dblk: jax.Array, j: int) -> jax.Array:
    """Bi-vectorized (rank-1) factorization of the ``(B, C2)`` diagonal-block
    strip whose pivot rows start at local row ``j``; rows above ``j+k`` are
    masked no-ops (they hold final U values).  Shared kernel/mirror code."""
    b, c2 = dblk.shape
    rows_b = jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0)
    cols_c2 = jax.lax.broadcasted_iota(jnp.int32, (1, c2), 1)

    def dstep(k, d):
        prow = row_at(d, j + k)
        piv = col_at(prow, k)
        urow = jnp.where(cols_c2 > k, prow, 0.0)
        colb = col_at(d, k)
        lb = jnp.where(rows_b > j + k, colb / piv, 0.0)
        d = d - lb * urow
        return jnp.where(cols_c2 == k, jnp.where(rows_b > j + k, lb, colb), d)

    return jax.lax.fori_loop(0, c2, dstep, dblk)


def solve_below_strip(diag: jax.Array, strip: jax.Array, j: int) -> jax.Array:
    """Multipliers of a below-diagonal ``(B, C2)`` strip: right-solve against
    the factored diagonal strip.  Operand values equal the rank-1 sequence's
    (pivot row ``j+k`` of ``diag`` is final by its iteration), so this is
    bitwise-identical to eliminating column-by-column.  Shared kernel/mirror
    code."""
    c2 = strip.shape[1]
    cols_c2 = jax.lax.broadcasted_iota(jnp.int32, (1, c2), 1)

    def bstep(k, st):
        prow = row_at(diag, j + k)
        piv = col_at(prow, k)
        urow = jnp.where(cols_c2 > k, prow, 0.0)
        lb = col_at(st, k) / piv  # every row is below the pivot here
        st = st - lb * urow
        return jnp.where(cols_c2 == k, lb, st)

    return jax.lax.fori_loop(0, c2, bstep, strip)


# Padded orders at or below this run the fused LU on one VMEM-resident block
# (static ref slices, no alignment constraint); above it the matrix streams
# from HBM in (·, B) column slabs, which Mosaic slices only at multiples of
# the 128-lane tile.  Shared by the megakernel and the mirror.
FUSED_VMEM_MAX_N = 512
_LANE = 128


def fused_block_size(n: int, block: int, *, vmem_budget_bytes: int = 40 * 2**20) -> int:
    """Effective block size of the fused LU driver for an (n, n) matrix.

    Shared by the megakernel and its mirror (same reasons as
    :func:`sub_block_width`).  Adjustments over ``min(block, n)``:

    * **padding**: the fused driver pads n up to ``S·B``; for n just above a
      block multiple (n=257, block=256) that nearly doubles the matrix.  At
      the same step count ``S``, ``B = ceil(n/S)`` rounded up to a 32
      multiple (a 128 multiple once the padded order streams from HBM)
      gives minimal padding — pick whichever candidate pads less.
    * **lanes**: on the HBM-streaming path (padded order above
      ``FUSED_VMEM_MAX_N``) B stays a multiple of 128 so every column-slab
      DMA is lane-aligned on TPU.
    * **VMEM**: the kernel holds three (N, B) fp32 scratch slabs; B drops
      to the next lower 128 multiple until they fit the budget (n=16384 →
      B=128, 25 MB).
    """
    B = min(block, n)
    S = -(-n // B)
    per = -(-n // S)
    balanced = min(block, -(-per // 32) * 32)
    if -(-n // balanced) * balanced > FUSED_VMEM_MAX_N:
        balanced = min(max(block, _LANE), -(-per // _LANE) * _LANE)
    if balanced >= 32 and -(-n // balanced) * balanced < S * B:
        B = balanced
    if -(-n // B) * B > FUSED_VMEM_MAX_N and B % _LANE:
        B = -(-B // _LANE) * _LANE
    while B > _LANE and 3 * (-(-n // B) * B) * B * 4 > vmem_budget_bytes:
        B = max(_LANE, (B // 2) // _LANE * _LANE)
    return B


def panel_factor(panel: jax.Array) -> jax.Array:
    """Unblocked bi-vectorized LU of a tall ``(m, b)`` panel (pivots in the
    top ``b`` rows, no pivoting — paper contract)."""
    m, bw = panel.shape
    rows = jnp.arange(m)
    cols = jnp.arange(bw)

    def body(k, p):
        pivot = p[k, k]
        l_col = jnp.where(rows > k, p[:, k] / pivot, 0.0)
        u_row = jnp.where(cols > k, p[k, :], 0.0)
        p = p - l_col[:, None] * u_row[None, :]
        return p.at[:, k].set(jnp.where(rows > k, l_col, p[:, k]))

    return jax.lax.fori_loop(0, bw, body, panel)


def blocked_lu(a: jax.Array, *, block: int = 256) -> jax.Array:
    """Right-looking blocked EbV LU on a packed square array."""
    n = a.shape[-1]
    block = min(block, n)
    for k0 in range(0, n, block):
        b = min(block, n - k0)
        panel = panel_factor(a[k0:, k0 : k0 + b])
        a = a.at[k0:, k0 : k0 + b].set(panel)
        if k0 + b < n:
            l11 = panel[:b]  # packed: unit-lower + U11
            # fused bi-vector step: U-row block via trsm against the unit-lower
            # panel factor, immediately consumed by the rank-b update.
            u12 = unit_lower_solve_packed(l11, a[k0 : k0 + b, k0 + b :])
            a = a.at[k0 : k0 + b, k0 + b :].set(u12)
            l21 = panel[b:]
            a = a.at[k0 + b :, k0 + b :].add(-(l21 @ u12))
    return a


def fused_lu_steps(a, *, block: int, num_steps: int) -> None:
    """Body of the fused blocked LU on an already-padded ``(S·B, S·B)``
    *ref*, factored in place: two-level panel factorization +
    trailing-tile trsm/update per step, every slice static.  Shared verbatim
    by the pure-jnp mirror (:func:`fused_blocked_lu`, which runs it on a
    :class:`ValueRef`) and the small-n VMEM megakernel
    (:func:`repro.kernels.ebv_lu.lu_fused`) — both trace these exact ops,
    which is what makes their packed factors bitwise-identical."""
    B, S = block, num_steps
    C2 = sub_block_width(B)
    for s in range(S):
        base = s * B
        # ---- panel: two-level factorization of the column slab
        for j in range(0, B, C2):
            r0 = base + j
            w = B - j - C2

            # (1) bi-vectorized factorization of the diagonal-block strip
            diag = factor_diag_strip(a[base : base + B, r0 : r0 + C2], j)
            a[base : base + B, r0 : r0 + C2] = diag

            # (2) unit-lower trsm: U rows of the strip vs the remaining cols
            if w:
                u = strip_trsm(diag[j : j + C2, :], a[r0 : r0 + C2, r0 + C2 : base + B])
                a[r0 : r0 + C2, r0 + C2 : base + B] = u
                lpart = diag[j + C2 :, :]
                blk = a[r0 + C2 : base + B, r0 + C2 : base + B]
                a[r0 + C2 : base + B, r0 + C2 : base + B] = (
                    blk - dot_f32(lpart, u)
                ).astype(blk.dtype)

            # (3) row blocks below: right-solve multipliers + GEMM retirement
            for r in range(s + 1, S):
                off = r * B
                strip = solve_below_strip(diag, a[off : off + B, r0 : r0 + C2], j)
                a[off : off + B, r0 : r0 + C2] = strip
                if w:
                    blkr = a[off : off + B, r0 + C2 : base + B]
                    a[off : off + B, r0 + C2 : base + B] = (
                        blkr - dot_f32(strip, u)
                    ).astype(blkr.dtype)
        # ---- trailing tiles: two-level trsm + rank-B update per row block
        for t in range(s + 1, S):
            tb = t * B
            for j in range(0, B, C2):
                r0 = base + j
                strip = strip_trsm(a[r0 : r0 + C2, r0 : r0 + C2], a[r0 : r0 + C2, tb : tb + B])
                a[r0 : r0 + C2, tb : tb + B] = strip
                if B - j - C2:
                    lpart = a[r0 + C2 : base + B, r0 : r0 + C2]
                    tail = a[r0 + C2 : base + B, tb : tb + B]
                    a[r0 + C2 : base + B, tb : tb + B] = (
                        tail - dot_f32(lpart, strip)
                    ).astype(tail.dtype)
            y = a[base : base + B, tb : tb + B]
            for r in range(s + 1, S):
                off = r * B
                lblk = a[off : off + B, base : base + B]
                blk = a[off : off + B, tb : tb + B]
                a[off : off + B, tb : tb + B] = (blk - dot_f32(lblk, y)).astype(blk.dtype)


def fused_blocked_lu(a: jax.Array, *, block: int = 256) -> jax.Array:
    """Pure-jnp mirror of the single-dispatch Pallas megakernel
    (:func:`repro.kernels.ebv_lu.lu_fused`) — op-for-op identical shapes and
    ordering, so the two produce bitwise-identical packed LU factors.

    Structure per step ``s`` (matrix padded to ``S·B`` with an inert identity
    tail): two-level panel factorization (``C2``-wide strip rank-1 loop, strip
    trsm, rank-``C2`` GEMM retirement per (B, C2) row block), then per
    trailing block-column tile a two-level unit-lower trsm and the rank-``B``
    trailing GEMM per row block.  This is also the fast ``impl="xla"`` path:
    O(B/C2) passes over each slab instead of the O(B) passes of
    :func:`blocked_lu`."""
    n = a.shape[-1]
    B = fused_block_size(n, block)
    S = -(-n // B)
    N = S * B
    a = pad_identity_tail(a, N)
    ref = ValueRef(a)
    fused_lu_steps(ref, block=B, num_steps=S)
    a = ref.value
    return a[:n, :n] if N != n else a


def cyclic_owners(num_blocks: int, num_executors: int) -> list[int]:
    """Standard block-cyclic owner schedule (ScaLAPACK-style baseline)."""
    return [k % num_executors for k in range(num_blocks)]


def ebv_folded_owners(num_blocks: int, num_executors: int) -> list[int]:
    """EbV-folded owner schedule: panels ``k`` and ``nb-1-k`` (whose trailing
    work sums to a constant) go to the same executor — equalized cumulative
    panel work, the paper's pairing at block granularity."""
    return [min(k, num_blocks - 1 - k) % num_executors for k in range(num_blocks)]
