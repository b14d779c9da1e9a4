"""SPIKE split banded solve: the band partitioned into per-device blocks.

Following the splitting approach of Li, Serban & Negrut (arXiv 1509.07919),
the row-aligned band ``arow[i, t] = A[i, i - bw + t]`` is cut into
``d = devices`` diagonal blocks of ``m = ceil(n / d)`` rows.  Writing the
global system per partition ``j``::

    A_j x_j  +  B̂_j x_{j-1}^(b)  +  Ĉ_j x_{j+1}^(t)  =  f_j

where ``x^(t)``/``x^(b)`` are a partition's top/bottom ``bw`` entries,
``B̂_j`` is nonzero only in its first ``bw`` rows (the band's left overhang
into the previous partition) and ``Ĉ_j`` only in its last ``bw`` rows (the
right overhang into the next).  Multiplying through by ``A_j^{-1}`` defines
the *spikes*::

    W_j = A_j^{-1} B̂_j      V_j = A_j^{-1} Ĉ_j      g_j = A_j^{-1} f_j

(each ``(m, bw)``; ``W_0 = 0`` and ``V_{d-1} = 0`` fall out of the global
band mask — partition 0 has no left overhang, partition d-1 no right one).
Restricting the recovery identity ``x_j = g_j − W_j x_{j-1}^(b) − V_j
x_{j+1}^(t)`` to each partition's top/bottom ``bw`` rows closes a *reduced
spike system* of order ``2·d·bw`` in the tip unknowns alone — identity
diagonal plus the spike tip blocks.  Factor time computes the local LU, the
spikes (one ``(m, 2bw)`` multi-RHS local solve), and the reduced matrix;
solve time is one local solve for ``g``, one small reduced solve for the
tips, and two rank-``bw`` GEMMs per partition for the recovery.

Everything here is the **pure-jnp mirror** plus the helpers *shared* with
the shard_map'd kernel entry (:mod:`repro.kernels.spike`): partitioning,
coupling extraction, reduced-system assembly, tip solve, and recovery are
one code path for both, so kernel-vs-mirror bitwise equality reduces to the
established :mod:`repro.core.banded` / :mod:`repro.kernels.banded` twin
contract for the per-partition local factor/solve.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .banded import banded_lu_blocked, banded_solve_blocked, pad_band_identity

__all__ = [
    "SpikeFactors",
    "spike_supported",
    "partition_band",
    "assemble_spike_factors",
    "spike_reduced_rhs",
    "spike_recover",
    "spike_lu",
    "spike_solve",
    "spike_linear_solve",
]


def spike_supported(n: int, bw: int, devices: int) -> bool:
    """Shape capability predicate for the SPIKE split.

    Requires ``bw >= 1`` (a pure-diagonal band has no couplings to split)
    and ``2*bw <= ceil(n / devices)``: each partition must hold its top and
    bottom tips disjointly — when ``bw >= n/devices`` the spikes overlap and
    the reduced-system closure is invalid, so the predicate rejects instead
    of returning garbage (dispatch falls back to replication)."""
    if devices < 1 or bw < 1 or n < 1:
        return False
    m = -(-n // devices)
    return 2 * bw <= m


def _coupling_blocks(ap: jax.Array, *, bw: int, m: int) -> tuple[jax.Array, jax.Array]:
    """Extract the dense coupling blocks from the padded band ``ap``
    reshaped ``(d, m, 2bw+1)``.

    ``B_j[r, q] = A[j·m+r, j·m−bw+q]`` lives at band offset ``t = q − r``
    (valid iff ``q ≥ r``); ``C_j[r', q] = A[j·m+m−bw+r', (j+1)·m+q]`` at
    ``t = 2bw + q − r'`` (valid iff ``q ≤ r'``).  Partition 0's B entries
    and partition d−1's C entries index outside the matrix and are already
    zero from the global band mask — no special-casing."""
    parts = ap  # (d, m, w)
    head = parts[:, :bw, :]          # rows that reach the previous partition
    tail = parts[:, m - bw :, :]     # rows that reach the next partition
    r = jnp.arange(bw)[:, None]
    q = jnp.arange(bw)[None, :]
    tb = q - r
    bmat = jnp.where(
        tb >= 0,
        jnp.take_along_axis(head, jnp.clip(tb, 0, None)[None, :, :], axis=2),
        0.0,
    )
    tc = 2 * bw + q - r
    cmat = jnp.where(
        tc <= 2 * bw,
        jnp.take_along_axis(tail, jnp.clip(tc, None, 2 * bw)[None, :, :], axis=2),
        0.0,
    )
    return bmat, cmat


def partition_band(
    arow: jax.Array, *, bw: int, devices: int
) -> tuple[jax.Array, jax.Array, int]:
    """Split the row-aligned band into per-partition operands.

    Returns ``(parts, coupling_rhs, m)``:

    * ``parts`` ``(d, m, 2bw+1)`` — each partition's *local* band: entries
      reaching outside the partition's own ``m`` columns are zeroed (they
      move into the couplings), identity pad rows fill the last partition
      when ``d`` does not divide ``n``;
    * ``coupling_rhs`` ``(d, m, 2bw)`` — the dense ``[B̂_j | Ĉ_j]`` spike
      right-hand sides (``B`` in the first ``bw`` rows of columns ``:bw``,
      ``C`` in the last ``bw`` rows of columns ``bw:``), ready for one
      multi-RHS local solve per partition;
    * ``m`` — the per-partition row count.
    """
    n, w = arow.shape
    assert w == 2 * bw + 1, f"band width {w} != 2*bw+1 for bw={bw}"
    if not spike_supported(n, bw, devices):
        raise ValueError(
            f"SPIKE split unsupported for n={n} bw={bw} devices={devices} "
            f"(requires bw >= 1 and 2*bw <= ceil(n/devices))"
        )
    d = devices
    m = -(-n // d)
    # defensive global mask: entries whose global column falls outside the
    # matrix must be zero for the coupling extraction's edge cases (valid
    # operands — e.g. make_banded_dd — already satisfy this bitwise).
    i = jnp.arange(n)[:, None]
    t = jnp.arange(w)[None, :]
    col = i - bw + t
    masked = jnp.where((col >= 0) & (col < n), arow, 0.0)
    ap = pad_band_identity(masked, bw, d * m).reshape(d, m, w)
    bmat, cmat = _coupling_blocks(ap, bw=bw, m=m)
    # local mask: keep only entries whose column stays inside the partition
    r = jnp.arange(m)[:, None]
    lcol = r - bw + t
    parts = jnp.where((lcol >= 0) & (lcol < m), ap, 0.0)
    zeros = jnp.zeros((d, m, bw), arow.dtype)
    bhat = zeros.at[:, :bw, :].set(bmat)
    chat = zeros.at[:, m - bw :, :].set(cmat)
    return parts, jnp.concatenate([bhat, chat], axis=-1), m


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SpikeFactors:
    """Factor-time artifact of the SPIKE split: per-partition packed band
    factors, the pre-solved spikes, and the assembled reduced matrix.

    ``packed`` exposes the stacked local factors as one ``(d·m, 2bw+1)``
    packed band so :func:`repro.core.health.factor_health` screens it like
    any banded factor (identity pad rows factor to pivot 1 — inert)."""

    local_lu: jax.Array   # (d, m, 2bw+1) per-partition packed band factors
    w_spikes: jax.Array   # (d, m, bw)  W_j = A_j^{-1} B̂_j
    v_spikes: jax.Array   # (d, m, bw)  V_j = A_j^{-1} Ĉ_j
    reduced: jax.Array    # (2·d·bw, 2·d·bw) reduced spike matrix
    n: int
    bw: int
    devices: int

    @property
    def m(self) -> int:
        return self.local_lu.shape[1]

    @property
    def packed(self) -> jax.Array:
        return self.local_lu.reshape(-1, self.local_lu.shape[-1])

    @property
    def shape(self):
        return self.packed.shape

    @property
    def dtype(self):
        return self.local_lu.dtype

    def tree_flatten(self):
        return (
            (self.local_lu, self.w_spikes, self.v_spikes, self.reduced),
            (self.n, self.bw, self.devices),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def assemble_spike_factors(
    local_lu: jax.Array, wv: jax.Array, *, n: int, bw: int, devices: int
) -> SpikeFactors:
    """Shared factor-time tail: split the stacked spike solve ``wv``
    ``(d, m, 2bw)`` into W/V, take the tips, and assemble the reduced spike
    matrix — identity diagonal plus the tip blocks.

    Unknown layout ``u = [x_0^t; x_0^b; x_1^t; x_1^b; …]`` (``bw`` rows per
    tip).  Restricting the recovery identity to the tips gives, per ``j``::

        x_j^t + Wt_j x_{j-1}^b + Vt_j x_{j+1}^t = gt_j
        x_j^b + Wb_j x_{j-1}^b + Vb_j x_{j+1}^t = gb_j

    so block-row ``2j`` carries ``Wt_j`` at block-column ``2(j−1)+1`` and
    ``Vt_j`` at ``2(j+1)``; block-row ``2j+1`` carries ``Wb_j``/``Vb_j`` at
    the same columns."""
    d, m = devices, local_lu.shape[1]
    w_sp = wv[..., :bw]
    v_sp = wv[..., bw:]
    wt, wb = w_sp[:, :bw, :], w_sp[:, m - bw :, :]
    vt, vb = v_sp[:, :bw, :], v_sp[:, m - bw :, :]
    red = jnp.eye(2 * d * bw, dtype=local_lu.dtype)
    for j in range(d):
        rt = 2 * j * bw
        rb = (2 * j + 1) * bw
        if j > 0:
            c = (2 * (j - 1) + 1) * bw
            red = red.at[rt : rt + bw, c : c + bw].set(wt[j])
            red = red.at[rb : rb + bw, c : c + bw].set(wb[j])
        if j < d - 1:
            c = 2 * (j + 1) * bw
            red = red.at[rt : rt + bw, c : c + bw].set(vt[j])
            red = red.at[rb : rb + bw, c : c + bw].set(vb[j])
    return SpikeFactors(
        local_lu=local_lu, w_spikes=w_sp, v_spikes=v_sp, reduced=red,
        n=n, bw=bw, devices=d,
    )


def spike_reduced_rhs(g: jax.Array, bw: int) -> jax.Array:
    """Tip right-hand side in the reduced system's unknown layout:
    ``[gt_0; gb_0; gt_1; …]`` from the stacked local solves ``g (d, m, k)``."""
    d, m, k = g.shape
    tips = jnp.stack([g[:, :bw, :], g[:, m - bw :, :]], axis=1)  # (d, 2, bw, k)
    return tips.reshape(2 * d * bw, k)


def spike_recover(factors: SpikeFactors, g: jax.Array, tips: jax.Array) -> jax.Array:
    """Shared recovery: ``x_j = g_j − W_j x_{j-1}^b − V_j x_{j+1}^t``,
    unpadded back to ``n`` rows.  ``tips`` is the reduced-system solution
    ``(2·d·bw, k)``."""
    d, bw = factors.devices, factors.bw
    k = g.shape[-1]
    t = tips.reshape(d, 2, bw, k)
    xt, xb = t[:, 0], t[:, 1]
    prev_xb = jnp.concatenate([jnp.zeros_like(xb[:1]), xb[:-1]], axis=0)
    next_xt = jnp.concatenate([xt[1:], jnp.zeros_like(xt[:1])], axis=0)
    hi = jax.lax.Precision.HIGHEST  # f32-accurate on TPU (DEFAULT is bf16 passes)
    x = (g - jnp.matmul(factors.w_spikes, prev_xb, precision=hi)
         - jnp.matmul(factors.v_spikes, next_xt, precision=hi))
    return x.reshape(d * factors.m, k)[: factors.n]


def spike_lu(
    arow: jax.Array, *, bw: int, devices: int, block: int | None = None
) -> SpikeFactors:
    """Pure-jnp mirror SPIKE factorization: per-partition
    :func:`repro.core.banded.banded_lu_blocked` plus one ``(m, 2bw)``
    multi-RHS spike solve, run as a Python loop over partitions (preserves
    the per-partition op order the shard_map'd kernel path replays)."""
    parts, rhs, _m = partition_band(arow, bw=bw, devices=devices)
    lus, wvs = [], []
    for j in range(devices):
        lu_j = banded_lu_blocked(parts[j], bw=bw, block=block)
        wvs.append(banded_solve_blocked(lu_j, rhs[j], bw=bw, block=block))
        lus.append(lu_j)
    return assemble_spike_factors(
        jnp.stack(lus), jnp.stack(wvs), n=arow.shape[0], bw=bw, devices=devices
    )


def _solve_rhs_parts(factors: SpikeFactors, b: jax.Array) -> tuple[jax.Array, bool]:
    """Normalize/pad the RHS into stacked per-partition columns ``(d, m, k)``."""
    squeeze = b.ndim == 1
    bm = b[:, None] if squeeze else b
    d, m = factors.devices, factors.m
    fp = jnp.zeros((d * m, bm.shape[1]), bm.dtype).at[: factors.n].set(bm)
    return fp.reshape(d, m, bm.shape[1]), squeeze


@jax.jit
def _finish_solve_compiled(factors: SpikeFactors, g: jax.Array) -> jax.Array:
    tips = jnp.linalg.solve(factors.reduced, spike_reduced_rhs(g, factors.bw))
    return spike_recover(factors, g, tips)


def _finish_solve(
    factors: SpikeFactors, g: jax.Array, squeeze: bool
) -> jax.Array:
    """Shared solve tail: reduced tip solve + recovery.  Jitted because the
    tail is a handful of small ops whose eager dispatch overhead would
    otherwise rival the local solves; kernel and mirror both land here, so
    the bitwise contract is unaffected."""
    x = _finish_solve_compiled(factors, g)
    return x[:, 0] if squeeze else x


def spike_solve(
    factors: SpikeFactors, b: jax.Array, *, block: int | None = None
) -> jax.Array:
    """Pure-jnp mirror SPIKE substitution: per-partition local solves for
    ``g`` (Python loop), then the shared reduced solve + recovery."""
    f, squeeze = _solve_rhs_parts(factors, b)
    g = jnp.stack([
        banded_solve_blocked(factors.local_lu[j], f[j], bw=factors.bw, block=block)
        for j in range(factors.devices)
    ])
    return _finish_solve(factors, g, squeeze)


def spike_linear_solve(
    arow: jax.Array, b: jax.Array, *, bw: int, devices: int, block: int | None = None
) -> jax.Array:
    """Factor + solve through the mirror path."""
    return spike_solve(spike_lu(arow, bw=bw, devices=devices, block=block), b, block=block)
