"""First-class ``Factorization`` artifact: packed factors + solve-ready
enrichments computed once at factor time.

The EbV paper's payoff lives in the solve phase, and the solve phase is
exactly where re-deriving state per call hurts: every
``banded_solve_kernelized`` dispatch used to re-skew the band into the
window-aligned layout, and every blocked sweep re-ran the sequential
``strip_trsm``/``strip_utrsm`` recurrences against the same diagonal
blocks.  Following the block-inversion structure of Chen, Liu & Yang
("Parallel Triangular Solvers on GPU", arXiv 1606.00541) and the
carry-solve-metadata-with-the-factors design of Li, Serban & Negrut
(arXiv 1509.07919), this module makes the factorization an *artifact*:

* ``packed``      — the legacy packed-LU layout (dense ``(…, n, n)`` or
                    row-aligned band ``(…, n, 2bw+1)``), unchanged, so
                    every pre-artifact consumer keeps working;
* ``linv``/``uinv`` — the **pre-inverted diagonal blocks**: for every
                    solve block the unit-lower and upper in-block windows
                    are inverted at factor time (one batched triangular
                    solve against the identity), so each solve sweep
                    becomes batched GEMM against the stored inverses — no
                    sequential recurrence remains on the solve path;
* ``tlo``/``tup`` — the **pre-coupled transfer blocks**
                    ``L^{-1}_i F_i^{above}`` / ``U^{-1}_i F_i^{below}``
                    (banded only): the skewed-band coupling columns
                    (:func:`repro.core.banded.band_to_skewed`), derived
                    once and already multiplied through the inverses, so
                    the solve never touches the band layout again and its
                    only sequential dependence is a ``bw``-row tail/head
                    recurrence resolved by associative scan;
* ``health``      — the embedded :class:`~repro.core.health.FactorHealth`
                    record, so cached artifacts are never re-screened;
* ``tier``/``fingerprint`` — accuracy-tier and cache-identity metadata
                    for the serving layer.

The artifact is a registered pytree (it crosses ``jit``/``vmap``
boundaries) and quacks like the packed array it wraps (``shape`` /
``ndim`` / ``dtype`` / ``__jax_array__``) — the one-release shim that
lets artifact and raw-ndarray call sites coexist.

Bitwise kernel≡mirror contract: the inverses are computed ONCE here (pure
jnp) and handed to both the Pallas kernels and the pure-jnp mirrors as
plain arrays; both sides then apply them through the *shared* sweep
helpers below (:func:`inverted_dense_sweeps` /
:func:`inverted_band_sweeps`), so the twins trace identical jaxprs and
stay bitwise-identical by construction.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from .banded import band_block_size, band_to_skewed, pad_band_identity
from .blocked import pad_identity_tail
from .health import FactorHealth

# Exact-tier GEMMs state f32 precision: a DEFAULT-precision f32 dot runs in
# bf16 passes on TPU.
_HIGHEST = jax.lax.Precision.HIGHEST

__all__ = [
    "Factorization",
    "dense_block_inverses",
    "banded_block_inverses",
    "banded_skewed_layout",
    "inverted_dense_sweeps",
    "inverted_band_sweeps",
    "dense_inverted_solve",
    "banded_inverted_solve",
    "equalized_rhs_tile",
    "factorize_dense",
    "factorize_banded",
    "dense_artifact",
    "banded_artifact",
    "packed_of",
]


# ---------------------------------------------------------------------------
# factor-time enrichment: pre-inverted diagonal blocks
# ---------------------------------------------------------------------------
def _packed_block_inverses(diags: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``L^{-1}`` / ``U^{-1}`` of a ``(S, B, B)`` stack of *packed* diagonal
    blocks (unit-lower L strictly below the diagonal, U on and above it) via
    batched triangular solves against the identity.  Entries outside each
    factor's triangle are ignored by construction, so the packed layout needs
    no unpacking.  This runs ONCE at factor time; the solve path then only
    ever GEMMs against the results."""
    s, b = diags.shape[0], diags.shape[1]
    eye = jnp.broadcast_to(jnp.eye(b, dtype=diags.dtype), (s, b, b))
    linv = jax.lax.linalg.triangular_solve(
        diags, eye, left_side=True, lower=True, unit_diagonal=True
    )
    uinv = jax.lax.linalg.triangular_solve(
        diags, eye, left_side=True, lower=False, unit_diagonal=False
    )
    return linv, uinv


def dense_block_inverses(lu: jax.Array, *, block: int) -> tuple[jax.Array, jax.Array]:
    """``(S, B, B)`` ``L^{-1}`` / ``U^{-1}`` stacks for the padded packed LU's
    diagonal blocks, computed once at factor time."""
    n = lu.shape[-1]
    b = min(block, n)
    s = -(-n // b)
    lup = pad_identity_tail(lu, s * b)
    diags = jax.vmap(
        lambda i: jax.lax.dynamic_slice(lup, (i * b, i * b), (b, b))
    )(jnp.arange(s))
    return _packed_block_inverses(diags)


def banded_skewed_layout(lu_band: jax.Array, *, bw: int, block: int | None = None):
    """Solve-layout skewed band ``G`` ``(S·C, C+2bw)`` of the packed band
    factors (the layout :func:`repro.core.banded.banded_solve_blocked`
    derives per call), plus its ``(C, S)`` blocking.  Derived ONCE at factor
    time and carried in the artifact."""
    n = lu_band.shape[-2]
    c = band_block_size(n, bw, block)
    s = -(-n // c)
    g = band_to_skewed(pad_band_identity(lu_band, bw, s * c), bw, c)
    return g, c, s


def banded_block_inverses(
    g: jax.Array, *, bw: int, block: int
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Banded solve enrichment from the skewed band ``G``: the in-window
    ``(S, C, C)`` ``L^{-1}`` / ``U^{-1}`` stacks plus the **pre-coupled**
    transfer blocks

    * ``tlo[i] = L^{-1}_i · F_i[:, :bw]``      (couples to the block above),
    * ``tup[i] = U^{-1}_i · F_i[:, bw+C:]``    (couples to the block below),

    each ``(S, C, bw)``.  With the coupling folded in at factor time the
    solve's only sequential dependence is the ``bw``-row tail/head
    recurrence (:func:`inverted_band_sweeps`) — everything else is one
    batched GEMM per sweep."""
    c = block
    gw = c + 2 * bw
    s = g.shape[-2] // c
    f = g.reshape(s, c, gw)
    linv, uinv = _packed_block_inverses(f[:, :, bw : bw + c])
    tlo = jnp.matmul(linv, f[:, :, :bw], precision=_HIGHEST, preferred_element_type=jnp.float32).astype(g.dtype)
    tup = jnp.matmul(uinv, f[:, :, bw + c :], precision=_HIGHEST, preferred_element_type=jnp.float32).astype(g.dtype)
    return linv, uinv, tlo, tup


# ---------------------------------------------------------------------------
# shared inverted-diagonal solve sweeps (kernel/mirror bitwise twins)
# ---------------------------------------------------------------------------
def inverted_dense_sweeps(read_tile, read_linv, read_uinv, x, *, num_steps: int, block: int):
    """Blocked forward+backward substitution where every diagonal step is one
    GEMM against the pre-inverted block — no ``strip_trsm`` recurrence on the
    solve path.  ``read_tile(r, i)`` yields the ``(B, B)`` factor tile,
    ``read_linv(i)`` / ``read_uinv(i)`` the stored inverses (DMA'd copies or
    value slices — both exact, so the bitwise mirror contract holds)."""
    s, b = num_steps, block
    rt = x.shape[1]

    def fwd(i, x):
        yi = jnp.dot(
            read_linv(i), jax.lax.dynamic_slice(x, (i * b, 0), (b, rt)),
            precision=_HIGHEST, preferred_element_type=jnp.float32,
        ).astype(x.dtype)
        x = jax.lax.dynamic_update_slice(x, yi, (i * b, 0))

        def off(r, x):
            blk = jax.lax.dynamic_slice(x, (r * b, 0), (b, rt)) - jnp.dot(
                read_tile(r, i), yi, precision=_HIGHEST, preferred_element_type=jnp.float32
            ).astype(x.dtype)
            return jax.lax.dynamic_update_slice(x, blk, (r * b, 0))

        return jax.lax.fori_loop(i + 1, s, off, x)

    x = jax.lax.fori_loop(0, s, fwd, x)

    def bwd(jj, x):
        i = s - 1 - jj
        xi = jnp.dot(
            read_uinv(i), jax.lax.dynamic_slice(x, (i * b, 0), (b, rt)),
            precision=_HIGHEST, preferred_element_type=jnp.float32,
        ).astype(x.dtype)
        x = jax.lax.dynamic_update_slice(x, xi, (i * b, 0))

        def off(r, x):
            blk = jax.lax.dynamic_slice(x, (r * b, 0), (b, rt)) - jnp.dot(
                read_tile(r, i), xi, precision=_HIGHEST, preferred_element_type=jnp.float32
            ).astype(x.dtype)
            return jax.lax.dynamic_update_slice(x, blk, (r * b, 0))

        return jax.lax.fori_loop(0, i, off, x)

    return jax.lax.fori_loop(0, s, bwd, x)


def _affine_scan(a: jax.Array, b: jax.Array) -> jax.Array:
    """All states ``y_i`` of the affine recurrence ``y_i = a_i @ y_{i-1} + b_i``
    (``y_{-1} = 0``) over a ``(S, k, k)`` / ``(S, k, m)`` stack, via
    associative composition of the affine maps — ``O(log S)`` batched GEMM
    levels instead of ``S`` sequential steps."""

    def combine(lo, hi):
        a_lo, b_lo = lo
        a_hi, b_hi = hi
        return (
            jnp.matmul(a_hi, a_lo, precision=_HIGHEST, preferred_element_type=jnp.float32).astype(a_lo.dtype),
            jnp.matmul(a_hi, b_lo, precision=_HIGHEST, preferred_element_type=jnp.float32).astype(b_lo.dtype)
            + b_hi,
        )

    return jax.lax.associative_scan(combine, (a, b), axis=0)[1]


def inverted_band_sweeps(
    linv: jax.Array, uinv: jax.Array, tlo: jax.Array, tup: jax.Array,
    xb: jax.Array, *, bw: int,
) -> jax.Array:
    """Two-phase banded substitution on pre-inverted factors.  ``xb`` is the
    RHS reshaped to solve blocks ``(S, C, m)``.

    Forward sweep ``L y = x``: the per-block solution is
    ``y_i = L^{-1}_i x_i − tlo_i · ytail_{i-1}`` where ``ytail`` is the last
    ``bw`` rows of the previous block — so phase 1 is ONE batched GEMM
    (``z = linv @ xb``), phase 2 resolves the tiny ``(bw, m)`` tail
    recurrence ``ytail_i = ztail_i − tlo^{tail}_i ytail_{i-1}`` with an
    associative scan, and phase 3 recovers every block with a second batched
    GEMM.  The backward sweep mirrors this on the first-``bw``-row heads.
    No sequential full-block recurrence remains anywhere on the solve path —
    this is the equal-contribution GEMM formulation of arXiv 1606.00541 with
    the SPIKE-style reduced tail system of arXiv 1509.07919."""
    s, c = linv.shape[0], linv.shape[1]
    m = xb.shape[-1]
    zero = jnp.zeros((1, bw, m), xb.dtype)

    z = jnp.matmul(linv, xb, precision=_HIGHEST, preferred_element_type=jnp.float32).astype(xb.dtype)
    ytail = _affine_scan(-tlo[:, c - bw :, :], z[:, c - bw :, :])
    prev = jnp.concatenate([zero, ytail[:-1]], axis=0)
    y = z - jnp.matmul(tlo, prev, precision=_HIGHEST, preferred_element_type=jnp.float32).astype(xb.dtype)

    w = jnp.matmul(uinv, y, precision=_HIGHEST, preferred_element_type=jnp.float32).astype(xb.dtype)
    xhead = jnp.flip(
        _affine_scan(-jnp.flip(tup[:, :bw, :], 0), jnp.flip(w[:, :bw, :], 0)), 0
    )
    nxt = jnp.concatenate([xhead[1:], zero], axis=0)
    return w - jnp.matmul(tup, nxt, precision=_HIGHEST, preferred_element_type=jnp.float32).astype(xb.dtype)


def equalized_rhs_tile(m: int, rhs_tile: int) -> int:
    """Equalized RHS tile width for stacked-RHS dispatches: instead of the
    legacy pad-to-``rhs_tile``-multiple (whose last tile is mostly padding),
    split the ``m`` columns into ``ceil(m / rhs_tile)`` *equal-width* tiles
    rounded up to a lane-friendly multiple of 8 — the paper's equalization
    idea applied to the solve grid."""
    tiles = max(1, -(-m // rhs_tile))
    rt = -(-m // tiles)
    if rt > 8:
        rt = -(-rt // 8) * 8
    return rt


# ---------------------------------------------------------------------------
# the artifact
# ---------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True, eq=False)
class Factorization:
    """Packed factors + solve-ready enrichments (see module docstring).

    Children (pytree leaves): ``packed``, ``linv``, ``uinv``, ``tlo``,
    ``tup``, ``health``.  Static aux: ``structure`` ("dense" | "banded"),
    ``bw``, ``block`` (the enrichment's solve-block size — the skewed-band
    layout descriptor), ``tier`` (accuracy tier the factors were produced
    under) and ``fingerprint`` (matrix identity for the serving cache; None
    for factors built under tracing)."""

    packed: Any
    linv: Any = None
    uinv: Any = None
    tlo: Any = None
    tup: Any = None
    health: FactorHealth | None = None
    structure: str = "dense"
    bw: int = 0
    block: int = 0
    tier: float = 0.0
    fingerprint: str | None = None

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        children = (self.packed, self.linv, self.uinv, self.tlo, self.tup, self.health)
        aux = (self.structure, self.bw, self.block, self.tier, self.fingerprint)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        packed, linv, uinv, tlo, tup, health = children
        structure, bw, block, tier, fingerprint = aux
        return cls(packed=packed, linv=linv, uinv=uinv, tlo=tlo, tup=tup,
                   health=health, structure=structure, bw=bw, block=block,
                   tier=tier, fingerprint=fingerprint)

    # -- array duck-typing (one-release legacy shim) ------------------------
    @property
    def shape(self):
        return self.packed.shape

    @property
    def ndim(self):
        return self.packed.ndim

    @property
    def dtype(self):
        return self.packed.dtype

    @property
    def n(self) -> int:
        return self.packed.shape[-2]

    @property
    def batched(self) -> bool:
        return self.packed.ndim > 2

    @property
    def enriched(self) -> bool:
        return self.linv is not None

    def __jax_array__(self):
        return self.packed

    def __array__(self, dtype=None):
        import numpy as np

        return np.asarray(self.packed, dtype=dtype)

    def __getitem__(self, idx):
        return self.packed[idx]

    def astype(self, dtype):
        return self.packed.astype(dtype)

    def with_meta(self, **kw) -> "Factorization":
        return dataclasses.replace(self, **kw)


def packed_of(x):
    """Artifact-or-array → the packed factor array (the legacy operand)."""
    return x.packed if isinstance(x, Factorization) else x


# ---------------------------------------------------------------------------
# constructors
# ---------------------------------------------------------------------------
def factorize_dense(
    packed: jax.Array,
    *,
    block: int = 256,
    tier: float = 0.0,
    health: FactorHealth | None = None,
    fingerprint: str | None = None,
    enrich: bool = True,
) -> Factorization:
    """Wrap packed dense LU factors ``(…, n, n)`` into an artifact,
    pre-inverting the diagonal blocks (in the ≥f32 compute dtype the tiled
    solve promotes to) unless ``enrich=False``."""
    if isinstance(packed, Factorization):
        return packed
    n = packed.shape[-1]
    b = min(block, n)
    linv = uinv = None
    if enrich:
        compute = jnp.promote_types(jnp.float32, packed.dtype)
        inv = functools.partial(dense_block_inverses, block=b)
        for _ in range(packed.ndim - 2):
            inv = jax.vmap(inv)
        linv, uinv = inv(packed.astype(compute))
    return Factorization(packed=packed, linv=linv, uinv=uinv, health=health,
                         structure="dense", bw=0, block=b, tier=tier,
                         fingerprint=fingerprint)


def factorize_banded(
    packed: jax.Array,
    *,
    bw: int,
    block: int | None = None,
    tier: float = 0.0,
    health: FactorHealth | None = None,
    fingerprint: str | None = None,
    enrich: bool = True,
) -> Factorization:
    """Wrap packed band LU factors ``(…, n, 2bw+1)`` into an artifact,
    deriving the skewed solve layout and pre-inverting the in-window
    diagonal blocks unless ``enrich=False``."""
    if isinstance(packed, Factorization):
        return packed
    n = packed.shape[-2]
    c = band_block_size(n, bw, block)
    linv = uinv = tlo = tup = None
    if enrich:
        compute = jnp.promote_types(jnp.float32, packed.dtype)

        def one(lb):
            g, _, _ = banded_skewed_layout(lb, bw=bw, block=c)
            return banded_block_inverses(g, bw=bw, block=c)

        fn = one
        for _ in range(packed.ndim - 2):
            fn = jax.vmap(fn)
        linv, uinv, tlo, tup = fn(packed.astype(compute))
    return Factorization(packed=packed, linv=linv, uinv=uinv, tlo=tlo, tup=tup,
                         health=health, structure="banded", bw=bw, block=c,
                         tier=tier, fingerprint=fingerprint)


def dense_artifact(x, *, block: int = 256) -> Factorization:
    """Artifact-or-array → *enriched* dense artifact (the legacy-array shim
    path: raw operands are wrapped and inverted on the fly)."""
    if isinstance(x, Factorization):
        if x.enriched:
            return x
        return factorize_dense(x.packed, block=x.block or block, tier=x.tier,
                               health=x.health, fingerprint=x.fingerprint)
    return factorize_dense(x, block=block)


def banded_artifact(x, *, bw: int, block: int | None = None) -> Factorization:
    """Artifact-or-array → *enriched* banded artifact (legacy-array shim)."""
    if isinstance(x, Factorization):
        if x.enriched:
            return x
        return factorize_banded(x.packed, bw=x.bw or bw, block=x.block or block,
                                tier=x.tier, health=x.health,
                                fingerprint=x.fingerprint)
    return factorize_banded(x, bw=bw, block=block)


# ---------------------------------------------------------------------------
# pure-jnp mirror drivers (op-identical twins of the Pallas kernels)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("block",))
def dense_inverted_solve(
    lu: jax.Array, linv: jax.Array, uinv: jax.Array, b: jax.Array, *, block: int = 256
) -> jax.Array:
    """Pure-jnp mirror of :func:`repro.kernels.trsm.solve_inverted` —
    identical math through the shared :func:`inverted_dense_sweeps`, so
    kernel and mirror stay bitwise-identical."""
    squeeze = b.ndim == 1
    bm = b[:, None] if squeeze else b
    out_dtype = bm.dtype
    compute = jnp.promote_types(jnp.float32, jnp.promote_types(lu.dtype, out_dtype))
    n, m = bm.shape
    s, bb = linv.shape[0], linv.shape[1]
    lup = pad_identity_tail(lu.astype(compute), s * bb)
    x = jnp.zeros((s * bb, m), compute).at[:n].set(bm.astype(compute))

    def read_tile(r, i):
        return jax.lax.dynamic_slice(lup, (r * bb, i * bb), (bb, bb))

    def read_linv(i):
        return jax.lax.dynamic_slice(linv, (i, 0, 0), (1, bb, bb))[0]

    def read_uinv(i):
        return jax.lax.dynamic_slice(uinv, (i, 0, 0), (1, bb, bb))[0]

    x = inverted_dense_sweeps(read_tile, read_linv, read_uinv, x,
                              num_steps=s, block=bb)
    x = x[:n].astype(out_dtype)
    return x[:, 0] if squeeze else x


@functools.partial(jax.jit, static_argnames=("n", "bw"))
def banded_inverted_solve(
    linv: jax.Array, uinv: jax.Array, tlo: jax.Array, tup: jax.Array,
    b: jax.Array, *, n: int, bw: int,
) -> jax.Array:
    """Pure-jnp mirror of
    :func:`repro.kernels.banded.banded_solve_inverted` — identical math
    through the shared :func:`inverted_band_sweeps`."""
    s, c = linv.shape[0], linv.shape[1]
    squeeze = b.ndim == 1
    bm = b[:, None] if squeeze else b
    out_dtype = bm.dtype
    compute = linv.dtype
    m = bm.shape[1]
    xb = jnp.zeros((s * c, m), compute).at[:n].set(bm.astype(compute))
    x = inverted_band_sweeps(linv, uinv, tlo, tup, xb.reshape(s, c, m), bw=bw)
    x = x.reshape(s * c, m)[:n].astype(out_dtype)
    return x[:, 0] if squeeze else x
