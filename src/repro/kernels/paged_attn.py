"""Pallas kernel: paged-KV decode attention — the serving engine's gather path.

The paged serving engine stores each slot's KV cache as fixed-size pages
scattered through a shared pool (``repro.serve.paged.PagePool``) instead of
one dense ``(max_len)`` row per slot.  Decode attention must therefore
*resolve the page table inside the kernel*: one grid program per batch row
walks the row's page table, gathers its pages into a contiguous
``(num_pages * page_size)`` KV view, and runs exactly the single-chunk
masked-softmax math of :func:`repro.models.common.attention`.

Like every kernel in this package it ships with a pure-jnp mirror
(:func:`paged_decode_attention_ref`) it must match **bitwise**, and traces
to exactly ONE ``pallas_call`` (asserted via
``repro.utils.hlo.primitive_count`` in tests/test_paged.py).

Bitwise contract with the dense decode path: the gathered view has the same
length as the dense cache row (``num_pages * page_size == max_len``), page
slots past the row's live length are masked to ``MASK_VALUE`` whose
``exp(MASK - m)`` underflows to exact 0, and unallocated page-table entries
(``-1``) gather zeros — so a paged serve is bitwise-identical per request
to a dense-slot serve (tests/test_paged.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.models.common import MASK_VALUE

from . import interpret_mode

__all__ = ["paged_decode_attention", "paged_decode_attention_ref"]


def _group_attention(q_g, k_g, v_g, length):
    """Decode attention of one KV head group: ``q_g`` (rep, Dh) queries
    against ``k_g``/``v_g`` (Sc, Dh).  The op sequence of the single-chunk
    branch of :func:`repro.models.common.attention` restricted to one
    group (the dense path's batched einsum runs this same 2-D contraction
    per (batch, group)), written with 2-D dots and a 2-D iota so Mosaic
    lowers it.  Returns (rep, Dh) in ``q_g.dtype``."""
    sc, dh = k_g.shape
    s = jax.lax.dot_general(
        q_g, k_g, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = s * dh**-0.5
    # contiguous paged rows: kv position j is valid iff j < length, which is
    # exactly the dense path's (pos >= 0) & (pos <= cur) mask
    mask = jax.lax.broadcasted_iota(jnp.int32, (1, sc), 1) < length
    s = jnp.where(mask, s, MASK_VALUE)
    m = jnp.maximum(s.max(-1), -1e25)
    p = jnp.exp(s - m[:, None])
    # f32 accumulation, rounded to the value dtype (what the dense path's
    # einsum does; Mosaic needs the 32-bit accumulator spelled out)
    out = jnp.dot(p.astype(v_g.dtype), v_g, preferred_element_type=jnp.float32).astype(v_g.dtype)
    out = out / jnp.maximum(p.sum(-1), 1e-30)[:, None].astype(out.dtype)
    return out.astype(q_g.dtype)


def _row_attention(q_row, read_kv, kvh: int, length):
    """One batch row: every KV head group against its gathered pages.
    ``read_kv(g)`` yields group ``g``'s (Sc, Dh) keys and values."""
    rep = q_row.shape[0] // kvh
    outs = []
    for g in range(kvh):
        k_g, v_g = read_kv(g)
        outs.append(_group_attention(q_row[g * rep : (g + 1) * rep], k_g, v_g, length))
    return jnp.concatenate(outs, axis=0)  # (H, Dh)


def _paged_attn_kernel(q_ref, pt_ref, len_ref, kp_ref, vp_ref, o_ref, *, num_row_pages: int):
    """One batch row: gather the row's pages per KV head, then single-chunk
    attention.

    q_ref (1, H, Dh); pt_ref (B, NP) int32 page table in SMEM (−1 =
    unallocated); len_ref (B,) int32 in SMEM; kp/vp_ref (P, KV, page, Dh)
    full pool, head-major so one head of one page is a tile-aligned
    (page, Dh) slab; o_ref (1, H, Dh).
    """
    i = pl.program_id(0)
    kvh = kp_ref.shape[1]

    def read_kv(g):
        ks_parts, vs_parts = [], []
        for j in range(num_row_pages):
            pid = pt_ref[i, j]
            safe = jnp.maximum(pid, 0)
            pk = kp_ref[safe, g]
            pv = vp_ref[safe, g]
            hole = pid < 0
            ks_parts.append(jnp.where(hole, jnp.zeros_like(pk), pk))
            vs_parts.append(jnp.where(hole, jnp.zeros_like(pv), pv))
        return jnp.concatenate(ks_parts, axis=0), jnp.concatenate(vs_parts, axis=0)

    o_ref[0] = _row_attention(q_ref[0], read_kv, kvh, len_ref[i])


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(
    q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
    page_table: jax.Array, lengths: jax.Array, *, interpret: bool | None = None,
) -> jax.Array:
    """Decode attention over a paged KV pool; grid over the batch.

    q: (B, H, Dh) current-token queries; k_pages/v_pages: (P, page, KV, Dh)
    shared page pool; page_table: (B, NP) int32, −1 = unallocated slot;
    lengths: (B,) int32 live tokens per row (the current position + 1).
    Returns (B, H * Dh) attention outputs in q.dtype.
    """
    interpret = interpret_mode(interpret)
    b, h, dh = q.shape
    p, page, kvh, _ = k_pages.shape
    np_ = page_table.shape[1]
    # head-major pool: one (page, Dh) slab per (page id, head) — the layout
    # Mosaic can index with a traced page id
    kt = k_pages.transpose(0, 2, 1, 3)
    vt = v_pages.transpose(0, 2, 1, 3)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, num_row_pages=np_),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, dh), lambda i: (i, 0, 0)),
            smem,
            smem,
            pl.BlockSpec((p, kvh, page, dh), lambda i: (0, 0, 0, 0)),
            pl.BlockSpec((p, kvh, page, dh), lambda i: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, dh), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, dh), q.dtype),
        interpret=interpret,
    )(q, page_table.astype(jnp.int32), jnp.asarray(lengths, jnp.int32).reshape(b), kt, vt)
    return out.reshape(b, h * dh)


def paged_decode_attention_ref(q, k_pages, v_pages, page_table, lengths):
    """Pure-jnp mirror of :func:`paged_decode_attention` (bitwise twin)."""
    b, np_ = page_table.shape
    page, kvh, dh = k_pages.shape[1], k_pages.shape[2], k_pages.shape[3]
    safe = jnp.maximum(page_table, 0)
    hole = (page_table < 0)[..., None, None, None]
    ks = jnp.where(hole, 0, k_pages[safe]).reshape(b, np_ * page, kvh, dh)
    vs = jnp.where(hole, 0, v_pages[safe]).reshape(b, np_ * page, kvh, dh)

    def row(q_row, k_row, v_row, length):
        out = _row_attention(q_row, lambda g: (k_row[:, g], v_row[:, g]), kvh, length)
        return out.reshape(-1)

    return jax.vmap(row)(q, ks, vs, jnp.asarray(lengths, jnp.int32))
