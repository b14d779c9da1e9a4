"""Pallas TPU kernels for the paper's compute hot-spot (LU factorization).

``<name>.py`` kernels + ``ops.py`` jit'd wrappers + ``ref.py`` numpy oracles.
On the CPU platform every kernel runs in Pallas interpret mode; everywhere
else it is compiled (Mosaic on TPU).  Kernels whose bodies Mosaic refuses
call :func:`require_interpret` and raise instead of compiling.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def interpret_mode(interpret: bool | None = None) -> bool:
    """The ``interpret=`` every kernel entry passes to ``pallas_call``: an
    explicit choice wins; otherwise interpret only on the CPU platform."""
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"


def require_interpret(name: str, refusal: str, interpret: bool | None) -> bool:
    """Resolve ``interpret`` for a kernel Mosaic cannot lower, raising a
    clear error when the call would compile it."""
    interpret = interpret_mode(interpret)
    if not interpret:
        raise NotImplementedError(
            f"{name} does not lower on Mosaic ({refusal}); it runs only in "
            "Pallas interpret mode on the CPU platform"
        )
    return interpret


def aligned(offset, stride: int):
    """Mark a traced row offset built from multiples of ``stride`` as a
    multiple of the 8-row sublane tile, which Mosaic must be able to prove
    for a dynamic ref slice (no-op when ``stride`` is not 8-aligned)."""
    return pl.multiple_of(offset, 8) if stride % 8 == 0 else offset


def lane_pad(x: jax.Array) -> jax.Array:
    """Zero-pad the last axis to whole 128-lane tiles: Mosaic slices and
    DMAs only lane-aligned widths (a skewed band row is ``C+2bw`` wide, a
    vector RHS one column).  Pad columns are never read back.  Interpret
    mode pads too, so the CPU tests run the layout the chip runs."""
    extra = -x.shape[-1] % 128
    if not extra:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, extra)])


def vmem_limit(scratch_bytes: int) -> int:
    """Scoped-VMEM limit for a kernel holding ``scratch_bytes`` of explicit
    buffers: the buffers plus 16 MiB for Mosaic's own temporaries, never
    below the 32 MiB default and within a v5e core's 128 MiB."""
    return int(min(max(scratch_bytes + 16 * 2**20, 32 * 2**20), 100 * 2**20))


from . import ebv_lu, trsm, banded, ops, paged_attn, ref  # noqa: E402,F401
