"""Production meshes.  Functions only — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax initialization).

Topology (TPU v5e target):
  * single pod: (data=16, model=16) — 256 chips;
  * multi-pod:  (pod=2, data=16, model=16) — 512 chips, the ``pod`` axis is
    the cross-DCI data-parallel axis (gradient all-reduce only, optionally
    int8-compressed — ``repro.train.grad_compress``).
"""
from __future__ import annotations

import jax


def _make(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """Arbitrary mesh (tests / examples / PP experiments)."""
    return _make(tuple(shape), tuple(axes))


def parse_mesh_arg(arg: str) -> jax.sharding.Mesh:
    """CLI ``--mesh`` spec → mesh: ``8`` → (model,), ``2x4`` →
    (data, model), ``2x2x2`` → (pod, data, model)."""
    dims = tuple(int(x) for x in arg.split("x"))
    names = {1: ("model",), 2: ("data", "model"), 3: ("pod", "data", "model")}.get(len(dims))
    if names is None:
        raise SystemExit(f"--mesh takes 1-3 'x'-separated dims, got {arg!r}")
    return make_mesh(dims, names)


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.  Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s inter-chip interconnect = 4 links of 50 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9, "ici_link_bytes_s": 50e9},
}


def peaks(device_kind: str) -> dict:
    """Peaks of ``device_kind``; a device missing from the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks recorded for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
