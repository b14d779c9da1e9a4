"""The :class:`Problem` descriptor — one hashable record per solver call.

Every dispatch decision in the repo flows through a ``Problem``: the public
ops in :mod:`repro.kernels.ops` build one from their array arguments, the
registry filters backends by capability against it, and the autotune cache
keys its measurements on it.  The descriptor is deliberately *shape-level*
(no array values): selection happens at trace time and must be a pure
function of shapes, dtype and device count.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

__all__ = ["Problem", "OPS", "STRUCTURES", "default_device_kind", "is_tpu"]

OPS = ("factor", "solve", "linear_solve", "decode")
STRUCTURES = ("dense", "banded", "batched_dense", "batched_banded", "paged_kv")


@dataclasses.dataclass(frozen=True)
class Problem:
    """Shape-level description of one solver invocation.

    ``n``        system order (for banded structures: number of band rows).
    ``bw``       band half-width; 0 for dense structures.
    ``batch``    leading batch size; 1 for unbatched structures.
    ``rhs``      RHS width for solve ops (1 for a vector RHS); 0 for factor.
    ``devices``  mesh extent the call spans; 1 means single-device.
    ``tolerance`` largest acceptable relative residual ``|Ax-b|/|b|``;
                 0.0 (the default) demands the exact tier, so approximate
                 backends (which declare a ``residual_bound``) are only
                 admitted when the caller states a tolerance they meet.
    ``verify_residual`` ask the registry to *measure* the relative residual
                 of eager ``linear_solve`` dispatches and treat a result
                 past the bound (``tolerance`` when set, else the exact-tier
                 default in ``registry.VERIFY_RESIDUAL_DEFAULT_BOUND``) as a
                 dispatch failure — feeding the escalation funnel instead of
                 returning a silently-wrong answer.
    ``enriched``  for solve ops: whether the factor operand is a
                 :class:`repro.core.factorization.Factorization` carrying
                 its factor-time enrichments (pre-inverted diagonal blocks).
                 The inverted-diagonal solve backends gate on it, so a raw
                 legacy operand is never steered into an
                 enrich-on-the-fly dispatch by a measured cache row.
                 Defaults True (the steady-state serving operand is an
                 enriched artifact); ``from_arrays`` downgrades it for raw
                 arrays.  Deliberately NOT part of the autotune cache key.
    ``device_kind`` ``jax.Device.device_kind`` of the device the call runs
                 on (``"cpu"``, ``"TPU v5 lite"``, ...); defaults to the
                 first device of the default backend.  Capability
                 predicates reject kernels Mosaic cannot lower when it names
                 a TPU, and the autotune cache keys on it so a timing taken
                 on one device kind never steers another.
    """

    op: str
    structure: str
    n: int
    dtype: str = "float32"
    bw: int = 0
    batch: int = 1
    rhs: int = 0
    devices: int = 1
    tolerance: float = 0.0
    verify_residual: bool = False
    enriched: bool = True
    device_kind: str = ""

    def __post_init__(self):
        if not self.device_kind:
            object.__setattr__(self, "device_kind", default_device_kind())
        if self.op not in OPS:
            raise ValueError(f"unknown op {self.op!r} (expected one of {OPS})")
        if self.structure not in STRUCTURES:
            raise ValueError(
                f"unknown structure {self.structure!r} (expected one of {STRUCTURES})"
            )
        if self.tolerance < 0:
            raise ValueError(f"tolerance must be >= 0, got {self.tolerance}")

    @property
    def tpu(self) -> bool:
        return is_tpu(self.device_kind)

    @property
    def banded(self) -> bool:
        return self.structure.endswith("banded")

    @property
    def batched(self) -> bool:
        return self.structure.startswith("batched_")

    @classmethod
    def from_arrays(
        cls, op: str, a, b=None, *, bw: int = 0, devices: int = 1,
        tolerance: float = 0.0, verify_residual: bool = False,
    ) -> "Problem":
        """Build a descriptor from the operand arrays.

        ``a`` is the matrix operand: ``(n, n)`` dense, ``(n, 2bw+1)``
        row-aligned band (``bw > 0``), or either with one leading batch
        axis.  ``b`` (optional) is the RHS whose trailing width becomes
        ``rhs`` (1 for a vector).
        """
        banded = bw > 0
        base = "banded" if banded else "dense"
        matrix_ndim = 2
        if a.ndim == matrix_ndim:
            structure, batch = base, 1
        elif a.ndim == matrix_ndim + 1:
            structure, batch = f"batched_{base}", int(a.shape[0])
        else:
            raise ValueError(
                f"{base} {op} expects a {matrix_ndim}-D matrix or one leading "
                f"batch axis; got shape {tuple(a.shape)}"
            )
        n = int(a.shape[-2]) if banded else int(a.shape[-1])
        rhs = 0
        if b is not None:
            # RHS ranks: (n,) / (n, m) unbatched, (B, n) / (B, n, m) batched
            rhs_ndim_vec = 1 + (1 if structure.startswith("batched_") else 0)
            rhs = 1 if b.ndim == rhs_ndim_vec else int(b.shape[-1])
        enriched = bool(getattr(a, "enriched", False)) if op == "solve" else True
        return cls(
            op=op,
            structure=structure,
            n=n,
            dtype=jnp.dtype(a.dtype).name,
            bw=int(bw),
            batch=batch,
            rhs=rhs,
            devices=int(devices),
            tolerance=float(tolerance),
            verify_residual=bool(verify_residual),
            enriched=enriched,
        )


def default_device_kind() -> str:
    """Device kind of the first device of the default backend."""
    return jax.devices()[0].device_kind


def is_tpu(device_kind: str) -> bool:
    return device_kind.lower().startswith("tpu")
