"""Single sharding-policy layer for the whole system.

Every parameter / activation / cache leaf in the model code carries a tuple
of **logical axis names** (``("embed", "heads_x_dim")``, ``("act_batch",
"act_seq", "act_embed")``, ...).  This module owns the only mapping from
those names to physical mesh axes:

  * ``RULE_PRESETS`` — named logical→mesh rule tables (``default`` is
    TP-over-``model`` + DP-over-``pod``/``data``; ``zero3`` additionally
    shards the ``embed`` axis over ``data``, ZeRO-3 style).
  * ``rules_for(cfg, mesh)`` — config-aware specialization: any rule whose
    shard granularity would split *below a whole head* (attention q/kv
    heads, SSD state heads) falls back to replication.  This is the EbV
    philosophy applied to placement: a shard that cannot be cut into equal
    whole units is not cut at all (see README.md).
  * ``use_mesh_rules(mesh, rules)`` / ``active_mesh()`` — a thread-local
    mesh+rules context; model code calls ``constrain(x, axes)`` which is a
    no-op outside any context, so the same code runs on 1 CPU device and on
    a production mesh.
  * ``resolve_spec(shape, axes)`` — logical axes → ``PartitionSpec`` with
    per-dimension divisibility fallback (an indivisible dim is replicated,
    never padded), recording every fallback in ``_CTX.log`` for the dry-run
    analysis artifacts.
  * ``split_axes`` / ``prepend_axis`` — pytree helpers for the
    ``(array, axes)`` leaf convention used by every ``init_*``.
  * ``shard_map`` — keyword wrapper over ``jax.shard_map`` that every
    repo call site goes through.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import NamedSharding, PartitionSpec


# ---------------------------------------------------------------------------
# rule presets: logical axis name -> mesh axis (str), tuple of mesh axes, or
# None (replicated).  Mesh axes absent from the active mesh are ignored.
# ---------------------------------------------------------------------------
_DEFAULT_RULES = {
    # parameters
    "embed": None,
    "vocab": "model",
    "heads_x_dim": "model",
    "kv_x_dim": "model",
    "ff": "model",
    "expert": None,  # experts replicated; TP slices d_ff (DESIGN.md §5)
    "layers": None,
    "ssm_inner": "model",
    "ssm_heads": "model",
    "state_heads": "model",
    # activations
    "act_batch": ("pod", "data"),
    "act_seq": "model",
    "act_embed": None,
    # decode caches
    "cache_batch": ("pod", "data"),
    "cache_seq": None,
    "cache_kv": "model",
}

RULE_PRESETS = {
    "default": dict(_DEFAULT_RULES),
    # ZeRO-3 style: additionally shard the embed (fan-in) dim of every
    # weight over the data axis; activations keep the default layout.
    "zero3": {**_DEFAULT_RULES, "embed": "data"},
}


# ---------------------------------------------------------------------------
# mesh + rules context
# ---------------------------------------------------------------------------
class _Ctx(threading.local):
    def __init__(self):
        self.mesh = None
        self.rules = None
        # fallback log: tuples of (logical_axis, mesh_axis, reason).  Kept
        # after the context exits so the dry-run can harvest it.
        self.log = []


_CTX = _Ctx()


def active_mesh():
    """The mesh installed by :func:`use_mesh_rules`, or None."""
    return _CTX.mesh


def active_rules():
    """The rule table installed by :func:`use_mesh_rules` (default preset
    when none was given)."""
    return _CTX.rules if _CTX.rules is not None else RULE_PRESETS["default"]


@contextlib.contextmanager
def use_mesh_rules(mesh, rules=None):
    """Install (mesh, rules) as the active sharding policy.

    ``rules=None`` means the ``default`` preset with resolve-time
    divisibility fallback only; pass :func:`rules_for` output for the
    config-aware head-granularity policy.  The fallback log is reset on
    entry and *kept* on exit (the dry-run reads it after compiling).
    """
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, dict(rules) if rules is not None else None
    _CTX.log = []
    try:
        yield mesh
    finally:
        _CTX.mesh, _CTX.rules = prev


# ---------------------------------------------------------------------------
# small mesh utilities (work on jax.sharding.Mesh and any duck-typed object
# with .axis_names / .shape — tests use a FakeMesh)
# ---------------------------------------------------------------------------
def axis_names(mesh) -> tuple:
    return tuple(mesh.axis_names)


def shape(mesh) -> dict:
    return dict(mesh.shape)


def devices(mesh):
    return getattr(mesh, "devices", None)


def split(mesh, axis: str, sizes, names):
    """Split one mesh axis into several (e.g. ``data=32`` → ``pod=2 ×
    data=16``); returns a new Mesh over the same devices."""
    sizes, names = tuple(sizes), tuple(names)
    old_names = axis_names(mesh)
    if axis not in old_names:
        raise ValueError(f"mesh has no axis {axis!r} (has {old_names})")
    msh = shape(mesh)
    prod = 1
    for s in sizes:
        prod *= s
    if prod != msh[axis]:
        raise ValueError(f"cannot split {axis}={msh[axis]} into {sizes}")
    new_shape, new_names = [], []
    for n in old_names:
        if n == axis:
            new_shape.extend(sizes)
            new_names.extend(names)
        else:
            new_shape.append(msh[n])
            new_names.append(n)
    return jax.sharding.Mesh(
        mesh.devices.reshape(tuple(new_shape)), tuple(new_names)
    )


def _mesh_axis_size(mesh, value) -> int:
    """Product of the sizes of the mesh axes a rule value refers to (axes
    missing from the mesh contribute 1)."""
    if value is None:
        return 1
    msh = shape(mesh)
    parts = value if isinstance(value, tuple) else (value,)
    size = 1
    for a in parts:
        size *= msh.get(a, 1)
    return size


# ---------------------------------------------------------------------------
# config-aware rules
# ---------------------------------------------------------------------------
def rules_for(cfg, mesh, base=None) -> dict:
    """Specialize a rule table to (config, mesh).

    Head-granularity policy: a logical axis that would be split below one
    whole unit (attention head, kv head, SSD state head) is replicated
    instead — sub-head shards break the GQA/SSD math and (EbV invariant)
    cannot be equal whole work units.  Per-dimension *size* divisibility is
    additionally enforced later by :func:`resolve_spec`.
    """
    rules = dict(base if base is not None else active_rules())
    rules.update(dict(getattr(cfg, "logical_rules_overrides", ()) or ()))

    def gate(name: str, units: int, what: str):
        value = rules.get(name)
        if value is None:
            return
        size = _mesh_axis_size(mesh, value)
        if size > 1 and units % size != 0:
            rules[name] = None
            _CTX.log.append(
                (name, str(value), f"{what}={units} % {size} != 0 -> replicated")
            )

    gate("heads_x_dim", cfg.num_heads, "num_heads")
    gate("kv_x_dim", cfg.num_kv_heads, "num_kv_heads")
    gate("cache_kv", cfg.num_kv_heads, "num_kv_heads")
    if getattr(cfg, "ssm_state", 0):
        gate("ssm_inner", cfg.ssm_heads, "ssm_heads")
        gate("ssm_heads", cfg.ssm_heads, "ssm_heads")
        gate("state_heads", cfg.ssm_heads, "ssm_heads")
    if getattr(cfg, "num_experts", 0):
        gate("expert", cfg.num_experts, "num_experts")
    return rules


# ---------------------------------------------------------------------------
# spec resolution
# ---------------------------------------------------------------------------
def resolve_spec(shape_, axes, *, mesh=None, rules=None) -> PartitionSpec:
    """Logical axes tuple → PartitionSpec for an array of ``shape_``.

    Per dimension: look its logical name up in the rules, drop mesh axes
    that are absent from the mesh or already used by another dimension, then
    keep the longest prefix of the remaining axes whose size product divides
    the dimension (indivisible → replicate, logged to ``_CTX.log``).
    """
    mesh = mesh if mesh is not None else active_mesh()
    if mesh is None:
        return PartitionSpec()
    rules = rules if rules is not None else active_rules()
    dims = tuple(shape_)
    ax = tuple(axes)
    if len(ax) < len(dims):
        ax = ax + (None,) * (len(dims) - len(ax))
    elif len(ax) > len(dims):
        raise ValueError(f"axes {ax} longer than shape {dims}")
    msh = shape(mesh)
    used: set = set()
    entries = []
    for dim, name in zip(dims, ax):
        value = rules.get(name) if name is not None else None
        parts = value if isinstance(value, tuple) else ((value,) if value else ())
        keep, prod = [], 1
        for a in parts:
            if a not in msh or a in used:
                continue
            if msh[a] == 1:
                continue  # size-1 axes add nothing; keep specs minimal
            if dim % (prod * msh[a]) == 0:
                keep.append(a)
                prod *= msh[a]
            else:
                _CTX.log.append(
                    (str(name), a, f"dim {dim} % {prod * msh[a]} != 0 -> replicated")
                )
                break  # prefix semantics: drop this axis and everything after
        used.update(keep)
        entries.append(None if not keep else (keep[0] if len(keep) == 1 else tuple(keep)))
    while entries and entries[-1] is None:
        entries.pop()
    return PartitionSpec(*entries)


def constrain(x, axes):
    """``with_sharding_constraint`` by logical axes; identity when no mesh
    context is active (single-device smoke paths)."""
    mesh = active_mesh()
    if mesh is None or not isinstance(mesh, jax.sharding.Mesh):
        return x
    spec = resolve_spec(x.shape, axes, mesh=mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# (array, axes)-pair pytree helpers
# ---------------------------------------------------------------------------
def _is_axes(x) -> bool:
    return isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x
    )


def _is_pair(x) -> bool:
    return (
        isinstance(x, tuple)
        and len(x) == 2
        and _is_axes(x[1])
        and not isinstance(x[0], (tuple, str))
    )


def split_axes(tree):
    """Split an init-style pytree whose leaves are ``(array, logical_axes)``
    pairs into (arrays_tree, axes_tree).  Bare array leaves get all-None
    axes of matching rank."""
    flat, treedef = jax.tree.flatten(tree, is_leaf=_is_pair)
    arrays, axes = [], []
    for leaf in flat:
        if _is_pair(leaf):
            arrays.append(leaf[0])
            axes.append(leaf[1])
        else:
            arrays.append(leaf)
            axes.append((None,) * getattr(leaf, "ndim", 0))
    return treedef.unflatten(arrays), treedef.unflatten(axes)


def prepend_axis(axes_tree, name: str):
    """Prepend a logical axis name to every axes tuple in a tree (layer
    stacking: per-layer axes → scanned-stack axes)."""
    return jax.tree.map(
        lambda ax: (name,) + tuple(ax), axes_tree, is_leaf=_is_axes
    )


# ---------------------------------------------------------------------------
# shard_map
# ---------------------------------------------------------------------------
def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with keyword-only specs; every repo call site goes
    through here."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma
    )
