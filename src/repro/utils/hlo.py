"""HLO-text analysis for the roofline: collective-op byte accounting.

``cost_analysis()`` has no collective-bytes entry, so we parse the compiled
HLO module text.  Optimized HLO references operands by name (no inline
shapes), so per-op bytes are derived from the *result* shape + the replica
group size ``g``:

    op                  operand bytes        wire bytes/device (ring)
    all-gather          result / g           result · (g−1)/g
    reduce-scatter      result · g           result · (g−1)   [operand=result·g]
    all-reduce          result               2 · result · (g−1)/g
    all-to-all          result               result · (g−1)/g
    collective-permute  result               result

"operand bytes" is the paper-brief accounting (sum of operand sizes);
"wire bytes" is the per-device transported estimate used for the roofline
collective term.
"""
from __future__ import annotations

import re
from collections import defaultdict

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")
_OP_SPLIT_RE = re.compile(r"\s(" + "|".join(_COLLECTIVES) + r")(-start)?\(")
_GROUPS_ITOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _group_size(tail: str, num_devices: int) -> int:
    m = _GROUPS_ITOTA_RE.search(tail)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(tail)
    if m:
        ids = [x for x in m.group(1).split(",") if x.strip()]
        return max(len(ids), 1)
    return num_devices


_COMP_NAME_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)")
_BODY_RE = re.compile(r"body=%([\w.\-]+)")
_COND_RE = re.compile(r"condition=%([\w.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count\\?":\{\\?"n\\?":\\?"(\d+)')
_CALLS_RE = re.compile(r"(?:calls|to_apply)=%([\w.\-]+)")


def computation_multipliers(hlo_text: str) -> tuple[dict, dict]:
    """Execution count of each HLO computation, derived from while
    ``known_trip_count`` annotations (scan bodies execute trip-count times —
    XLA's static cost analysis counts them once).

    Computation headers sit at column 0 and end with '{'; instructions are
    indented.  Returns (multiplier per computation name, lines per comp)."""
    comps: dict[str, list[str]] = {}
    entry = None
    cur = None
    for raw in hlo_text.splitlines():
        if not raw.strip():
            continue
        s = raw.strip()
        if not raw[0].isspace():
            if s.rstrip().endswith("{"):
                m = _COMP_NAME_RE.match(s)
                if m and m.group(2) != "HloModule":
                    cur = m.group(2)
                    comps[cur] = []
                    if m.group(1):
                        entry = cur
                continue
            if s == "}":
                cur = None
                continue
        if s == "}":
            cur = None
            continue
        if cur is not None:
            comps[cur].append(s)

    edges: dict[str, list[tuple[str, int]]] = defaultdict(list)
    for cname, lines in comps.items():
        for l in lines:
            n = 1
            tm = _TRIP_RE.search(l)
            if " while(" in l and tm:
                n = int(tm.group(1))
            for rex in (_BODY_RE, _COND_RE, _CALLS_RE):
                for target in rex.findall(l):
                    edges[cname].append((target, n))

    mult: dict[str, float] = defaultdict(float)
    if entry is None:
        entry = next(iter(comps), None)
    if entry is not None:
        mult[entry] = 1.0
        # relax in passes (call graph is a DAG; few levels deep)
        for _ in range(32):
            changed = False
            new = defaultdict(float)
            new[entry] = 1.0
            for parent, targets in edges.items():
                for child, n in targets:
                    new[child] += mult[parent] * n
            if dict(new) != dict(mult):
                mult = new
                changed = True
            if not changed:
                break
    return dict(mult), comps


def collective_bytes(hlo_text: str, *, num_devices: int = 1, weighted: bool = True) -> dict:
    """Per-collective-kind operand bytes + per-device wire-byte estimate.
    With ``weighted=True`` each op is multiplied by its computation's
    execution count (scan trip counts)."""
    operand: dict = defaultdict(float)
    wire: dict = defaultdict(float)
    counts: dict = defaultdict(float)
    if weighted:
        mult, comps = computation_multipliers(hlo_text)
        items = [(l, mult.get(c, 1.0)) for c, lines in comps.items() for l in lines]
    else:
        items = [(l.strip(), 1.0) for l in hlo_text.splitlines()]
    for stripped, weight in items:
        if "=" not in stripped or "-done(" in stripped:
            continue
        m = _OP_SPLIT_RE.search(stripped)
        if m is None:
            continue
        kind = m.group(1)
        left, tail = stripped[: m.start()], stripped[m.end() :]
        result = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(left))
        if result == 0:
            continue
        g = max(_group_size(tail, num_devices), 1)
        if kind == "all-gather":
            op_b, wire_b = result / g, result * (g - 1) / g
        elif kind == "reduce-scatter":
            op_b, wire_b = result * g, result * (g - 1)
        elif kind == "all-reduce":
            op_b, wire_b = result, 2 * result * (g - 1) / g
        elif kind == "all-to-all":
            op_b, wire_b = result, result * (g - 1) / g
        else:  # collective-permute
            op_b, wire_b = result, float(result)
        operand[kind] += op_b * weight
        wire[kind] += wire_b * weight
        counts[kind] += weight
    return {
        "operand_bytes": {k: round(v) for k, v in operand.items()},
        "wire_bytes": {k: round(v) for k, v in wire.items()},
        "counts": {k: round(v) for k, v in counts.items()},
        "total": round(sum(operand.values())),
        "total_wire": round(sum(wire.values())),
    }


def op_histogram(hlo_text: str, ops=("fusion", "custom-call", "while", "dot", "convolution")) -> dict:
    hist = defaultdict(int)
    for line in hlo_text.splitlines():
        for op in ops:
            if f" {op}(" in line:
                hist[op] += 1
    return dict(hist)


def primitive_count(jaxpr, name: str) -> int:
    """Count occurrences of primitive ``name`` in a (closed) jaxpr, recursing
    into sub-jaxprs (cond/scan/while/pjit bodies).  Used to assert dispatch
    counts — e.g. the single-dispatch LU driver must trace to exactly one
    ``pallas_call``."""
    from jax.extend.core import ClosedJaxpr, Jaxpr  # local: keep module import-light

    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            count += 1
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(sub, (Jaxpr, ClosedJaxpr)):
                    count += primitive_count(sub, name)
    return count


def cost_analysis_dict(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a plain dict."""
    return dict(compiled.cost_analysis())
