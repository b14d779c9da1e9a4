"""Reduce one profiler trace (``.xplane.pb``) to device time.

- Device operations are the events on the ``XLA Ops`` lines of each
  ``/device:`` plane.  Only where the caller allows it (the CPU backend, in
  tests) are they, with no device plane, the host events that carry an
  ``hlo_op`` stat; on a chip a trace with no device plane is an error.
- Busy time is the union of the operations' intervals inside the ``window``
  span, averaged over the devices; the idle share is ``1 - busy / window``.
  Only the cell's own device planes, which the caller names, count; one of
  them that ran nothing counts as busy 0.
- Device time inside a kind of host span is the busy time that falls inside
  the spans of that name.
- The breakdown lists the device operations that took the most time, and the
  longest idle gaps, each labelled by the host span it fell in.
"""
from __future__ import annotations

import glob
import os
import warnings
from collections import defaultdict

WINDOW = "window"
TOP = 10


def latest_trace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _stats(event) -> dict:
    try:
        with warnings.catch_warnings():  # the reader's stat type warns on import
            warnings.simplefilter("ignore", DeprecationWarning)
            return dict(event.stats)
    except Exception:  # an event whose stats the reader cannot decode
        return {}


def read(path: str, planes, host_ops_allowed: bool = False):
    """``(ops_by_device, host_spans)``: for each of ``planes``, the names of
    the cell's device planes, a list of ``(name, start_ns, end_ns)``, empty
    where it ran nothing; and every host event as the same triple."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    found: dict[str, list] = {}
    host, host_ops = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            found[plane.name] = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for line in plane.lines if line.name == "XLA Ops"
                for e in line.events
            ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    host.append(span)
                    if "hlo_op" in _stats(e):
                        host_ops.append(span)
    if not any(found.values()) and host_ops_allowed and host_ops:
        return {"/host:CPU": host_ops}, host
    return {name: found.get(name, []) for name in planes}, host


def union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def covered(merged, lo: float, hi: float) -> float:
    """Length of ``merged`` (sorted, disjoint) inside ``[lo, hi]``."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def reduce(path: str, span_names, *, planes, host_ops_allowed: bool = False) -> dict:
    devices, host = read(path, planes, host_ops_allowed)
    windows = [(s, e) for name, s, e in host if name == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} span in {path}")
    w0, w1 = windows[0]
    spans = sorted((s, e, name) for name, s, e in host if name in span_names and s < w1 and e > w0)
    if not any(devices.values()):
        raise ValueError(f"no device operation in {path}")

    busy, busy_in = [], defaultdict(list)
    op_time: dict[str, float] = defaultdict(float)
    gaps = []
    for ops in devices.values():
        merged = union((max(s, w0), min(e, w1)) for _, s, e in ops)
        busy.append(covered(merged, w0, w1))
        per_kind = defaultdict(float)
        for s, e, name in spans:
            per_kind[name] += covered(merged, s, e)
        for name in span_names:
            busy_in[name].append(per_kind[name])
        for name, s, e in ops:
            op_time[name] += max(0.0, min(e, w1) - max(s, w0))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps.append((g1 - g0, _label(spans, (g0 + g1) / 2)))

    n_dev = len(devices)
    window_s = (w1 - w0) * 1e-9
    busy_s = sum(busy) / n_dev * 1e-9
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps, key=lambda g: -g[0])[:TOP]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "busy_in_s": {k: sum(v) / n_dev * 1e-9 for k, v in busy_in.items()},
        "device_ops": [[name, t / n_dev * 1e-9] for name, t in top_ops],
        "idle_gaps": [[label, g * 1e-9] for g, label in top_gaps],
    }


def _label(spans, t: float) -> str:
    inside = [name for s, e, name in spans if s <= t <= e]
    return inside[-1] if inside else "between spans"
