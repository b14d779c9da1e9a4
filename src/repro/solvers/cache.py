"""Measured autotune cache: persisted backend timings keyed by problem shape.

The selection engine (:mod:`repro.solvers.registry`) prefers *measurement*
over heuristics: when the cache holds timings for a problem close enough in
size to the one being dispatched, the fastest measured capable backend wins;
otherwise selection falls back to the static priorities (which reproduce the
pre-registry hardcoded thresholds).

The cache is one JSON file:

* ``$REPRO_SOLVERS_CACHE`` when set (tests and ``scripts/check.sh`` pin a
  repo-local file for determinism),
* ``~/.cache/repro_solvers.json`` otherwise.

It is populated by ``scripts/autotune.py`` (the ``time_shootout`` harness
from :mod:`benchmarks.common`) and *seeded* by the smoke bench
(``benchmarks/run.py --smoke`` records the shootout rows it already times,
so the committed ``BENCH_kernels.json`` and the dispatch decisions can never
silently disagree).

Nearest-size matching: a measurement only transfers to problems within
``NEAREST_MAX_RATIO`` (4x) in both ``n`` and effective band width.  Beyond
that the regimes differ too much (a 16384-order measurement says nothing
about an 96-order dispatch) and the static heuristics take over.
"""
from __future__ import annotations

import json
import math
import os
import tempfile
import warnings

from .problem import Problem

__all__ = [
    "AutotuneCache",
    "ENV_VAR",
    "NEAREST_MAX_RATIO",
    "cache_path",
    "get_cache",
    "invalidate",
]

ENV_VAR = "REPRO_SOLVERS_CACHE"
DEFAULT_USER_PATH = os.path.join("~", ".cache", "repro_solvers.json")
NEAREST_MAX_RATIO = 4.0
_VERSION = 1

# fields that identify a measurement row (rhs/batch excluded: timings are
# dominated by n/bw, and keying on every shape dimension would fragment the
# cache into single-use entries).  ``tolerance`` IS a key field: approximate
# tiers are not value-identical to the exact tier, so a measurement taken at
# a loose tolerance must never steer a tighter problem's selection (entries
# persisted before the field existed load as tolerance-0 == exact rows).
# ``devices`` is likewise a key field: the single-device and mesh-sharded
# candidate sets are disjoint (SPIKE vs replication), so a single-device
# measured win must never steer a multi-device dispatch or vice versa
# (pre-devices caches load as devices-1 == local rows).  ``device_kind`` is
# a key field too: a timing taken on the CPU (Pallas in interpret mode) says
# nothing about a TPU, so rows persisted before the field existed load as
# CPU rows and never steer a chip's dispatch.
_KEY_FIELDS = ("op", "structure", "dtype", "bw", "n", "tolerance", "devices", "device_kind")


def cache_path() -> str:
    return os.path.expanduser(os.environ.get(ENV_VAR) or DEFAULT_USER_PATH)


_KEY_DEFAULTS = {"tolerance": 0.0, "devices": 1, "device_kind": "cpu"}


def _entry_key(e: dict) -> tuple:
    # entries built by hand (tests, old tools) may omit tolerance == exact,
    # devices == 1 (single-device) and device_kind == "cpu"
    return tuple(
        e.get(f, _KEY_DEFAULTS[f]) if f in _KEY_DEFAULTS else e[f]
        for f in _KEY_FIELDS
    )


def _problem_key(p: Problem) -> tuple:
    return (p.op, p.structure, p.dtype, p.bw, p.n, float(p.tolerance), int(p.devices),
            p.device_kind)


class AutotuneCache:
    """In-memory view of the persisted measurement file."""

    def __init__(self, path: str | None = None, entries: list[dict] | None = None):
        self.path = path
        self.entries: list[dict] = entries or []

    # -- persistence --------------------------------------------------------
    @classmethod
    def load(cls, path: str) -> "AutotuneCache":
        entries: list[dict] = []
        try:
            with open(path) as f:
                raw = json.load(f)
            for e in raw.get("entries", []):
                e.setdefault("tolerance", 0.0)  # pre-tolerance caches = exact rows
                e.setdefault("devices", 1)  # pre-devices caches = local rows
                e.setdefault("device_kind", "cpu")  # pre-device-kind caches = CPU rows
                if all(f in e for f in _KEY_FIELDS) and isinstance(e.get("times_us"), dict):
                    entries.append(e)
        except FileNotFoundError:
            pass  # no cache yet == empty cache
        except (OSError, ValueError, AttributeError, TypeError, KeyError) as err:
            # truncated write, hand-edited file, or a JSON document of the
            # wrong shape: warn (a silently-vanished cache looks like a perf
            # regression) and start empty — static priorities take over until
            # fresh measurements land.
            warnings.warn(
                f"autotune cache {path!r} is unreadable "
                f"({type(err).__name__}: {err}); starting with an empty cache",
                RuntimeWarning,
                stacklevel=2,
            )
            entries = []
        return cls(path=path, entries=entries)

    def save(self, path: str | None = None) -> str:
        path = path or self.path or cache_path()
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        payload = {"version": _VERSION, "entries": self.entries}
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(os.path.abspath(path)), suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
                f.write("\n")
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        self.path = path
        return path

    # -- recording ----------------------------------------------------------
    def record(self, problem: Problem, times_us: dict[str, float]) -> dict:
        """Merge backend timings for ``problem``'s shape key; returns the
        entry.  Existing timings for the same backend are overwritten (latest
        measurement wins)."""
        key = _problem_key(problem)
        for e in self.entries:
            if _entry_key(e) == key:
                e["times_us"].update({k: round(float(v), 2) for k, v in times_us.items()})
                return e
        entry = dict(zip(_KEY_FIELDS, key))
        entry["times_us"] = {k: round(float(v), 2) for k, v in times_us.items()}
        self.entries.append(entry)
        return entry

    def record_widths(self, problem: Problem, width_us: dict[int, float]) -> dict:
        """Merge stacked-RHS coalescing-width timings (width → measured µs
        per dispatch at that width) into ``problem``'s entry.  Consumed by
        :meth:`best_width` — the serve layer's coalescing-width cap."""
        key = _problem_key(problem)
        for e in self.entries:
            if _entry_key(e) == key:
                entry = e
                break
        else:
            entry = dict(zip(_KEY_FIELDS, key))
            entry["times_us"] = {}
            self.entries.append(entry)
        entry.setdefault("width_us", {}).update(
            {str(int(w)): round(float(v), 2) for w, v in width_us.items()}
        )
        return entry

    def record_page_sizes(self, problem: Problem, page_us: dict[int, float]) -> dict:
        """Merge KV-cache page-size timings (page size → measured paged-serve
        µs at that size) into ``problem``'s entry (op="decode",
        structure="paged_kv", n=max_len).  Consumed by
        :meth:`best_page_size` — the serving engine's default page size."""
        key = _problem_key(problem)
        for e in self.entries:
            if _entry_key(e) == key:
                entry = e
                break
        else:
            entry = dict(zip(_KEY_FIELDS, key))
            entry["times_us"] = {}
            self.entries.append(entry)
        entry.setdefault("page_us", {}).update(
            {str(int(p)): round(float(v), 2) for p, v in page_us.items()}
        )
        return entry

    # -- lookup -------------------------------------------------------------
    def lookup(self, problem: Problem) -> dict | None:
        key = _problem_key(problem)
        for e in self.entries:
            if _entry_key(e) == key:
                return e
        return None

    def _matches(self, problem: Problem) -> list[tuple[float, dict]]:
        out = []
        for e in self.entries:
            # exact match on every non-size key — in particular tolerance,
            # devices and device kind: nearest-size transfer interpolates
            # over *speed*, never over *accuracy tier* (a loose-tolerance
            # win must not leak into a tight dispatch), *device count* (the
            # single-device and mesh-sharded candidate sets are disjoint) or
            # *device kind* (a CPU interpret-mode timing is no TPU timing).
            if (
                e["op"], e["structure"], e["dtype"],
                e.get("tolerance", 0.0), e.get("devices", 1),
                e.get("device_kind", "cpu"),
            ) != (
                problem.op, problem.structure, problem.dtype,
                float(problem.tolerance), int(problem.devices),
                problem.device_kind,
            ):
                continue
            n_ratio = max(e["n"], problem.n) / max(min(e["n"], problem.n), 1)
            bwa, bwb = e["bw"] + 1, problem.bw + 1
            bw_ratio = max(bwa, bwb) / min(bwa, bwb)
            if n_ratio > NEAREST_MAX_RATIO or bw_ratio > NEAREST_MAX_RATIO:
                continue
            out.append((math.log(n_ratio) + math.log(bw_ratio), e))
        out.sort(key=lambda t: t[0])
        return out

    def best(self, problem: Problem, candidates: list[str]) -> str | None:
        """Fastest measured backend among ``candidates`` for the nearest
        matching measurement, or None when nothing transferable exists."""
        for _, e in self._matches(problem):
            times = {k: v for k, v in e["times_us"].items() if k in candidates}
            if times:
                return min(times, key=times.get)
        return None

    def best_width(self, problem: Problem) -> int | None:
        """Measured-best coalescing width (most µs-per-column efficient) for
        the nearest matching stacked-RHS sweep, or None when nothing
        transferable was measured — callers fall back to full coalescing."""
        for _, e in self._matches(problem):
            wu = e.get("width_us")
            if wu:
                return int(min(wu, key=lambda w: wu[w] / int(w)))
        return None

    def best_page_size(self, problem: Problem) -> int | None:
        """Measured-fastest KV page size for the nearest matching paged-serve
        sweep, or None when nothing transferable was measured — the engine
        falls back to its built-in default."""
        for _, e in self._matches(problem):
            pu = e.get("page_us")
            if pu:
                return int(min(pu, key=pu.get))
        return None


# ---------------------------------------------------------------------------
# module-level cache with mtime-based reload (the autotune script and the
# smoke bench write the file mid-process; dispatch must see fresh data)
# ---------------------------------------------------------------------------
_loaded: tuple[str, float, AutotuneCache] | None = None


def _mtime(path: str) -> float:
    try:
        return os.stat(path).st_mtime
    except OSError:
        return -1.0


def get_cache() -> AutotuneCache:
    global _loaded
    path = cache_path()
    mt = _mtime(path)
    if _loaded is not None and _loaded[0] == path and _loaded[1] == mt:
        return _loaded[2]
    cache = AutotuneCache.load(path)
    _loaded = (path, mt, cache)
    return cache


def invalidate() -> None:
    """Drop the module-level cache (tests that swap ``$REPRO_SOLVERS_CACHE``)."""
    global _loaded
    _loaded = None
