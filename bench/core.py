"""The harness: finds each part of a cell by its name, runs the cell once,
and reduces the run to its metrics and its correctness readings.

Layout, all under ``bench/``, each part found by the name that
``BENCHMARK.json`` or a data file gives it:

- ``configs/<config>.json``: sizes, precision, limits, generator, reference;
- ``traffic/<mix>.json``: the mix's parameters, read by ``loops/<loop>.py``;
- ``generators/<generator>.py``: operators and right-hand sides from a key,
  and ``STRUCTURE`` (``"dense"``, or ``"banded"`` with ``bw(config)``);
- ``metrics/<metric>.py``: ``value(run)`` of one metric, or ``None``;
- ``work/<op>.py``: ``count(n, bw, k, itemsize)`` → least FLOPs and bytes;
- ``peaks.json``: published peaks keyed by ``device_kind``.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import re
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")

# key streams: every operand of a run is drawn from (seed, stream, index)
OPERATOR, RHS, WARM = 1, 2, 3


class NoChip(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# finding parts by name
# ---------------------------------------------------------------------------
def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def part_path(root: str, kind: str, name: str, ext: str) -> str:
    if not NAME.match(name):
        raise ValueError(f"{kind} name {name!r} is not a valid name")
    return os.path.join(root, "bench", kind, name + ext)


def data(root: str, kind: str, name: str) -> dict:
    with open(part_path(root, kind, name, ".json")) as f:
        return json.load(f)


_MODULES: dict[str, object] = {}


def module(root: str, kind: str, name: str):
    path = part_path(root, kind, name, ".py")
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}".replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {[w['name'] for w in bm['workloads']]}")


def metrics_of(bm: dict, cell: str, section: str) -> list[dict]:
    """The metrics of ``section`` that ``cell`` reports."""
    return [m for m in bm[section] if cell in m.get("workloads", [cell])]


def peaks(root: str, device_kind: str) -> dict:
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def bandwidth(generator, config: dict) -> int:
    """Half-bandwidth of the generator's operators, 0 for dense ones."""
    return generator.bw(config) if generator.STRUCTURE == "banded" else 0


def key_of(seed: int):
    """A PRNG key from any whole number (seeds may pass 32 bits)."""
    import jax.numpy as jnp

    words = np.random.SeedSequence(int(seed) % 2**64).generate_state(2, dtype=np.uint32)
    return jnp.asarray(words)


def fold(key, *path):
    import jax

    for p in path:
        key = jax.random.fold_in(key, p)
    return key


# ---------------------------------------------------------------------------
# compilations, counted through JAX's monitoring events
# ---------------------------------------------------------------------------
class Compiles:
    """Counts lowerings and backend compilations while it is installed."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.count = {e: 0 for e in self.EVENTS}

    def __call__(self, event, duration, **_):
        if event in self.count:
            self.count[event] += 1

    @contextlib.contextmanager
    def installed(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        try:
            yield self
        finally:
            jax.monitoring.unregister_event_duration_listener(self)


# ---------------------------------------------------------------------------
# one run of one cell
# ---------------------------------------------------------------------------
class Run:
    """What one run records: host spans and the registry dispatches made
    inside each, the window, the answers to check, counts and latencies."""

    def __init__(self, root, cell, config, traffic, generator, solver, seed, seconds, trace):
        self.root, self.cell, self.config, self.traffic = root, cell, config, traffic
        self.generator, self.solver = generator, solver
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.key = key_of(seed)
        self.spans: list[tuple[str, float, float]] = []
        self.calls: list[dict] = []
        self.answers: list[tuple] = []
        self.latencies_s: list[float] = []
        self.attempted = self.failed = 0
        self.window = (0.0, 0.0)
        self.measuring = False
        self.setup_s = float("nan")
        self.reduced: dict | None = None
        self.device_kind = ""
        self.devices: list = []  # the cell's chips, set by ``run.run_cell``
        self.notes: list[str] = []
        self._active = None

    # -- spans and dispatches ------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        """A host span; the caller blocks on the call's outputs inside it, so
        the device work it caused falls inside the span."""
        import jax

        prev, self._active = self._active, name
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            t1 = time.perf_counter()
            self._active = prev
            if self.measuring:
                self.spans.append((name, t0, t1))

    def on_dispatch(self, problem, backend):
        if self.measuring:
            self.calls.append({
                "span": self._active, "op": problem.op, "structure": problem.structure,
                "n": problem.n, "bw": problem.bw, "k": problem.rhs, "dtype": problem.dtype,
                "devices": problem.devices, "backend": backend.name,
            })

    def start_window(self) -> float:
        self.measuring = True
        self.window = (time.perf_counter(), 0.0)
        return self.window[0]

    def end_window(self):
        self.window = (self.window[0], time.perf_counter())
        self.measuring = False

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def past_window(self) -> bool:
        return time.perf_counter() - self.window[0] >= self.seconds

    def note(self, line: str):
        self.notes.append(line)

    def sample(self, count: int) -> list[int]:
        """Indices of the answers to check: ``check_sample`` of them drawn
        from the seed, or all of them."""
        want = self.traffic.get("check_sample", count)
        if want >= count:
            return list(range(count))
        rng = np.random.default_rng([int(self.seed) % 2**63, 7])
        return sorted(int(i) for i in rng.choice(count, size=want, replace=False))

    # -- what the metrics read ----------------------------------------------
    def spans_named(self, name: str) -> list[float]:
        return [t1 - t0 for n, t0, t1 in self.spans if n == name]

    def work(self, call: dict) -> tuple[float, float]:
        op = f"{call['structure']}_{'lu' if call['op'] == 'factor' else 'solve'}"
        itemsize = int(np.dtype(call["dtype"]).itemsize)
        return module(self.root, "work", op).count(n=call["n"], bw=call["bw"], k=call["k"], itemsize=itemsize)

    def peaks(self) -> dict:
        return peaks(self.root, self.device_kind)


def roofline(run: Run, span: str, op: str, structure: str, name: str):
    """Least time of the ``op`` calls made inside ``span`` spans over the
    device busy time inside those spans, in %.  ``None`` when there is
    nothing to read.  A call counts the work of its whole problem, whatever
    implements it, spread over the chips it spans; busy time is the mean
    over the cell's chips."""
    calls = [c for c in run.calls if c["span"] == span and c["op"] == op and c["structure"] == structure]
    if not calls or run.reduced is None:
        return None
    busy = run.reduced["busy_in_s"].get(span, 0.0)
    if busy <= 0.0:
        return None
    pk = run.peaks()
    t_flops = t_bytes = t_min = 0.0
    for c in calls:
        flops, nbytes = run.work(c)
        t_flops += flops / pk["flops_bf16"] / c["devices"]
        t_bytes += nbytes / pk["hbm_bytes_s"] / c["devices"]
        t_min += max(flops / pk["flops_bf16"], nbytes / pk["hbm_bytes_s"]) / c["devices"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    run.note(f"{name}: {len(calls)} calls, bound={bound}, flops_time_s={t_flops!r} "
             f"bytes_time_s={t_bytes!r} device_busy_in_{span}_s={busy!r}")
    return 100.0 * t_min / busy


def mfu(run: Run, ops: tuple[str, ...], name: str):
    """Useful FLOPs of the window's ``ops`` calls over the window and the
    bf16 peak of the cell's chips, in %."""
    calls = [c for c in run.calls if c["op"] in ops]
    flops = sum(run.work(c)[0] for c in calls)
    if not flops or run.window_s <= 0:
        return None
    run.note(f"{name}: {len(calls)} calls, useful_flops={flops!r} window_s={run.window_s!r}")
    return 100.0 * flops / run.window_s / (len(run.devices) * run.peaks()["flops_bf16"])


def idle_share(run: Run):
    if run.reduced is None:
        return None
    return 100.0 * run.reduced["idle_share"]
