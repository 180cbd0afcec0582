"""The benchmark's driver: finds a cell's files by name, builds it,
times its window, checks its answers and reduces its trace.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``configs[].file``: the deployment (graph, diffusion model, k, eps,
  ell, store, theta per arena, batch);
* ``bench/traffic/<traffic>.json``: the traffic mix, a data file whose
  ``kind`` names the driver in ``bench/kinds/<kind>.py``;
* ``bench/metrics/<metric>.py``: one per-layer metric, a ``read(run)``
  that returns a number or None when the run has nothing to read.

A cell that is not (yet) in ``BENCHMARK.json`` may be parked in
``bench/parked/<cell>.json``: the entries it would add there, under the
same section names.  `load_cell` reads them only for a name that
``BENCHMARK.json`` lacks, so the command and the tests can run a parked
cell; the driver never does.

A kind module provides ``setup(cell, seed, log) -> state``,
``window(state, clock) -> Window`` and ``check(state, window, *,
control=False) -> (checks, wrong, control_checks)``: each number
compared as ``{name: (value, limit)}``, the answers found wrong, and
with ``control`` the same numbers with the cell's control (the
reference one precision lower) in the program's place.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from types import ModuleType

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "bench")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str) -> ModuleType:
    """Import the file at ``path`` as a module (metric files have dots
    in their names, so they are loaded by path)."""
    spec = importlib.util.spec_from_file_location(
        "bench_file_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    kind: ModuleType
    end_to_end: list        # metric entries this cell reports
    per_layer: list
    readers: dict           # per-layer metric name -> module


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, *, root: str = ROOT, overrides: dict = None
              ) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files.
    ``overrides`` replaces top-level configuration keys (tests use it to
    run a cell at a size a CPU holds)."""
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    parked = os.path.join(root, "bench", "parked", name + ".json")
    if name not in cells and os.path.isfile(parked):
        for section, entries in read_json(parked).items():
            bench[section] = bench[section] + entries
        cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = read_json(os.path.join(root, cfg_entry["file"]))
    config.update(overrides or {})
    bdir = os.path.join(root, "bench")
    traffic = read_json(os.path.join(bdir, "traffic", w["traffic"] + ".json"))
    kind = load_module(os.path.join(bdir, "kinds", traffic["kind"] + ".py"),
                       "kind_" + traffic["kind"])
    per_layer = [m for m in bench["per_layer"] if _applies(m, name)]
    readers = {m["name"]: load_module(
        os.path.join(bdir, "metrics", m["name"] + ".py"), m["name"])
        for m in per_layer}
    return Cell(name, int(w["chips"]), config, traffic, kind,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                per_layer, readers)


def engine_config(config: dict, seed: int):
    """The engine's `IMMConfig` for a deployment file."""
    from repro.core.engine import IMMConfig
    return IMMConfig(k=int(config["k"]), eps=float(config["eps"]),
                     ell=float(config["ell"]), model=config["model"],
                     batch=int(config["batch"]),
                     max_theta=int(config["theta_per_arena"]),
                     store=config["store"], seed=int(seed))


# ------------------------------------------------------------- window ---

class CompileCount:
    """Counts backend compilations as JAX reports them."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


class Clock:
    """The measured window.  Kinds call `start` once, `done()` between
    units of work, and `stop()` after the last unit has finished on the
    device.  In a traced run the profiler starts ``lead_s`` into the
    window and the window ends ``trace_s`` later, when the trace stops."""

    def __init__(self, seconds: float, *, trace_dir: str = None,
                 lead_s: float = 1.0, trace_s: float = 3.0):
        self.seconds = float(seconds)
        self.trace_dir = trace_dir
        self.lead_s = min(lead_s, self.seconds / 4)
        self.trace_s = trace_s
        self.t0 = self.t1 = None
        self.tracing = False
        self.traced = False
        self.traced_from = self.traced_to = None   # window clock, s
        self._ann = None

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def start(self) -> None:
        self.t0 = time.perf_counter()

    def done(self) -> bool:
        t = self.now()
        if self.trace_dir is None:
            return t >= self.seconds
        if not self.tracing and not self.traced and t >= self.lead_s:
            self._start_trace()
        elif self.tracing and t >= self.lead_s + self.trace_s:
            self._stop_trace()
        return self.traced or t >= max(self.seconds,
                                        self.lead_s + self.trace_s + 60)

    def _start_trace(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench.traced")
        self._ann.__enter__()
        self.traced_from = self.now()
        self.tracing = True

    def _stop_trace(self) -> None:
        import jax
        self.traced_to = self.now()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.tracing, self.traced = False, True

    def stop(self) -> float:
        """End the window; returns its length in seconds."""
        if self.tracing:
            self._stop_trace()
        self.t1 = time.perf_counter()
        return self.t1 - self.t0


@dataclasses.dataclass
class Window:
    metrics: dict           # end-to-end metric name -> value
    attempted: int
    failed: int = 0
    info: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader gets."""
    cell: Cell
    trace: object           # bench.trace.Trace
    window: Window
    spans: list             # the program's obs spans (Chrome events)
    device_kind: str
    state: object           # the kind's state


# ---------------------------------------------------------------- run ---

def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, control: bool = False,
        trace_dir: str = None) -> dict:
    """Set up, measure and check one run; returns the result object
    whose JSON is the last line of the command's output."""
    import jax
    from repro import obs
    from bench import trace as btrace

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = CompileCount()
    obs.reset()
    if trace:
        obs.enable(jax_annotations=True)
    own_dir = trace and trace_dir is None
    if own_dir:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    state = cell.kind.setup(cell, seed, log)
    clock = Clock(seconds, trace_dir=trace_dir if trace else None,
                  trace_s=float(cell.traffic.get("trace_seconds", 3.0)))
    c0 = compiles.n
    win = cell.kind.window(state, clock)
    setup_s = clock.t0 - t_start
    in_window = compiles.n - c0
    devices = jax.devices()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    log(f"window: {json.dumps(win.info)}")
    log(f"window_s={clock.t1 - clock.t0:.6f} compiles_in_window="
        f"{in_window} setup_s={setup_s:.6f} memory_peak_bytes={peak}")
    spans = obs.chrome_trace()["traceEvents"] if trace else []
    obs.reset()

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        tr = btrace.load(trace_dir)
        if own_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        r = Run(cell, tr, win, spans, devices[0].device_kind, state)
        metrics = {}
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(r)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        breakdown = tr.breakdown()
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for m in cell.end_to_end:
            if m["name"] in win.metrics:
                metrics[m["name"]] = {"value": float(win.metrics[m["name"]]),
                                      "unit": m["unit"]}
    checks, wrong, ctrl = cell.kind.check(state, win, control=control)
    for k, (v, lim) in (ctrl or {}).items():
        log(f"control {k}: {v} (limit {lim})")
    result = {"correct": all(v <= lim for v, lim in checks.values()),
              "attempted": int(win.attempted),
              "failed": int(win.failed + wrong),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if ctrl is not None:
        result["control"] = {k: {"value": v, "limit": lim}
                             for k, (v, lim) in ctrl.items()}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result
