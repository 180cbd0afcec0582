"""Span-based phase tracing with Chrome trace-event export.

EFFICIENTIMM's wins came from *attributing* time to phases; this tracer
makes the same attribution a first-class runtime artifact instead of a
per-benchmark hand-rolled timer.  A span is one timed phase:

    with tracer.span("sample", tier="engine", sampler="IC/dense"):
        visited, counter, _ = sample(key)

Spans nest naturally (a ``store.write`` span inside an ``extend`` span
inside a ``run`` span), are tracked per thread (a `threading.local`
stack gives each span its depth and parent), and are recorded
host-side only on ``__exit__`` — one ``perf_counter_ns`` pair and one
locked list append per span, nothing inside ``jax.jit``.

**Clock.**  ``ts`` is microseconds since the Unix epoch on the realtime
clock, the clock a JAX profiler capture stamps its host and device
events with: the tracer reads ``time.time_ns()`` once when it is made
and adds ``perf_counter_ns`` deltas to it, so spans keep the monotonic
clock's resolution and share the profiler's origin.  A ``.xplane.pb``
stores its events relative to its ``profile_start_time``; a span lies at
``ts * 1e3 - profile_start_time`` on that timeline.

**Device values.**  A span argument may be a device scalar (a
``jax.Array``), or a `Deferred` scalar times a host integer.  The
tracer holds the reference and never reads it while the run goes on;
`chrome_trace` and `events` resolve every held value once, all in one
``jax.device_get``, after the work that produced them.  Arguments known
only inside the span go in through `Span.set`.

Export is the Chrome trace-event format (``ph: "X"`` complete events
with microsecond ``ts``/``dur``), the JSON Perfetto and
``chrome://tracing`` load directly: `chrome_trace()` returns the dict,
`write(path)` dumps it.  Events carry ``cat`` = the instrumented tier
(``engine`` / ``store`` / ``stream`` / ``serve`` / ``host`` /
``bench``), so trace consumers (and the CI gate
``scripts/check_obs.py``) can assert per-tier coverage, and ``args``
carries the span's labels plus its nesting ``depth`` and ``parent``
span name.

The optional **device bridge** (``jax_annotations=True``) additionally
enters a ``jax.profiler.TraceAnnotation(name)`` for every span, so when
a JAX device profile is captured alongside, the device timeline carries
the same phase names as the host spans and the two line up in Perfetto.
The bridge changes nothing about what executes — annotations are
metadata on the trace, never on the computation.
"""
from __future__ import annotations

import collections
import json
import sys
import threading
import time

#: Phase names the instrumented tiers emit (a catalog, not a closed
#: set — user spans may use any name).  See docs/observability.md.
PHASES = (
    "run", "round", "extend", "sample", "store.write", "store.grow",
    "count", "select", "influence", "collective", "compute", "delta",
    "refresh", "admission", "cache", "serve.batch", "replica.sync",
    "flush", "host.gc",
)


class Deferred:
    """A device scalar times a host integer, resolved at export as a
    Python int (``int(value) * scale``): a span argument whose product
    may pass 2**31 though its device factor fits int32."""

    __slots__ = ("value", "scale")

    def __init__(self, value, scale: int = 1):
        self.value = value
        self.scale = int(scale)


def _is_device_value(v) -> bool:
    """Whether ``v`` is held until export: a `Deferred` or a
    ``jax.Array`` (none can exist before jax is imported)."""
    if isinstance(v, Deferred):
        return True
    jax = sys.modules.get("jax")
    return jax is not None and isinstance(v, jax.Array)


class Span:
    """One in-flight phase; a context manager handed out by `Tracer.span`."""

    __slots__ = ("tracer", "name", "tier", "args", "t0", "depth",
                 "parent", "_ann")

    def __init__(self, tracer: "Tracer", name: str, tier: str, args: dict):
        self.tracer = tracer
        self.name = name
        self.tier = tier
        self.args = args
        self.t0 = 0
        self.depth = 0
        self.parent = ""
        self._ann = None

    def __enter__(self) -> "Span":
        stack = self.tracer._stack()
        self.depth = len(stack)
        self.parent = stack[-1].name if stack else ""
        stack.append(self)
        if self.tracer._annotate is not None:
            self._ann = self.tracer._annotate(self.name)
            self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        stack = self.tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        self.tracer._record(self, t1)
        return False

    def set(self, **args) -> None:
        """Add arguments to the span before it closes (values known
        only after its work was issued, device scalars among them)."""
        self.args.update(args)


class Tracer:
    """Collects completed spans; exports Chrome trace-event JSON.

    ``max_events`` bounds memory on indefinite serving runs: past it the
    oldest events are dropped (the count is reported in ``dropped``).
    """

    def __init__(self, *, jax_annotations: bool = False,
                 max_events: int = 1 << 20):
        # reentrant: a ``host.gc`` span may record from a collection that
        # starts while this thread holds the lock
        self._lock = threading.RLock()
        self._events: list[dict] = []
        # the events with unresolved device args, a subsequence of
        # _events in the same order
        self._held: collections.deque = collections.deque()
        self._local = threading.local()
        self._epoch_ns = time.perf_counter_ns()
        self._wall_ns = time.time_ns()
        self.max_events = int(max_events)
        self.dropped = 0
        self._annotate = None
        if jax_annotations:
            from jax.profiler import TraceAnnotation
            self._annotate = TraceAnnotation

    # ------------------------------------------------------------ record

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, *, tier: str = "", **args) -> Span:
        """A context manager timing one phase (see module docstring)."""
        return Span(self, name, tier, args)

    def _record(self, span: Span, t1_ns: int) -> None:
        ev = {
            "name": span.name,
            "cat": span.tier or "user",
            "ph": "X",
            # microseconds since the Unix epoch (module docstring)
            "ts": (self._wall_ns + span.t0 - self._epoch_ns) / 1e3,
            "dur": (t1_ns - span.t0) / 1e3,
            "pid": 0,
            "tid": threading.get_ident() & 0x7FFFFFFF,
            "args": {**span.args, "depth": span.depth,
                     "parent": span.parent},
        }
        held = any(_is_device_value(v) for v in span.args.values())
        with self._lock:
            self._events.append(ev)
            if held:
                self._held.append(ev)
            if len(self._events) > self.max_events:
                drop = len(self._events) - self.max_events
                for old in self._events[:drop]:
                    if self._held and self._held[0] is old:
                        self._held.popleft()
                del self._events[:drop]
                self.dropped += drop

    def _resolve(self) -> None:
        """Replace every held device value by its Python number, all in
        one ``jax.device_get`` (called under the lock, at export)."""
        held, self._held = self._held, collections.deque()
        if not held:
            return
        import jax
        keys = [(ev, k) for ev in held for k, v in ev["args"].items()
                if _is_device_value(v)]
        got = jax.device_get([
            v.value if isinstance(v, Deferred) else v
            for v in (ev["args"][k] for ev, k in keys)])
        for (ev, k), x in zip(keys, got):
            v = ev["args"][k]
            ev["args"][k] = (int(x) * v.scale if isinstance(v, Deferred)
                             else x.tolist())

    # ------------------------------------------------------------ export

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def events(self, name: str = None, tier: str = None) -> list[dict]:
        """Completed span events (copies), optionally filtered; held
        device values are resolved first."""
        with self._lock:
            self._resolve()
            evs = list(self._events)
        if name is not None:
            evs = [e for e in evs if e["name"] == name]
        if tier is not None:
            evs = [e for e in evs if e["cat"] == tier]
        return evs

    def durations_s(self, name: str, tier: str = None) -> list[float]:
        """Every completed ``name`` span's duration in seconds, in
        completion order — the registry-snapshot analogue of a hand
        timer list (BENCH emitters consume this)."""
        return [e["dur"] / 1e6 for e in self.events(name, tier)]

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._held.clear()
            self.dropped = 0

    def chrome_trace(self) -> dict:
        """The trace as a Chrome trace-event dict: load the written JSON
        in Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``.
        Held device values are resolved here, once."""
        with self._lock:
            self._resolve()
            events = list(self._events)
            dropped = self.dropped
        meta = [{
            "name": "process_name", "ph": "M", "pid": 0, "tid": 0,
            "args": {"name": "repro-imtrace"},
        }]
        return {"traceEvents": meta + events,
                "displayTimeUnit": "ms",
                "otherData": {"dropped_events": dropped}}

    def write(self, path: str) -> str:
        """Dump `chrome_trace` as JSON; returns ``path``."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f, indent=1)
        return path
