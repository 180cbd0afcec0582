"""IMTrace — phase-timed spans + a metrics registry for every tier.

The repo-wide observability switchboard.  Instrumented code (engine,
store, stream, serve, launch, benchmarks) calls the module-level helpers
unconditionally:

    from repro import obs

    with obs.span("sample", tier="engine"):
        ...
    obs.counter("store.rows_written").add(B)
    obs.gauge("store.bytes_per_device").set(tile_bytes)
    obs.histogram("serve.latency_ms", tenant=name).observe(ms)

and this module routes them to a live `MetricsRegistry` + `Tracer` when
observability is **enabled**, or to shared no-op singletons when it is
**disabled** (the default).

**Overhead contract** (the reason the switch exists):

  * *Disabled* (default): every helper is one module-global flag check
    returning a pre-built singleton — no allocation, no lock, no
    string formatting; ``span`` returns a reusable null context
    manager.  Nothing is recorded anywhere.
  * *Enabled*: records are host-side only — a ``perf_counter_ns`` pair
    per span, one locked increment per metric.  Nothing in this package
    is ever called inside ``jax.jit`` / ``shard_map`` / Pallas kernels,
    so tracing can never alter a compiled computation or touch a PRNG
    stream.  A span argument may be a device scalar the instrumented
    code already has: the tracer holds the reference and never reads
    it while the run goes on; `chrome_trace` resolves every held value
    once, in one transfer, after the work.  So no instrument adds a
    device call or a device sync while the run goes on.
  * *Either way*: seed-for-seed results are bitwise identical with obs
    on and off (gated by ``tests/force_obs_check.py`` on a forced
    8-device 2x4 mesh and ``tests/test_obs.py`` single-device).

``enable(jax_annotations=True)`` additionally bridges every span into a
``jax.profiler.TraceAnnotation`` so a device profile captured alongside
carries the same phase names as the host spans, and wraps each Python
garbage collection in a ``host.gc`` span (tier ``host``), so an idle
stretch of the device during one is named.
Span timestamps are on the profiler's realtime clock (`Tracer`).

Snapshots: ``obs.snapshot()`` / ``obs.write_metrics(path)`` export the
registry (consumed by ``benchmarks/_emit.py`` and the ``--metrics-out``
launch flags); ``obs.chrome_trace()`` / ``obs.write_trace(path)`` export
the span timeline as Chrome trace-event JSON loadable in Perfetto
(``--trace-out``).  See docs/observability.md for the metric catalog
and span-phase names.
"""
from __future__ import annotations

import contextlib
import gc

from repro.obs.metrics import (                           # noqa: F401
    Counter, Gauge, Histogram, LATENCY_BUCKETS_MS, MetricsRegistry,
    SIZE_BUCKETS, series_key,
)
from repro.obs.tracer import (                            # noqa: F401
    PHASES, Deferred, Span, Tracer,
)

_enabled = False
_registry: MetricsRegistry = MetricsRegistry()
_tracer: Tracer = Tracer()
_gc_open: Span = None       # the host.gc span of a running collection

#: Reusable null context manager handed out by `span` when disabled
#: (contextlib.nullcontext is reentrant and reusable by contract).
_NULL_SPAN = contextlib.nullcontext()


class _NoopInstrument:
    """Shared do-nothing stand-in for every instrument kind."""

    __slots__ = ()

    def add(self, n: int = 1) -> None:
        pass

    def set(self, v: float) -> None:
        pass

    def observe(self, v: float) -> None:
        pass

    value = 0
    max = 0.0
    count = 0
    sum = 0.0

    def percentile(self, p: float) -> float:
        return 0.0


_NOOP = _NoopInstrument()


# ------------------------------------------------------------- switch ----

def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: one ``host.gc`` span per collection."""
    global _gc_open
    if phase == "start":
        _gc_open = _tracer.span("host.gc", tier="host",
                                generation=info["generation"])
        _gc_open.__enter__()
    elif _gc_open is not None:
        done, _gc_open = _gc_open, None
        done.set(collected=info["collected"])
        done.__exit__(None, None, None)


def _hook_gc(on: bool) -> None:
    if on and _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    elif not on and _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def enable(*, registry: MetricsRegistry = None, tracer: Tracer = None,
           jax_annotations: bool = False) -> None:
    """Turn observability on (idempotent).

    Fresh ``registry``/``tracer`` objects replace the current ones when
    given; otherwise new empty ones are installed on the first enable
    and kept across enable/disable cycles (so a disable/enable pair
    does not silently wipe collected data — call `reset` for that).
    ``jax_annotations`` rebuilds the tracer with the device bridge.
    While obs stays enabled with a bridged tracer, each Python garbage
    collection is a ``host.gc`` span: its use is to name the idle
    stretches of a device profile.
    """
    global _enabled, _registry, _tracer
    if registry is not None:
        _registry = registry
    if tracer is not None:
        _tracer = tracer
    elif jax_annotations and _tracer._annotate is None:
        _tracer = Tracer(jax_annotations=True)
    _hook_gc(_tracer._annotate is not None)
    _enabled = True


def disable() -> None:
    """Turn observability off: helpers return no-op singletons again.
    Already-collected data stays readable via `snapshot`/`chrome_trace`."""
    global _enabled
    _hook_gc(False)
    _enabled = False


def reset() -> None:
    """Disable and drop all collected data (test isolation)."""
    global _registry, _tracer
    disable()
    _registry = MetricsRegistry()
    _tracer = Tracer()


def enabled() -> bool:
    return _enabled


# -------------------------------------------------------------- access ----

def get_metrics() -> MetricsRegistry:
    """The live registry (whatever the switch state — callers that hold
    it record unconditionally; prefer the module helpers)."""
    return _registry


def get_tracer() -> Tracer:
    """The live tracer (see `get_metrics` caveat)."""
    return _tracer


def counter(name: str, **labels):
    """`Counter` for ``(name, labels)`` — the shared no-op when disabled."""
    return _registry.counter(name, **labels) if _enabled else _NOOP


def gauge(name: str, **labels):
    """`Gauge` for ``(name, labels)`` — the shared no-op when disabled."""
    return _registry.gauge(name, **labels) if _enabled else _NOOP


def histogram(name: str, buckets=None, **labels):
    """`Histogram` for ``(name, labels)`` — the shared no-op when
    disabled.  ``buckets`` (ascending upper bounds) applies on first
    creation; defaults to `LATENCY_BUCKETS_MS`."""
    if not _enabled:
        return _NOOP
    return _registry.histogram(name, buckets=buckets, **labels)


def span(name: str, *, tier: str = "", **args):
    """Context manager timing one phase — a reusable null context when
    disabled.  ``tier`` tags the Chrome-trace event category.  Entering
    it gives the `Span` (``None`` when disabled), whose ``set`` adds
    arguments known only inside it:

        with obs.span("sample", tier="engine") as sp:
            out, steps = fn(key)
            if sp is not None:          # nothing built when disabled
                sp.set(steps=steps)     # a device scalar, read at export
    """
    return _tracer.span(name, tier=tier, **args) if _enabled else _NULL_SPAN


# -------------------------------------------------------------- export ----

def snapshot() -> dict:
    """The metrics registry snapshot (see `MetricsRegistry.snapshot`)."""
    return _registry.snapshot()


def chrome_trace() -> dict:
    """The span timeline as a Chrome trace-event dict (held device
    values resolved, once)."""
    return _tracer.chrome_trace()


def write_metrics(path: str) -> str:
    """Dump the registry snapshot as JSON; returns ``path``."""
    return _registry.write(path)


def write_trace(path: str) -> str:
    """Dump the Chrome trace as JSON; returns ``path``."""
    return _tracer.write(path)
