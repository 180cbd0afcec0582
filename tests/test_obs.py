"""IMTrace unit gates: registry exactness, span nesting, the disabled
no-op contract, thread-safety under concurrent recording, and the
single-device bitwise seed-identity guarantee (the forced-8-device 2x4
analogue lives in tests/force_obs_check.py)."""
import json
import threading

import numpy as np
import pytest

from repro import obs
from repro.core.engine import InfluenceEngine, IMMConfig
from repro.graphs import rmat_graph
from repro.obs.metrics import Histogram, MetricsRegistry, series_key


@pytest.fixture(autouse=True)
def _isolated_obs():
    """Each test starts disabled with empty registry/tracer and leaves
    the module switch the way it found it (off)."""
    obs.reset()
    yield
    obs.reset()


# ---------------------------------------------------------------- metrics


def test_histogram_percentiles_exact_on_bucket_boundaries():
    h = Histogram("t", buckets=(1.0, 2.0, 4.0, 8.0))
    # 100 observations, every one on a bucket boundary: quantiles are
    # exact, not bucket-rounded
    for v, times in ((1.0, 50), (2.0, 30), (4.0, 15), (8.0, 5)):
        for _ in range(times):
            h.observe(v)
    assert h.count == 100
    assert h.percentile(50.0) == 1.0     # rank 50 is the 50th 1.0
    assert h.percentile(51.0) == 2.0     # rank 51 crosses into 2.0
    assert h.percentile(80.0) == 2.0
    assert h.percentile(81.0) == 4.0
    assert h.percentile(95.0) == 4.0
    assert h.percentile(99.0) == 8.0
    assert h.percentile(100.0) == 8.0
    assert h.percentile(0.0) == 1.0      # rank clamps to the first obs
    assert h.sum == pytest.approx(50 + 60 + 60 + 40)


def test_histogram_overflow_reports_exact_max():
    h = Histogram("t", buckets=(1.0, 2.0))
    h.observe(0.5)
    h.observe(1000.0)                    # past the last bound: +Inf bucket
    h.observe(7.25)
    assert h.percentile(99.0) == 1000.0  # exact observed max, not "+Inf"
    d = h.to_dict()
    assert d["buckets"][-1] == ["+Inf", 2]
    assert d["max"] == 1000.0 and d["min"] == 0.5


def test_histogram_empty_and_validation():
    h = Histogram("t", buckets=(1.0,))
    assert h.percentile(50.0) == 0.0
    with pytest.raises(ValueError):
        h.percentile(101.0)
    with pytest.raises(ValueError):
        Histogram("t", buckets=())
    with pytest.raises(ValueError):
        Histogram("t", buckets=(2.0, 1.0))


def test_registry_identity_labels_and_kind_mismatch():
    reg = MetricsRegistry()
    a = reg.counter("serve.cache_hits", tenant="t0")
    b = reg.counter("serve.cache_hits", tenant="t0")
    c = reg.counter("serve.cache_hits", tenant="t1")
    assert a is b and a is not c
    assert a.key == series_key("serve.cache_hits", {"tenant": "t0"})
    assert a.key == "serve.cache_hits{tenant=t0}"
    with pytest.raises(TypeError):
        reg.gauge("serve.cache_hits", tenant="t0")
    reg.histogram("lat", buckets=(1.0, 2.0))
    with pytest.raises(ValueError):
        reg.histogram("lat", buckets=(1.0, 3.0))
    with pytest.raises(ValueError):
        reg.counter("neg").add(-1)


def test_gauge_tracks_running_max():
    reg = MetricsRegistry()
    g = reg.gauge("store.occupancy")
    assert g.max == 0.0                  # unwritten gauge reports zeros
    for v in (0.25, 0.9, 0.4):
        g.set(v)
    assert g.value == 0.4 and g.max == 0.9
    snap = reg.snapshot()
    assert snap["gauges"]["store.occupancy"] == {"value": 0.4, "max": 0.9}


def test_snapshot_schema_and_json_round_trip():
    obs.enable()
    obs.counter("c").add(3)
    obs.gauge("g").set(1.5)
    obs.histogram("h", buckets=(1.0, 2.0)).observe(1.0)
    snap = json.loads(json.dumps(obs.snapshot()))
    assert snap["counters"]["c"] == 3
    h = snap["histograms"]["h"]
    assert sum(c for _, c in h["buckets"]) == h["count"] == 1
    assert h["buckets"][-1][0] == "+Inf"


# ----------------------------------------------------------------- spans


def test_span_nesting_orders_depth_and_parent():
    obs.enable()
    with obs.span("run", tier="engine"):
        with obs.span("extend", tier="engine"):
            with obs.span("store.write", tier="store"):
                pass
        with obs.span("select", tier="engine"):
            pass
    evs = obs.get_tracer().events()
    # completion order: innermost first, root last
    assert [e["name"] for e in evs] == \
        ["store.write", "extend", "select", "run"]
    by_name = {e["name"]: e for e in evs}
    assert by_name["run"]["args"]["depth"] == 0
    assert by_name["run"]["args"]["parent"] == ""
    assert by_name["extend"]["args"] == \
        {**by_name["extend"]["args"], "depth": 1, "parent": "run"}
    assert by_name["store.write"]["args"]["depth"] == 2
    assert by_name["store.write"]["args"]["parent"] == "extend"
    assert by_name["select"]["args"]["parent"] == "run"
    # a child span lies inside its parent's [ts, ts+dur] window
    run, wr = by_name["run"], by_name["store.write"]
    assert run["ts"] <= wr["ts"]
    assert wr["ts"] + wr["dur"] <= run["ts"] + run["dur"] + 1e-6


def test_emit_helpers_consume_obs():
    """The BENCH emit helpers read the tracer/registry: span medians
    (with a last-N window) and snapshot scalars by series key."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
    from benchmarks._emit import git_sha, snapshot_scalar, span_median_s

    obs.enable()
    assert span_median_s("collective", "bench") == 0.0   # nothing yet
    for _ in range(5):
        with obs.span("collective", tier="bench"):
            pass
    assert span_median_s("collective", "bench") > 0.0
    durs = obs.get_tracer().durations_s("collective", "bench")
    import statistics
    assert span_median_s("collective", "bench", last=3) == \
        pytest.approx(statistics.median(durs[-3:]))
    obs.counter("c").add(7)
    obs.gauge("g").set(2.5)
    obs.histogram("h", buckets=(1.0, 2.0)).observe(2.0)
    snap = obs.snapshot()
    assert snapshot_scalar(snap, "c") == 7
    assert snapshot_scalar(snap, "g") == 2.5
    assert snapshot_scalar(snap, "h") == 2.0           # p50
    assert snapshot_scalar(snap, "absent", default=-1.0) == -1.0
    assert isinstance(git_sha(), str) and git_sha()    # never raises


def test_chrome_trace_is_valid_and_durations_readable():
    obs.enable()
    with obs.span("collective", tier="bench", step=1):
        pass
    trace = obs.chrome_trace()
    assert trace["displayTimeUnit"] == "ms"
    phs = [e["ph"] for e in trace["traceEvents"]]
    assert phs.count("M") == 1 and phs.count("X") == 1
    x = [e for e in trace["traceEvents"] if e["ph"] == "X"][0]
    assert x["cat"] == "bench" and x["args"]["step"] == 1
    assert x["dur"] >= 0
    durs = obs.get_tracer().durations_s("collective", "bench")
    assert len(durs) == 1 and durs[0] == pytest.approx(x["dur"] / 1e6)


def test_tracer_bounds_events_and_counts_drops():
    obs.enable(tracer=obs.Tracer(max_events=4))
    for i in range(10):
        with obs.span("s", i=i):
            pass
    tr = obs.get_tracer()
    assert len(tr) == 4 and tr.dropped == 6
    # the survivors are the newest events
    assert [e["args"]["i"] for e in tr.events()] == [6, 7, 8, 9]
    assert tr.chrome_trace()["otherData"]["dropped_events"] == 6


# ------------------------------------------------------------ switchboard


def test_disabled_mode_records_nothing():
    assert not obs.enabled()
    c = obs.counter("x")
    c.add(5)
    obs.gauge("y").set(1.0)
    obs.histogram("z").observe(3.0)
    with obs.span("run", tier="engine"):
        with obs.span("extend", tier="engine"):
            pass
    assert c is obs.gauge("anything")    # one shared no-op singleton
    assert c.value == 0 and c.percentile(99.0) == 0.0
    assert len(obs.get_metrics()) == 0
    assert len(obs.get_tracer()) == 0
    snap = obs.snapshot()
    assert snap == {"counters": {}, "gauges": {}, "histograms": {}}


def test_disable_keeps_data_reset_drops_it():
    obs.enable()
    obs.counter("c").add(1)
    with obs.span("s"):
        pass
    obs.disable()
    obs.counter("c").add(100)            # no-op while disabled
    assert obs.snapshot()["counters"]["c"] == 1
    assert len(obs.get_tracer()) == 1
    obs.enable()
    obs.counter("c").add(1)              # same series continues
    assert obs.snapshot()["counters"]["c"] == 2
    obs.reset()
    assert not obs.enabled()
    assert obs.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


def test_concurrent_recording_is_exact():
    """Many worker threads (the IMServe pattern) hammer one counter, one
    gauge, and one labeled histogram — no lost increments, no torn
    bucket counts."""
    obs.enable()
    threads, per = 8, 500

    def work(t):
        c = obs.counter("serve.cache_hits", tenant="t0")
        h = obs.histogram("serve.latency_ms", tenant="t0",
                          buckets=(1.0, 2.0, 4.0))
        for i in range(per):
            c.add(1)
            h.observe(float(1 << (i % 3)))
            obs.gauge("serve.queue_depth", tenant="t0").set(i)
            with obs.span("cache", tier="serve", worker=t):
                pass

    ts = [threading.Thread(target=work, args=(t,)) for t in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    snap = obs.snapshot()
    assert snap["counters"]["serve.cache_hits{tenant=t0}"] == threads * per
    h = snap["histograms"]["serve.latency_ms{tenant=t0}"]
    assert h["count"] == threads * per
    assert sum(c for _, c in h["buckets"]) == h["count"]
    # each boundary value got exactly its share
    assert [c for _, c in h["buckets"]][:3] == \
        [threads * len(range(i, per, 3)) for i in range(3)]
    assert len(obs.get_tracer().events("cache", "serve")) == threads * per


# ----------------------------------------------------- numerics invariance


def test_obs_on_off_bitwise_identical_single_device():
    """The acceptance property, single-device: a fully instrumented run
    (spans + metrics live) is seed-for-seed bitwise identical to the
    disabled run, and the enabled run actually traced the engine and
    store tiers with nesting."""
    g = rmat_graph(96, 512, seed=2)
    cfg = IMMConfig(k=4, batch=64, max_theta=128, seed=3)

    r_off = InfluenceEngine(g, cfg).run()
    assert not obs.enabled()

    obs.enable()
    eng = InfluenceEngine(g, cfg)
    r_on = eng.run()
    inf_on = eng.influences([r_on.seeds[:2]])
    obs.disable()

    np.testing.assert_array_equal(np.asarray(r_off.seeds),
                                  np.asarray(r_on.seeds))
    np.testing.assert_array_equal(np.asarray(r_off.counter),
                                  np.asarray(r_on.counter))
    assert r_off.theta == r_on.theta
    assert r_off.influence == r_on.influence
    eng_off = InfluenceEngine(g, cfg)
    eng_off.extend(r_off.theta)
    np.testing.assert_allclose(inf_on,
                               eng_off.influences([r_on.seeds[:2]]),
                               rtol=1e-6)

    # the enabled run produced real telemetry: nested engine + store spans
    tr = obs.get_tracer()
    assert tr.events(tier="engine") and tr.events(tier="store")
    ext = tr.events("extend", "engine")
    assert ext and all(e["args"]["parent"] in ("run", "round")
                       for e in ext)
    wr = tr.events("store.write", "store")
    assert wr and all(e["args"]["depth"] >= 2 for e in wr)
    snap = obs.snapshot()
    assert snap["counters"]["engine.rounds"] >= 1
    assert snap["counters"]["store.rows_written"] == r_on.theta
    assert snap["gauges"]["engine.theta"]["value"] == r_on.theta
    assert 0.0 < snap["gauges"]["store.occupancy"]["value"] <= 1.0


# ------------------------------------------- clock, device values, gc


def test_span_ts_is_on_the_realtime_clock():
    """``ts`` is microseconds since the Unix epoch (the clock a JAX
    profile stamps its events with), at ``perf_counter`` resolution."""
    import time
    obs.enable()
    before = time.time_ns()
    with obs.span("s"):
        pass
    after = time.time_ns()
    ev = obs.get_tracer().events("s")[0]
    assert before - 1e6 <= ev["ts"] * 1e3 <= after + 1e6


def test_device_scalars_are_held_and_resolved_once_at_export():
    import jax.numpy as jnp
    obs.enable()
    steps = jnp.int32(7)
    with obs.span("sample", tier="engine") as sp:
        sp.set(steps=steps, coins=obs.Deferred(steps, 2**31), sets=3)
    tr = obs.get_tracer()
    assert len(tr._held) == 1
    raw = tr._events[0]["args"]
    assert raw["steps"] is steps                  # held, not read
    ev = [e for e in obs.chrome_trace()["traceEvents"] if e["ph"] == "X"][0]
    assert ev["args"]["steps"] == 7 and type(ev["args"]["steps"]) is int
    assert ev["args"]["coins"] == 7 * 2**31       # a Python int past 2**31
    assert ev["args"]["sets"] == 3
    assert not tr._held
    json.dumps(obs.chrome_trace())                # resolved for good
    assert tr.events("sample")[0]["args"]["coins"] == 7 * 2**31


def test_disabled_span_enters_as_none_and_holds_nothing():
    with obs.span("sample", tier="engine") as sp:
        assert sp is None
    g = rmat_graph(96, 512, seed=2)
    eng = InfluenceEngine(g, IMMConfig(k=4, batch=64, max_theta=128,
                                       seed=3))
    eng.extend(128)
    tr = obs.get_tracer()
    assert len(tr) == 0 and not tr._held
    assert eng.store.count == 128


def test_dropped_events_release_their_held_values():
    import jax.numpy as jnp
    obs.enable(tracer=obs.Tracer(max_events=2))
    for i in range(5):
        with obs.span("s") as sp:
            sp.set(v=jnp.int32(i))
    tr = obs.get_tracer()
    assert len(tr._held) == 2
    assert [e["args"]["v"] for e in tr.events()] == [3, 4]


def test_gc_spans_follow_the_switch():
    import gc
    obs.enable(jax_annotations=True)
    assert obs._on_gc in gc.callbacks
    gc.collect()
    evs = obs.get_tracer().events("host.gc", "host")
    assert evs and evs[-1]["args"]["generation"] == 2
    assert evs[-1]["args"]["collected"] >= 0 and evs[-1]["dur"] >= 0
    obs.disable()
    assert obs._on_gc not in gc.callbacks
    gc.collect()
    assert len(obs.get_tracer().events("host.gc")) == len(evs)
    obs.enable()                      # the bridged tracer is kept
    assert obs._on_gc in gc.callbacks
    obs.reset()
    assert obs._on_gc not in gc.callbacks
    obs.enable()                      # no bridge, no gc spans
    assert obs._on_gc not in gc.callbacks
