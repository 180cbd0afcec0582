"""Runtime layer: fault tolerance, checkpointing, straggler, compression,
elastic resharding."""
import os
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis")  # property tests need it; skip on clean machines
from hypothesis import given, settings, strategies as st

from repro.checkpoint.store import (
    save_checkpoint, load_checkpoint, latest_step, CheckpointManager,
)
from repro.runtime.loop import TrainLoop, LoopConfig, RemeshRequested
from repro.runtime.straggler import StragglerMonitor
from repro.runtime.compression import (
    compress_int8, decompress_int8, init_error_feedback,
    compress_with_feedback,
)
from repro.runtime.elastic import reshard_tree, replicated_plan
from repro.launch.mesh import make_mesh

settings.register_profile("ci3", deadline=None, max_examples=20)
settings.load_profile("ci3")


# ------------------------------------------------------------- checkpoint ----

def test_checkpoint_roundtrip_nested():
    with tempfile.TemporaryDirectory() as d:
        tree = {"a": jnp.arange(5), "b": [jnp.ones((2, 2)),
                                          {"c": jnp.float32(3.0)}],
                "t": (jnp.zeros(3), jnp.int32(7))}
        save_checkpoint(d, 3, tree)
        step, got = load_checkpoint(d)
        assert step == 3
        np.testing.assert_array_equal(got["a"], np.arange(5))
        assert isinstance(got["b"], list) and isinstance(got["t"], tuple)
        assert float(got["b"][1]["c"]) == 3.0


def test_checkpoint_rolling_retention_and_latest():
    with tempfile.TemporaryDirectory() as d:
        for s in (1, 2, 3, 4, 5):
            save_checkpoint(d, s, {"x": jnp.int32(s)}, keep=2)
        assert latest_step(d) == 5
        files = [f for f in os.listdir(d) if f.endswith(".npz")]
        assert len(files) == 2


def test_checkpoint_latest_pointer_fallback():
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 1, {"x": jnp.int32(1)})
        save_checkpoint(d, 2, {"x": jnp.int32(2)})
        with open(os.path.join(d, "latest"), "w") as f:
            f.write("999")                         # stale pointer
        step, tree = load_checkpoint(d)
        assert step == 2 and int(tree["x"]) == 2


def test_checkpoint_no_partial_files_visible():
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 1, {"x": jnp.zeros(10)})
        leftovers = [f for f in os.listdir(d) if ".tmp" in f]
        assert leftovers == []


def test_manager_restore_or_init():
    with tempfile.TemporaryDirectory() as d:
        m = CheckpointManager(d, save_every=1)
        step, tree = m.restore_or_init(lambda: {"x": jnp.int32(42)})
        assert step == 0 and int(tree["x"]) == 42
        m.save(7, {"x": jnp.int32(7)})
        step, tree = m.restore_or_init(lambda: {"x": jnp.int32(42)})
        assert step == 7 and int(tree["x"]) == 7


# ------------------------------------------------------------ fault loop ----

def test_loop_retries_transient_fault():
    with tempfile.TemporaryDirectory() as d:
        faults = {"n": 1}

        def inject(step, retries):
            if step == 3 and faults["n"] > 0:
                faults["n"] -= 1
                return True
            return False

        loop = TrainLoop(
            LoopConfig(total_steps=6, checkpoint_dir=d, save_every=2,
                       max_retries=2),
            lambda s, b: (s + b, {"v": s}), lambda step: jnp.float32(1.0),
            lambda: jnp.float32(0.0), inject_fault=inject)
        final = loop.run()
        assert float(final) == 6.0
        assert loop.recoveries == 0          # retry succeeded, no restore


def test_loop_restores_from_checkpoint_and_replays():
    with tempfile.TemporaryDirectory() as d:
        faults = {"n": 3}

        def inject(step, retries):
            if step == 4 and faults["n"] > 0:
                faults["n"] -= 1
                return True
            return False

        loop = TrainLoop(
            LoopConfig(total_steps=8, checkpoint_dir=d, save_every=2,
                       max_retries=2),
            lambda s, b: (s + b, {"v": s}), lambda step: jnp.float32(1.0),
            lambda: jnp.float32(0.0), inject_fault=inject)
        final = loop.run()
        assert float(final) == 8.0           # deterministic replay
        assert loop.recoveries == 1


def test_loop_requests_remesh_on_persistent_straggle():
    with tempfile.TemporaryDirectory() as d:
        import time as _t

        def slow_step(s, b):
            if float(s) >= 6.0:
                _t.sleep(0.05)
            return s + b, {"v": s}

        loop = TrainLoop(
            LoopConfig(total_steps=30, checkpoint_dir=d, save_every=100,
                       straggler_threshold=1.5),
            slow_step, lambda step: jnp.float32(1.0),
            lambda: jnp.float32(0.0))
        with pytest.raises(RemeshRequested):
            loop.run()
        # checkpoint must have been written before raising
        assert latest_step(d) is not None


# -------------------------------------------------------------- straggler ----

def test_straggler_monitor_flags_outlier():
    m = StragglerMonitor(threshold=2.0, warmup_steps=2)
    for i in range(5):
        assert not m.observe(i, 0.1)
    assert m.observe(5, 0.5)
    assert not m.unhealthy
    assert m.observe(6, 0.5) and m.observe(7, 0.5)
    assert m.unhealthy


def test_straggler_ewma_excludes_outliers():
    m = StragglerMonitor(threshold=2.0, warmup_steps=1)
    m.observe(0, 0.1)
    m.observe(1, 10.0)   # flagged; must not poison the EWMA
    assert m.ewma == pytest.approx(0.1)


# ------------------------------------------------------------ compression ----

@given(st.integers(0, 1000))
def test_compress_roundtrip_error_bound(seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (64,)) * 3.0
    q, s = compress_int8(x)
    err = np.abs(np.asarray(decompress_int8(q, s) - x))
    assert err.max() <= float(s) / 2 + 1e-6    # half-ulp of the quantizer


def test_error_feedback_reduces_bias():
    """With error feedback, the accumulated quantized stream converges to
    the accumulated true stream (bounded residual)."""
    g = jnp.full((8,), 0.01)                   # tiny constant gradient
    ef = init_error_feedback({"g": g})
    acc = np.zeros(8)
    for _ in range(100):
        qt, ef = compress_with_feedback({"g": g}, ef)
        q, s = qt["g"]
        acc += np.asarray(decompress_int8(q, s))
    np.testing.assert_allclose(acc, np.full(8, 1.0), rtol=0.05)


# ---------------------------------------------------------------- elastic ----

def test_reshard_tree_roundtrip():
    mesh = make_mesh((1,), ("data",))
    tree = {"w": np.arange(8.0), "b": [np.ones((2, 2))]}
    out = reshard_tree(tree, replicated_plan(mesh))
    np.testing.assert_array_equal(np.asarray(out["w"]), tree["w"])
    assert out["w"].sharding.mesh.shape["data"] == 1


def test_checkpoint_then_reshard_elasticity():
    """Save under one 'mesh', restore into another (CPU: 1-device meshes
    with different axis layouts — exercises the full path)."""
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 1, {"w": jnp.arange(16.0).reshape(4, 4)})
        _, host_tree = load_checkpoint(d)
        mesh2 = make_mesh((1, 1), ("data", "model"))
        out = reshard_tree(host_tree, replicated_plan(mesh2))
        np.testing.assert_array_equal(
            np.asarray(out["w"]), np.arange(16.0).reshape(4, 4))
