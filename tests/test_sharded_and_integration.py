"""Sharded selection on local meshes, hlo_analysis, training integration,
and a subprocess production dry-run sanity cell."""
import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.selection import select_dense, select_dense_sharded
from repro.launch.hlo_analysis import analyze_module, parse_module
from repro.launch.mesh import make_mesh


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_select_dense_sharded_equals_local():
    """The psum-combined sharded selection (paper C1) == single-device."""
    mesh = make_mesh((1, 1), ("data", "model"))
    rng = np.random.default_rng(0)
    R = jnp.asarray((rng.random((64, 32)) < 0.3).astype(np.uint8))
    valid = jnp.ones((64,), bool)
    s1, f1, g1 = select_dense(R, valid, 5)
    s2, f2, g2 = select_dense_sharded(mesh, R, valid, 5,
                                      theta_axes=("data",),
                                      vertex_axis="model")
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
    assert float(f1) == pytest.approx(float(f2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


# ----------------------------------------------------------- hlo analysis ----

def test_hlo_analyzer_scan_trip_count():
    def f(ws, x):
        def body(x, w):
            return jnp.tanh(x @ w), None
        return jax.lax.scan(body, x, ws)[0]

    ws = jax.ShapeDtypeStruct((8, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    c = jax.jit(f).lower(ws, x).compile()
    counts = analyze_module(c.as_text())
    assert counts.flops == 8 * 2 * 32 * 64 * 64
    assert counts.unknown_trip_loops == 0


def test_hlo_analyzer_nested_and_tags():
    def f(ws, x):
        def outer(x, _):
            def inner(x, w):
                return x @ w, None
            return jax.lax.scan(inner, x, ws)[0], None
        return jax.lax.scan(outer, x, None, length=3)[0]

    ws = jax.ShapeDtypeStruct((4, 32, 32), jnp.float32)
    x = jax.ShapeDtypeStruct((16, 32), jnp.float32)
    c = jax.jit(f).lower(ws, x).compile()
    counts = analyze_module(c.as_text())
    assert counts.flops == 3 * 4 * 2 * 16 * 32 * 32
    assert counts.bytes > 0


def test_hlo_parse_module_entry():
    c = jax.jit(lambda x: x @ x).lower(
        jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile()
    comps, types, entry = parse_module(c.as_text())
    assert entry is not None and entry in comps


# ---------------------------------------------------------- train integr. ----

def test_train_loop_lm_loss_decreases():
    from repro.launch.train import train_lm
    with tempfile.TemporaryDirectory() as d:
        state, losses, loop = train_lm(
            "qwen1.5-0.5b", smoke=True, steps=40, batch=8, seq_len=32,
            checkpoint_dir=d, save_every=20, log=lambda *a: None)
        assert np.mean(losses[-5:]) < np.mean(losses[:5])


def test_train_resume_from_checkpoint():
    from repro.launch.train import train_lm
    with tempfile.TemporaryDirectory() as d:
        _, losses1, _ = train_lm(
            "qwen1.5-0.5b", smoke=True, steps=10, batch=4, seq_len=32,
            checkpoint_dir=d, save_every=5, log=lambda *a: None)
        # second run resumes at step 10 and continues to 20
        _, losses2, loop2 = train_lm(
            "qwen1.5-0.5b", smoke=True, steps=20, batch=4, seq_len=32,
            checkpoint_dir=d, save_every=5, log=lambda *a: None)
        assert loop2.history[0].step == 10


def test_serve_generates():
    from repro.launch.serve import LMServer
    from repro.configs import get_arch
    cfg = get_arch("qwen1.5-0.5b").smoke_config
    server = LMServer(cfg, max_len=64)
    prompts = jax.random.randint(jax.random.PRNGKey(0), (2, 8), 0, cfg.vocab)
    out = server.generate(prompts, 4)
    assert out.shape == (2, 4)
    assert (np.asarray(out) >= 0).all() and (np.asarray(out) < cfg.vocab).all()


def test_im_run_end_to_end():
    from repro.launch.im_run import run
    out, engine = run("com-Amazon", scale=0.002, model="IC", k=5,
                      max_theta=512, log=lambda *a: None)
    assert engine.theta == out["theta"]
    assert out["influence"] > 0
    assert len(out["seeds"]) >= 5


# ------------------------------------------------- production cell (slow) ----

@pytest.mark.slow
def test_production_dryrun_subprocess_cell():
    """One cheap production cell end-to-end in a fresh process (512 fake
    devices): proves the make_production_mesh + lower + compile path."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    with tempfile.TemporaryDirectory() as d:
        out = os.path.join(d, "cell.json")
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--arch", "fm", "--shape", "serve_p99",
             "--mesh", "both", "--out", out],
            env=env, capture_output=True, text=True, timeout=540)
        assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
        res = json.load(open(out))
        assert len(res) == 2 and all(c["ok"] for c in res)
        assert {c["mesh"] for c in res} == {"16x16", "2x16x16"}
