"""A run with the timed path broken underneath comes out not correct.

Each test drives the rest of a run as the command does, past its look
for a chip, with one fault planted in the program where the answer is
produced: a commit that leaves the arena unchanged, half of a batch or
of the arena left out, one answer altered.  (The cells run on one chip,
so there is no exchange between chips to leave out.)"""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from repro.core import fused, selection, store
from repro.kernels import ops

SEED = 2**31 + 777


def run(cell, tiny):
    c = harness.load_cell(cell, overrides=tiny)
    return harness.run(c, seed=SEED, seconds=0.5, trace=False,
                       t_start=time.perf_counter())


def test_sample_commit_leaves_the_arena_unchanged(monkeypatch, tiny):
    monkeypatch.setattr(fused, "_commit_write",
                        lambda R, sizes, counter, *_: (R, sizes, counter))
    assert run("amazon_ic.sample", tiny)["correct"] is False


def test_sample_commit_writes_rows_but_not_counter_or_sizes(monkeypatch,
                                                            tiny):
    def rows_only(R, sizes, counter, stored, batch_sizes, colsum, start):
        return (jax.lax.dynamic_update_slice(R, stored, (start, 0)),
                sizes, counter)

    monkeypatch.setattr(fused, "_commit_write", rows_only)
    r = run("amazon_ic.sample", tiny)
    assert r["correct"] is False
    checks = {k: v["value"] for k, v in r["checks"].items()}
    assert checks["rows_wrong"] == 0
    assert checks["counter_wrong"] > 0 and checks["sizes_wrong"] > 0


@pytest.mark.parametrize("cell", ["amazon_ic.sample", "amazon_lt.sample"])
def test_sample_half_the_batch_left_out(monkeypatch, tiny, cell):
    real = ops.arena_commit

    def half(rows, **kw):
        keep = jnp.arange(rows.shape[0]) < rows.shape[0] // 2
        return real(rows * keep[:, None].astype(rows.dtype), **kw)

    monkeypatch.setattr(ops, "arena_commit", half)
    assert run(cell, tiny)["correct"] is False


@pytest.mark.parametrize("cell", ["amazon_ic.sample", "amazon_lt.sample"])
def test_sample_rows_altered(monkeypatch, tiny, cell):
    real = ops.arena_commit

    def flip(rows, **kw):
        return real(rows.at[:, 0].set(1 - rows[:, 0]), **kw)

    monkeypatch.setattr(ops, "arena_commit", flip)
    assert run(cell, tiny)["correct"] is False


def test_select_over_half_the_arena(monkeypatch, tiny):
    real = selection.select_dense

    def half(R, valid, k, method="rebuild"):
        return real(R, valid & (jnp.arange(valid.shape[0]) % 2 == 0), k,
                    method)

    monkeypatch.setattr(selection, "select_dense", half)
    assert run("amazon_ic.select", tiny)["correct"] is False


def test_select_seed_altered(monkeypatch, tiny):
    real = selection.select_dense

    def alter(R, valid, k, method="rebuild"):
        seeds, frac, gains = real(R, valid, k, method)
        return seeds.at[-1].add(1), frac, gains

    monkeypatch.setattr(selection, "select_dense", alter)
    assert run("amazon_ic.select", tiny)["correct"] is False


def test_serve_hits_over_half_the_arena(monkeypatch, tiny):
    real = store._bitmap_hits

    def half(R, valid, S):
        return real(R, valid & (jnp.arange(valid.shape[0]) % 2 == 0), S)

    monkeypatch.setattr(store, "_bitmap_hits", half)
    assert run("amazon_ic.serve", tiny)["correct"] is False


def test_serve_answer_altered(monkeypatch, tiny):
    real = store._bitmap_hits

    def alter(R, valid, S):
        return real(R, valid, S) + 1.0 / valid.sum()

    monkeypatch.setattr(store, "_bitmap_hits", alter)
    assert run("amazon_ic.serve", tiny)["correct"] is False
