"""Compile the IM kernels and the dense selection program for a TPU v5e
that is described, not attached, at com-Amazon's full width.

Interpret mode runs a kernel's semantics on the CPU; it cannot see what
the chip's compiler refuses (block shapes off the (8, 128) tiling, casts
Mosaic does not lower, programs that do not fit HBM).  These compiles
can: each kernel must lower to a ``tpu_custom_call``, and ``select_dense``
over a 16,384 x 334,863 uint8 arena must fit the chip's 16 GiB and, with
the adaptive update, read the whole arena only outside its pick loop.

The topology is described inside a module-scoped fixture, so a worker
that cannot describe it skips these tests and every worker collects the
same ones.  The persistent compilation cache is off around the compiles:
an entry compiled for a described chip cannot be read back without one.
"""
import os

import pytest

N = 334_863          # com-Amazon |V|
M = 1_851_744        # com-Amazon's directed edges (925,872 both ways)
THETA = 16_384       # the default --max-theta
B = 256              # IMMConfig.batch
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(one_chip, shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _kernel_case(name, one_chip):
    import jax.numpy as jnp
    from repro.kernels.commit import arena_commit
    from repro.kernels.coverage_matvec import coverage_matvec
    from repro.kernels.coverage_rows import (coverage_rows, row_view,
                                             words_per_row)
    from repro.kernels.ic_frontier import ic_frontier_step
    from repro.kernels.packed_count import packed_count, token_count
    from repro.kernels.segment_or import segment_or

    S = lambda shape, dt: _spec(one_chip, shape, dt)    # noqa: E731
    words = -(-N // 8)
    return {
        "arena_commit_bitmap": (lambda r: arena_commit(r, kind="bitmap"),
                                [S((B, N), jnp.uint8)]),
        "arena_commit_packed": (lambda r: arena_commit(r, kind="packed"),
                                [S((B, N), jnp.uint8)]),
        "coverage_matvec": (coverage_matvec,
                            [S((THETA,), jnp.float32),
                             S((THETA, N), jnp.uint8)]),
        "row_view": (row_view, [S((THETA, N), jnp.uint8),
                                S((THETA,), jnp.bool_)]),
        "coverage_rows": (coverage_rows,
                          [S((THETA,), jnp.bool_),
                           S((THETA, words_per_row(N), 128), jnp.int32)]),
        "packed_count": (lambda p, a: packed_count(p, a, n=N),
                         [S((THETA, words), jnp.uint8),
                          S((THETA,), jnp.float32)]),
        # narrow token rows unroll in one block, wide ones loop over
        # 128-lane chunks: both shapes of the kernel
        "token_count_s64": (lambda t, a: token_count(t, a, n=N),
                            [S((THETA, 64), jnp.int32),
                             S((THETA,), jnp.float32)]),
        "token_count_s512": (lambda t, a: token_count(t, a, n=N),
                             [S((THETA, 512), jnp.int32),
                              S((THETA,), jnp.float32)]),
        "segment_or": (lambda live, src: segment_or(live, src, n=N),
                       [S((M, B), jnp.bool_), S((M,), jnp.int32)]),
        "ic_frontier_n4096": (ic_frontier_step,
                              [S((B, 4096), jnp.uint8),
                               S((B, 4096), jnp.uint8),
                               S((4096, 4096), jnp.float32),
                               S((B, 4096), jnp.float32)]),
    }[name]


@pytest.mark.parametrize("name", [
    "arena_commit_bitmap", "arena_commit_packed", "coverage_matvec",
    "row_view", "coverage_rows", "packed_count", "token_count_s64", "token_count_s512",
    "segment_or", "ic_frontier_n4096"])
def test_kernel_compiles_for_v5e(one_chip, name):
    import jax
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("method", ["rebuild", "decrement"])
def test_select_dense_fits_v5e(one_chip, method, monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.core.selection import select_dense
    from repro.kernels import ops

    # the described chip is not the default backend: steer the kernel
    # dispatch to the branch it takes on a TPU
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    compiled = jax.jit(lambda R, v: select_dense(R, v, 50, method)).lower(
        _spec(one_chip, (THETA, N), jnp.uint8),
        _spec(one_chip, (THETA,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, f"select_dense needs {total / 2**30:.2f} GiB"


def test_select_packed_fits_v5e(one_chip, monkeypatch):
    """The greedy loop over a bit-packed arena at com-Amazon's size: its
    counts run the packed decode-and-count kernel, and the program fits
    the chip."""
    import jax
    import jax.numpy as jnp
    from repro.core.pack.selection import select_packed
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    compiled = jax.jit(lambda R, v: select_packed(R, v, N, 50)).lower(
        _spec(one_chip, (THETA, -(-N // 8)), jnp.uint8),
        _spec(one_chip, (THETA,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, f"select_packed needs {total / 2**30:.2f} GiB"


def test_select_dense_pick_loop_reads_only_listed_rows(one_chip, monkeypatch):
    """The adaptive update at com-Amazon's size: one kernel reads the
    whole arena, before the pick loop (the row view, which also builds
    the counter); inside the loop only the listed-rows kernel runs over
    the view, and anything else that takes the arena reads one column
    (at most theta elements).  No `coverage_matvec` pass is left, and
    no other arena-sized copy is made."""
    import re
    import jax
    import jax.numpy as jnp
    from repro.core.selection import select_dense
    from repro.kernels import ops
    from repro.kernels.coverage_rows import words_per_row

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    text = jax.jit(lambda R, v: select_dense(R, v, 50, "rebuild")).lower(
        _spec(one_chip, (THETA, N), jnp.uint8),
        _spec(one_chip, (THETA,), jnp.bool_)).compile().as_text()
    calls = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("jit(row_view)" in c for c in calls) == 1
    assert not any("coverage_matvec" in c for c in calls)
    for c in calls:
        assert ("jit(coverage_rows)" if "/while/" in c else
                "jit(row_view)") in c, c
    # every fused computation that takes the arena returns a column
    arena = rf"u8\[{THETA},{N}\]"
    readers = re.findall(rf"\n%(\S+) \([^\n]*?\w: {arena}[^\n]*\) -> "
                         rf"(\w+)\[([\d,]*)\]", text)
    assert readers
    for name, _, dims in readers:
        size = 1
        for d in filter(None, dims.split(",")):
            size *= int(d)
        assert size <= THETA, name
    # no instruction but the row view's kernel makes an arena-sized
    # array: the others pass the arena or the view along
    big = (rf"(?:{arena}|u8\[{N},{THETA}\]|"
           rf"s32\[{THETA},{words_per_row(N)},128\])")
    made = set(re.findall(rf"= {big}\S* (\S+)\(", text))
    assert made <= {"parameter", "get-tuple-element", "bitcast"}, made


@pytest.mark.parametrize("stable", [False, True])
def test_sparse_ic_loop_fits_v5e(one_chip, stable, monkeypatch):
    """The sparse IC traversal at com-Amazon's width: its pull step runs
    the segment_or kernel, holds no (m, B) float coins and no scatter,
    and the whole loop fits the chip."""
    import re
    import jax.numpy as jnp
    from repro.core.sampler import _sparse_loop
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    m = 1 << 21 if stable else M       # stable samplers pad m to pow2
    compiled = _sparse_loop.lower(
        _spec(one_chip, (2,), jnp.uint32), _spec(one_chip, (m,), jnp.int32),
        _spec(one_chip, (m,), jnp.int32), _spec(one_chip, (m,), jnp.float32),
        n_nodes=N, batch=B, stable=stable, with_steps=True,
        csr=_spec(one_chip, (m,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"= \S+ scatter\(", text)
    # a float (m, B) array may exist only inside a fusion, never as a
    # buffer of the loop's own
    assert not re.search(rf"%\S+ = f32\[{m},{B}\]\S* (fusion|copy)\(",
                         text)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, f"_sparse_loop needs {total / 2**30:.2f} GiB"
