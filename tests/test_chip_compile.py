"""Compile-only checks for the TPU v5e: the main path's kernels and step
programs at the chip smoke test's sizes, compiled for a described (not
attached) ``v5e:2x2`` topology. Nothing runs; what the chip's compiler
would refuse (a misaligned tile, too much VMEM, a program over 16 GB)
fails here at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

DIM = 24576          # chip_smoke.DIM
BLOCK_ROWS = 16
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("cols", [1, 8, 128])
def test_usec_matvec_kernel_compiles(topo, cols):
    from repro.kernels.usec_matvec import usec_matvec_padded

    one = SingleDeviceSharding(topo.devices[0])
    compiled = usec_matvec_padded.lower(
        _sds((BLOCK_ROWS, DIM), jnp.float32, one),
        _sds((DIM, cols), jnp.float32, one), bm=BLOCK_ROWS, bk=512,
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "%usec_matvec." in text  # the kernel's name in device traces


@pytest.mark.parametrize("cols", [1, 8, 128])
def test_usec_segmented_kernel_compiles(topo, cols):
    from repro.kernels.usec_segmented import usec_segmented_padded

    one = SingleDeviceSharding(topo.devices[0])
    b = DIM // BLOCK_ROWS
    compiled = usec_segmented_padded.lower(
        _sds((1, DIM, DIM), jnp.float32, one),
        _sds((b,), jnp.int32, one), _sds((b,), jnp.int32, one),
        _sds((DIM, cols), jnp.float32, one),
        block_rows=BLOCK_ROWS, bk=512,
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "%usec_segmented." in text


def _worker_mesh(devices):
    from repro.launch.mesh import make_worker_mesh

    return make_worker_mesh(len(devices), devices=list(devices))


def _step_args(mesh, n, t_stage, b):
    """Shapes of one step's arguments, placed as the runner places them."""
    by_w = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    rpt = DIM // (n if n > 1 else 1)
    return (
        _sds((n, t_stage, rpt, DIM), jnp.float32, by_w),
        _sds((n, b), jnp.int32, by_w), _sds((n, b), jnp.int32, by_w),
        _sds((n, b), jnp.int32, by_w), _sds((n, b), jnp.float32, by_w),
        _sds((n,), jnp.int32, by_w), _sds((DIM,), jnp.float32, rep),
    )


def _assert_named(text, program):
    """The program and its block loop carry the names a profiler trace
    shows: ``jit_<program>`` on the XLA Modules line, ``usec_blocks`` in
    the ops' scope."""
    assert f"HloModule jit_{program}," in text
    assert re.search(rf'op_name="jit\({program}\)/[^"]*usec_blocks/', text)


def _fits(compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    return total < HBM_BYTES, total


def test_one_chip_stepwise_executor_compiles(topo):
    from repro.kernels.ops import executor_matmul
    from repro.runtime.executor import make_matvec_executor

    mesh = _worker_mesh(topo.devices[:1])
    ex = make_matvec_executor(mesh, "data", rows_total=DIM,
                              block_rows=BLOCK_ROWS,
                              matmul=executor_matmul("pallas"))
    compiled = ex.lower(*_step_args(mesh, 1, 1, DIM // BLOCK_ROWS)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    _assert_named(text, "usec_step")
    fits, total = _fits(compiled)
    assert fits, total


def test_one_chip_fused_executor_compiles(topo):
    from repro.api.workload import MatVecPowerIteration
    from repro.kernels.ops import executor_matmul
    from repro.runtime.executor import make_fused_executor

    mesh = _worker_mesh(topo.devices[:1])
    wl = MatVecPowerIteration()
    k, b = 8, DIM // BLOCK_ROWS
    ex = make_fused_executor(
        mesh, "data", rows_total=DIM, block_rows=BLOCK_ROWS, fuse_steps=k,
        matmul=executor_matmul("pallas"), update=wl.fused_update(),
        segmented_fn=wl.segmented_fn("pallas", block_rows=BLOCK_ROWS))
    by_kw = NamedSharding(mesh, P(None, "data"))
    rep = NamedSharding(mesh, P())
    staged = _step_args(mesh, 1, 1, b)[0]
    compiled = ex.lower(
        staged,
        _sds((k, 1, b), jnp.int32, by_kw), _sds((k, 1, b), jnp.int32, by_kw),
        _sds((k, 1, b), jnp.int32, by_kw), _sds((k, 1), jnp.int32, by_kw),
        _sds((k, 1, b, 1), jnp.int32, by_kw), _sds((k, 1, b), bool, by_kw),
        _sds((k, 1), bool, rep), _sds((k,), bool, rep),
        _sds((DIM,), jnp.float32, rep),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "%usec_segmented." in text
    _assert_named(text, "usec_window")
    fits, total = _fits(compiled)
    assert fits, total


@pytest.mark.parametrize("arrival", ["barrier", "first"])
def test_four_chip_executor_compiles(topo, arrival):
    """N=4 workers on the 2x2 mesh, cyclic J=3: the barrier step psums
    (an all-reduce across chips); the first-arrival partials stay on their
    own chips (no collective at all)."""
    from repro.kernels.ops import executor_matmul
    from repro.runtime.executor import (
        make_matvec_executor,
        make_worker_executor,
    )

    mesh = _worker_mesh(topo.devices)
    make = make_matvec_executor if arrival == "barrier" \
        else make_worker_executor
    ex = make(mesh, "data", rows_total=DIM, block_rows=BLOCK_ROWS,
              matmul=executor_matmul("pallas"))
    t_stage, rpt = 3, DIM // 4
    compiled = ex.lower(
        *_step_args(mesh, 4, t_stage, t_stage * rpt // BLOCK_ROWS)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    _assert_named(text, "usec_step" if arrival == "barrier"
                  else "usec_partials")
    collectives = ("all-reduce", "all-gather", "all-to-all",
                   "collective-permute")
    if arrival == "barrier":
        assert "all-reduce" in text
    else:
        assert not any(c in text for c in collectives)
    fits, total = _fits(compiled)
    assert fits, total
    assert np.isfinite(total)


BENCH_DIM = 32768    # the benchmark cells' operand width


@pytest.mark.parametrize("n,arrival", [(1, "barrier"), (4, "first")])
def test_streamed_step_program_compiles(topo, n, arrival):
    """The benchmark's step programs at width 32768, with the block list
    the runner derives on a TPU for a one-column linear workload: N=1's
    barrier step, and first-arrival partials over J=3 cyclic tiles on the
    2x2 mesh (b_max = 3 tiles x 512 blocks). One ``usec_stream`` kernel
    per worker, reading the staged tiles in place: no loop over blocks
    and no sliced copy of the tiles."""
    from repro.api.workload import MatVecPowerIteration
    from repro.kernels.ops import executor_matmul
    from repro.runtime.elastic_runner import RunnerConfig, stream_block_fn
    from repro.runtime.executor import (
        make_matvec_executor,
        make_worker_executor,
    )

    mesh = _worker_mesh(topo.devices[:n])
    make = make_matvec_executor if arrival == "barrier" \
        else make_worker_executor
    stream = stream_block_fn(MatVecPowerIteration(),
                             RunnerConfig(block_rows=BLOCK_ROWS), "tpu",
                             BENCH_DIM, BENCH_DIM)
    ex = make(mesh, "data", rows_total=BENCH_DIM, block_rows=BLOCK_ROWS,
              matmul=executor_matmul("pallas"), stream_fn=stream)
    t_stage, rpt = (1, BENCH_DIM) if n == 1 else (3, BENCH_DIM // 4)
    b = t_stage * rpt // BLOCK_ROWS
    assert b == (2048 if n == 1 else 1536)
    by_w = NamedSharding(mesh, P("data"))
    plan = _sds((n, b), jnp.int32, by_w)
    compiled = ex.lower(
        _sds((n, t_stage, rpt, BENCH_DIM), jnp.float32, by_w), plan, plan,
        plan, _sds((n, b), jnp.float32, by_w), _sds((n,), jnp.int32, by_w),
        _sds((BENCH_DIM,), jnp.float32, NamedSharding(mesh, P())),
    ).compile()
    assert ex.block_paths == ["stream"]
    text = compiled.as_text()
    assert "%usec_stream." in text
    assert "%usec_matvec." not in text
    assert not re.search(r"\swhile\(", text)
    assert "dynamic-slice" not in text
    _assert_named(text, "usec_step" if arrival == "barrier"
                  else "usec_partials")
    fits, total = _fits(compiled)
    assert fits, total
