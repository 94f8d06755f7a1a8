"""The trace path's kernels compile for a TPU v5e — with no chip attached.

The TPU compiler is installed with jaxlib; it compiles for a topology
that is described rather than present.  These tests catch what the
Pallas interpreter and the CPU backend never see: Mosaic's tiling and
layout rules, VMEM limits, and programs that do not fit the device.  The
engine dispatch sees the CPU here, so each test compiles the kernel or
the jitted function itself (`replay_megakernel(..., interpret=False)`).

The topology is described inside a module fixture — never at import —
so every test worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.accelerator import DramConfig, tpu_like_config
from repro.core.dram import decode_requests
from repro.core.replay import replay_decoded
from repro.core.workloads import Op
from repro.kernels.replay import replay_megakernel
from repro.trace.generator import DEFAULT_SPEC, _op_regions, \
    gemm_request_stream

CAP = DEFAULT_SPEC.cap          # 4096 requests per stream
STREAMS = 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _streams(sharding, shape=(STREAMS, CAP)):
    f32 = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
    i32 = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)
    return f32, i32


@pytest.mark.parametrize("shared", [False, True],
                         ids=["private-dram", "shared-dram-short-queues"])
def test_megakernel_compiles_for_v5e(one_chip, shared):
    """The fused replay at the trace cap over a batch of streams;
    `shared` adds per-channel queues shorter than a chunk (in-chunk
    queue heads) and two cores."""
    cfg = DramConfig(read_queue=4, write_queue=2) if shared else DramConfig()

    def replay(t, fb, ch, row, w, v):
        return replay_megakernel(t, fb, ch, row, w, v, cfg, interpret=False,
                                 per_channel_queues=shared,
                                 n_cores=2 if shared else 1)

    f32, i32 = _streams(one_chip)
    compiled = jax.jit(replay).lower(f32, *[i32] * 5).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 6 * STREAMS * CAP * 4


def test_xla_replay_driver_compiles_for_v5e(one_chip):
    cfg = DramConfig()

    def replay(t, fb, ch, row, w, v):
        out = replay_decoded(t, fb, ch, row, w != 0, v != 0, cfg,
                             engine="xla")
        return out["done"], out["shift"], out["hits"]

    f32, i32 = _streams(one_chip)
    compiled = jax.jit(replay).lower(f32, *[i32] * 5).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0


def test_stream_generation_and_decode_compile_for_v5e(one_chip):
    """One op's demand stream (`gemm_request_stream`) and its address
    decode, as the trace sweep runs them."""
    cfg = tpu_like_config(array=32, dataflow="os", sram_mb=1.0)
    op = Op("qkv", 4096, 1536, 4608)
    core, comp, dram = _op_regions(cfg, op)

    def stream(scalars):
        M, N, K, R, C, comp_, di, dfl, dow, dor = scalars
        t, addr, w, v, scale = gemm_request_stream(
            cfg.dataflow, M, N, K, R, C, comp_, di, dfl, dow, dor,
            cfg.memory.word_bytes, DEFAULT_SPEC)
        return (t, *decode_requests(addr, cfg.dram), w, v, scale)

    vals = (op.M, op.N, op.K, core.rows, core.cols, comp,
            dram["dram_ifmap"], dram["dram_filter"],
            dram["dram_ofmap_writes"], dram["dram_ofmap_reads"])
    args = tuple(jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
                 for _ in vals)
    compiled = jax.jit(stream).lower(args).compile()
    out = jax.eval_shape(stream, tuple(jnp.float32(x) for x in vals))
    assert out[0].shape == (CAP,) and out[1].dtype == jnp.int32
    assert compiled.memory_analysis() is not None
