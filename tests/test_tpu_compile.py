"""Compile-only checks for a described TPU v5e chip: the Pallas kernels at
real widths and the channel-plane scan must be accepted by the chip's
compiler. Interpret-mode tests cannot see a refused block layout; these
can, with no chip attached. Nothing here runs a program.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file. Keep these tests in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

N = 2560 * 6912          # one stablelm-3b MLP matrix, flattened
K = 3                    # self + two neighbors
_SB = 2048               # core.compression int8 scale-block lanes


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache.compilation_cache import (
        reset_cache)
    # a described-chip compile cannot be read back without the chip, so
    # keep it out of any persistent cache the environment names (the cache
    # decides once per process whether it is on: reset it both ways)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled.as_text()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_gossip_mix_compiles(one_chip):
    hlo = _compile(lambda b, w: ops.gossip_mix(b, w, interpret=False),
                   _shape((K, N), jnp.float32, one_chip),
                   _shape((K,), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo


def test_gossip_mix_q8_compiles(one_chip):
    lanes = N + (-N) % _SB
    hlo = _compile(
        lambda x, q, s, w: ops.gossip_mix_q8(x, q, s, w, interpret=False),
        _shape((N,), jnp.float32, one_chip),
        _shape((K, lanes), jnp.int8, one_chip),
        _shape((K, lanes // _SB), jnp.float32, one_chip),
        _shape((K + 1,), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo


def test_quantize_int8_compiles(one_chip):
    hlo = _compile(lambda x: ops.quantize_int8(x, interpret=False),
                   _shape((2560, 6912), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo


def test_dequantize_int8_compiles(one_chip):
    hlo = _compile(lambda q, s: ops.dequantize_int8(q, s, interpret=False),
                   _shape((2560, 6912), jnp.int8, one_chip),
                   _shape((2560, 6912 // 256), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo


def test_flash_attention_gqa_compiles(one_chip):
    qkv = _shape((1, 2048, 32, 80), jnp.bfloat16, one_chip)
    hlo = _compile(
        lambda q, k, v: ops.flash_attention_gqa(q, k, v, interpret=False),
        qkv, qkv, qkv)
    assert "tpu_custom_call" in hlo


def test_grouped_matmul_compiles_under_the_node_vmap(one_chip):
    """DeepSeek-V2-Lite's expert gate projection for two nodes of 1024
    tokens (6144 pairs sorted over 64 experts, 8 held), forward and
    backward: the three products compile to Pallas kernels under their own
    names, which the benchmark's trace reader looks for."""
    from repro.kernels.grouped_matmul import KERNEL_NAMES, grouped_matmul

    def loss(x, w, sizes):
        return jnp.sin(grouped_matmul(x, w, sizes, 0, interpret=False).astype(
            jnp.float32)).sum()

    hlo = _compile(lambda x, w, s: jax.vmap(jax.grad(loss, (0, 1)))(x, w, s),
                   _shape((2, 6144, 2048), jnp.bfloat16, one_chip),
                   _shape((2, 8, 2048, 1408), jnp.bfloat16, one_chip),
                   _shape((2, 64), jnp.int32, one_chip))
    calls = [line.split("=")[0].strip().lstrip("%") for line in hlo.splitlines()
             if "tpu_custom_call" in line and "=" in line]
    assert {c.split(".")[0] for c in calls} == set(KERNEL_NAMES), calls


def test_static_channel_scan_compiles(one_chip):
    """The jitted TDM round loop of the static world at n = 16, traced
    under x64 as ``precompute_trace_scan`` traces it."""
    from repro.sim.jit_trace import _round_scan
    from repro.sim.mac import _packets
    from repro.sim.scenario import get_scenario

    n, rounds = 16, 10
    cfg = get_scenario("static", n_nodes=n)
    n_pkts = len(_packets(cfg.model_bits, cfg.mac.packet_bits))
    fn = _round_scan(n, n_pkts, 1 + int(cfg.mac.max_retx_rounds), False,
                     1.0, float(cfg.bandwidth_hz),
                     float(cfg.mac.per_packet_overhead_s),
                     float(cfg.compute_s_per_round), 0, rounds)
    with jax.enable_x64(True):
        compiled = fn.lower(
            _shape((n,), jnp.float64, one_chip),
            _shape((n_pkts,), jnp.float64, one_chip),
            _shape((n, n), jnp.bool_, one_chip),
            _shape((n, n), jnp.bool_, one_chip)).compile()
    assert compiled.memory_analysis() is not None
