"""The main path's Pallas kernels compile for a TPU v5e at qwen2-0.5b width.

Interpret mode (what the rest of the suite runs) accepts blocks that the
TPU's Mosaic compiler refuses, so each kernel is also compiled here for a
described, unattached v5e chip: no chip is needed, nothing runs, and a
refused block shape or VMEM overrun fails the test.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and the test workers
all import this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.fc_gemv import fc_gemv
from repro.kernels.paged_decode_attention import paged_decode_attention

# qwen2-0.5b: d_model 896, 14 query / 2 KV heads of 64, d_ff 4864, vocab
# 151936 — (K, N) of q and o, k and v, gate and up, down, lm_head
FC_SHAPES = [(896, 896), (896, 128), (896, 4864), (4864, 896),
             (896, 151936)]
NKV, G, HD = 2, 7, 64
SLOTS = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for an unattached chip can be written there but never read."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_kernels(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    return "\n".join(line for line in text.splitlines()
                     if 'custom_call_target="tpu_custom_call"' in line)


@pytest.mark.parametrize("m", [8, 32])
@pytest.mark.parametrize("k,n", FC_SHAPES)
def test_fc_gemv_compiles(one_chip, m, k, n):
    calls = _compiled_kernels(
        lambda x, w: fc_gemv(x, w, interpret=False),
        _spec(one_chip, (m, k)), _spec(one_chip, (k, n)))
    assert "papi_fc_gemv" in calls


@pytest.mark.parametrize("q_rows", [1, 4])
def test_decode_attention_compiles(one_chip, q_rows):
    kv = _spec(one_chip, (SLOTS, 256, NKV, HD))
    calls = _compiled_kernels(
        lambda q, k, v, lens: decode_attention(q, k, v, lens, q_rows=q_rows,
                                               interpret=False),
        _spec(one_chip, (SLOTS, NKV, q_rows * G, HD)), kv, kv,
        _spec(one_chip, (SLOTS,), jnp.int32))
    assert "papi_decode_attention" in calls


@pytest.mark.parametrize("q_rows", [1, 4])
def test_paged_decode_attention_compiles(one_chip, q_rows):
    pages, page_size, blocks = 1 + SLOTS * 16, 16, 16
    kv = _spec(one_chip, (pages, page_size, NKV, HD))
    calls = _compiled_kernels(
        lambda q, k, v, lens, tables: paged_decode_attention(
            q, k, v, lens, tables, q_rows=q_rows, interpret=False),
        _spec(one_chip, (SLOTS, NKV, q_rows * G, HD)), kv, kv,
        _spec(one_chip, (SLOTS,), jnp.int32),
        _spec(one_chip, (SLOTS, blocks), jnp.int32))
    assert "papi_paged_decode_attention" in calls


@pytest.mark.parametrize("q_rows", [1, 64])
def test_paged_decode_attention_compiles_at_the_cell_shapes(one_chip, q_rows):
    """qwen2-0.5b.creative: 32 slots, tables of 256 pages of 16, a pool of
    2,049 pages; decode (q_rows 1) and the 64-token mixed waves (448 score
    rows per KV head), both at 16 pages per compute block."""
    kv = _spec(one_chip, (2049, 16, NKV, HD))
    calls = _compiled_kernels(
        lambda q, k, v, lens, tables: paged_decode_attention(
            q, k, v, lens, tables, q_rows=q_rows, interpret=False),
        _spec(one_chip, (32, NKV, q_rows * G, HD)), kv, kv,
        _spec(one_chip, (32,), jnp.int32),
        _spec(one_chip, (32, 256), jnp.int32))
    assert len(calls.splitlines()) == 1
    assert "papi_paged_decode_attention" in calls
