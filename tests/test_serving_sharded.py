"""Mesh-sharded serving tests (§5.3 layout).

These need >= 8 host devices, so CI runs this file in a dedicated step with

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest -q tests/test_serving_sharded.py

Under the plain tier-1 invocation (1 CPU device) everything here skips.

What is asserted:
  * the mesh-sharded engine's token streams are identical to the 1-device
    engine's — greedy argmax is invariant to GSPMD's ulp-level reduction
    reordering, so serving output is exactly reproducible across mesh
    shapes;
  * the scheduler's FC_PU <-> FC_PIM flip still takes effect under a mesh
    (each variant traces its own partitioned executable, incl. the
    shard_map'd fc_gemv banks);
  * the head-sharded flash-decode kernel (one Attn-PIM unit per KV shard)
    is bit-identical to the unsharded kernel, standalone and inside the
    engine.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.serving import PapiEngine, ServeRequest

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8",
)


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("qwen2-0.5b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _mesh(dp, tp):
    from repro.launch.mesh import make_serving_mesh
    return make_serving_mesh(dp, tp)


def _run(cfg, params, reqs, **kw):
    defaults = dict(max_slots=4, cache_capacity=64, prefill_len=8,
                    alpha=6.0, eos_token=1, debug_invariants=True)
    defaults.update(kw)
    eng = PapiEngine(cfg, params, **defaults)
    for i, (prompt, n) in enumerate(reqs):
        eng.submit(ServeRequest(i, prompt, max_new_tokens=n))
    results = eng.run(max_iterations=300)
    streams = {r.req_id: (r.tokens, r.finished_reason) for r in results}
    return streams, eng


REQS = [([3 + i, 5, 7, 11], 4 + 3 * i) for i in range(6)]


@needs8
def test_mesh_tokens_identical_to_one_device(small_model):
    """launch acceptance: 8-way tensor-parallel decode emits the exact token
    stream of the single-device engine, request for request."""
    cfg, params = small_model
    want, _ = _run(cfg, params, REQS)
    got, eng = _run(cfg, params, REQS, mesh=_mesh(1, 8))
    assert eng.mesh is not None
    assert got == want


@needs8
def test_mesh_scheduler_flip_takes_effect(small_model):
    """Under a mesh the FC flip must still switch executables: with staggered
    request lengths both variants appear in the iteration stats, and the
    pim iterations (shard_map'd fc_gemv banks) leave the tokens unchanged."""
    cfg, params = small_model
    want, weng = _run(cfg, params, REQS, alpha=3.0)
    got, eng = _run(cfg, params, REQS, alpha=3.0, mesh=_mesh(1, 8))
    variants = {s.fc_variant for s in eng.stats if s.rlp > 0}
    assert variants == {"pu", "pim"}
    assert eng.scheduler.num_reschedules >= 1
    assert got == want


@needs8
def test_mesh_speculative_matches_one_device(small_model):
    """The fused draft/verify/accept scan partitioned over the mesh accepts
    exactly the same windows as the 1-device engine."""
    cfg, params = small_model
    draft_cfg = get_config("qwen2-0.5b").reduced()
    draft_params = init_params(draft_cfg, jax.random.PRNGKey(9))
    reqs = REQS[:3]
    want, _ = _run(cfg, params, reqs, spec_len=3,
                   draft=(draft_cfg, draft_params))
    got, _ = _run(cfg, params, reqs, spec_len=3,
                  draft=(draft_cfg, draft_params), mesh=_mesh(1, 8))
    assert got == want


@needs8
def test_mesh_dp_axis_also_matches(small_model):
    """A (2, 4) mesh — data-replicated engine x 4 FC banks — same tokens."""
    cfg, params = small_model
    want, _ = _run(cfg, params, REQS[:4])
    got, _ = _run(cfg, params, REQS[:4], mesh=_mesh(2, 4))
    assert got == want


@needs8
def test_decode_attention_sharded_bit_identical():
    """One Attn-PIM unit per KV shard: no cross-shard term exists, so the
    shard_map'd kernel must be BIT-identical to the unsharded one."""
    from repro.kernels import decode_attention, decode_attention_sharded
    b, nkv, g, hd, skv = 2, 8, 2, 32, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, nkv, g, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, skv, nkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, skv, nkv, hd), jnp.float32)
    lens = jnp.asarray([37, 128], jnp.int32)
    mesh = _mesh(1, 8)
    got = decode_attention_sharded(q, k, v, lens, mesh=mesh, block_k=32,
                                   interpret=True)
    want = decode_attention(q, k, v, lens, block_k=32, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@needs8
def test_decode_attention_sharded_indivisible_heads_fall_back():
    """2 KV heads on an 8-way axis cannot split: the wrapper must fall back
    to the replicated kernel instead of mis-sharding."""
    from repro.kernels import decode_attention, decode_attention_sharded
    b, nkv, g, hd, skv = 2, 2, 2, 32, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, nkv, g, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, skv, nkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, skv, nkv, hd), jnp.float32)
    lens = jnp.asarray([11, 64], jnp.int32)
    got = decode_attention_sharded(q, k, v, lens, mesh=_mesh(1, 8),
                                   block_k=32, interpret=True)
    want = decode_attention(q, k, v, lens, block_k=32, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@needs8
def test_decode_attention_sharded_windowed_bit_identical():
    """Query windows under the KV-head shard_map (TLP>1 verify form): each
    shard masks its own heads' window rows locally, no cross-shard term —
    bit-identical to the unsharded windowed kernel."""
    from repro.kernels import decode_attention, decode_attention_sharded
    b, nkv, g, hd, skv, t = 2, 8, 2, 32, 128, 3
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, nkv, t * g, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, skv, nkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, skv, nkv, hd), jnp.float32)
    lens = jnp.asarray([37, 128], jnp.int32)
    got = decode_attention_sharded(q, k, v, lens, mesh=_mesh(1, 8),
                                   block_k=32, interpret=True, q_rows=t)
    want = decode_attention(q, k, v, lens, block_k=32, interpret=True,
                            q_rows=t)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@needs8
def test_paged_decode_attention_sharded_windowed_bit_identical():
    """The windowed paged kernel under the KV-head shard_map: tables/lens
    replicate, each shard streams its heads' pages for all t window rows —
    bit-identical to the unsharded windowed paged kernel."""
    from repro.kernels import (paged_decode_attention,
                               paged_decode_attention_sharded)
    b, nkv, g, hd, page, nblk, t = 2, 8, 2, 32, 16, 4, 3
    num_pages = b * nblk + 1
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (b, nkv, t * g, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (num_pages, page, nkv, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (num_pages, page, nkv, hd), jnp.float32)
    lens = jnp.asarray([37, 64], jnp.int32)
    tables = jnp.asarray(
        np.arange(1, num_pages).reshape(b, nblk), jnp.int32)
    got = paged_decode_attention_sharded(q, kp, vp, lens, tables,
                                         mesh=_mesh(1, 8), interpret=True,
                                         q_rows=t)
    want = paged_decode_attention(q, kp, vp, lens, tables, interpret=True,
                                  q_rows=t)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@needs8
def test_spec_attn_pim_paged_mesh_matches_unsharded_dense(small_model):
    """The full ISSUE 5 composition: speculative verify windows + paged KV
    + the windowed block-table kernel + a (1, 2) KV-head mesh — token
    streams must equal the 1-device dense XLA engine's."""
    cfg, params = small_model
    draft_cfg = get_config("qwen2-0.5b").reduced()
    draft_params = init_params(draft_cfg, jax.random.PRNGKey(9))
    reqs = REQS[:3]
    want, _ = _run(cfg, params, reqs, spec_len=3,
                   draft=(draft_cfg, draft_params))
    got, eng = _run(cfg, params, reqs, spec_len=3,
                    draft=(draft_cfg, draft_params), kv_layout="paged",
                    page_size=16, attn_pim=True, mesh=_mesh(1, 2))
    assert eng.mesh is not None and eng.kv is not None
    assert got == want


@needs8
def test_attn_pim_engine_sharded_matches_unsharded(small_model):
    """The engine's Attn-PIM path (flash-decode kernel) under a (1, 2) mesh —
    exactly one KV head per shard for this GQA config — emits the same
    tokens as the unsharded Attn-PIM engine."""
    cfg, params = small_model
    assert cfg.num_kv_heads == 2
    want, _ = _run(cfg, params, REQS[:3], attn_pim=True)
    got, _ = _run(cfg, params, REQS[:3], attn_pim=True, mesh=_mesh(1, 2))
    assert got == want


@needs8
def test_paged_engine_sharded_matches_unsharded(small_model):
    """Paged KV + KV-head sharding: the paged engine under a (1, 2) mesh
    with the block-table Pallas kernel (one Attn-PIM unit per KV-head
    shard, pages resolved in the index_map) emits the same tokens as the
    unsharded paged engine AND the unsharded dense engine."""
    cfg, params = small_model
    want, _ = _run(cfg, params, REQS[:3])
    paged, _ = _run(cfg, params, REQS[:3], kv_layout="paged", page_size=16,
                    attn_pim=True)
    sharded, eng = _run(cfg, params, REQS[:3], kv_layout="paged",
                        page_size=16, attn_pim=True, mesh=_mesh(1, 2))
    assert eng.mesh is not None and eng.kv is not None
    assert paged == want
    assert sharded == want


@needs8
def test_paged_engine_sharded_xla_path_matches_unsharded(small_model):
    """Paged + mesh WITHOUT attn_pim: the pool dim cannot shard (physical
    page ids index the whole pool), so the engine must still store the
    pools head-sharded — the default rules under a mesh switch to the
    attn_pim table for any paged engine — and the XLA page-gather decode
    path must emit the same tokens as the unsharded engines."""
    from repro.distributed.sharding import serve_rules
    cfg, params = small_model
    want, _ = _run(cfg, params, REQS[:3])
    got, eng = _run(cfg, params, REQS[:3], kv_layout="paged", page_size=16,
                    mesh=_mesh(1, 2))
    assert got == want
    assert eng.rules == serve_rules(attn_pim=True)


@needs8
def test_paged_mesh_chunked_prefill_matches_unsharded(small_model):
    """Chunked admission under a mesh: a prompt 4x the prefill window runs
    its chunk waves through the partitioned chunk step (paged scatter over
    head-sharded pools) and must emit the exact tokens of the 1-device
    DENSE engine, alongside a short prompt admitted in the same wave."""
    cfg, params = small_model
    no_eos = cfg.vocab_size - 1
    reqs = [(list(range(3, 3 + 33)), 6), ([3, 5, 7], 5)]
    want, _ = _run(cfg, params, reqs, eos_token=no_eos)
    got, eng = _run(cfg, params, reqs, eos_token=no_eos,
                    kv_layout="paged", page_size=8, mesh=_mesh(1, 2))
    assert eng.mesh is not None
    assert got == want


@needs8
def test_paged_decode_attention_sharded_bit_identical():
    """The paged kernel shard_mapped over KV heads (tables/lens replicated,
    page pools split on the head dim) must be BIT-identical to the
    unsharded paged kernel."""
    from repro.kernels import (paged_decode_attention,
                               paged_decode_attention_sharded)
    b, nkv, g, hd, page, nblk = 2, 8, 2, 32, 16, 4
    num_pages = b * nblk + 1
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (b, nkv, g, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (num_pages, page, nkv, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (num_pages, page, nkv, hd), jnp.float32)
    lens = jnp.asarray([37, 64], jnp.int32)
    tables = jnp.asarray(
        np.arange(1, num_pages).reshape(b, nblk), jnp.int32)
    mesh = _mesh(1, 8)
    got = paged_decode_attention_sharded(q, kp, vp, lens, tables, mesh=mesh,
                                         interpret=True)
    want = paged_decode_attention(q, kp, vp, lens, tables, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@needs8
def test_sharded_fc_gemv_col_banks_bit_identical():
    """Column-split FC-PIM banks concatenate without any cross-bank
    reduction — bit-identical to the single-bank kernel."""
    from jax.sharding import PartitionSpec as P

    from repro.kernels import fc_gemv
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 128), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), (128, 256), jnp.float32)
    mesh = _mesh(1, 8)
    got = jax.shard_map(lambda xs, ws: fc_gemv(xs, ws, interpret=True),
                        mesh=mesh, in_specs=(P(), P(None, "model")),
                        out_specs=P(None, "model"), check_vma=False)(x, w)
    want = fc_gemv(x, w, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@needs8
def test_mesh_serve_streaming_matches_unsharded(small_model):
    """Continuous-batching serve() composes with the mesh: live staggered
    arrivals under 8-way tensor parallelism stream the exact token
    sequences of the unsharded offline batch run."""
    cfg, params = small_model
    want, _ = _run(cfg, params, REQS)

    eng = PapiEngine(cfg, params, max_slots=4, cache_capacity=64,
                     prefill_len=8, alpha=6.0, eos_token=1,
                     debug_invariants=True, mesh=_mesh(1, 8))
    sched = []
    for i, (prompt, n) in enumerate(REQS):
        sched.append([ServeRequest(i, prompt, max_new_tokens=n)])
        sched.append([])
    streams: dict[int, list[int]] = {}
    finals = {}
    for ev in eng.serve(sched):
        if ev.finished:
            finals[ev.req_id] = (ev.result.tokens, ev.result.finished_reason)
        else:
            streams.setdefault(ev.req_id, []).append(ev.token)
    assert finals == want
    for rid, (toks, _) in finals.items():
        assert streams.get(rid, []) == toks
