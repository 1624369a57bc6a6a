"""Paged KV-cache serving tests: token identity with the dense engine,
the paged-only long-context scenario, and the block-table Pallas kernel.

The contract under test (ISSUE 3 + ISSUE 5 acceptance):
  * on any workload BOTH layouts can hold, the paged engine emits token
    streams identical to the dense engine — greedy and speculative;
  * a request whose prompt+generation exceeds the dense per-slot capacity
    completes under the paged layout (pooled pages, no uniform slot cap);
  * the paged flash-decode kernel is bit-identical to the dense kernel on
    identical KV contents (same block update, block_k = the paged kernel's
    compute block) — for every query-window width t (plain decode, TLP>1
    verify, chunk waves) — and never reads a page past a slot's length;
  * under attn_pim the WINDOWED kernel serves speculative verify and
    chunked prefill too, token-identically to the XLA engines, and
    `gather_kv_pages` never traces (poison-tested);
  * prompt truncation is GONE: prompts longer than the prefill window are
    chunked through it and complete in full;
  * the pool drains: after all requests finish, every page is free again.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import decode_attention, paged_decode_attention
from repro.models import init_params
from repro.serving import PapiEngine, ServeRequest


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("qwen2-0.5b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def draft_model():
    cfg = get_config("qwen2-0.5b").reduced()
    return cfg, init_params(cfg, jax.random.PRNGKey(9))


# eos that random-init weights essentially never argmax to — keeps the
# generation lengths deterministic across layouts and long for the
# long-context scenario
NO_EOS = get_config("qwen2-0.5b").reduced().vocab_size - 1

# mixed-length workload: short and long prompts, staggered budgets
MIXED = [([3 + i, 5, 7, 11][: 2 + i % 3], 3 + 4 * i) for i in range(6)]


def _run(cfg, params, reqs, **kw):
    defaults = dict(max_slots=4, cache_capacity=64, prefill_len=8,
                    alpha=6.0, eos_token=1, debug_invariants=True)
    defaults.update(kw)
    eng = PapiEngine(cfg, params, **defaults)
    for i, (prompt, n) in enumerate(reqs):
        eng.submit(ServeRequest(i, list(prompt), max_new_tokens=n))
    results = eng.run(max_iterations=500)
    streams = {r.req_id: (r.tokens, r.finished_reason) for r in results}
    return streams, eng


def _assert_drained(eng):
    eng.kv.alloc.check()
    assert eng.kv.alloc.mapped_count == 0
    assert eng.kv.alloc.reserved_unmapped == 0
    assert eng.kv.alloc.free_count == eng.kv.alloc.num_pages


def test_paged_greedy_identical_to_dense(small_model):
    cfg, params = small_model
    want, _ = _run(cfg, params, MIXED)
    got, eng = _run(cfg, params, MIXED, kv_layout="paged", page_size=16)
    assert got == want
    _assert_drained(eng)


def test_paged_speculative_identical_to_dense(small_model, draft_model):
    """Draft/verify/accept + device-side cache rewind over block tables:
    same accepted windows, same tokens — and the host-side page rewind
    returns every page by drain time."""
    cfg, params = small_model
    want, _ = _run(cfg, params, MIXED, spec_len=3, draft=draft_model)
    got, eng = _run(cfg, params, MIXED, spec_len=3, draft=draft_model,
                    kv_layout="paged", page_size=8)
    assert got == want
    _assert_drained(eng)


def test_paged_unfused_host_loop_matches_fused(small_model, draft_model):
    """The legacy per-step host loop drives the same paged cache."""
    cfg, params = small_model
    reqs = MIXED[:3]
    want, _ = _run(cfg, params, reqs, spec_len=3, draft=draft_model,
                   kv_layout="paged", page_size=8)
    got, _ = _run(cfg, params, reqs, spec_len=3, draft=draft_model,
                  kv_layout="paged", page_size=8, fused=False)
    assert got == want


def test_paged_completes_request_beyond_dense_slot_capacity(small_model):
    """THE paged-only scenario: prompt + generation far exceeds the
    64-token dense slot, but fits the page pool — the dense engine clamps
    the budget, the paged engine completes it in full."""
    cfg, params = small_model
    prompt = [3, 5, 7, 11, 13, 17]
    want_new = 100
    assert len(prompt) + want_new > 64

    dense, _ = _run(cfg, params, [(prompt, want_new)], eos_token=NO_EOS)
    assert len(dense[0][0]) < want_new        # clamped to the slot budget

    paged, eng = _run(cfg, params, [(prompt, want_new)], eos_token=NO_EOS,
                      kv_layout="paged", page_size=16)
    tokens, reason = paged[0]
    assert len(tokens) == want_new and reason == "length"
    # and the dense stream is a prefix of the paged one (same model path)
    assert tokens[: len(dense[0][0])] == dense[0][0]
    assert eng.kv.alloc.watermark >= eng.kv.pages_for(len(prompt) + want_new)
    _assert_drained(eng)


def test_paged_admission_defers_until_pages_free(small_model):
    """More demand than the pool holds at once: admission must defer (not
    reject), keep order, and finish everyone."""
    cfg, params = small_model
    reqs = [([3 + i, 5, 7], 40) for i in range(6)]
    got, eng = _run(cfg, params, reqs, eos_token=NO_EOS, cache_capacity=32,
                    kv_layout="paged", page_size=8)
    assert sorted(got) == list(range(6))
    assert all(len(t) == 40 and r == "length" for t, r in got.values())
    _assert_drained(eng)


def test_paged_set_spec_len_widen_rebudgets_or_clamps(small_model,
                                                      draft_model):
    """Widening the speculative window mid-run must re-budget live slots'
    page reservations — and clamp the window instead of letting the
    per-iteration ensure() blow up with MemoryError when the pool is
    already fully promised (regression: set_spec_len used to leave the old
    reservations in place and the next decode iteration crashed)."""
    cfg, params = small_model
    eng = PapiEngine(cfg, params, max_slots=2, cache_capacity=32,
                     prefill_len=8, alpha=6.0, eos_token=NO_EOS,
                     spec_len=2, draft=draft_model,
                     kv_layout="paged", page_size=4)
    # pool = 2*32/4 = 16 usable pages; each request reserves
    # pages_for(3 + 27 + 2) = 8 — the two together promise the whole pool
    for i in range(2):
        eng.submit(ServeRequest(i, [3, 5, 7], max_new_tokens=27))
    eng.run(max_iterations=2, abort_in_flight=False)
    assert eng.active_slots == [0, 1]
    assert eng.kv.alloc.available == 0
    eng.set_spec_len(6)             # nothing uncommitted: must clamp
    assert eng.spec_len == 2
    res = eng.run(max_iterations=300)
    assert sorted(r.req_id for r in res) == [0, 1]
    assert all(len(r.tokens) == 27 and r.finished_reason == "length"
               for r in res)
    _assert_drained(eng)

    # with headroom the widen goes through and the wider window is served
    eng2 = PapiEngine(cfg, params, max_slots=2, cache_capacity=64,
                      prefill_len=8, alpha=6.0, eos_token=NO_EOS,
                      spec_len=2, draft=draft_model,
                      kv_layout="paged", page_size=4)
    eng2.submit(ServeRequest(0, [3, 5, 7], max_new_tokens=20))
    eng2.run(max_iterations=2, abort_in_flight=False)
    eng2.set_spec_len(6)
    assert eng2.spec_len == 6
    res2 = eng2.run(max_iterations=300)
    assert len(res2[0].tokens) == 20 and res2[0].finished_reason == "length"
    _assert_drained(eng2)

    # table width also caps the window: a slot admitted flush against
    # max_blocks has no rows left, so the widen clamps even though the
    # POOL has plenty of free pages
    eng3 = PapiEngine(cfg, params, max_slots=2, cache_capacity=64,
                      prefill_len=8, alpha=6.0, eos_token=NO_EOS,
                      spec_len=2, draft=draft_model,
                      kv_layout="paged", page_size=4, max_blocks=6)
    eng3.submit(ServeRequest(0, [3, 5, 7], max_new_tokens=40))
    eng3.run(max_iterations=2,      # admitted clamped to the 24-token table
             abort_in_flight=False)
    assert eng3.kv.alloc.available > 0
    eng3.set_spec_len(6)
    assert eng3.spec_len == 2
    res3 = eng3.run(max_iterations=300)[0]
    assert res3.finished_reason == "length" and len(res3.tokens) == 19
    _assert_drained(eng3)


def test_paged_attn_pim_kernel_path_matches_xla(small_model):
    """attn_pim=True routes paged plain decode through the block-table
    Pallas kernel; tokens must match the XLA gather path and the dense
    engine."""
    cfg, params = small_model
    reqs = MIXED[:3]
    want, _ = _run(cfg, params, reqs)
    got, _ = _run(cfg, params, reqs, kv_layout="paged", page_size=16,
                  attn_pim=True)
    assert got == want


def test_paged_speculative_attn_pim_matches_dense(small_model, draft_model):
    """THE ISSUE 5 path: speculative verify windows (TLP=3) over the paged
    layout through the WINDOWED block-table kernel — draft steps, verify
    windows, accept/rewind — must emit the dense XLA engine's exact
    tokens, and drain the pool."""
    cfg, params = small_model
    want, _ = _run(cfg, params, MIXED, spec_len=3, draft=draft_model)
    got, eng = _run(cfg, params, MIXED, spec_len=3, draft=draft_model,
                    kv_layout="paged", page_size=8, attn_pim=True)
    assert got == want
    _assert_drained(eng)


@pytest.mark.parametrize("kv_layout", ["dense", "paged"])
def test_chunked_prefill_attn_pim_matches_xla(small_model, kv_layout):
    """Chunked admission under attn_pim: every chunk wave is a query
    window through the windowed kernel (t = prefill_len, per-slot masked
    writes), and the streams must match the XLA-path engine's — long and
    short prompts alike."""
    cfg, params = small_model
    kw = dict(kv_layout="paged", page_size=8) if kv_layout == "paged" else {}
    reqs = [(list(range(3, 3 + 20)), 4), ([3, 5], 4),
            (list(range(5, 5 + 30)), 3)]
    want, _ = _run(cfg, params, reqs, **kw)
    got, _ = _run(cfg, params, reqs, attn_pim=True, **kw)
    assert got == want


def test_no_page_gather_traced_under_attn_pim(small_model, draft_model,
                                              monkeypatch):
    """ISSUE 5 acceptance: with attn_pim active, NO jitted decode / verify
    / chunk program may call `gather_kv_pages` — the paged kernel resolves
    pages inside its index_map.  Poison the gather and run the full
    gauntlet (chunked admission, plain decode, speculative draft+verify):
    a single traced gather raises."""
    from repro.models import layers

    def boom(pages, tables):
        raise AssertionError(
            "gather_kv_pages traced on the attn_pim hot path")

    cfg, params = small_model
    reqs = [(list(range(3, 3 + 20)), 5), ([3, 5, 7], 6)]
    kw = dict(kv_layout="paged", page_size=8, spec_len=3, draft=draft_model,
              eos_token=NO_EOS)
    want, _ = _run(cfg, params, reqs, **kw)          # XLA gather path
    monkeypatch.setattr(layers, "gather_kv_pages", boom)
    got, eng = _run(cfg, params, reqs, attn_pim=True, **kw)
    assert got == want
    _assert_drained(eng)


def test_paged_iter_stats_surface_pool_state(small_model):
    cfg, params = small_model
    _, eng = _run(cfg, params, MIXED, kv_layout="paged", page_size=16)
    busy = [s for s in eng.stats if s.new_tokens > 0]
    assert busy and any(s.kv_pages_used > 0 for s in busy)
    assert max(s.kv_page_watermark for s in eng.stats) == eng.kv.alloc.watermark
    assert all(0.0 <= s.kv_fragmentation <= 1.0 for s in eng.stats)
    # dense engines report zeros (fields exist but stay inert)
    _, dense_eng = _run(cfg, params, MIXED[:2])
    assert all(s.kv_pages_used == 0 for s in dense_eng.stats)


@pytest.mark.parametrize("kv_layout", ["dense", "paged"])
def test_long_prompts_complete_untruncated(small_model, kv_layout):
    """`p = min(len(prompt), prefill_len)` used to silently drop the prompt
    head; admission now CHUNKS any prompt through the prefill window, so
    long prompts complete in full — no truncation flag, no warning, and the
    streams match an engine whose window holds each prompt one-shot."""
    cfg, params = small_model
    kw = {"page_size": 16} if kv_layout == "paged" else {}

    def run(prefill_len):
        eng = PapiEngine(cfg, params, max_slots=2, cache_capacity=64,
                         prefill_len=prefill_len, alpha=6.0, eos_token=1,
                         kv_layout=kv_layout, **kw)
        eng.submit(ServeRequest(0, list(range(3, 3 + 20)), max_new_tokens=3))
        eng.submit(ServeRequest(1, [3, 5], max_new_tokens=3))
        eng.submit(ServeRequest(2, list(range(5, 5 + 30)), max_new_tokens=3))
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            results = {r.req_id: r for r in eng.run(max_iterations=100)}
        return results, caught

    results, caught = run(prefill_len=8)      # 20- and 30-token prompts chunk
    oneshot, _ = run(prefill_len=32)          # every prompt fits one window
    assert not any("prefill_len" in str(w.message) for w in caught)
    for i in range(3):
        assert results[i].tokens == oneshot[i].tokens


def _paged_copy(kd, vd, tables, page):
    """A page pool holding the dense cache's first `nblk` blocks of each
    slot at the pages its table names (page 0 and others left zero)."""
    b, nblk = tables.shape
    kp = np.zeros((b * nblk + 1, page) + kd.shape[2:], np.float32)
    vp = np.zeros_like(kp)
    for i in range(b):
        for blk in range(nblk):
            kp[tables[i, blk]] = np.asarray(kd)[i, blk * page:(blk + 1) * page]
            vp[tables[i, blk]] = np.asarray(vd)[i, blk * page:(blk + 1) * page]
    return kp, vp


# (page_size, max_blocks, lens): compute blocks of bk = 6 * 32 = 192
# positions (the table holds 6 pages), and of 16 * 16 = 256 positions over
# a table of 20 pages, which 16 does not divide; lens end mid-page, on the
# table's end, on a compute-block boundary, one past it, and at 1 (a parked
# slot)
BK_CASES = {192: (32, 6, [33, 192, 7]),
            256: (16, 20, [37, 256, 257, 1, 320])}


@pytest.mark.parametrize("bk", sorted(BK_CASES))
@pytest.mark.parametrize("t", [1, 2, 4])
def test_paged_kernel_bit_identical_to_dense_kernel(t, bk):
    """Identical KV contents scattered across a shuffled page pool: the
    paged kernel (live pages, `ppc` per compute block) must be
    BIT-identical to the dense kernel at block_k = bk — each compute block
    runs the same `block_update`.  Holds for every query-window width: t=1
    plain decode, t=2, and a spec-window t=4 (the windowed rows share the
    body's intra-window mask), skipping dead pages or walking them all."""
    from repro.kernels.paged_decode_attention import pages_per_block
    page, nblk, lens = BK_CASES[bk]
    assert pages_per_block(page, nblk, t * 4) * page == bk
    b, nkv, g, hd = len(lens), 2, 4, 64
    S = -(-nblk * page // bk) * bk        # the dense cache in whole blocks
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, nkv, t * g, hd), jnp.float32)
    kd = jax.random.normal(ks[1], (b, S, nkv, hd), jnp.float32)
    vd = jax.random.normal(ks[2], (b, S, nkv, hd), jnp.float32)
    lens = jnp.asarray([max(n, t) for n in lens], jnp.int32)

    rng = np.random.default_rng(0)
    tables = rng.permutation(np.arange(1, b * nblk + 1)).reshape(b, nblk)
    kp, vp = _paged_copy(kd, vd, tables, page)

    for skip in (True, False):
        want = decode_attention(q, kd, vd, lens, block_k=bk,
                                interpret=True, block_skip=skip, q_rows=t)
        got = paged_decode_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                                     lens, jnp.asarray(tables),
                                     interpret=True, block_skip=skip,
                                     q_rows=t)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("t", [1, 3])
def test_paged_kernel_never_reads_a_dead_page(t):
    """Every pool page outside the slots' live prefixes holds NaN, the
    garbage page too, and the tables point past each prefix at NaN pages:
    with block_skip=True the output is finite and equal to that over a
    clean pool.  A dead page that reached the math (a NaN V row times a
    masked p = 0) would make it NaN."""
    page, nblk, lens = BK_CASES[256]
    b, nkv, g, hd = len(lens), 2, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, nkv, t * g, hd), jnp.float32)
    kp = np.asarray(jax.random.normal(ks[1], (b * nblk + 1, page, nkv, hd)))
    vp = np.asarray(jax.random.normal(ks[2], kp.shape))
    lens = jnp.asarray([max(n, t) for n in lens], jnp.int32)
    tables = np.random.default_rng(1).permutation(
        np.arange(1, b * nblk + 1)).reshape(b, nblk)
    live = np.zeros(len(kp), bool)
    for i, n in enumerate(np.asarray(lens)):
        live[tables[i, :-(-n // page)]] = True
        if i % 2:                         # past the prefix: garbage page
            tables[i, -(-n // page):] = 0
    kn, vn = kp.copy(), vp.copy()
    kn[~live] = np.nan
    vn[~live] = np.nan
    args = (lens, jnp.asarray(tables))
    clean = paged_decode_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                                   *args, interpret=True, q_rows=t)
    got = paged_decode_attention(q, jnp.asarray(kn), jnp.asarray(vn),
                                 *args, interpret=True, q_rows=t)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


def test_paged_windowed_kernel_matches_gather_oracle():
    """The windowed paged kernel vs the exact hot path it replaced:
    `gather_kv_pages` + the XLA windowed softmax.  Greedy-level agreement
    is what the engine gates assert; here the raw outputs must agree to
    f32 tolerance across ragged lens and a shuffled pool."""
    from repro.models.layers import (decode_attention_pim_paged,
                                     decode_attention_xla, gather_kv_pages)
    b, t, nh, nkv, hd, page, nblk = 3, 3, 4, 2, 32, 16, 5
    num_pages = b * nblk + 1
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (b, t, nh, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (num_pages, page, nkv, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (num_pages, page, nkv, hd), jnp.float32)
    rng = np.random.default_rng(2)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, num_pages)).reshape(b, nblk), jnp.int32)
    lens = jnp.asarray([t, 37, page * nblk], jnp.int32)   # min, mid, full
    pos = lens - t
    kg, vg = gather_kv_pages(kp, tables), gather_kv_pages(vp, tables)
    want = decode_attention_xla(q, kg, vg, cache_len=lens, q_offset=pos)
    got = decode_attention_pim_paged(q, kp, vp, tables, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t", [1, 3])
def test_paged_kernel_garbage_table_entries_masked(t):
    """Entries at/past a request's last valid block may point anywhere
    (the engine points them at the garbage page) — they must not leak into
    the output, skipping on or off, single-query or windowed (a window's
    rows mask everything past their own position, so garbage never leaks
    backward into any row)."""
    b, nkv, g, hd, page, nblk = 2, 2, 2, 32, 16, 4
    num_pages = b * nblk + 1
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, nkv, t * g, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (num_pages, page, nkv, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (num_pages, page, nkv, hd), jnp.float32)
    lens = jnp.asarray([20, 7], jnp.int32)      # 2 blocks / 1 block valid
    tables = np.array([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
    scrubbed = tables.copy()
    scrubbed[0, 2:] = 0                         # beyond-len -> garbage page
    scrubbed[1, 1:] = 0
    for skip in (True, False):
        a = paged_decode_attention(q, kp, vp, lens, jnp.asarray(tables),
                                   interpret=True, block_skip=skip, q_rows=t)
        c = paged_decode_attention(q, kp, vp, lens, jnp.asarray(scrubbed),
                                   interpret=True, block_skip=skip, q_rows=t)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
