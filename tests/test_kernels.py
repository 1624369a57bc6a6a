"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

All kernels run in interpret mode on CPU (the kernel body executes in
Python) — this validates the BlockSpec indexing, scratch accumulation and
online-softmax math that will run compiled on TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.fc_gemv import fc_gemv
from repro.kernels.ssd_scan import ssd_scan


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# decode_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,nkv,g,hd,skv,block_k",
    [
        (2, 2, 4, 64, 256, 128),
        (1, 4, 1, 128, 512, 256),   # MHA (g=1)
        (3, 1, 12, 64, 384, 128),   # extreme GQA, ragged grid
        (2, 2, 7, 128, 256, 256),   # odd group size, single kv block
    ],
)
def test_decode_attention_sweep(b, nkv, g, hd, skv, block_k, dtype):
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (b, nkv, g, hd), dtype)
    k = jax.random.normal(keys[1], (b, skv, nkv, hd), dtype)
    v = jax.random.normal(keys[2], (b, skv, nkv, hd), dtype)
    lens = jax.random.randint(keys[3], (b,), 1, skv + 1)
    got = decode_attention(q, k, v, lens, block_k=block_k, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


def test_decode_attention_masks_beyond_len():
    """KV positions past lens must not affect the output."""
    b, nkv, g, hd, skv = 1, 2, 4, 64, 256
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (b, nkv, g, hd), jnp.float32)
    k = jax.random.normal(keys[1], (b, skv, nkv, hd), jnp.float32)
    v = jax.random.normal(keys[2], (b, skv, nkv, hd), jnp.float32)
    lens = jnp.array([100], jnp.int32)
    out1 = decode_attention(q, k, v, lens, block_k=128, interpret=True)
    k2 = k.at[:, 100:].set(999.0)
    v2 = v.at[:, 100:].set(-999.0)
    out2 = decode_attention(q, k2, v2, lens, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_block_skip_bit_identical(dtype):
    """Ragged continuous batch: skipping fully-masked KV blocks (clamped
    index_map + pl.when no-op) must be BIT-identical to streaming them all —
    a fully-masked tile contributes exactly alpha=1.0, p=+0.0."""
    b, nkv, g, hd, skv, block_k = 5, 2, 4, 64, 512, 128
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(keys[0], (b, nkv, g, hd), dtype)
    k = jax.random.normal(keys[1], (b, skv, nkv, hd), dtype)
    v = jax.random.normal(keys[2], (b, skv, nkv, hd), dtype)
    # raggedness spanning: sub-block, block-aligned, mid, near-full, full
    lens = jnp.array([1, 128, 200, 511, 512], jnp.int32)
    skip = decode_attention(q, k, v, lens, block_k=block_k, interpret=True,
                            block_skip=True)
    full = decode_attention(q, k, v, lens, block_k=block_k, interpret=True,
                            block_skip=False)
    np.testing.assert_array_equal(
        np.asarray(skip, np.float32), np.asarray(full, np.float32))
    # and both still match the oracle
    want = ref.decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(
        np.asarray(skip, np.float32), np.asarray(want, np.float32),
        **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,nkv,g,hd,skv,block_k,t",
    [
        (2, 2, 4, 64, 256, 128, 2),    # TLP=2 verify window
        (3, 1, 12, 64, 384, 128, 4),   # extreme GQA, spec window
        (1, 4, 1, 128, 512, 256, 3),   # MHA (g=1), odd window
        (2, 2, 7, 128, 256, 256, 8),   # chunk-wave-sized window, odd group
    ],
)
def test_decode_attention_windowed_sweep(b, nkv, g, hd, skv, block_k, t,
                                         dtype):
    """Query windows (TLP>1): the kernel's intra-window causal mask vs the
    pure-jnp oracle, across GQA ratios and ragged per-request lengths."""
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(keys[0], (b, nkv, t * g, hd), dtype)
    k = jax.random.normal(keys[1], (b, skv, nkv, hd), dtype)
    v = jax.random.normal(keys[2], (b, skv, nkv, hd), dtype)
    # lens >= t: every window row keeps at least its own diagonal position
    lens = jax.random.randint(keys[3], (b,), t, skv + 1)
    got = decode_attention(q, k, v, lens, block_k=block_k, interpret=True,
                           q_rows=t)
    want = ref.decode_attention_window_ref(q, k, v, lens, q_rows=t)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype))


def test_decode_attention_windowed_causal_within_window():
    """Row r must not see KV written for later window rows: perturbing KV at
    positions past row r's own leaves rows 0..r bit-unchanged."""
    b, nkv, g, hd, skv, t = 1, 2, 2, 64, 256, 4
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(keys[0], (b, nkv, t * g, hd), jnp.float32)
    k = jax.random.normal(keys[1], (b, skv, nkv, hd), jnp.float32)
    v = jax.random.normal(keys[2], (b, skv, nkv, hd), jnp.float32)
    lens = jnp.array([100], jnp.int32)        # window rows at 96..99
    out = decode_attention(q, k, v, lens, block_k=128, interpret=True,
                           q_rows=t)
    for r in range(t):
        pos_r = 100 - t + r                   # row r's absolute position
        k2 = k.at[:, pos_r + 1:].set(999.0)
        v2 = v.at[:, pos_r + 1:].set(-999.0)
        out2 = decode_attention(q, k2, v2, lens, block_k=128, interpret=True,
                                q_rows=t)
        # rows 0..r (kernel rows 0..(r+1)*g-1) see nothing past pos_r
        np.testing.assert_array_equal(
            np.asarray(out[:, :, : (r + 1) * g]),
            np.asarray(out2[:, :, : (r + 1) * g]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_windowed_block_skip_bit_identical(dtype):
    """Ragged block skipping must stay bit-exact for query windows: the
    clamp keys on the full window length, and fully-masked tiles contribute
    exactly nothing to every row."""
    b, nkv, g, hd, skv, block_k, t = 5, 2, 4, 64, 512, 128, 3
    keys = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(keys[0], (b, nkv, t * g, hd), dtype)
    k = jax.random.normal(keys[1], (b, skv, nkv, hd), dtype)
    v = jax.random.normal(keys[2], (b, skv, nkv, hd), dtype)
    lens = jnp.array([3, 128, 200, 511, 512], jnp.int32)
    skip = decode_attention(q, k, v, lens, block_k=block_k, interpret=True,
                            block_skip=True, q_rows=t)
    full = decode_attention(q, k, v, lens, block_k=block_k, interpret=True,
                            block_skip=False, q_rows=t)
    np.testing.assert_array_equal(
        np.asarray(skip, np.float32), np.asarray(full, np.float32))
    want = ref.decode_attention_window_ref(q, k, v, lens, q_rows=t)
    np.testing.assert_allclose(
        np.asarray(skip, np.float32), np.asarray(want, np.float32),
        **_tol(dtype))


def test_decode_attention_window_matches_xla_layer_path():
    """The [b, t, nH, hd] wrapper (`layers.decode_attention_pim`) against
    `layers.decode_attention_xla` — the routing-level oracle pair that
    attention_block dispatches between."""
    from repro.models.layers import decode_attention_pim, decode_attention_xla
    b, t, nh, nkv, hd, skv = 3, 4, 6, 2, 32, 128
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (b, t, nh, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, skv, nkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, skv, nkv, hd), jnp.float32)
    pos = jnp.asarray([0, 17, 124], jnp.int32)   # incl. pos=0 and near-full
    want = decode_attention_xla(q, k, v, cache_len=pos + t, q_offset=pos)
    got = decode_attention_pim(q, k, v, lens=pos + t)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# paged_decode_attention: compute blocks from shapes, grid from live pages
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("page,max_blocks,rows,ppc", [
    (16, 256, 7, 16),       # qwen2-0.5b decode: bk = 256
    (16, 256, 64 * 7, 16),  # its 64-token waves: a [448, 256] f32 score
    (16, 256, 512 * 7, 4),  # 512-token waves: the score budget binds
    (32, 6, 8, 6),          # a table shorter than a full block
    (512, 8, 7, 1),         # pages longer than a block: one per block
])
def test_pages_per_block_adapts_to_shapes(page, max_blocks, rows, ppc):
    from repro.kernels.paged_decode_attention import (SCORE_BYTES,
                                                      pages_per_block)
    assert pages_per_block(page, max_blocks, rows) == ppc
    if ppc > 1:
        assert 4 * rows * ppc * page <= SCORE_BYTES


@pytest.mark.parametrize("block_skip", [True, False])
def test_paged_schedule_walks_only_live_blocks(block_skip):
    """The grid's steps are each slot's compute blocks in order: exactly
    cdiv(lens, bk) of them with skipping (one for an empty slot, whose
    output stays zero), every table entry without."""
    from repro.kernels.paged_decode_attention import _schedule
    page, ppc, max_blocks = 16, 4, 10                   # bk = 64
    lens = jnp.asarray([1, 64, 65, 0, 160], jnp.int32)
    steps, slot, block = _schedule(lens, page_size=page, ppc=ppc,
                                   max_blocks=max_blocks,
                                   block_skip=block_skip)
    per_slot = [1, 1, 2, 1, 3] if block_skip else [3] * 5
    assert int(steps) == sum(per_slot)
    assert slot.shape == block.shape == (5 * 3,)
    want = [(i, n) for i, k in enumerate(per_slot) for n in range(k)]
    got = list(zip(np.asarray(slot)[:int(steps)].tolist(),
                   np.asarray(block)[:int(steps)].tolist()))
    assert got == want


def test_paged_empty_slot_reads_zero():
    """lens == 0 (which the engine never produces) gives zeros, as the
    dense kernel's skipping does."""
    from repro.kernels.paged_decode_attention import paged_decode_attention
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (2, 2, 4, 32), jnp.float32)
    kp = jax.random.normal(ks[1], (5, 16, 2, 32), jnp.float32)
    vp = jax.random.normal(ks[2], (5, 16, 2, 32), jnp.float32)
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    out = paged_decode_attention(q, kp, vp, jnp.asarray([0, 20], jnp.int32),
                                 tables, interpret=True)
    assert not np.asarray(out[0]).any()
    dense = decode_attention(q, jnp.stack([kp[1:3].reshape(32, 2, 32),
                                           kp[3:5].reshape(32, 2, 32)]),
                             jnp.stack([vp[1:3].reshape(32, 2, 32),
                                        vp[3:5].reshape(32, 2, 32)]),
                             jnp.asarray([0, 20], jnp.int32), block_k=32,
                             interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(dense))


# ---------------------------------------------------------------------------
# fc_gemv
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "m,K,N,bk,bn",
    [
        (1, 512, 256, 128, 128),     # pure GEMV
        (8, 1024, 512, 256, 256),    # RLP*TLP = 8
        (32, 768, 384, 256, 128),    # ragged blocks
        (4, 256, 256, 256, 256),     # single block
    ],
)
def test_fc_gemv_sweep(m, K, N, bk, bn, dtype):
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    x = jax.random.normal(keys[0], (m, K), dtype)
    w = jax.random.normal(keys[1], (K, N), dtype) / np.sqrt(K)
    got = fc_gemv(x, w, block_k=bk, block_n=bn, interpret=True)
    want = ref.fc_gemv_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


def test_fc_variants_identical():
    """PAPI's two FC paths (pu / pim) must be numerically interchangeable —
    the scheduler flips between them at runtime."""
    from repro.kernels.ops import fc_forward
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 512), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(4), (512, 512), jnp.float32) / 32
    a = fc_forward(x, w, "pu")
    b = fc_forward(x, w, "pim", interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,nh,l,hp,n,chunk",
    [
        (2, 2, 128, 32, 16, 32),
        (1, 4, 256, 64, 64, 64),
        (2, 1, 64, 64, 128, 64),    # single chunk
        (1, 2, 96, 32, 16, 32),     # 3 chunks
    ],
)
def test_ssd_scan_sweep(b, nh, l, hp, n, chunk, dtype):
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    dtx = (jax.random.normal(keys[0], (b, nh, l, hp)) * 0.5).astype(dtype)
    # realistic decays: lt = dt * A with dt ~ softplus, A in [-16, -1]
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, nh, l)) - 1.0)
    A = -jnp.exp(jax.random.uniform(keys[2], (nh,), minval=0.0, maxval=2.0))
    lt = (dt * A[None, :, None]).astype(jnp.float32)
    B = (jax.random.normal(keys[3], (b, l, n)) * 0.5).astype(dtype)
    C = (jax.random.normal(keys[0], (b, l, n)) * 0.5).astype(dtype)
    got = ssd_scan(dtx, lt, B, C, chunk=chunk, interpret=True)
    want = ref.ssd_scan_ref(dtx, lt, B, C)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol
    )


def test_ssd_scan_matches_model_chunked_path():
    """The Pallas kernel and the model's pure-JAX chunked SSD must agree."""
    from repro.models.ssm import _ssd_chunked
    b, nh, l, hp, n, chunk = 2, 2, 128, 32, 16, 32
    keys = jax.random.split(jax.random.PRNGKey(6), 5)
    x = jax.random.normal(keys[0], (b, l, nh, hp), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, l, nh)) - 1.0)
    A = -jnp.exp(jax.random.uniform(keys[2], (nh,), minval=0.0, maxval=2.0))
    B = jax.random.normal(keys[3], (b, l, n), jnp.float32) * 0.5
    C = jax.random.normal(keys[4], (b, l, n), jnp.float32) * 0.5

    y_model, _ = _ssd_chunked(x, dt, A, B, C, chunk)

    dtx = jnp.moveaxis(dt[..., None] * x, 1, 2)      # [b, nh, l, hp]
    lt = jnp.moveaxis(dt * A[None, None, :], 1, 2)   # [b, nh, l]
    Bm, Cm = B, C
    y_kernel = ssd_scan(dtx, lt, Bm, Cm, chunk=chunk, interpret=True)
    y_kernel = jnp.moveaxis(y_kernel, 1, 2)          # [b, l, nh, hp]
    np.testing.assert_allclose(
        np.asarray(y_kernel), np.asarray(y_model), rtol=1e-4, atol=1e-4
    )
