"""Transformer building blocks: norms, RoPE/M-RoPE, MLPs, attention.

Everything is a pure function over explicit parameter dicts (no framework).
Attention comes in three flavors:
  * `flash_attention`  — blockwise online-softmax attention (pure JAX scan),
    used for training / prefill so a 32k x 32k score matrix is never
    materialized.  This is the XLA path; the Pallas TPU kernel in
    `repro.kernels` implements the same math for the decode hot-spot.
  * `decode_attention` — one (or TLP) query tokens against a KV cache.
  * dense fallback for tiny smoke shapes.

Numerics policy: matmuls run in the params' dtype (bf16 on the production
path), softmax/normalization statistics accumulate in f32.
"""
from __future__ import annotations

import contextlib
import math
import threading
from typing import Mapping

import jax
import jax.numpy as jnp

from repro.distributed.sharding import current_mesh, shard
from repro.models.linear import papi_linear

Params = Mapping[str, jax.Array]

_attn_state = threading.local()


def current_attn_impl() -> str:
    """Decode-attention implementation: "xla" (default softmax path) or
    "pim" (the Pallas flash-decode kernel — the Attn-PIM analogue, sharded
    one unit per KV shard when a mesh is installed)."""
    return getattr(_attn_state, "impl", "xla")


@contextlib.contextmanager
def attn_impl(impl: str):
    assert impl in ("xla", "pim"), impl
    prev = current_attn_impl()
    _attn_state.impl = impl
    try:
        yield
    finally:
        _attn_state.impl = prev


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    # statistics in f32; the DATAPATH stays in the params' dtype.  Keeping
    # the normalized tensor bf16 halves the backward's weight-grad
    # activation all-gathers under sequence parallelism (§Perf iteration 2).
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    # explicit broadcast: keeps the sanitizer's rank-promotion-raise happy
    w = jnp.reshape(weight.astype(x.dtype), (1,) * (x.ndim - 1) + (-1,))
    return x * inv * w


def layernorm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Scale-only LayerNorm (bias-free, matching our parameter accounting)."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    w = jnp.reshape(weight.astype(x.dtype), (1,) * (x.ndim - 1) + (-1,))
    return ((x.astype(jnp.float32) - mean) * inv).astype(x.dtype) * w


def norm(x: jax.Array, weight: jax.Array, kind: str, eps: float) -> jax.Array:
    if kind == "rmsnorm":
        return rmsnorm(x, weight, eps)
    return layernorm(x, weight, eps)


# ---------------------------------------------------------------------------
# Rotary position embeddings (RoPE + qwen2-vl's M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies, shape [head_dim // 2] (f32)."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., seq, heads, head_dim]; positions: broadcastable to [..., seq]."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)                          # [hd/2]
    pos = positions[..., None].astype(jnp.float32)       # [..., seq, 1]
    angles = pos * jnp.reshape(inv, (1,) * (pos.ndim - 1) + (-1,))
    angles = angles[..., None, :]                        # broadcast over heads
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_m_rope(
    x: jax.Array,
    positions: jax.Array,        # [..., 3, seq] (temporal, height, width)
    theta: float,
    sections: tuple[int, ...],   # frequency split of hd/2, sums to hd/2
) -> jax.Array:
    """qwen2-vl multimodal RoPE: hd/2 frequency slots are partitioned into
    (temporal, height, width) sections, each rotated by its own position id."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)                          # [hd/2]
    # Per-frequency slot: which of the 3 position streams rotates it.
    sec_id = jnp.repeat(
        jnp.arange(len(sections)), jnp.asarray(sections), total_repeat_length=hd // 2
    )                                                    # [hd/2] in {0,1,2}
    pos = jnp.take(positions, sec_id, axis=-2)           # [..., hd/2, seq]
    pos = jnp.swapaxes(pos, -1, -2).astype(jnp.float32)  # [..., seq, hd/2]
    inv_b = jnp.reshape(inv, (1,) * (pos.ndim - 1) + (-1,))
    angles = (pos * inv_b)[..., None, :]                 # [..., seq, 1, hd/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def swiglu_mlp(x: jax.Array, p: Params) -> jax.Array:
    """LLaMA-style gated MLP: down( silu(gate(x)) * up(x) )."""
    gate = papi_linear(x, p["w_gate"], tp="col")
    up = papi_linear(x, p["w_up"], tp="col")
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    act = shard(act, None, None, "act_ffn")
    return papi_linear(act, p["w_down"], tp="row")


def gelu_mlp(x: jax.Array, p: Params) -> jax.Array:
    """GPT-style 2-layer MLP with biases."""
    h = papi_linear(x, p["w_in"], tp="col") + p["b_in"]
    h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(x.dtype)
    h = shard(h, None, None, "act_ffn")
    return papi_linear(h, p["w_out"], tp="row") + p["b_out"]


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def qkv_project(
    x: jax.Array,
    p: Params,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """[b, s, d] -> q[b, s, nH, hd], k/v[b, s, nKV, hd]."""
    b, s, d = x.shape

    def proj(w):  # [d, nh, hd] applied through the scheduled FC path
        nh, hd = w.shape[1], w.shape[2]
        # the K/V weights' logical bank dim is "kv_heads" (for MHA every
        # projection is, matching their stored ("kv_heads" -> replicated)
        # layout); only GQA's query weight banks over "heads"
        bank = "kv_heads" if nh == num_kv_heads else "heads"
        return papi_linear(x, w.reshape(d, nh * hd), tp="col", bank=bank,
                           units=nh).reshape(b, s, nh, hd)

    q, k, v = proj(p["w_q"]), proj(p["w_k"]), proj(p["w_v"])
    if "b_q" in p:
        q = q + p["b_q"][None, None]
        k = k + p["b_k"][None, None]
        v = v + p["b_v"][None, None]
    # Re-shard at the attention boundary ONCE per layer: heads over `model`
    # where divisible (TP attention), otherwise an explicit seq-gather here.
    # Without this constraint the seq(SP)-sharded K/V flow into the blocked
    # flash loops and XLA all-gathers them per (q-block x kv-block)
    # iteration — x6144 collective multipliers in the dry-run.
    q = shard(q, "batch", None, "act_heads", None)
    k = shard(k, "batch", None, "act_kv_heads", None)
    v = shard(v, "batch", None, "act_kv_heads", None)
    return q, k, v


def out_project(attn: jax.Array, p: Params) -> jax.Array:
    """[b, s, nH, hd] -> [b, s, d]."""
    b, s, nh, hd = attn.shape
    w = p["w_o"]
    return papi_linear(attn.reshape(b, s, nh * hd), w.reshape(nh * hd, -1),
                       tp="row", bank="heads", units=nh)


def _repeat_kv(k: jax.Array, group: int) -> jax.Array:
    """[b, s, nKV, hd] -> [b, s, nKV*group, hd] for GQA."""
    if group == 1:
        return k
    b, s, nkv, hd = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, nkv, group, hd))
    return k.reshape(b, s, nkv * group, hd)


def dense_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool
) -> jax.Array:
    """Reference attention, materializes [b, h, sq, sk].  Smoke shapes only."""
    group = q.shape[2] // k.shape[2]
    k, v = _repeat_kv(k, group), _repeat_kv(v, group)
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqs,bshk->bqhk", probs, v)


def expand_kv_heads(k: jax.Array, nh: int) -> jax.Array:
    """GQA KV expansion via a static head-index gather: [b,s,nKV,hd] ->
    [b,s,nH,hd].  Unlike a (nkv, group) reshape of the query tensor, the
    gather keeps the TP-sharded head dim intact for ANY group size (96 heads
    / 16 shards works even though 96 = 8 KV x 12 group is per-dim
    indivisible), so no all-gather is provoked under tensor parallelism."""
    nkv = k.shape[2]
    if nkv == nh:
        return k
    idx = jnp.arange(nh) // (nh // nkv)
    return jnp.take(k, idx, axis=2)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    q_block: int = 512,
    kv_block: int = 512,
) -> jax.Array:
    """Blockwise online-softmax attention (pure JAX).

    Peak score memory is [b, heads, q_block, kv_block] instead of [sq, sk].
    GQA KV heads are expanded by static gather (see expand_kv_heads) so the
    whole computation stays cleanly sharded over the head dim.
    """
    b, sq, nh, hd = q.shape
    sk = k.shape[1]
    if sq % q_block or sk % kv_block:
        # Fall back for ragged smoke shapes.
        return dense_attention(q, k, v, causal=causal)
    k = expand_kv_heads(k, nh)
    v = expand_kv_heads(v, nh)
    scale = 1.0 / math.sqrt(hd)
    nqb, nkb = sq // q_block, sk // kv_block

    qg = q.reshape(b, nqb, q_block, nh, hd)
    kb = k.reshape(b, nkb, kv_block, nh, hd)
    vb = v.reshape(b, nkb, kv_block, nh, hd)
    q_pos = jnp.arange(sq).reshape(nqb, q_block)
    k_pos = jnp.arange(sk).reshape(nkb, kv_block)

    def per_qblock(qi: jax.Array, qblk: jax.Array) -> jax.Array:
        # qblk: [b, qb, nh, hd]
        acc0 = jnp.zeros((b, q_block, nh, hd), jnp.float32)
        m0 = jnp.full((b, q_block, nh), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, q_block, nh), jnp.float32)

        def body(carry, inp):
            acc, m, l = carry
            ki, kblk, vblk = inp
            s = jnp.einsum("bqhk,bshk->bqhs", qblk, kblk).astype(jnp.float32)
            s = s * scale
            if causal:
                mask = q_pos[qi][:, None] >= k_pos[ki][None, :]
                s = jnp.where(mask[None, :, None, :], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # Guard fully-masked rows (m_new = -inf): exp(-inf - -inf) = nan.
            m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(jnp.isinf(m_new)[..., None], 0.0, p)
            alpha = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - m_safe))
            l = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bqhs,bshk->bqhk", p.astype(v.dtype), vblk)
            acc = acc * alpha[..., None] + pv.astype(jnp.float32)
            return (acc, m_new, l), None

        ks = jnp.arange(nkb)
        (acc, m, l), _ = jax.lax.scan(
            body, (acc0, m0, l0),
            (ks, jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0)),
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.astype(q.dtype)

    outs = jax.lax.map(
        lambda args: per_qblock(*args),
        (jnp.arange(nqb), jnp.moveaxis(qg, 1, 0)),
    )                                                     # [nqb, b, qb, nh, hd]
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sq, nh, hd)
    return out


def decode_attention_xla(
    q: jax.Array,        # [b, t, nH, hd] (t = TLP query tokens)
    k_cache: jax.Array,  # [b, S, nKV, hd]
    v_cache: jax.Array,  # [b, S, nKV, hd]
    cache_len: jax.Array | int,   # valid prefix length (new tokens included)
    q_offset: jax.Array | int = 0,  # absolute position of q[0] in the stream
) -> jax.Array:
    """Decode attention against a (padded) KV cache — XLA path.

    `cache_len` / `q_offset` may be scalars or per-request [b] arrays
    (continuous batching => ragged positions).  Positions >= cache_len are
    masked; within the t query tokens the mask is causal from `q_offset`.
    """
    b, t, nh, hd = q.shape
    skv, nkv = k_cache.shape[1], k_cache.shape[2]
    group = nh // nkv
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(b, t, nkv, group, hd)
    s = jnp.einsum("bthgk,bshk->bthgs", qg, k_cache).astype(jnp.float32) * scale
    cache_len = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    q_offset = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,))
    kv_pos = jnp.arange(skv)
    q_pos = q_offset[:, None] + jnp.arange(t)[None, :]          # [b, t]
    valid = (kv_pos[None, None, :] <= q_pos[..., None]) & (
        kv_pos[None, None, :] < cache_len[:, None, None]
    )                                                            # [b, t, skv]
    s = jnp.where(valid[:, :, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bthgs,bshk->bthgk", p.astype(v_cache.dtype), v_cache)
    return out.reshape(b, t, nh, hd)


def gather_kv_pages(pages: jax.Array, tables: jax.Array) -> jax.Array:
    """Materialize a contiguous per-slot KV view from the page pool:
    [num_pages, page, nkv, hd] gathered by [b, max_blocks] block tables ->
    [b, max_blocks * page, nkv, hd].  This is the XLA decode path for the
    paged cache — since the Pallas kernels went windowed it is OFF the
    jitted hot path under attn_impl("pim") and survives as the tested
    bit-identity oracle (the paged kernel reads the live pages through
    the block table without ever building this view)."""
    b, nblk = tables.shape
    _, page, nkv, hd = pages.shape
    g = jnp.take(pages, tables, axis=0)          # [b, nblk, page, nkv, hd]
    return g.reshape(b, nblk * page, nkv, hd)


def _fold_query_window(q: jax.Array, nkv: int) -> jax.Array:
    """[b, t, nH, hd] -> the kernels' [b, nkv, t*g, hd] row layout: rows are
    (window, group)-row-major within each KV head (row = r * g + gg), the
    order the windowed kernels' intra-window causal mask assumes."""
    b, t, nh, hd = q.shape
    g = nh // nkv
    qh = q.reshape(b, t, nkv, g, hd).transpose(0, 2, 1, 3, 4)
    return qh.reshape(b, nkv, t * g, hd)


def _unfold_query_window(out: jax.Array, t: int, nh: int) -> jax.Array:
    """Inverse of `_fold_query_window`: [b, nkv, t*g, hd] -> [b, t, nH, hd]."""
    b, nkv, tg, hd = out.shape
    o = out.reshape(b, nkv, t, tg // t, hd).transpose(0, 2, 1, 3, 4)
    return o.reshape(b, t, nh, hd)


def decode_attention_pim_paged(
    q: jax.Array,        # [b, t, nH, hd] — t >= 1 query-window rows
    k_pages: jax.Array,  # [num_pages, page, nKV, hd]
    v_pages: jax.Array,  # [num_pages, page, nKV, hd]
    tables: jax.Array,   # [b, max_blocks] int32 block tables
    lens: jax.Array,     # [b] valid lengths (ALL t window tokens included)
) -> jax.Array:
    """Paged decode attention through the block-table Pallas kernel — the
    Attn-PIM path over bank-row pages, for any TLP t >= 1 (plain decode,
    speculative verify windows, chunked-prefill waves).  The t window rows
    sit at consecutive absolute positions `lens - t .. lens - 1`
    (intra-window causal mask inside the kernel); no contiguous page view is
    ever materialized.  Under a mesh the kernel shard_maps over KV heads
    exactly like the dense `decode_attention_pim` (tables and lens
    replicate; each head shard holds the full page pool for its heads)."""
    from repro.kernels.paged_decode_attention import (
        paged_decode_attention, paged_decode_attention_sharded)
    b, t, nh, hd = q.shape
    nkv = k_pages.shape[2]
    qh = _fold_query_window(q, nkv)
    lens = jnp.broadcast_to(jnp.asarray(lens, jnp.int32), (b,))
    mesh = current_mesh()
    if mesh is not None:
        out = paged_decode_attention_sharded(qh, k_pages, v_pages, lens,
                                             tables, mesh=mesh, q_rows=t)
    else:
        out = paged_decode_attention(qh, k_pages, v_pages, lens, tables,
                                     q_rows=t)
    return _unfold_query_window(out, t, nh)


def decode_attention_pim(
    q: jax.Array,        # [b, t, nH, hd] — t >= 1 query-window rows
    k_cache: jax.Array,  # [b, S, nKV, hd]
    v_cache: jax.Array,  # [b, S, nKV, hd]
    lens: jax.Array,     # [b] valid lengths (ALL t window tokens included)
) -> jax.Array:
    """Decode attention through the Pallas flash-decode kernel — the
    Attn-PIM path, for any TLP t >= 1 (plain decode, speculative verify
    windows, chunked-prefill waves).  The t window rows sit at consecutive
    absolute positions `lens - t .. lens - 1`; the kernel applies the
    intra-window causal mask.  Under a mesh the kernel is `shard_map`-split
    over KV heads (one Attn-PIM unit per KV shard, see
    `kernels.decode_attention_sharded`); head layout matches
    `decode_attention_xla`'s GQA grouping (head = kv * group + g)."""
    from repro.kernels.decode_attention import (decode_attention,
                                                decode_attention_sharded)
    b, t, nh, hd = q.shape
    nkv = k_cache.shape[2]
    qh = _fold_query_window(q, nkv)
    lens = jnp.broadcast_to(jnp.asarray(lens, jnp.int32), (b,))
    mesh = current_mesh()
    if mesh is not None:
        out = decode_attention_sharded(qh, k_cache, v_cache, lens, mesh=mesh,
                                       q_rows=t)
    else:
        out = decode_attention(qh, k_cache, v_cache, lens, q_rows=t)
    return _unfold_query_window(out, t, nh)
