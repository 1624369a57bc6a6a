"""Model definition: one parameterized decoder/encoder covering all assigned
families (dense / moe / ssm / hybrid / vlm / audio).

Parameters are described once by `model_spec(cfg)` (shape + logical sharding
axes + init law per leaf); `init_params` and `param_logical_axes` both derive
from it, so the two can never drift.  Layer parameters are stacked on a
leading `num_layers` axis and consumed by `jax.lax.scan` — this keeps the
lowered HLO size O(1) in depth (deepseek-67b has 95 layers) and is what the
multi-pod dry-run compiles.

Entry points:
  init_params(cfg, key)                 -> params pytree
  param_logical_axes(cfg)               -> matching pytree of logical axis tuples
  init_cache(cfg, batch, capacity)      -> decode cache pytree
  forward_train(cfg, params, batch)     -> (loss, metrics)
  prefill(cfg, params, batch, cache)    -> (last_logits, cache)
  prefill_chunk(cfg, params, cache, toks, lens) -> (next_tok, cache)
  decode_step(cfg, params, cache, toks) -> (logits, cache)
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Mapping

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S

PyTree = Any


# ===========================================================================
# Parameter specs
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class PSpec:
    shape: tuple[int, ...]
    logical: tuple[str | None, ...]
    init: str = "normal"      # normal | zeros | ones | a_log | dt_bias
    std: float = 0.02


def _attn_spec(cfg: ModelConfig, residual_std: float) -> dict[str, PSpec]:
    d, nh, nkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    std = d ** -0.5
    p = {
        "w_q": PSpec((d, nh, hd), ("fsdp", "heads", None), std=std),
        "w_k": PSpec((d, nkv, hd), ("fsdp", "kv_heads", None), std=std),
        "w_v": PSpec((d, nkv, hd), ("fsdp", "kv_heads", None), std=std),
        "w_o": PSpec((nh, hd, d), ("heads", None, "fsdp"), std=residual_std),
    }
    if cfg.qkv_bias:
        p["b_q"] = PSpec((nh, hd), ("heads", None), init="zeros")
        p["b_k"] = PSpec((nkv, hd), ("kv_heads", None), init="zeros")
        p["b_v"] = PSpec((nkv, hd), ("kv_heads", None), init="zeros")
    return p


def _mlp_spec(cfg: ModelConfig, residual_std: float) -> dict[str, PSpec]:
    d, f = cfg.d_model, cfg.d_ff
    std = d ** -0.5
    if cfg.mlp == "swiglu":
        return {
            "w_gate": PSpec((d, f), ("fsdp", "ffn"), std=std),
            "w_up": PSpec((d, f), ("fsdp", "ffn"), std=std),
            "w_down": PSpec((f, d), ("ffn", "fsdp"), std=residual_std),
        }
    return {
        "w_in": PSpec((d, f), ("fsdp", "ffn"), std=std),
        "b_in": PSpec((f,), ("ffn",), init="zeros"),
        "w_out": PSpec((f, d), ("ffn", "fsdp"), std=residual_std),
        "b_out": PSpec((d,), (None,), init="zeros"),
    }


def _moe_spec(cfg: ModelConfig, residual_std: float) -> dict[str, PSpec]:
    assert cfg.moe is not None
    d, f, e = cfg.d_model, cfg.moe.d_ff, cfg.moe.num_experts
    std = d ** -0.5
    return {
        "w_router": PSpec((d, e), (None, None), std=std),
        "w_gate": PSpec((e, d, f), ("experts", "fsdp", None), std=std),
        "w_up": PSpec((e, d, f), ("experts", "fsdp", None), std=std),
        "w_down": PSpec((e, f, d), ("experts", None, "fsdp"), std=residual_std),
    }


def _ssm_spec(cfg: ModelConfig, residual_std: float) -> dict[str, PSpec]:
    assert cfg.ssm is not None
    s = cfg.ssm
    d = cfg.d_model
    di, nh, n, k = s.d_inner(d), s.n_heads(d), s.d_state, s.conv_kernel
    std = d ** -0.5
    return {
        "w_z": PSpec((d, di), ("fsdp", "ssm_heads"), std=std),
        "w_x": PSpec((d, di), ("fsdp", "ssm_heads"), std=std),
        "w_B": PSpec((d, n), ("fsdp", None), std=std),
        "w_C": PSpec((d, n), ("fsdp", None), std=std),
        "w_dt": PSpec((d, nh), ("fsdp", "ssm_heads"), std=std),
        "conv_x": PSpec((k, di), (None, "ssm_heads"), std=(1 / math.sqrt(k))),
        "conv_B": PSpec((k, n), (None, None), std=(1 / math.sqrt(k))),
        "conv_C": PSpec((k, n), (None, None), std=(1 / math.sqrt(k))),
        "A_log": PSpec((nh,), ("ssm_heads",), init="a_log"),
        "D": PSpec((nh,), ("ssm_heads",), init="ones"),
        "dt_bias": PSpec((nh,), ("ssm_heads",), init="dt_bias"),
        "norm_w": PSpec((di,), ("ssm_heads",), init="ones"),
        "w_out": PSpec((di, d), ("ssm_heads", "fsdp"), std=residual_std),
    }


def _layer_spec(cfg: ModelConfig, residual_std: float) -> dict[str, Any]:
    """Spec of ONE layer (unstacked)."""
    d = cfg.d_model
    if cfg.family in ("ssm", "hybrid"):
        return {
            "norm": PSpec((d,), (None,), init="ones"),
            "ssm": _ssm_spec(cfg, residual_std),
        }
    block = {
        "norm1": PSpec((d,), (None,), init="ones"),
        "attn": _attn_spec(cfg, residual_std),
        "norm2": PSpec((d,), (None,), init="ones"),
    }
    if cfg.family == "moe":
        block["moe"] = _moe_spec(cfg, residual_std)
    else:
        block["mlp"] = _mlp_spec(cfg, residual_std)
    return block


def model_spec(cfg: ModelConfig) -> dict[str, Any]:
    d, v = cfg.d_model, cfg.vocab_size
    n_res = 2 * cfg.num_layers
    residual_std = (d ** -0.5) / math.sqrt(max(n_res, 1))

    spec: dict[str, Any] = {
        "embed": {"w": PSpec((v, d), ("embed_vocab", None), std=0.02)},
        "final_norm": {"w": PSpec((d,), (None,), init="ones")},
    }

    layer = _layer_spec(cfg, residual_std)
    spec["layers"] = jax.tree.map(
        lambda ps: PSpec(
            (cfg.num_layers,) + ps.shape, ("scan",) + ps.logical, ps.init, ps.std
        ),
        layer,
        is_leaf=lambda x: isinstance(x, PSpec),
    )

    if cfg.family == "hybrid":
        # One weight-tied attention+MLP block shared across applications.
        spec["shared"] = {
            "norm1": PSpec((d,), (None,), init="ones"),
            "attn": _attn_spec(cfg, residual_std),
            "norm2": PSpec((d,), (None,), init="ones"),
            "mlp": _mlp_spec(cfg, residual_std),
        }
    if cfg.family == "audio":
        spec["mask_embed"] = {"w": PSpec((d,), (None,), std=0.02)}
    if cfg.decoder and not cfg.tie_embeddings:
        spec["lm_head"] = {"w": PSpec((d, v), ("fsdp", "embed_vocab"), std=d ** -0.5)}
    return spec


def _is_pspec(x) -> bool:
    return isinstance(x, PSpec)


def init_params(cfg: ModelConfig, key: jax.Array) -> PyTree:
    spec = model_spec(cfg)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_pspec)
    keys = jax.random.split(key, len(leaves))
    dtype = jnp.dtype(cfg.dtype)

    def make(ps: PSpec, k: jax.Array) -> jax.Array:
        if ps.init == "zeros":
            return jnp.zeros(ps.shape, dtype)
        if ps.init == "ones":
            return jnp.ones(ps.shape, dtype)
        if ps.init == "a_log":
            assert cfg.ssm is not None
            u = jax.random.uniform(k, ps.shape, jnp.float32,
                                   cfg.ssm.a_min, cfg.ssm.a_max)
            return jnp.log(u)  # keep f32: A_log is a recurrence-critical param
        if ps.init == "dt_bias":
            # softplus^{-1}(dt) for dt ~ logU[1e-3, 1e-1]
            u = jax.random.uniform(k, ps.shape, jnp.float32)
            dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
            return dt + jnp.log(-jnp.expm1(-dt))
        x = jax.random.truncated_normal(k, -3.0, 3.0, ps.shape, jnp.float32)
        return (x * ps.std).astype(dtype)

    params = [make(ps, k) for ps, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, params)


def param_logical_axes(cfg: ModelConfig) -> PyTree:
    return jax.tree.map(lambda ps: ps.logical, model_spec(cfg), is_leaf=_is_pspec)


def param_shapes(cfg: ModelConfig) -> PyTree:
    dtype = jnp.dtype(cfg.dtype)

    def to_sds(ps: PSpec):
        dt = jnp.float32 if ps.init in ("a_log", "dt_bias") else dtype
        return jax.ShapeDtypeStruct(ps.shape, dt)

    return jax.tree.map(to_sds, model_spec(cfg), is_leaf=_is_pspec)


def param_shardings(cfg: ModelConfig, rules, mesh) -> PyTree:
    """NamedShardings for the params pytree under a rule table + mesh.
    Dims the mesh axes don't divide replicate (per-arch head counts etc.) —
    the same `filter_spec_for_shape` policy activation constraints use."""
    from repro.distributed.sharding import tree_shardings
    return tree_shardings(param_logical_axes(cfg), param_shapes(cfg),
                          rules, mesh)


def cache_shardings(cfg: ModelConfig, batch: int, capacity: int,
                    rules, mesh) -> PyTree:
    """NamedShardings for the decode cache (mirrors init_cache).  Under
    `serve_rules()` the KV sequence dim lands on the tensor axis — each
    shard owns a contiguous KV slice, the Attn-PIM-next-to-its-KV layout."""
    from repro.distributed.sharding import tree_shardings
    shapes = jax.eval_shape(lambda: init_cache(cfg, batch, capacity))
    return tree_shardings(cache_logical_axes(cfg), shapes, rules, mesh)


# ===========================================================================
# KV / state caches
# ===========================================================================

def init_cache(cfg: ModelConfig, batch: int, capacity: int) -> PyTree:
    """Decode cache.  `capacity` = max sequence length held."""
    dtype = jnp.dtype(cfg.dtype)
    cache: dict[str, Any] = {"pos": jnp.zeros((batch,), jnp.int32)}
    nkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        cache["k"] = jnp.zeros((cfg.num_layers, batch, capacity, nkv, hd), dtype)
        cache["v"] = jnp.zeros((cfg.num_layers, batch, capacity, nkv, hd), dtype)
    elif cfg.family == "ssm":
        assert cfg.ssm is not None
        st = S.init_state(batch, cfg.d_model, cfg.ssm, dtype)
        cache["ssm"] = jax.tree.map(
            lambda x: jnp.zeros((cfg.num_layers,) + x.shape, x.dtype), st
        )
    elif cfg.family == "hybrid":
        assert cfg.ssm is not None and cfg.hybrid is not None
        st = S.init_state(batch, cfg.d_model, cfg.ssm, dtype)
        cache["ssm"] = jax.tree.map(
            lambda x: jnp.zeros((cfg.num_layers,) + x.shape, x.dtype), st
        )
        napps = cfg.num_attention_applications()
        cache["k"] = jnp.zeros((napps, batch, capacity, nkv, hd), dtype)
        cache["v"] = jnp.zeros((napps, batch, capacity, nkv, hd), dtype)
    return cache


def cache_logical_axes(cfg: ModelConfig) -> PyTree:
    """Logical axes for the cache pytree (mirrors init_cache)."""
    axes: dict[str, Any] = {"pos": (None,)}
    if cfg.family in ("dense", "moe", "vlm", "audio", "hybrid"):
        axes["k"] = ("scan", "batch", "act_kv_seq", "kv_heads", None)
        axes["v"] = ("scan", "batch", "act_kv_seq", "kv_heads", None)
    if cfg.family in ("ssm", "hybrid"):
        axes["ssm"] = S.SSMState(
            conv_x=("scan", "batch", None, "ssm_heads"),
            conv_B=("scan", "batch", None, None),
            conv_C=("scan", "batch", None, None),
            ssm=("scan", "batch", "ssm_heads", None, None),
        )
    return axes


def init_paged_cache(cfg: ModelConfig, max_slots: int, num_pages: int,
                     page_size: int, max_blocks: int | None = None) -> PyTree:
    """Paged decode cache: KV lives in a pool of fixed-size pages (one page
    = one Attn-PIM bank row) instead of per-slot dense slabs, and a per-slot
    block table maps logical KV blocks to physical pages.

    Physical page 0 is the shared garbage page (never allocated — see
    `serving/kv_pages.py`): block tables init to 0, so writes from
    not-yet-admitted slots land there harmlessly.

    Total KV bytes scale with `num_pages * page_size` for the whole pool,
    not `max_slots * capacity` — and a single request may span (almost) the
    entire pool, which no dense slot layout permits.
    """
    assert cfg.family in ("dense", "moe", "vlm", "audio"), (
        f"paged KV cache needs a pure attention KV cache; {cfg.family} "
        "carries SSM state that has no sequence dim to page")
    if max_blocks is None:
        max_blocks = num_pages - 1
    dtype = jnp.dtype(cfg.dtype)
    nkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "pos": jnp.zeros((max_slots,), jnp.int32),
        "k": jnp.zeros((cfg.num_layers, num_pages, page_size, nkv, hd), dtype),
        "v": jnp.zeros((cfg.num_layers, num_pages, page_size, nkv, hd), dtype),
        "block_tables": jnp.zeros((max_slots, max_blocks), jnp.int32),
    }


def paged_cache_logical_axes(cfg: ModelConfig) -> PyTree:
    """Logical axes for the paged cache (mirrors init_paged_cache).  The
    page-pool dim replicates; the KV-head dim carries the Attn-PIM unit
    sharding (`serve_rules(attn_pim=True)` maps kv_heads -> model), so the
    head-sharded flash-decode layout from the dense cache carries over."""
    return {
        "pos": (None,),
        "k": ("scan", None, None, "kv_heads", None),
        "v": ("scan", None, None, "kv_heads", None),
        "block_tables": (None, None),
    }


def paged_cache_shardings(cfg: ModelConfig, max_slots: int, num_pages: int,
                          page_size: int, max_blocks: int | None,
                          rules, mesh) -> PyTree:
    """NamedShardings for the paged cache under a rule table + mesh."""
    from repro.distributed.sharding import tree_shardings
    shapes = jax.eval_shape(
        lambda: init_paged_cache(cfg, max_slots, num_pages, page_size,
                                 max_blocks))
    return tree_shardings(paged_cache_logical_axes(cfg), shapes, rules, mesh)


# ===========================================================================
# Blocks
# ===========================================================================

def _write_kv(k_cache, v_cache, k_new, v_new, pos):
    """Write [b, t, nkv, hd] at per-request positions pos [b]."""
    def upd(cache, new, p):
        return jax.lax.dynamic_update_slice(cache, new, (p, 0, 0))
    k_cache = jax.vmap(upd)(k_cache, k_new, pos)
    v_cache = jax.vmap(upd)(v_cache, v_new, pos)
    return k_cache, v_cache


def _write_kv_masked(k_cache, v_cache, k_new, v_new, pos, valid_lens):
    """Like `_write_kv`, but only the first `valid_lens[b]` of the t new
    tokens are written per request; the rest are dropped entirely.

    Chunked prefill needs this: the final chunk of a prompt is ragged, and
    slots that are not part of the chunk wave (live decoding requests, idle
    slots) ride the fixed-shape batch with valid_lens == 0.  A
    dynamic_update_slice cannot mask, and worse, it clamps a start index
    near the capacity edge DOWNWARD — silently overwriting earlier live KV.
    Scatter with out-of-range indices in "drop" mode does exactly what is
    needed: masked rows index one past the capacity and vanish.
    """
    b, t = k_new.shape[0], k_new.shape[1]
    cap = k_cache.shape[1]
    idx = pos[:, None] + jnp.arange(t)[None, :]               # [b, t]
    idx = jnp.where(jnp.arange(t)[None, :] < valid_lens[:, None], idx, cap)
    bidx = jnp.arange(b)[:, None]
    k_cache = k_cache.at[bidx, idx].set(k_new, mode="drop")
    v_cache = v_cache.at[bidx, idx].set(v_new, mode="drop")
    return k_cache, v_cache


def _paged_rows(pos, t, tables, page_size):
    """(physical page, row) coordinates for t new tokens per slot.

    Logical position `pos[b] + j` lands in logical block `(pos+j) //
    page_size` at row `(pos+j) % page_size`; the block table resolves the
    physical page.  Blocks past the table width clamp to the last entry —
    the engine guarantees mapped coverage for every *live* slot, and idle
    slots' tables are all garbage-page so their writes collide there
    harmlessly (see serving/kv_pages.py)."""
    tok = pos[:, None] + jnp.arange(t)[None, :]             # [b, t]
    blk = jnp.clip(tok // page_size, 0, tables.shape[1] - 1)
    phys = jnp.take_along_axis(tables, blk, axis=1)         # [b, t]
    return phys, tok % page_size


def _write_kv_paged(k_cache, v_cache, k_new, v_new, pos, tables,
                    valid_lens=None):
    """Scatter [b, t, nkv, hd] into the page pools [P, page, nkv, hd].

    With `valid_lens` (chunked prefill's ragged final chunk, and the
    valid_lens == 0 rows of slots that are not chunking this wave), tokens
    past the valid prefix are redirected to the shared garbage page 0 —
    they never touch a live request's pages."""
    page_size = k_cache.shape[1]
    phys, row = _paged_rows(pos, k_new.shape[1], tables, page_size)
    if valid_lens is not None:
        valid = jnp.arange(k_new.shape[1])[None, :] < valid_lens[:, None]
        phys = jnp.where(valid, phys, 0)
    k_cache = k_cache.at[phys, row].set(k_new)
    v_cache = v_cache.at[phys, row].set(v_new)
    return k_cache, v_cache


def _apply_positional(cfg: ModelConfig, q, k, positions):
    if cfg.family == "audio":
        return q, k  # hubert: conv positional frontend (stubbed) — no RoPE
    if cfg.m_rope:
        q = L.apply_m_rope(q, positions, cfg.rope_theta, tuple(cfg.m_rope_sections))
        k = L.apply_m_rope(k, positions, cfg.rope_theta, tuple(cfg.m_rope_sections))
    else:
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k


def _decode_attention(q, k_cache, v_cache, pos, tables=None):
    """THE decision point for decode-path attention — plain decode,
    speculative verify windows, and chunked-prefill waves all land here
    with a [b, t, nh, hd] query window at consecutive absolute positions
    ``pos .. pos + t - 1`` (intra-window causal: KV position j is visible
    to window row r iff j <= pos + r).

    Under `attn_impl("pim")` EVERY case — any t >= 1, dense or paged — runs
    the Pallas flash-decode kernel (the Attn-PIM unit): dense streams the
    per-slot slab, paged reads each slot's live pages through the block
    table inside the kernel, so `gather_kv_pages` never appears in a jitted
    program on this path.  The XLA softmax path (with the page gather when
    paged) remains as the tested oracle."""
    t = q.shape[1]
    if L.current_attn_impl() == "pim":
        if tables is not None:
            return L.decode_attention_pim_paged(q, k_cache, v_cache, tables,
                                                lens=pos + t)
        return L.decode_attention_pim(q, k_cache, v_cache, lens=pos + t)
    if tables is not None:
        # XLA oracle path: gather the slots' pages into a contiguous view
        # and reuse the dense ragged-masked attention
        k_cache = L.gather_kv_pages(k_cache, tables)
        v_cache = L.gather_kv_pages(v_cache, tables)
    return L.decode_attention_xla(q, k_cache, v_cache,
                                  cache_len=pos + t, q_offset=pos)


def attention_block(
    cfg: ModelConfig,
    p: Mapping[str, Any],
    h: jax.Array,
    positions: jax.Array,
    kv: tuple[jax.Array, jax.Array] | None,
    pos: jax.Array | None,
    mode: str,                      # train | prefill | decode
    tables: jax.Array | None = None,   # [b, max_blocks] => paged KV layout
    write_lens: jax.Array | None = None,  # [b] chunked prefill: valid new
                                          # tokens per slot (None = all t)
):
    """Pre-norm attention sub-block.  Returns (h, new_kv|None)."""
    a_in = L.norm(h, p["norm1"], cfg.norm, cfg.norm_eps)
    q, k, v = L.qkv_project(a_in, p["attn"], cfg.num_heads, cfg.num_kv_heads,
                            cfg.resolved_head_dim)
    q, k = _apply_positional(cfg, q, k, positions)
    new_kv = None
    if mode == "decode" and tables is not None:
        # paged layout: kv are page pools [num_pages, page, nkv, hd]
        assert kv is not None and pos is not None
        k_cache, v_cache = _write_kv_paged(kv[0], kv[1], k, v, pos, tables,
                                           valid_lens=write_lens)
        attn = _decode_attention(q, k_cache, v_cache, pos, tables)
        new_kv = (k_cache, v_cache)
    elif mode == "decode":
        assert kv is not None and pos is not None
        if write_lens is not None:
            # chunked prefill: ragged tails / non-chunking slots must not
            # write — and the hot decode path keeps its dynamic_update_slice
            k_cache, v_cache = _write_kv_masked(kv[0], kv[1], k, v, pos,
                                                write_lens)
        else:
            k_cache, v_cache = _write_kv(kv[0], kv[1], k, v, pos)
        attn = _decode_attention(q, k_cache, v_cache, pos)
        new_kv = (k_cache, v_cache)
    else:
        attn = L.flash_attention(q, k, v, causal=cfg.causal)
        if kv is not None:  # prefill: persist the new KV
            new_kv = _write_kv(kv[0], kv[1], k, v, jnp.zeros_like(pos))
    h = h + L.out_project(attn, p["attn"])
    h = shard(h, "batch", "seq", None)
    return h, new_kv


def mlp_block(cfg: ModelConfig, p: Mapping[str, Any], h: jax.Array):
    """Pre-norm MLP / MoE sub-block.  Returns (h, aux_loss)."""
    m_in = L.norm(h, p["norm2"], cfg.norm, cfg.norm_eps)
    if cfg.family == "moe":
        assert cfg.moe is not None
        y, aux = M.moe_mlp(m_in, p["moe"], cfg.moe)
    else:
        mlp = L.swiglu_mlp if cfg.mlp == "swiglu" else L.gelu_mlp
        y, aux = mlp(m_in, p["mlp"]), jnp.zeros((), jnp.float32)
    h = h + y
    h = shard(h, "batch", "seq", None)
    return h, aux


def ssm_block(cfg: ModelConfig, p: Mapping[str, Any], h: jax.Array,
              state: S.SSMState | None, mode: str):
    u = L.norm(h, p["norm"], cfg.norm, cfg.norm_eps)
    assert cfg.ssm is not None
    y, new_state = S.mamba2_block(u, p["ssm"], cfg.ssm, cfg.d_model,
                                  state=state, decode=(mode == "decode"))
    h = h + y
    h = shard(h, "batch", "seq", None)
    return h, new_state


# ===========================================================================
# Backbone
# ===========================================================================

def _transformer_backbone(cfg, params, h, positions, cache, mode, remat,
                          write_lens=None):
    """Scan over stacked transformer layers (dense/moe/vlm/audio).

    With a cache, the FULL stacked KV tensors ride in the scan *carry* and
    each layer dynamic-update-slices its own [1, ...] slab in place.  Passing
    them as xs/ys instead would give the loop separate input and output
    stacked buffers — 2x the KV bytes live (13 GB/device extra for
    command-r-plus decode_32k; §Perf iteration 6).
    """
    use_cache = cache is not None
    pos = cache["pos"] if use_cache else None
    # paged layout marker: the per-layer kv rides the scan carry either way,
    # shaped [slots, S, ...] dense or [num_pages, page, ...] paged
    tables = cache.get("block_tables") if use_cache else None

    aux0 = jnp.zeros((), jnp.float32)
    if use_cache:
        def body(carry, lp):
            h, aux, i, kfull, vfull = carry
            kc = jax.lax.dynamic_index_in_dim(kfull, i, 0, keepdims=False)
            vc = jax.lax.dynamic_index_in_dim(vfull, i, 0, keepdims=False)
            h, new_kv = attention_block(cfg, lp, h, positions, (kc, vc),
                                        pos, mode, tables=tables,
                                        write_lens=write_lens)
            kfull = jax.lax.dynamic_update_slice_in_dim(
                kfull, new_kv[0][None], i, 0)
            vfull = jax.lax.dynamic_update_slice_in_dim(
                vfull, new_kv[1][None], i, 0)
            h, aux_l = mlp_block(cfg, lp, h)
            return (h, aux + aux_l, i + 1, kfull, vfull), None

        (h, aux, _, kfull, vfull), _ = jax.lax.scan(
            body,
            (h, aux0, jnp.zeros((), jnp.int32), cache["k"], cache["v"]),
            params["layers"],
        )
        new_cache = dict(cache)
        new_cache["k"], new_cache["v"] = kfull, vfull
        return h, aux, new_cache

    def body(carry, lp):
        h, aux = carry
        h, _ = attention_block(cfg, lp, h, positions, None, None, mode)
        h, aux_l = mlp_block(cfg, lp, h)
        return (h, aux + aux_l), None

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable
        )
    (h, aux), _ = jax.lax.scan(body, (h, aux0), params["layers"])
    return h, aux, None


def _ssm_backbone(cfg, params, h, cache, mode, remat):
    use_cache = cache is not None

    def body(carry, xs):
        h = carry
        if use_cache:
            lp, st = xs
            h, new_st = ssm_block(cfg, lp, h, st, mode)
        else:
            lp = xs
            h, new_st = ssm_block(cfg, lp, h, None, mode)
        return h, new_st

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable
        )

    if use_cache:
        h, sts = jax.lax.scan(body, h, (params["layers"], cache["ssm"]))
        new_cache = dict(cache)
        new_cache["ssm"] = sts
    else:
        h, sts = jax.lax.scan(body, h, params["layers"])
        new_cache = None
    return h, jnp.zeros((), jnp.float32), new_cache


def _hybrid_backbone(cfg, params, h, positions, cache, mode, remat):
    """zamba2: segments of `period` mamba blocks, shared attention between.

    The shared attention block (weight-tied) is applied after backbone layer
    i whenever i % period == period-1, i.e. `num_layers // period` times.
    Static python structure — no lax.cond — so each application has a static
    KV-cache index.
    """
    assert cfg.hybrid is not None
    period = cfg.hybrid.period
    napps = cfg.num_attention_applications()
    use_cache = cache is not None
    pos = cache["pos"] if use_cache else None
    shared = params["shared"]

    def seg_slice(tree, lo, hi):
        return jax.tree.map(lambda x: x[lo:hi], tree)

    def run_segment(h, lo, hi, cache_seg):
        def body(carry, xs):
            hh = carry
            if use_cache:
                lp, st = xs
                hh, new_st = ssm_block(cfg, lp, hh, st, mode)
            else:
                lp = xs
                hh, new_st = ssm_block(cfg, lp, hh, None, mode)
            return hh, new_st

        bd = body
        if remat:
            bd = jax.checkpoint(bd, policy=jax.checkpoint_policies.nothing_saveable)
        xs = (seg_slice(params["layers"], lo, hi),)
        if use_cache:
            xs = xs + (cache_seg,)
            h, sts = jax.lax.scan(bd, h, xs if len(xs) > 1 else xs[0])
            return h, sts
        h, sts = jax.lax.scan(bd, h, xs[0])
        return h, None

    def shared_app(h, kv):
        """One shared attention+MLP application (weight-tied block).
        (Wrapping this in jax.checkpoint was tried and REFUTED — zamba2
        train residency stayed ~34 GB and compile time grew 8x; the
        residency lives in the SSD chunk tensors, not these blocks.)"""
        h, new_kv = attention_block(cfg, shared, h, positions, kv, pos, mode)
        h, _ = mlp_block(cfg, shared, h)
        return h, new_kv

    new_ssm_parts = []
    new_k, new_v = (cache["k"], cache["v"]) if use_cache else (None, None)
    lo = 0
    for app in range(napps):
        hi = lo + period
        cache_seg = (jax.tree.map(lambda x: x[lo:hi], cache["ssm"])
                     if use_cache else None)
        h, sts = run_segment(h, lo, hi, cache_seg)
        if use_cache:
            new_ssm_parts.append(sts)
        # shared attention + MLP application #app
        kv = ((new_k[app], new_v[app]) if use_cache else None)
        h, new_kv = shared_app(h, kv)
        if use_cache and new_kv is not None:
            new_k = new_k.at[app].set(new_kv[0])
            new_v = new_v.at[app].set(new_kv[1])
        lo = hi
    if lo < cfg.num_layers:  # remainder backbone layers
        cache_seg = (jax.tree.map(lambda x: x[lo:], cache["ssm"])
                     if use_cache else None)
        h, sts = run_segment(h, lo, cfg.num_layers, cache_seg)
        if use_cache:
            new_ssm_parts.append(sts)

    new_cache = None
    if use_cache:
        new_cache = dict(cache)
        new_cache["ssm"] = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *new_ssm_parts
        )
        new_cache["k"], new_cache["v"] = new_k, new_v
    return h, jnp.zeros((), jnp.float32), new_cache


def backbone(cfg, params, h, positions, cache, mode, remat=False,
             write_lens=None):
    h = shard(h, "batch", "seq", None)
    if cfg.family == "ssm":
        assert write_lens is None, "chunked prefill needs maskable KV writes"
        return _ssm_backbone(cfg, params, h, cache, mode, remat)
    if cfg.family == "hybrid":
        assert write_lens is None, "chunked prefill needs maskable KV writes"
        return _hybrid_backbone(cfg, params, h, positions, cache, mode, remat)
    return _transformer_backbone(cfg, params, h, positions, cache, mode,
                                 remat, write_lens=write_lens)


# ===========================================================================
# Heads / embedding
# ===========================================================================

def embed_tokens(cfg, params, tokens):
    w = params["embed"]["w"]
    h = jnp.take(w, tokens, axis=0)
    return h


def embed_inputs(cfg, params, batch: Mapping[str, jax.Array]):
    """Family-dependent input embedding.  Returns (h [b,s,d], positions)."""
    if cfg.family == "audio":
        frames = batch["frames"]
        if "mask" in batch:
            m = batch["mask"][..., None].astype(frames.dtype)
            frames = frames * (1 - m) + params["mask_embed"]["w"] * m
        pos = jnp.arange(frames.shape[1])[None, :]
        return frames, pos
    if cfg.family == "vlm" and "patch_embeds" in batch:
        text = embed_tokens(cfg, params, batch["tokens"])
        h = jnp.concatenate([batch["patch_embeds"].astype(text.dtype), text], axis=1)
        positions = batch["positions"]        # [b, 3, s] M-RoPE triples
        return h, positions
    tokens = batch["tokens"]
    h = embed_tokens(cfg, params, tokens)
    if "positions" in batch:
        return h, batch["positions"]
    return h, jnp.arange(tokens.shape[1])[None, :]


def lm_logits(cfg, params, h):
    h = L.norm(h, params["final_norm"]["w"], cfg.norm, cfg.norm_eps)
    if cfg.decoder and not cfg.tie_embeddings:
        logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"]["w"])
    else:
        logits = jnp.einsum("bsd,vd->bsv", h, params["embed"]["w"])
    return shard(logits, "batch", "seq", "vocab")


def cross_entropy(logits, targets, mask):
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(
        logits.astype(jnp.float32), targets[..., None], axis=-1
    )[..., 0]
    nll = (lse - gold) * mask
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(nll) / denom


# ===========================================================================
# Steps
# ===========================================================================

def forward_train(cfg, params, batch, *, remat=True):
    """One unjitted training forward: returns (loss, metrics)."""
    h, positions = embed_inputs(cfg, params, batch)
    h, aux, _ = backbone(cfg, params, h, positions, None, "train", remat=remat)
    logits = lm_logits(cfg, params, h)
    mask = batch.get("target_mask")
    if mask is None:
        mask = jnp.ones(batch["targets"].shape, jnp.float32)
    mask = mask.astype(jnp.float32)
    if cfg.family == "vlm":
        # labels only exist for the text tail; pad the vision prefix out.
        pad = logits.shape[1] - batch["targets"].shape[1]
        tgt = jnp.pad(batch["targets"], ((0, 0), (pad, 0)))
        mask = jnp.pad(mask, ((0, 0), (pad, 0)))
    else:
        tgt = batch["targets"]
    ce = cross_entropy(logits, tgt, mask)
    aux_w = cfg.moe.aux_loss_weight if cfg.moe is not None else 0.0
    loss = ce + aux_w * aux / max(cfg.num_layers, 1)
    return loss, {"ce": ce, "aux": aux}


def prefill(cfg, params, batch, cache):
    """Process the prompt, fill the cache, return last-position logits."""
    h, positions = embed_inputs(cfg, params, batch)
    h, _, cache = backbone(cfg, params, h, positions, cache, "prefill")
    prompt_lens = batch.get("prompt_lens")
    if prompt_lens is None:
        prompt_lens = jnp.full((h.shape[0],), h.shape[1], jnp.int32)
    cache["pos"] = prompt_lens.astype(jnp.int32)
    idx = jnp.clip(prompt_lens - 1, 0, h.shape[1] - 1)
    h_last = jnp.take_along_axis(h, idx[:, None, None], axis=1)
    logits = lm_logits(cfg, params, h_last)
    return logits[:, 0], cache


def prefill_to_slots(cfg, params, batch, cache, src):
    """Batched admission: prefill a fixed-shape batch of new requests and
    merge each into its assigned slot of the engine cache — one compiled call
    regardless of how many slots are admitted this iteration.

    batch:  {"tokens": [n, P] int32, "prompt_lens": [n] int32} — rows past
            the number of actually-admitted requests are padding (their
            results are simply never merged).
    cache:  the engine's slot cache, batch dim = max_slots.
    src:    [max_slots] int32 — src[s] = the prefill-batch row admitted into
            slot s, or -1 to leave slot s untouched.  Fixed shape, so the
            call never recompiles as the admitted set varies.

    Returns (first_tokens [max_slots] int32, cache): first_tokens[s] is the
    greedy first output token for slots with src[s] >= 0 (garbage elsewhere).
    """
    n, p_len = batch["tokens"].shape
    # The temp cache only ever holds the prompt's KV, so size it to the
    # prefill window — NOT the slot capacity (which would double peak KV
    # memory for large-capacity engines).  Stale slot KV past the prompt is
    # masked out by decode's cache_len anyway.
    if "k" in cache:
        p_len = min(p_len, cache["k"].shape[2])
    tmp = init_cache(cfg, n, p_len)
    logits, tmp = prefill(cfg, params, batch, tmp)

    take = jnp.clip(src, 0)                       # [slots] row gather index
    keep = src < 0                                # [slots] untouched slots

    def merge(old, new):
        # old: [L, slots, ...], new: [L, n, ...] — gather-by-slot then select
        gathered = jnp.take(new, take, axis=1)
        mask = keep.reshape((1, -1) + (1,) * (old.ndim - 2))
        return jnp.where(mask, old, gathered)

    def merge_head(old, new):
        # KV merge over the first p_len sequence positions only
        head = merge(old[:, :, :p_len], new)
        return old.at[:, :, :p_len].set(head)

    cache = dict(cache)
    for key in ("k", "v"):
        if key in cache:
            cache[key] = merge_head(cache[key], tmp[key])
    if "ssm" in cache:
        cache["ssm"] = jax.tree.map(merge, cache["ssm"], tmp["ssm"])
    cache["pos"] = jnp.where(keep, cache["pos"], jnp.take(tmp["pos"], take))
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)        # [n]
    first_slots = jnp.where(keep, -1, jnp.take(first, take))
    return first_slots, cache


def prefill_to_pages(cfg, params, batch, cache, src):
    """Batched admission into the PAGED cache: prefill a fixed-shape batch
    and scatter each admitted request's prompt KV onto its block-table
    pages — one compiled call per admission wave, same contract as
    `prefill_to_slots`.

    batch/src: as in `prefill_to_slots` (src[s] = prefill row admitted into
    slot s, or -1).  cache: a paged cache from `init_paged_cache`, whose
    `block_tables` rows for admitted slots already map enough pages to hold
    the prompt (the engine's allocator guarantees this before calling).

    Rows the mask rejects — padding slots and positions past a prompt's
    length — are redirected to the shared garbage page 0, so the scatter
    stays fixed-shape without ever touching live pages.
    """
    n, p_len = batch["tokens"].shape
    slots, max_blocks = cache["block_tables"].shape
    page_size = cache["k"].shape[2]
    tmp = init_cache(cfg, n, p_len)
    logits, tmp = prefill(cfg, params, batch, tmp)

    take = jnp.clip(src, 0)                       # [slots] row gather index
    keep = src < 0                                # [slots] untouched slots
    tables = cache["block_tables"]

    tok = jnp.broadcast_to(jnp.arange(p_len)[None, :], (slots, p_len))
    lens = jnp.take(batch["prompt_lens"], take)                  # [slots]
    valid = (~keep)[:, None] & (tok < lens[:, None])             # [slots, P]
    blk = jnp.clip(tok // page_size, 0, max_blocks - 1)
    phys = jnp.take_along_axis(tables, blk, axis=1)              # [slots, P]
    phys = jnp.where(valid, phys, 0)              # rejected rows -> garbage
    row = tok % page_size

    cache = dict(cache)
    for key in ("k", "v"):
        new = jnp.take(tmp[key], take, axis=1)    # [L, slots, P, nkv, hd]
        cache[key] = cache[key].at[:, phys, row].set(new)
    cache["pos"] = jnp.where(keep, cache["pos"], jnp.take(tmp["pos"], take))
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)        # [n]
    first_slots = jnp.where(keep, -1, jnp.take(first, take))
    return first_slots, cache


def prefill_chunk(cfg, params, cache, tokens, chunk_lens):
    """One wave of CHUNKED prefill: feed a [max_slots, P] window of prompt
    tokens through the decode path at each slot's current cache position.

    Admission splits any prompt longer than the compiled prefill window into
    P-token chunks.  Chunk 0 goes through `prefill_to_slots` /
    `prefill_to_pages` (positions 0..P-1); every later chunk goes through
    this entry point, which

      * embeds the window at per-slot ABSOLUTE positions ``cache["pos"] + j``
        (RoPE must see prompt offsets, not 0..P-1);
      * runs the backbone in decode mode, so each chunk token attends to all
        previously-written KV plus its own chunk prefix — exactly the
        one-shot prefill's causal mask restricted to this window;
      * writes KV at the running offset, masked per slot to the first
        ``chunk_lens[s]`` tokens (dense: out-of-window scatter indices are
        dropped; paged: they land on the shared garbage page), so the ragged
        final chunk and the slots NOT chunking this wave (live decodes,
        idle slots — rows with ``chunk_lens[s] == 0``) never touch live
        cache entries;
      * advances ``cache["pos"]`` by ``chunk_lens`` (0 leaves a slot put).

    Fixed shapes throughout: one compiled program serves every wave of
    every admission, like `prefill_to_slots`.

    Returns ``(next_tok, cache)``: ``next_tok[s]`` is the greedy token
    following the last valid position of slot s's chunk — the request's
    first output token when this was its final chunk (garbage for rows with
    ``chunk_lens[s] == 0``; the engine only reads rows it finalized).

    Bit-identity contract: a prompt admitted through these waves produces
    exactly the one-shot prefill's logits (asserted against the raw-model
    oracle in `tests/test_serving_chunked.py`).  The engine's preemption
    path leans on this — a preempted request is requeued as
    ``prompt + tokens-so-far`` and recomputed through THIS entry point, so
    its continuation token equals the decode step the preemption skipped
    and the caller-visible stream is unchanged.
    """
    logits, cache = chunk_logits(cfg, params, cache, tokens, chunk_lens)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return nxt, cache


def chunk_logits(cfg, params, cache, tokens, chunk_lens):
    """`prefill_chunk`'s body, stopping at the last-valid-position logits.

    Returns ``(logits [slots, V], cache)`` where ``logits[s]`` is the
    distribution after the last valid token of slot s's chunk (garbage for
    rows with ``chunk_lens[s] == 0``).  ``prefill_chunk`` is exactly
    ``argmax(chunk_logits(...))``; the continuous-batching serve loop calls
    this directly so it can fold fault injection between the logits and the
    argmax inside ONE jitted program (see `serving/engine.py`)."""
    b, t = tokens.shape
    pos = cache["pos"]
    positions = pos[:, None] + jnp.arange(t)[None, :]
    if cfg.m_rope:
        positions = jnp.broadcast_to(positions[:, None, :], (b, 3, t))
    h, positions = embed_inputs(cfg, params, {"tokens": tokens,
                                              "positions": positions})
    h, _, cache = backbone(cfg, params, h, positions, cache, "decode",
                           write_lens=chunk_lens)
    idx = jnp.clip(chunk_lens - 1, 0, t - 1)
    h_last = jnp.take_along_axis(h, idx[:, None, None], axis=1)
    logits = lm_logits(cfg, params, h_last)
    cache = dict(cache)
    cache["pos"] = pos + chunk_lens.astype(jnp.int32)
    return logits[:, 0], cache


def mixed_step(cfg, params, cache, tokens, chunk_lens, pin_mask, pin_pos):
    """One continuous-batching wave: prefill chunks AND single-token decodes
    in the SAME device program.

    A decode is just a chunk of length 1 — row s with ``chunk_lens[s] == 1``
    holding the slot's last committed token attends to everything written so
    far plus itself, writes one KV entry at ``pos``, and its logits row is
    the next-token distribution, bit-identical to `decode_step` on that slot
    (same backbone ops on the same cache values).  So the serve loop packs
    newly admitted requests' prompt chunks and ongoing decodes into one
    ``[slots, P]`` window and dispatches a single program per iteration —
    the NeuPIMs-style mixed prefill/decode sub-batch, in software.

    ``pin_mask`` / ``pin_pos`` repair host-tracked prefill offsets: while a
    slot is mid-prefill it also rides every *other* program the engine
    dispatches (speculative verify, the plain fused step) as a masked
    garbage row whose ``cache["pos"]`` drifts.  The wave re-anchors those
    rows to the host's authoritative chunk offset before embedding
    (``where(pin_mask, pin_pos, pos)``); decoding rows keep the
    device-resident position.

    Returns ``(logits [slots, V], cache)`` exactly like `chunk_logits`.
    """
    cache = dict(cache)
    cache["pos"] = jnp.where(pin_mask, pin_pos,
                             cache["pos"]).astype(jnp.int32)
    return chunk_logits(cfg, params, cache, tokens, chunk_lens)


def decode_step(cfg, params, cache, tokens, positions=None):
    """tokens [b, t] -> (logits [b, t, V], new cache).  t = TLP (1 for the
    dry-run serve_step; >1 verifies a speculative window)."""
    b, t = tokens.shape[0], tokens.shape[1]
    pos = cache["pos"]
    if positions is None:
        positions = pos[:, None] + jnp.arange(t)[None, :]
        if cfg.m_rope:
            positions = jnp.broadcast_to(positions[:, None, :], (b, 3, t))
    batch = {"tokens": tokens, "positions": positions}
    h, positions = embed_inputs(cfg, params, batch)
    h, _, cache = backbone(cfg, params, h, positions, cache, "decode")
    logits = lm_logits(cfg, params, h)
    cache["pos"] = pos + t
    return logits, cache
