"""Flash-decode GQA attention Pallas kernel — the Attn-PIM analogue.

PAPI's Attn-PIM executes attention *next to the KV data* with modest compute
(1 FPU / 2 banks), because decode attention is always memory-bound: each KV
byte is read once per query — and that includes the TLP>1 verify windows
speculative decoding produces (§4–5): a t-token window still streams the
cache exactly once, amortized over t query rows.  The TPU-native
translation is a kernel whose HBM traffic is exactly one streaming pass
over the KV cache, with the online softmax state held in VMEM:

  grid = (batch, S // block_k)             last axis innermost/sequential
  K/V blocks:  [block_k, nkv, hd]   streamed HBM -> VMEM once
  Q block:     [nkv, R, hd]         R = q_rows * g query rows per KV head
  scratch:     acc [nkv, R, hd] f32, m/l [nkv, R, 128] f32 softmax state

In VMEM each KV position's (nkv, hd) slice of a K/V block fills a whole
(sublane, 128-lane) tile, so a block costs about block_k * 4 KiB per
buffer; the default block_k of 256 keeps the four double-buffered K/V
blocks near 4 MiB.

Query windows (TLP > 1)
-----------------------
``q_rows=t`` generalizes the single decode token to a window of t query
rows per KV head group — the speculative verify step (TLP = spec window)
and chunked-prefill waves.  The R = t*g rows are (window, group)-row-major:
row = r * g + gg holds window token r of grouped head gg, all t*g rows
share one streaming KV pass and one MXU score matrix per block.  Masking
is intra-window causal: the rows sit at consecutive absolute positions
``lens - t .. lens - 1``, so KV position j is visible to window row r iff
``j < lens - (t - 1) + r``.  For q_rows=1 this degrades to the plain
``j < lens`` ragged mask, bit-identically.  ``lens >= q_rows`` is required
(every row must keep at least its own diagonal position, or its softmax
normalizer would be empty) — the engine guarantees it: lens = pos + t with
pos >= 0.

Masking uses per-request cache lengths (continuous batching => ragged),
delivered via scalar prefetch (`PrefetchScalarGridSpec`) so they are
available *before* each grid step's DMA is issued.

Ragged block skipping
---------------------
A continuous batch is ragged: slot A may hold 2000 cached tokens while slot
B holds 40.  This dense kernel's grid still runs `capacity // block_k` KV
steps for every slot; with ``block_skip=True`` (default) a step whose block
lies entirely past the request's cache length does no work:

  * the K/V `index_map` clamps the block index to the request's last valid
    block — consecutive grid steps then fetch the *same* block, which the
    Pallas pipeline recognizes and elides the redundant HBM->VMEM DMA;
  * the kernel body wraps the score/softmax/accumulate computation
    (`block_update`) in a `pl.when(kb * block_k < length)` no-op, so
    fully-masked tiles spend neither MXU nor VPU cycles.

The dead grid steps themselves still run (their index map, the pipeline's
bookkeeping, the empty body).  The paged kernel
(`kernels/paged_decode_attention.py`), which serves the engine, runs no
step for a dead block at all: its grid is each slot's live compute blocks,
and it copies only the live pages.  Both call the same `block_update`.

Numerics are bit-identical with skipping on or off for any `lens >= 1`
batch (tested): a fully-masked tile contributes p = exp(NEG_INF - m) = +0.0
and alpha = 1.0 exactly, i.e. nothing.  (For the degenerate lens == 0 the
skipped kernel returns zeros while the unskipped one would emit a uniform
average over garbage — the engine never produces lens < 1, it parks idle
slots at pos = 1.)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30
BLOCK_K = 256      # default KV positions per block


def init_state(acc_ref, m_ref, l_ref):
    """Empty online-softmax state, before a request's first KV block."""
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def block_update(q_ref, k_refs, v_refs, acc_ref, m_ref, l_ref, *, start,
                 length, q_rows=1):
    """Fold one KV block into the online-softmax state: the per-block math
    of both flash-decode kernels (the paged kernel calls it too, so the
    two are bit-identical at equal block sizes).

    q_ref [nkv, R, hd]; k_refs, v_refs: refs [n, nkv, hd] whose rows,
    in order, hold the block_k KV positions ``start .. start + block_k -
    1`` (one ref for the dense kernel, one per page for the paged one);
    acc/m/l as in `_kernel`.  Positions at or past a row's limit are masked
    to NEG_INF, so their K may hold anything; their V must be finite
    (p = 0 multiplies it).  Each head's sums run over its own block_k
    positions only, so the result does not depend on how many KV heads
    share the block (a KV-head shard gives the same bits)."""
    def _head(h):
        q = q_ref[h]                                      # [R, hd]
        k = jnp.concatenate([r[:, h, :] for r in k_refs])  # [block_k, hd]
        v = jnp.concatenate([r[:, h, :] for r in v_refs])  # [block_k, hd]
        scale = 1.0 / math.sqrt(q.shape[-1])

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                         # [R, block_k]

        kv_pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if q_rows == 1:
            # plain ragged mask — the seed kernel's exact expression
            limit = length
        else:
            # intra-window causal mask: window row r (= row-index // g) sits
            # at absolute position length - q_rows + r, so it sees KV
            # positions j < length - (q_rows - 1) + r
            g = s.shape[0] // q_rows
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
            limit = length - (q_rows - 1) + row
        s = jnp.where(kv_pos < limit, s, NEG_INF)

        m_prev = m_ref[h, :, 0:1]                         # [R, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)        # [R, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                            # [R, block_k]
        alpha = jnp.exp(m_prev - m_new)                   # [R, 1]

        l_new = l_ref[h, :, 0:1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                 # [R, hd]
        acc_ref[h] = acc_ref[h] * alpha + pv
        m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    # the KV heads share one block (see `decode_attention`); each runs its
    # own online softmax over its R query rows
    for h in range(q_ref.shape[0]):
        _head(h)


def finalize(o_ref, acc_ref, l_ref):
    """Normalize the accumulated state into the output block."""
    l = l_ref[:, :, 0:1]
    o_ref[...] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _kernel(
    lens_ref,      # SMEM [b] int32 — scalar-prefetched per-request lengths
    q_ref,         # [nkv, R, hd]   R = q_rows * g, (window, group)-row-major
    k_ref,         # [block_k, nkv, hd]
    v_ref,         # [block_k, nkv, hd]
    o_ref,         # [nkv, R, hd]
    acc_ref,       # VMEM [nkv, R, hd] f32
    m_ref,         # VMEM [nkv, R, 128] f32 (lane-padded running max)
    l_ref,         # VMEM [nkv, R, 128] f32 (lane-padded running sum)
    *,
    block_k: int,
    num_kb: int,
    block_skip: bool,
    q_rows: int = 1,
):
    i = pl.program_id(0)
    kb = pl.program_id(1)
    length = lens_ref[i]

    pl.when(kb == 0)(lambda: init_state(acc_ref, m_ref, l_ref))
    compute = functools.partial(
        block_update, q_ref, [k_ref], [v_ref], acc_ref, m_ref, l_ref,
        start=kb * block_k, length=length, q_rows=q_rows)
    if block_skip:
        # fully-masked tile => no-op (the DMA was already elided by the
        # clamped index_map; this skips the compute as well)
        pl.when(kb * block_k < length)(compute)
    else:
        compute()
    pl.when(kb == num_kb - 1)(lambda: finalize(o_ref, acc_ref, l_ref))


@functools.partial(
    jax.jit, static_argnames=("block_k", "interpret", "block_skip", "q_rows"))
def decode_attention(
    q: jax.Array,          # [b, nkv, R, hd]   R = q_rows * g
    k_cache: jax.Array,    # [b, S, nkv, hd]
    v_cache: jax.Array,    # [b, S, nkv, hd]
    lens: jax.Array,       # [b] int32 valid lengths (ALL q_rows included)
    *,
    block_k: int = BLOCK_K,
    interpret: bool | None = None,
    block_skip: bool = True,
    q_rows: int = 1,
) -> jax.Array:
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, nkv, g, hd = q.shape
    assert g % q_rows == 0, (g, q_rows)
    skv = k_cache.shape[1]
    block_k = min(block_k, skv)
    assert skv % block_k == 0, (skv, block_k)
    num_kb = skv // block_k
    lens1 = lens.astype(jnp.int32).reshape(b)

    def q_index(i, kb, lens_ref):
        return (i, 0, 0, 0)

    def kv_index(i, kb, lens_ref):
        if not block_skip:
            return (i, kb, 0, 0)
        # clamp to the request's last valid block: repeated indices make the
        # pipeline skip the redundant fetch for fully-masked tiles
        last = jnp.maximum(pl.cdiv(lens_ref[i], block_k) - 1, 0)
        return (i, jnp.minimum(kb, last), 0, 0)

    # A grid step holds every KV head of one (request, KV block), batch dim
    # squeezed: the q/out block is [nkv, R, hd] and the K/V block
    # [block_k, nkv, hd].  Mosaic wants a block's last two dims (8, 128)-
    # aligned or whole; these are whole, where a per-head K/V block's size-1
    # head dim would be refused.
    grid = (b, num_kb)
    kernel = functools.partial(_kernel, block_k=block_k, num_kb=num_kb,
                               block_skip=block_skip, q_rows=q_rows)
    sq = pl.Squeezed()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((sq, nkv, g, hd), q_index),
            pl.BlockSpec((sq, block_k, nkv, hd), kv_index),
            pl.BlockSpec((sq, block_k, nkv, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((sq, nkv, g, hd), q_index),
        scratch_shapes=[
            pltpu.VMEM((nkv, g, hd), jnp.float32),
            pltpu.VMEM((nkv, g, 128), jnp.float32),
            pltpu.VMEM((nkv, g, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="papi_decode_attention",
    )(lens1, q, k_cache, v_cache)


def decode_attention_sharded(
    q: jax.Array,          # [b, nkv, R, hd]   R = q_rows * g
    k_cache: jax.Array,    # [b, S, nkv, hd]
    v_cache: jax.Array,    # [b, S, nkv, hd]
    lens: jax.Array,       # [b] int32 valid lengths (ALL q_rows included)
    *,
    mesh,
    axis: str = "model",
    block_k: int = BLOCK_K,
    interpret: bool | None = None,
    block_skip: bool = True,
    q_rows: int = 1,
) -> jax.Array:
    """One Attn-PIM unit per KV shard (§5.3): the kernel, `shard_map`-split
    over the KV-head dim of `axis`.

    Attention-PIM in the paper sits next to its slice of the KV cache and
    never talks to its neighbours; the head dim is the axis with exactly that
    property — each shard runs the full online-softmax pass over its local
    heads' KV stream and no cross-shard reduction exists, so the result is
    bit-identical to the unsharded kernel (tested).  Query windows
    (``q_rows > 1``, the speculative verify / chunked-prefill form) shard
    identically: the window rows ride the head dim they belong to, so each
    shard masks its own rows locally.  When the head count does not divide
    the axis (small GQA models on wide meshes) the unsharded kernel runs
    replicated instead — same divisibility fallback the rule tables use for
    weights.
    """
    nkv = q.shape[1]
    size = dict(mesh.shape).get(axis, 1)
    if size <= 1 or nkv % size != 0:
        return decode_attention(q, k_cache, v_cache, lens, block_k=block_k,
                                interpret=interpret, block_skip=block_skip,
                                q_rows=q_rows)
    kernel = functools.partial(decode_attention, block_k=block_k,
                               interpret=interpret, block_skip=block_skip,
                               q_rows=q_rows)
    return jax.shard_map(
        lambda qs, ks, vs, ls: kernel(qs, ks, vs, ls),
        mesh=mesh,
        in_specs=(P(None, axis), P(None, None, axis), P(None, None, axis),
                  P()),
        out_specs=P(None, axis),
        check_vma=False,
    )(q, k_cache, v_cache, lens)
