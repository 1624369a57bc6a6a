"""Paged flash-decode GQA attention — Attn-PIM over bank-row pages.

The dense kernel (`kernels/decode_attention.py`) streams a per-slot
contiguous KV slab.  Under the paged KV cache the physical layout is a pool
of fixed-size pages (`[num_pages, page_size, nkv, hd]` — one page per
Attn-PIM bank row, see `serving/kv_pages.py`) and a per-request block table
maps logical KV blocks to physical pages.  This kernel runs the SAME online
softmax over that layout:

  grid = (batch, max_blocks)              last axis innermost/sequential
  scalar prefetch:  lens   [b]            per-request valid lengths
                    tables [b, max_blocks] logical block -> physical page

The K/V `index_map` resolves `tables[i, kb]` *before* each grid step's DMA
is issued (that is what `PrefetchScalarGridSpec` buys us), so the gather is
free: the pipeline simply fetches block `kb`'s page from wherever it
physically lives.  No `[b, S, ...]` contiguous view is ever materialized.

Ragged block skipping carries over unchanged: for blocks entirely past a
request's length, the logical block index is clamped to the last valid one
(consecutive grid steps then fetch the same physical page, and the Pallas
pipeline elides the redundant DMA) and the kernel body no-ops via
`pl.when`.

Bit-identity with the dense kernel is by construction: the kernel *body* is
literally `decode_attention._kernel` (imported, not copied) with
`block_k = page_size` — on identical KV contents the two kernels execute
the same sequence of per-block operations, so outputs are bit-equal
(asserted in `tests/test_serving_paged.py`).

Query windows (``q_rows > 1``) carry over from the dense kernel unchanged:
the q block holds R = q_rows * g (window, group)-row-major rows per KV
head, `lens` counts ALL q_rows window tokens, and the shared body applies
the intra-window causal mask (row r sees KV position j iff
``j < lens - (q_rows - 1) + r``).  This is what puts speculative verify
windows and chunked-prefill waves on the paged Pallas hot path — the XLA
alternative must first materialize the whole `[b, max_blocks * page, ...]`
pool view via `models.layers.gather_kv_pages`.

Block-table safety contract (any q_rows >= 1): entries at or past a
request's last valid block may point anywhere (the engine points them at
the shared garbage page) — with `block_skip=True` they are clamped away,
and with `block_skip=False` their scores are masked to -inf by `lens`, so
either way they never reach the output.  Window rows extend the contract
forward in time: row r masks everything past its own absolute position, so
table entries covering positions written for LATER rows of the same window
(or garbage beyond the window) never leak backward into row r.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.kernels.decode_attention import _kernel


def _paged_kernel(lens_ref, tables_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, page_size, num_blocks,
                  block_skip, q_rows=1):
    # tables_ref is consumed exclusively by the index_map (the DMA source
    # address); the arithmetic is the dense kernel's, block_k = page_size.
    del tables_ref
    _kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
            block_k=page_size, num_kb=num_blocks, block_skip=block_skip,
            q_rows=q_rows)


@functools.partial(
    jax.jit, static_argnames=("interpret", "block_skip", "q_rows"))
def paged_decode_attention(
    q: jax.Array,          # [b, nkv, R, hd]   R = q_rows * g
    k_pages: jax.Array,    # [num_pages, page_size, nkv, hd]
    v_pages: jax.Array,    # [num_pages, page_size, nkv, hd]
    lens: jax.Array,       # [b] int32 valid lengths (ALL q_rows included)
    tables: jax.Array,     # [b, max_blocks] int32 physical page ids
    *,
    interpret: bool | None = None,
    block_skip: bool = True,
    q_rows: int = 1,
) -> jax.Array:
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, nkv, g, hd = q.shape
    assert g % q_rows == 0, (g, q_rows)
    page_size = k_pages.shape[1]
    num_blocks = tables.shape[1]
    lens1 = lens.astype(jnp.int32).reshape(b)
    tables1 = tables.astype(jnp.int32).reshape(b, num_blocks)

    def q_index(i, kb, lens_ref, tables_ref):
        return (i, 0, 0, 0)

    def kv_index(i, kb, lens_ref, tables_ref):
        if block_skip:
            # clamp to the request's last valid logical block; repeated
            # physical indices let the pipeline skip the redundant fetch
            last = jnp.maximum(pl.cdiv(lens_ref[i], page_size) - 1, 0)
            kb = jnp.minimum(kb, last)
        return (tables_ref[i, kb], 0, 0, 0)

    # blocks as in the dense kernel: all KV heads of one page per grid step
    grid = (b, num_blocks)
    kernel = functools.partial(_paged_kernel, page_size=page_size,
                               num_blocks=num_blocks, block_skip=block_skip,
                               q_rows=q_rows)
    sq = pl.Squeezed()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((sq, nkv, g, hd), q_index),
            pl.BlockSpec((sq, page_size, nkv, hd), kv_index),
            pl.BlockSpec((sq, page_size, nkv, hd), kv_index),
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
        name="papi_paged_decode_attention",
    )(lens1, tables1, q, k_pages, v_pages)


def paged_decode_attention_sharded(
    q: jax.Array,          # [b, nkv, R, hd]   R = q_rows * g
    k_pages: jax.Array,    # [num_pages, page_size, nkv, hd]
    v_pages: jax.Array,    # [num_pages, page_size, nkv, hd]
    lens: jax.Array,       # [b] int32 (ALL q_rows included)
    tables: jax.Array,     # [b, max_blocks] int32
    *,
    mesh,
    axis: str = "model",
    interpret: bool | None = None,
    block_skip: bool = True,
    q_rows: int = 1,
) -> jax.Array:
    """One Attn-PIM unit per KV-head shard, paged edition (§5.3).

    Identical split to `decode_attention_sharded`: the KV-head dim is the
    axis with no cross-shard reduction, so each shard runs the full paged
    online-softmax pass over its local heads' pages and the result is
    bit-identical to the unsharded kernel — query windows included (the
    window rows ride their head's shard).  Lens and block tables are
    replicated — page ids index the pool dim, which every shard holds in
    full for its own heads.  Indivisible head counts fall back to the
    replicated kernel, matching the dense wrapper.
    """
    nkv = q.shape[1]
    size = dict(mesh.shape).get(axis, 1)
    if size <= 1 or nkv % size != 0:
        return paged_decode_attention(q, k_pages, v_pages, lens, tables,
                                      interpret=interpret,
                                      block_skip=block_skip, q_rows=q_rows)
    kernel = functools.partial(paged_decode_attention, interpret=interpret,
                               block_skip=block_skip, q_rows=q_rows)
    return jax.shard_map(
        lambda qs, ks, vs, ls, ts: kernel(qs, ks, vs, ls, ts),
        mesh=mesh,
        in_specs=(P(None, axis), P(None, None, axis), P(None, None, axis),
                  P(), P()),
        out_specs=P(None, axis),
        check_vma=False,
    )(q, k_pages, v_pages, lens, tables)
