"""Paged flash-decode GQA attention — Attn-PIM over bank-row pages.

The dense kernel (`kernels/decode_attention.py`) streams a per-slot
contiguous KV slab.  Under the paged KV cache the physical layout is a pool
of fixed-size pages (`[num_pages, page_size, nkv, hd]` — one page per
Attn-PIM bank row, see `serving/kv_pages.py`) and a per-request block table
maps logical KV blocks to physical pages.  This kernel runs the SAME online
softmax over that layout, and reads only each request's live pages:

  grid = (steps,)          each slot's live compute blocks, slot by slot;
                           `steps` is computed from `lens` at run time
  scalar prefetch:  slot, block [b * cdiv(max_blocks, ppc)]  per step
                    lens   [b]             per-request valid lengths
                    tables [b, max_blocks] logical block -> physical page
  K/V operands:     the pool, ppc times each: operand j is page j of the
                    step's compute block, [page_size, nkv, hd]

Live-page walk
--------------
A compute block holds ``ppc`` pages, ``bk = ppc * page_size`` KV positions.
Slot i gets exactly ``cdiv(lens[i], bk)`` grid steps (one for an empty
slot, whose output stays zero), and step (i, n) brings in the pages
``n * ppc .. n * ppc + ppc - 1`` through ``ppc`` K and ``ppc`` V operands,
each with an index map that reads ``tables[i, kb]`` from the prefetched
table.  The pipeline double-buffers every operand, so the next step's
pages (the next block, or the next slot's first) are copied while the
current block computes.  No grid step runs and no page is copied for a
dead block, so the kernel's work follows the live context, not
``b * max_blocks``.  Operands past a slot's last live page (the tail of its
last block) repeat that page, which the position mask hides.

Why operands and not hand-written copies: the pool's minor dim (hd = 64 at
qwen2-0.5b) is under the 128-lane tile, and the installed Mosaic refuses a
DMA from any slice of such an HBM ref ("Slice shape along dimension 3 must
be aligned to tiling (128)"), while its own pipeline copies such blocks.

``ppc`` adapts to the shapes (`pages_per_block`): the most pages with
``bk <= BLOCK_K`` (the dense kernel's default block), no more than the
table holds, and an f32 ``[R, bk]`` score block within `SCORE_BYTES` —
decode (R = g) and 64-token waves (R = 64 * g) at page 16 both take 16.

Bit-identity with the dense kernel is by construction: each compute block
runs `decode_attention.block_update` (imported, not copied) over its ppc
pages in order, so on identical KV contents this kernel equals
``decode_attention(..., block_k=bk)`` bit for bit (asserted in
`tests/test_serving_paged.py`).

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
the shared garbage page).  With `block_skip=True` they are never read; with
`block_skip=False` (the walk-everything oracle) every entry is copied and
its scores are masked to -inf by `lens`, so either way they never reach the
output.  Window rows extend the contract forward in time: row r masks
everything past its own absolute position, so table entries covering
positions written for LATER rows of the same window (or garbage beyond the
window) never leak backward into row r.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.kernels.decode_attention import (BLOCK_K, block_update, finalize,
                                            init_state)

# VMEM for one KV head's f32 [R, bk] score block: long query windows take
# fewer pages per compute block
SCORE_BYTES = 1 << 20


def pages_per_block(page_size: int, max_blocks: int, rows: int) -> int:
    """Pages per compute block for a table of `max_blocks` pages of
    `page_size` and R = `rows` query rows per KV head."""
    return max(1, min(BLOCK_K // page_size, max_blocks,
                      SCORE_BYTES // (4 * rows * page_size)))


def _pages(lens, page_size, max_blocks, block_skip):
    """Table entries each slot reads: its live pages, or all of them."""
    if not block_skip:
        return jnp.full_like(lens, max_blocks)
    return jnp.minimum(pl.cdiv(lens, page_size), max_blocks)


def _schedule(lens, *, page_size, ppc, max_blocks, block_skip):
    """The grid's steps: every slot's compute blocks, slot after slot (at
    least one per slot, so each output block is written).  Returns (steps,
    slot of each step, block of each step); the arrays are padded to the
    largest schedule, and the grid runs only the first `steps`."""
    b = lens.shape[0]
    nblk = jnp.maximum(pl.cdiv(
        _pages(lens, page_size, max_blocks, block_skip), ppc), 1)
    # compares and sums, not a cumsum and a gather: a few fused XLA ops
    i = jnp.arange(b)
    ends = jnp.sum(jnp.where(i[None, :] <= i[:, None], nblk[None, :], 0),
                   axis=1)
    step = jnp.arange(b * pl.cdiv(max_blocks, ppc), dtype=jnp.int32)
    past = step[:, None] >= ends[None, :]
    slot = jnp.minimum(jnp.sum(past, axis=1, dtype=jnp.int32), b - 1)
    block = step - jnp.sum(jnp.where(past, nblk[None, :], 0), axis=1)
    return ends[-1], slot, block


def _paged_kernel(slot_ref, block_ref, lens_ref, tables_ref, q_ref, *refs,
                  page_size, ppc, max_blocks, block_skip, q_rows):
    k_refs, v_refs = refs[:ppc], refs[ppc:2 * ppc]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * ppc:]
    step = pl.program_id(0)
    slot, n = slot_ref[step], block_ref[step]
    length = lens_ref[slot]
    start = n * ppc * page_size
    pages = _pages(length, page_size, max_blocks, block_skip)

    pl.when(n == 0)(lambda: init_state(acc_ref, m_ref, l_ref))
    compute = functools.partial(
        block_update, q_ref, k_refs, v_refs, acc_ref, m_ref, l_ref,
        start=start, length=length, q_rows=q_rows)
    if block_skip:
        # only a slot of length 0 has a step past its length: its output
        # stays zero, as the dense kernel's does
        pl.when(start < length)(compute)
    else:
        compute()
    last = jnp.maximum(pl.cdiv(pages, ppc), 1) - 1
    pl.when(n == last)(lambda: finalize(o_ref, acc_ref, l_ref))


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
    max_blocks = tables.shape[1]
    ppc = pages_per_block(page_size, max_blocks, g)
    lens1 = lens.astype(jnp.int32).reshape(b)
    tables1 = tables.astype(jnp.int32).reshape(b, max_blocks)
    steps, slot, block = _schedule(lens1, page_size=page_size, ppc=ppc,
                                   max_blocks=max_blocks,
                                   block_skip=block_skip)

    def q_index(s, slot_ref, block_ref, lens_ref, tables_ref):
        return (slot_ref[s], 0, 0, 0)

    def page_index(j):
        # page j of the step's compute block; past the slot's last page
        # (the tail of its last block) the last page again, which the
        # position mask hides
        def index(s, slot_ref, block_ref, lens_ref, tables_ref):
            i = slot_ref[s]
            last = jnp.maximum(_pages(lens_ref[i], page_size, max_blocks,
                                      block_skip), 1) - 1
            return (tables_ref[i, jnp.minimum(block_ref[s] * ppc + j, last)],
                    0, 0, 0)
        return index

    kernel = functools.partial(_paged_kernel, page_size=page_size, ppc=ppc,
                               max_blocks=max_blocks, block_skip=block_skip,
                               q_rows=q_rows)
    sq = pl.Squeezed()
    page_specs = [pl.BlockSpec((sq, page_size, nkv, hd), page_index(j))
                  for j in range(ppc)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(steps,),
        in_specs=[pl.BlockSpec((sq, nkv, g, hd), q_index),
                  *page_specs, *page_specs],
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
            # a slot's steps revisit its output block
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="papi_paged_decode_attention",
    )(slot, block, lens1, tables1, q, *[k_pages] * ppc, *[v_pages] * ppc)


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
