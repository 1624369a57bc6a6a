"""Weight-streaming skinny matmul Pallas kernel — the FC-PIM analogue.

PAPI's FC-PIM executes the FC kernel when it is memory-bound (low RLP*TLP):
each weight element is read from DRAM once and reused across the few
activation rows.  The TPU translation: a matmul kernel organized so the
weight matrix makes exactly one HBM -> VMEM pass, with the skinny activation
block pinned in VMEM for the whole kernel:

  grid = (N // block_n, K // block_k)    k innermost (accumulate in scratch)
  x block: [m, block_k]      m = RLP*TLP rows, pinned (same block all n)
  w block: [block_k, block_n] streamed once
  acc:     [m, block_n] f32 scratch

Block tuning: unless the caller pins block sizes, `_auto_blocks` picks the
largest Mosaic-legal blocks of (K, N) whose *double-buffered* working set
fits a conservative VMEM budget — the pipeline overlaps the next weight
tile's DMA with the current tile's FLOPs, so both buffers must be resident
at once.
Bigger tiles amortize grid/DMA overhead; the budget keeps two w-tiles, two
x-tiles, the f32 accumulator and the output block co-resident.

When RLP*TLP is large the MXU path (plain jnp.dot / XLA) wins — that flip is
exactly PAPI's scheduling decision, made by `core.scheduler` and validated by
`core.calibration` on this very pair of implementations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Conservative per-core VMEM budget for the kernel's working set (real VMEM
# is ~16 MiB; leave headroom for the pipeline's own bookkeeping).
_VMEM_BUDGET_BYTES = 8 * 1024 * 1024


# Mosaic tiles a block's last two dims by (sublanes, 128 lanes): each must
# be a multiple of its tile or the full array dim.  K is the lane dim of the
# x block and the second-minor dim of the w block, N the lane dim of the w
# and output blocks, so both are held to the lane tile (which is also a
# multiple of every dtype's sublane packing).
_LANE = 128


def _legal_blocks(dim: int) -> list[int]:
    """Block sizes Mosaic accepts for `dim`, largest first: the full dim and
    every divisor that is a multiple of the lane tile."""
    return [dim] + [b for b in range((dim - 1) // _LANE * _LANE, 0, -_LANE)
                    if dim % b == 0]


def _pick(dim: int, target: int) -> int:
    """Largest legal block of `dim` that is <= target, else the smallest."""
    legal = _legal_blocks(dim)
    return next((b for b in legal if b <= target), legal[-1])


def _working_set_bytes(m: int, bk: int, bn: int, itemsize: int) -> int:
    # 2x for double buffering of the streamed/pinned input tiles; the f32
    # accumulator and output tile are single-buffered.
    return (2 * bk * bn * itemsize        # w tiles (streamed)
            + 2 * m * bk * itemsize       # x tiles (pinned, revolving)
            + m * bn * 4                  # acc scratch (f32)
            + m * bn * itemsize)          # output tile


def _auto_blocks(m: int, K: int, N: int, itemsize: int) -> tuple[int, int]:
    """Pick Mosaic-legal (block_k, block_n) fitting the double-buffered
    VMEM budget; a dim with no legal block under the target (not a multiple
    of 128) keeps its full size."""
    for target in (1024, 768, 512, 384, 256, 128):
        bk, bn = _pick(K, target), _pick(N, target)
        if _working_set_bytes(m, bk, bn, itemsize) <= _VMEM_BUDGET_BYTES:
            return bk, bn
    return bk, bn


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, num_kb: int):
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(kb == num_kb - 1)
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "block_n", "interpret"))
def fc_gemv(
    x: jax.Array,      # [m, K]  (m = RLP*TLP, small)
    w: jax.Array,      # [K, N]
    *,
    block_k: int | None = None,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, K = x.shape
    K2, N = w.shape
    assert K == K2
    auto_k, auto_n = _auto_blocks(m, K, N, x.dtype.itemsize)
    block_k = auto_k if block_k is None else min(block_k, K)
    block_n = auto_n if block_n is None else min(block_n, N)
    assert K % block_k == 0 and N % block_n == 0, (K, N, block_k, block_n)
    num_kb = K // block_k

    grid = (N // block_n, num_kb)
    kernel = functools.partial(_kernel, num_kb=num_kb)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, block_k), lambda n, k: (0, k)),
            pl.BlockSpec((block_k, block_n), lambda n, k: (k, n)),
        ],
        out_specs=pl.BlockSpec((m, block_n), lambda n, k: (0, n)),
        out_shape=jax.ShapeDtypeStruct((m, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # n parallel, k sequential: the k accumulation must stay ordered,
            # the n tiles are independent so the pipeline can double-buffer
            # the weight stream across both axes.
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="papi_fc_gemv",
    )(x, w)
