"""Mamba2 SSD chunk-scan Pallas kernel.

The SSD algorithm's hot loop: per (batch, head), chunks are processed in
sequence; each chunk is three small dense matmuls (MXU work) plus a rank-1
state update, with the [hp, n] recurrent state living in VMEM scratch across
the sequential chunk axis:

  grid = (b, nh, n_chunks)        chunk axis innermost ("arbitrary")
  per chunk:  CB   = C_c @ B_c^T              [cs, cs]
              y    = (CB * L) @ dtx_c          intra-chunk, L = decay mask
                   + (exp(cum) * C_c) @ S^T    inter-chunk from carried state
              S    = exp(cum_last) * S + (E * dtx_c)^T @ B_c

Inputs are pre-discretized (dtx = dt*x, lt = dt*A) and the within-chunk
cumulative log-decay `cum` is precomputed by the wrapper — the kernel is pure
matmul + elementwise, mapping straight onto MXU/VPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(
    dtx_ref,    # [1, 1, 1, cs, hp]
    cum_ref,    # [1, 1, 1, cs]  f32 inclusive within-chunk cumsum of lt
    b_ref,      # [1, 1, cs, n]
    c_ref,      # [1, 1, cs, n]
    o_ref,      # [1, 1, 1, cs, hp]
    state_ref,  # VMEM [hp, n] f32
):
    cb = pl.program_id(2)

    @pl.when(cb == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    dtx = dtx_ref[0, 0, 0].astype(jnp.float32)        # [cs, hp]
    cum = cum_ref[0, 0, 0]                            # [cs]
    B = b_ref[0, 0].astype(jnp.float32)               # [cs, n]
    C = c_ref[0, 0].astype(jnp.float32)               # [cs, n]
    cs = dtx.shape[0]

    # intra-chunk: (C B^T ∘ L) @ dtx
    CB = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # [cs, cs]
    seg = cum[:, None] - cum[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 1)
    L = jnp.where(ii >= jj, jnp.exp(seg), 0.0)
    y = jax.lax.dot_general(CB * L, dtx, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)    # [cs, hp]

    # inter-chunk: (exp(cum) * C) @ state^T
    state = state_ref[...]                                          # [hp, n]
    y += jax.lax.dot_general(
        jnp.exp(cum)[:, None] * C, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[0, 0, 0] = y.astype(o_ref.dtype)

    # state update: exp(cum_last) * state + (E*dtx)^T @ B
    e_to_end = jnp.exp(cum[-1] - cum)                               # [cs]
    s_chunk = jax.lax.dot_general(
        dtx * e_to_end[:, None], B, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                               # [hp, n]
    state_ref[...] = jnp.exp(cum[-1]) * state + s_chunk


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    dtx: jax.Array,   # [b, nh, l, hp]  dt_t * x_t
    lt: jax.Array,    # [b, nh, l]      dt_t * A_h (f32 log-decay)
    B: jax.Array,     # [b, l, n]
    C: jax.Array,     # [b, l, n]
    *,
    chunk: int = 256,
    interpret: bool | None = None,
) -> jax.Array:       # [b, nh, l, hp]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, nh, l, hp = dtx.shape
    n = B.shape[-1]
    cs = min(chunk, l)
    assert l % cs == 0, (l, cs)
    nc = l // cs

    cum = jnp.cumsum(
        lt.astype(jnp.float32).reshape(b, nh, nc, cs), axis=-1
    )                                                  # [b, nh, nc, cs]
    dtx_c = dtx.reshape(b, nh, nc, cs, hp)
    B_c = B.reshape(b, nc, cs, n)
    C_c = C.reshape(b, nc, cs, n)

    grid = (b, nh, nc)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, cs, hp), lambda i, h, c: (i, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, cs), lambda i, h, c: (i, h, c, 0)),
            pl.BlockSpec((1, 1, cs, n), lambda i, h, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, cs, n), lambda i, h, c: (i, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, cs, hp), lambda i, h, c: (i, h, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nh, nc, cs, hp), dtx.dtype),
        scratch_shapes=[pltpu.VMEM((hp, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="papi_ssd_scan",
    )(dtx_c, cum, B_c, C_c)
    return out.reshape(b, nh, l, hp)
