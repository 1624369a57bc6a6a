"""Plain float32 forward pass of a model, written from the published
description and independent of the program under test.

The layer and logit equations are the configuration's family's
(bench/families/<family>.py: `reference_layer`, `reference_logits`); this
module embeds the tokens (every family's globals hold its input embedding
as `embed` [V, d]), runs the layers in order and compares the served
tokens.  It keeps the helpers that families share: `_rmsnorm`, `_rope` and
`_attention`.

It runs layer by layer, making each layer's weights again from the seed
(bench/weights.py), so that it fits beside nothing else on the chip.  Every
matrix product goes through `matmul`; the control passes one that rounds
both operands to fp8 first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec
from bench import weights as W

QUERY_BLOCK = 512


def matmul_f32(x, w):
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _fp8(x):
    """Round to float8_e4m3fn with one scale for the whole tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32), scale


def matmul_fp8(x, w):
    """The control: both operands rounded to fp8 (e4m3, per-tensor scale),
    products summed in float32."""
    xq, sx = _fp8(x)
    wq, sw = _fp8(w)
    return matmul_f32(xq, wq) * (sx * sw)


MATMULS = {"f32": matmul_f32, "fp8": matmul_fp8}


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [T, heads, hd] at positions 0..T-1."""
    t, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, mm):
    """Causal GQA attention, query blocks of QUERY_BLOCK rows."""
    t, nh, hd = q.shape
    nkv = k.shape[1]
    g = nh // nkv
    kh = jnp.repeat(k, g, axis=1).transpose(1, 2, 0)      # [nh, hd, T]
    vh = jnp.repeat(v, g, axis=1).transpose(1, 0, 2)      # [nh, T, hd]
    outs = []
    for lo in range(0, t, QUERY_BLOCK):
        qb = q[lo:lo + QUERY_BLOCK].transpose(1, 0, 2)     # [nh, b, hd]
        s = mm(qb, kh) / jnp.sqrt(jnp.float32(hd))         # [nh, b, T]
        rows = lo + jnp.arange(qb.shape[1])[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= rows, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(mm(p, vh).transpose(1, 0, 2))          # [b, nh, hd]
    return jnp.concatenate(outs, 0)


@functools.lru_cache(maxsize=None)
def _fns(frozen: str, kind: str):
    config = W.thaw(frozen)
    family = spec.family(config)
    mm = MATMULS[kind]
    f32 = functools.partial(jax.tree.map, lambda x: x.astype(jnp.float32))

    @jax.jit
    def embed(g, tokens):
        e = f32(g)["embed"]
        if kind == "fp8":
            q, scale = _fp8(e)
            e = q * scale
        return e[tokens]

    @jax.jit
    def layer(lw, h, l):
        return family.reference_layer(config, mm, f32(lw), h, l)

    @jax.jit
    def logits(g, h):
        return family.reference_logits(config, mm, f32(g), h)

    return embed, layer, logits


@jax.jit
def _gaps(ref, served, other):
    """Per row: how far the reference's logit of the served token, and of
    the token that `other` puts first, lie below the reference's best."""
    best = jnp.max(ref, -1)
    got = jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    pick = jnp.argmax(other, -1)
    alt = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return best - got, best - alt


def _bucket(n: int) -> int:
    """Padded length: a power of two from 256 up, so that few shapes
    compile."""
    return max(256, 1 << (n - 1).bit_length())


def served_gaps(config: dict, seed: int, prompts: list[list[int]],
                served: list[list[int]], control: bool = False):
    """Teacher-forced over each prompt and its served tokens: for every
    served token, the gap by which the float32 reference's logit of that
    token lies below the reference's best.  With `control`, also the gap of
    the token that the fp8 reference puts first at the same position.
    Returns ([np.ndarray per request], [np.ndarray per request] or None).

    Sequences are padded at the end to a power of two, which the causal
    mask keeps from every real position."""
    kinds = ("f32", "fp8") if control else ("f32",)
    frozen = W.freeze(config)
    g = W.globals_(config, seed)
    seqs = [list(p) + list(t[:-1]) for p, t in zip(prompts, served)]
    with jax.default_matmul_precision("highest"):
        hs = {}
        for kind in kinds:
            embed, _, _ = _fns(frozen, kind)
            hs[kind] = []
            for s in seqs:
                toks = np.zeros(_bucket(len(s)), np.int32)
                toks[:len(s)] = s
                hs[kind].append(embed(g, jnp.asarray(toks)))
        for l in range(config["num_hidden_layers"]):
            lw = W.layer(config, seed, l)
            for kind in kinds:
                layer = _fns(frozen, kind)[1]
                hs[kind] = [layer(lw, h, jnp.int32(l)) for h in hs[kind]]
            del lw
        got, alt = [], []
        for i, (p, t) in enumerate(zip(prompts, served)):
            pos = np.arange(len(p) - 1, len(p) - 1 + len(t))
            g_rows, a_rows = [], []
            for lo in range(0, len(pos), QUERY_BLOCK):
                n = min(QUERY_BLOCK, len(pos) - lo)
                rows = np.full(QUERY_BLOCK, pos[lo + n - 1], np.int32)
                rows[:n] = pos[lo:lo + n]
                toks = np.zeros(QUERY_BLOCK, np.int32)
                toks[:n] = t[lo:lo + n]
                rows = jnp.asarray(rows)
                ref = _fns(frozen, "f32")[2](g, hs["f32"][i][rows])
                other = (_fns(frozen, "fp8")[2](g, hs["fp8"][i][rows])
                         if control else ref)
                gap, gap_alt = _gaps(ref, jnp.asarray(toks), other)
                g_rows.append(np.asarray(gap)[:n])
                a_rows.append(np.asarray(gap_alt)[:n])
            got.append(np.concatenate(g_rows))
            alt.append(np.concatenate(a_rows))
    return got, (alt if control else None)
