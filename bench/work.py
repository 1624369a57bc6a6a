"""Operations and bytes the algorithm needs, computed from shapes.

Counts are of the work a request needs, not of what the program happens to
compute: padded rows, walked-but-dead cache blocks and copies of the cache
are left out.  All tensors are 2 bytes (bf16) unless said otherwise.
"""
from __future__ import annotations

from bench.weights import dims

BYTES = 2


def fc_shapes(config: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of the seven FC projections of one layer, in the order
    the program runs them."""
    m = dims(config)
    d, nh, nkv, hd, f = m["d"], m["nh"], m["nkv"], m["hd"], m["f"]
    return [("q", d, nh * hd), ("k", d, nkv * hd), ("v", d, nkv * hd),
            ("o", nh * hd, d), ("gate", d, f), ("up", d, f), ("down", f, d)]


def fc_call(m_rows: int, k: int, n: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one [M, K] x [K, N] product: weights read once,
    activations read and written once."""
    return 2.0 * m_rows * k * n, BYTES * (k * n + m_rows * k + m_rows * n)


def layer_matmul_params(config: dict) -> int:
    return sum(k * n for _, k, n in fc_shapes(config))


def attention_work(config: dict, ctx: int, q_rows: int = 1) -> tuple[float, float]:
    """(FLOPs, bytes) of decode attention, all layers, for `q_rows` query
    rows of one sequence over `ctx` live KV positions: scores and weighted
    sum (4 ctx nh hd per row), the live K and V read once, q read and the
    output written."""
    m = dims(config)
    flops = 4.0 * q_rows * ctx * m["nh"] * m["hd"]
    nbytes = (ctx * 2 * m["nkv"] * m["hd"] + 2 * q_rows * m["nh"] * m["hd"]) \
        * BYTES
    return m["L"] * flops, m["L"] * nbytes


def token_flops(config: dict, ctx: int, logits: bool) -> float:
    """Model FLOPs of one token at a position that sees `ctx` positions
    (itself included): 2 x layer matmul parameters, attention over the
    context, and the LM head where the token's logits are needed."""
    m = dims(config)
    f = 2.0 * m["L"] * layer_matmul_params(config)
    f += 4.0 * m["L"] * m["nh"] * m["hd"] * ctx
    if logits:
        f += 2.0 * m["d"] * m["V"]
    return f


def prompt_flops(config: dict, prompt_len: int) -> float:
    """Model FLOPs of a whole prompt: every position with its causal
    context, and the LM head once, for the first output token."""
    m = dims(config)
    n = prompt_len
    f = 2.0 * m["L"] * layer_matmul_params(config) * n
    f += 4.0 * m["L"] * m["nh"] * m["hd"] * n * (n + 1) / 2
    return f + 2.0 * m["d"] * m["V"]
