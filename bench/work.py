"""Operations and bytes the algorithm needs, computed from shapes.

Counts are of the work a request needs, not of what the program happens to
compute: padded rows, walked-but-dead cache blocks and copies of the cache
are left out.  All tensors are 2 bytes (bf16) unless said otherwise.  What
a model's layers hold is its family's (bench/families/<family>.py), so the
counts of a model are the family's; this module keeps the signatures that
the metric readers call.
"""
from __future__ import annotations

from bench import spec

BYTES = 2


def fc_shapes(config: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of the FC projections of one layer, in the order the
    program runs them."""
    return spec.family(config).fc_shapes(config)


def fc_call(m_rows: int, k: int, n: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one [M, K] x [K, N] product: weights read once,
    activations read and written once."""
    return 2.0 * m_rows * k * n, BYTES * (k * n + m_rows * k + m_rows * n)


def layer_matmul_params(config: dict) -> int:
    return sum(k * n for _, k, n in fc_shapes(config))


def attention_work(config: dict, ctx: int, q_rows: int = 1) -> tuple[float, float]:
    """(FLOPs, bytes) of decode attention, all layers, for `q_rows` query
    rows of one sequence over `ctx` live KV positions."""
    return spec.family(config).attention_work(config, ctx, q_rows)


def token_flops(config: dict, ctx: int, logits: bool) -> float:
    """Model FLOPs of one token at a position that sees `ctx` positions
    (itself included), with the LM head where the token's logits are
    needed."""
    return spec.family(config).token_flops(config, ctx, logits)


def prompt_flops(config: dict, prompt_len: int) -> float:
    """Model FLOPs of a whole prompt, and the LM head once, for the first
    output token."""
    return spec.family(config).prompt_flops(config, prompt_len)
