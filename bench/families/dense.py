"""The dense decoder family: Qwen2 / Llama layers with a tied LM head.

One module per model family (bench/spec.py finds it by the `family` that a
configuration file names) holds everything that depends on the family's
tensors and equations:

    model_config, program_params      the program's config and tree
    layer_laws, global_laws           the weights' names, shapes and laws
    reference_layer, reference_logits the plain float32 reference
    fc_shapes, token_flops, prompt_flops, attention_work
                                      the work the algorithm needs

Only `model_config` and `program_params` touch the program.  The reference
calls `reference_layer(config, mm, lw, h, l)` with the layer's index `l` as
a traced int32 scalar, so that one compiled layer serves every layer; a
family whose layers differ by kind selects on it with `jnp.where` or
`lax.switch`.  Every family's globals hold the input embedding as `embed`
[V, d], which bench/reference.py looks up.

Weights (d model width, nh/nkv query/KV heads of hd, f MLP width, V
vocabulary):

    embed [V, d] (tied LM head), final_norm [d]
    per layer: input_norm [d], q [d, nh, hd], k/v [d, nkv, hd],
               q_bias [nh, hd], k_bias/v_bias [nkv, hd] (with attention
               bias), o [nh, hd, d], post_norm [d], gate/up [d, f],
               down [f, d]

Reference, written from the published description and independent of the
program under test:

    h0 = embed[tokens]
    per layer:  a = rmsnorm(h) * input_norm
                q, k, v = a Wq + bq, a Wk + bk, a Wv + bv, RoPE on q and k
                  (rotate-half form, inverse frequencies theta^(-2i/hd))
                h += softmax(q k^T / sqrt(hd), causal, GQA) v Wo
                m = rmsnorm(h) * post_norm
                h += (silu(m Wgate) * (m Wup)) Wdown
    logits = (rmsnorm(h) * final_norm) embed^T        (tied LM head)
"""
from __future__ import annotations

import math

import jax

from bench.reference import _attention, _rmsnorm, _rope
from bench.work import BYTES, layer_matmul_params


def dims(config: dict) -> dict:
    d = config["hidden_size"]
    nh = config["num_attention_heads"]
    return dict(d=d, nh=nh, nkv=config["num_key_value_heads"],
                hd=config.get("head_dim") or d // nh,
                f=config["intermediate_size"], V=config["vocab_size"],
                L=config["num_hidden_layers"])


# the program

def model_config(config: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig

    m = dims(config)
    assert config["hidden_act"] == "silu", config["hidden_act"]
    return ModelConfig(
        name=config["name"], family="dense", num_layers=m["L"],
        d_model=m["d"], num_heads=m["nh"], num_kv_heads=m["nkv"],
        d_ff=m["f"], vocab_size=m["V"], head_dim=m["hd"],
        qkv_bias=bool(config["attention_bias"]), mlp="swiglu",
        norm="rmsnorm", norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=config["torch_dtype"])


def program_params(cfg, w: dict) -> dict:
    """The benchmark's weights in the program's tree."""
    lw = w["layers"]
    attn = {"w_q": lw["q"], "w_k": lw["k"], "w_v": lw["v"], "w_o": lw["o"]}
    if "q_bias" in lw:
        attn.update(b_q=lw["q_bias"], b_k=lw["k_bias"], b_v=lw["v_bias"])
    return {
        "embed": {"w": w["embed"]},
        "final_norm": {"w": w["final_norm"]},
        "layers": {"norm1": lw["input_norm"], "attn": attn,
                   "norm2": lw["post_norm"],
                   "mlp": {"w_gate": lw["gate"], "w_up": lw["up"],
                           "w_down": lw["down"]}},
    }


# the weights

def layer_laws(config: dict) -> dict[str, tuple[tuple[int, ...], str, float]]:
    """name -> (shape, law, scale) for one layer.  Projections into the
    residual stream are scaled down by sqrt(2 L), the usual depth-scaled
    init, with L the published depth (so a model cut in depth holds the
    first layers of the whole one); norm weights scatter around 1 and
    biases around 0, so that both reach the comparison with the
    reference."""
    m = dims(config)
    d, nh, nkv, hd, f = m["d"], m["nh"], m["nkv"], m["hd"], m["f"]
    depth = config.get("published", {}).get("num_hidden_layers", m["L"])
    res = d ** -0.5 / math.sqrt(2 * depth)
    laws = {
        "input_norm": ((d,), "around_one", 0.05),
        "q": ((d, nh, hd), "normal", d ** -0.5),
        "k": ((d, nkv, hd), "normal", d ** -0.5),
        "v": ((d, nkv, hd), "normal", d ** -0.5),
        "o": ((nh, hd, d), "normal", res),
        "post_norm": ((d,), "around_one", 0.05),
        "gate": ((d, f), "normal", d ** -0.5),
        "up": ((d, f), "normal", d ** -0.5),
        "down": ((f, d), "normal", res),
    }
    if config["attention_bias"]:
        laws["q_bias"] = ((nh, hd), "normal", 0.1)
        laws["k_bias"] = ((nkv, hd), "normal", 0.1)
        laws["v_bias"] = ((nkv, hd), "normal", 0.1)
    return laws


def global_laws(config: dict) -> dict[str, tuple[tuple[int, ...], str, float]]:
    """name -> (shape, law, scale) of the tensors outside the layers."""
    m = dims(config)
    return {"embed": ((m["V"], m["d"]), "normal", 0.02),
            "final_norm": ((m["d"],), "around_one", 0.05)}


# the reference

def reference_layer(config: dict, mm, lw: dict, h, l):
    """One layer on the float32 state h [T, d]; every layer is alike, so
    the layer index `l` is not read."""
    del l
    m = dims(config)
    d, nh, nkv, hd = m["d"], m["nh"], m["nkv"], m["hd"]
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    t = h.shape[0]
    a = _rmsnorm(h, lw["input_norm"], eps)
    q = mm(a, lw["q"].reshape(d, nh * hd)).reshape(t, nh, hd)
    k = mm(a, lw["k"].reshape(d, nkv * hd)).reshape(t, nkv, hd)
    v = mm(a, lw["v"].reshape(d, nkv * hd)).reshape(t, nkv, hd)
    if "q_bias" in lw:
        q, k, v = q + lw["q_bias"], k + lw["k_bias"], v + lw["v_bias"]
    q, k = _rope(q, theta), _rope(k, theta)
    att = _attention(q, k, v, mm).reshape(t, nh * hd)
    h = h + mm(att, lw["o"].reshape(nh * hd, d))
    x = _rmsnorm(h, lw["post_norm"], eps)
    act = jax.nn.silu(mm(x, lw["gate"])) * mm(x, lw["up"])
    return h + mm(act, lw["down"])


def reference_logits(config: dict, mm, g: dict, h):
    """Logits of the float32 states h [T, d]: the tied LM head."""
    x = _rmsnorm(h, g["final_norm"], config["rms_norm_eps"])
    return mm(x, g["embed"].T)


# the work counts

def fc_shapes(config: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of the seven FC projections of one layer, in the order
    the program runs them."""
    m = dims(config)
    d, nh, nkv, hd, f = m["d"], m["nh"], m["nkv"], m["hd"], m["f"]
    return [("q", d, nh * hd), ("k", d, nkv * hd), ("v", d, nkv * hd),
            ("o", nh * hd, d), ("gate", d, f), ("up", d, f), ("down", f, d)]


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
