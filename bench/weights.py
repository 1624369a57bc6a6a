"""Seeded random weights of a dense decoder, made on the device.

The benchmark makes the weights itself, so that the plain reference can
make the same numbers again, layer by layer, without taking anything from
the program under test.  Every tensor's key is derived from the seed, the
layer index and the tensor's name alone, so `layer(cfg, seed, l)` gives
bit for bit the slice `l` of `model(cfg, seed)["layers"]`.

Names and shapes (d model width, nh/nkv query/KV heads of hd, f MLP width,
V vocabulary):

    embed [V, d] (tied LM head), final_norm [d]
    per layer: input_norm [d], q [d, nh, hd], k/v [d, nkv, hd],
               q_bias [nh, hd], k_bias/v_bias [nkv, hd] (with attention
               bias), o [nh, hd, d], post_norm [d], gate/up [d, f],
               down [f, d]
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def dims(config: dict) -> dict:
    d = config["hidden_size"]
    nh = config["num_attention_heads"]
    return dict(d=d, nh=nh, nkv=config["num_key_value_heads"],
                hd=config.get("head_dim") or d // nh,
                f=config["intermediate_size"], V=config["vocab_size"],
                L=config["num_hidden_layers"])


def serve_dtype(config: dict):
    return _DTYPES[config["torch_dtype"]]


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


def seed_key(seed: int) -> jax.Array:
    """A key from any seed below 2**64."""
    key = jax.random.key(0)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed & 0xFFFFFFFF)


def _tensor(key, name: str, shape, law: str, scale: float, dtype):
    k = jax.random.fold_in(key, sum(ord(c) << (8 * i)
                                    for i, c in enumerate(name[:4])))
    k = jax.random.fold_in(k, len(name))
    x = jax.random.normal(k, shape, jnp.float32) * scale
    if law == "around_one":
        x = x + 1.0
    return x.astype(dtype)


def _layer(config: dict, key, l, dtype) -> dict:
    lk = jax.random.fold_in(key, 1000 + l)
    return {name: _tensor(lk, name, shape, law, scale, dtype)
            for name, (shape, law, scale) in layer_laws(config).items()}


def _globals(config: dict, key, dtype) -> dict:
    m = dims(config)
    return {"embed": _tensor(key, "embed", (m["V"], m["d"]), "normal", 0.02,
                             dtype),
            "final_norm": _tensor(key, "final_norm", (m["d"],), "around_one",
                                  0.05, dtype)}


def model(config: dict, seed: int) -> dict:
    """All weights in the served dtype, in one jitted call on the default
    device: {"embed", "final_norm", "layers": {name: [L, ...]}}."""
    dtype = serve_dtype(config)
    @jax.jit
    def make(key):
        cfg = config
        layers = jax.vmap(lambda l: _layer(cfg, key, l, dtype))(
            jnp.arange(cfg["num_hidden_layers"]))
        return dict(_globals(cfg, key, dtype), layers=layers)

    return make(seed_key(seed))


@functools.lru_cache(maxsize=8)
def _layer_fn(frozen: str):
    config = thaw(frozen)
    dtype = serve_dtype(config)
    return jax.jit(lambda key, l: _layer(config, key, l, dtype))


def freeze(config: dict) -> str:
    """The configuration as a hashable key for the caches of jitted
    functions; `thaw` gives it back."""
    return json.dumps(config, sort_keys=True)


def thaw(frozen: str) -> dict:
    return json.loads(frozen)


def layer(config: dict, seed: int, l: int) -> dict:
    """Layer `l`'s weights in the served dtype: the same numbers as slice
    `l` of `model(config, seed)["layers"]`."""
    fn = _layer_fn(freeze(config))
    return fn(seed_key(seed), jnp.int32(l))


def globals_(config: dict, seed: int) -> dict:
    return jax.jit(lambda key: _globals(config, key, serve_dtype(config)))(
        seed_key(seed))
