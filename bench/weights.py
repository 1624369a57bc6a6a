"""Seeded random weights of a model, made on the device.

The benchmark makes the weights itself, so that the plain reference can
make the same numbers again, layer by layer, without taking anything from
the program under test.  Every tensor's key is derived from the seed, the
layer index and the tensor's name alone, so `layer(cfg, seed, l)` gives
bit for bit the slice `l` of `model(cfg, seed)["layers"]`.

The names, shapes, laws and scales of the tensors come from the
configuration's family (bench/families/<family>.py): `layer_laws` for the
tensors of one layer, `global_laws` for those outside the layers.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from bench import spec

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def serve_dtype(config: dict):
    return _DTYPES[config["torch_dtype"]]


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


def _tensors(laws: dict, key, dtype) -> dict:
    return {name: _tensor(key, name, shape, law, scale, dtype)
            for name, (shape, law, scale) in laws.items()}


def _layer(config: dict, key, l, dtype) -> dict:
    lk = jax.random.fold_in(key, 1000 + l)
    return _tensors(spec.family(config).layer_laws(config), lk, dtype)


def _globals(config: dict, key, dtype) -> dict:
    return _tensors(spec.family(config).global_laws(config), key, dtype)


def model(config: dict, seed: int) -> dict:
    """All weights in the served dtype, in one jitted call on the default
    device: the family's global tensors by name, and "layers": {name:
    [L, ...]}."""
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
