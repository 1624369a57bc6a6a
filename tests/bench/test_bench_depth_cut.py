"""A configuration cut in depth (`reduced: ["num_hidden_layers"]`, the
published depth under `published`) runs the first layers of the whole
model: every width as published, the same per-layer parameter shapes, the
same layer weights and the same layer function.  Checked on a small dense
configuration, since the weight law does not depend on the widths."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, system, weights

WHOLE = {
    "name": "small", "family": "dense", "hidden_size": 128,
    "intermediate_size": 256, "num_hidden_layers": 12,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 64,
    "hidden_act": "silu", "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
    "tie_word_embeddings": True, "attention_bias": True,
    "torch_dtype": "bfloat16", "reduced": []}
CUT = dict(WHOLE, num_hidden_layers=6, reduced=["num_hidden_layers"],
           published={"num_hidden_layers": 12})


def test_cut_keeps_every_width():
    cut, whole = system.model_config(CUT), system.model_config(WHOLE)
    assert (cut.num_layers, whole.num_layers) == (6, 12)
    assert dataclasses.replace(cut, num_layers=12) == whole


def test_per_layer_parameter_shapes():
    from repro.models import param_shapes

    cut = param_shapes(system.model_config(CUT))["layers"]
    whole = param_shapes(system.model_config(WHOLE))["layers"]
    for a, b in zip(jax.tree.leaves(cut), jax.tree.leaves(whole)):
        assert a.shape[0] == 6 and b.shape[0] == 12
        assert a.shape[1:] == b.shape[1:] and a.dtype == b.dtype


def test_first_layers_are_the_whole_models():
    """Layers 0..5 of the cut model have the whole model's weights, bit for
    bit, and the reference's layer function maps a state to the same next
    state."""
    h = jnp.asarray(np.random.default_rng(0).normal(size=(64, 128)),
                    jnp.float32)
    fa = reference._fns(weights.freeze(CUT), "f32")[1]
    fb = reference._fns(weights.freeze(WHOLE), "f32")[1]
    for l in (0, 3, 5):
        a = weights.layer(CUT, 5, l)
        b = weights.layer(WHOLE, 5, l)
        assert a.keys() == b.keys()
        for k in a:
            assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k
        assert np.array_equal(np.asarray(fa(a, h, l)),
                              np.asarray(fb(b, h, l)))
    stacked = weights.model(CUT, 5)["layers"]
    assert stacked["q"].shape[0] == 6
    assert np.array_equal(np.asarray(stacked["down"][5]),
                          np.asarray(weights.layer(WHOLE, 5, 5)["down"]))
