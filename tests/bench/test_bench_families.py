"""A configuration names its model family, and bench/families/<family>.py
holds the family's weights, reference and work counts.

The dense family gives the numbers that the harness gave before families
existed, to the bit: the golden digests and counts below were computed with
the same code on the tree before the family split (weights by sha256 of
their bf16 bytes, reference gaps by sha256 of their float32 bytes, on the
CPU).  A family that exists only in a test runs through the harness's
weights, reference and work paths with no edit to a file under bench/."""
import hashlib
import json

import jax.numpy as jnp
import numpy as np
import pytest

from tiny_cell import CONFIG, ROOT, make_bench
from bench import reference, spec, weights, work

SMALL = {
    "name": "small", "family": "dense", "hidden_size": 128,
    "intermediate_size": 256, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 64, "hidden_act": "silu", "rms_norm_eps": 1e-05,
    "rope_theta": 10000.0, "tie_word_embeddings": True,
    "attention_bias": False, "torch_dtype": "bfloat16",
    "reduced": ["num_hidden_layers"], "published": {"num_hidden_layers": 12}}
CONFIGS = {"small": SMALL, "tiny": CONFIG}
SEED = 2**33 + 5

GOLDEN = {
    "small": {
        "model": "ad3bbf07c9dc025e", "layer1": "d8603fed84e88180",
        "globals": "6c8cd10ed90bf472", "gaps": "602cac0f084ad062",
        "control_gaps": "9b7341bed2b69112",
        "widest": [0.7502831816673279, 0.8799266815185547],
        "control_widest": [0.07352712750434875, 0.08390763401985168],
        "fc_shapes": [["q", 128, 128], ["k", 128, 64], ["v", 128, 64],
                      ["o", 128, 128], ["gate", 128, 256], ["up", 128, 256],
                      ["down", 256, 128]],
        "layer_matmul_params": 147456,
        "token_flops": [886272.0, 902656.0, 1038336.0, 1054720.0],
        "prompt_flops": [2679808.0, 65764864.0],
        "attention_work": [[1536000.0, 769536], [9830400.0, 175104]]},
    "tiny": {
        "model": "418054cc2c265900", "layer1": "099b5491f9eca195",
        "globals": "54a99ab23448dedd", "gaps": "e05053d550e3cd1e",
        "control_gaps": "bb6cc1798aafe569",
        "widest": [0.78624427318573, 0.9011908173561096],
        "control_widest": [0.13231173157691956, 0.06939539313316345],
        "fc_shapes": [["q", 64, 64], ["k", 64, 32], ["v", 64, 32],
                      ["o", 64, 64], ["gate", 64, 128], ["up", 64, 128],
                      ["down", 128, 64]],
        "layer_matmul_params": 36864,
        "token_flops": [147968.0, 213504.0, 198656.0, 264192.0],
        "prompt_flops": [510976.0, 11659776.0],
        "attention_work": [[512000.0, 256512], [3276800.0, 58368]]},
}


def _digest(arrays: dict) -> str:
    h = hashlib.sha256()
    for name in sorted(arrays):
        a = np.asarray(arrays[name])
        h.update(name.encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).view(np.uint8).tobytes())
    return h.hexdigest()[:16]


def _sample(config):
    rng = np.random.default_rng(3)
    v = config["vocab_size"]
    lens = [(20, 12), (37, 30)]
    return ([rng.integers(0, v, p).tolist() for p, _ in lens],
            [rng.integers(0, v, s).tolist() for _, s in lens])


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_dense_weights_match_golden(name):
    config, want = CONFIGS[name], GOLDEN[name]
    w = weights.model(config, SEED)
    flat = dict({k: v for k, v in w.items() if k != "layers"},
                **{"layers." + k: v for k, v in w["layers"].items()})
    assert _digest(flat) == want["model"]
    assert _digest(weights.layer(config, SEED, 1)) == want["layer1"]
    assert _digest(weights.globals_(config, SEED)) == want["globals"]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_dense_reference_gaps_match_golden(name):
    config, want = CONFIGS[name], GOLDEN[name]
    got, alt = reference.served_gaps(config, SEED, *_sample(config),
                                     control=True)
    assert _digest({str(i): g for i, g in enumerate(got)}) == want["gaps"]
    assert _digest({str(i): a for i, a in enumerate(alt)}) == \
        want["control_gaps"]
    assert [float(g.max()) for g in got] == want["widest"]
    assert [float(a.max()) for a in alt] == want["control_widest"]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_dense_work_counts_match_golden(name):
    config, want = CONFIGS[name], GOLDEN[name]
    assert [list(s) for s in work.fc_shapes(config)] == want["fc_shapes"]
    assert work.layer_matmul_params(config) == want["layer_matmul_params"]
    assert [work.token_flops(config, c, lg) for c in (1, 100)
            for lg in (False, True)] == want["token_flops"]
    assert [work.prompt_flops(config, n) for n in (3, 70)] == \
        want["prompt_flops"]
    assert [list(work.attention_work(config, 1000)),
            list(work.attention_work(config, 100, 64))] == \
        want["attention_work"]


# A family with an untied LM head, experts [E, d, f] that every token uses
# with equal weight, no attention, and layers of two kinds: even layers add
# the experts' output to the residual stream, odd layers subtract it.
TOY_FAMILY = '''
import jax.numpy as jnp

from bench.reference import _rmsnorm

E = 4


def _dims(config):
    return (config["hidden_size"], config["expert_size"],
            config["vocab_size"], config["num_hidden_layers"])


def layer_laws(config):
    d, f, _, _ = _dims(config)
    return {"norm": ((d,), "around_one", 0.05),
            "experts_up": ((E, d, f), "normal", d ** -0.5),
            "experts_down": ((E, f, d), "normal", f ** -0.5)}


def global_laws(config):
    d, _, v, _ = _dims(config)
    return {"embed": ((v, d), "normal", 1.0),
            "final_norm": ((d,), "around_one", 0.05),
            "lm_head": ((d, v), "normal", d ** -0.5)}


def reference_layer(config, mm, lw, h, l):
    x = _rmsnorm(h, lw["norm"], 1e-6)
    y = sum(mm(jnp.tanh(mm(x, lw["experts_up"][e])), lw["experts_down"][e])
            for e in range(E)) / E
    return h + jnp.where(l % 2 == 0, 1.0, -1.0) * y


def reference_logits(config, mm, g, h):
    return mm(_rmsnorm(h, g["final_norm"], 1e-6), g["lm_head"])


def fc_shapes(config):
    d, f, _, _ = _dims(config)
    return [("up", d, E * f), ("down", E * f, d)]


def attention_work(config, ctx, q_rows=1):
    return 0.0, 0.0


def token_flops(config, ctx, logits):
    d, f, v, layers = _dims(config)
    return 2.0 * layers * 2 * E * d * f + (2.0 * d * v if logits else 0.0)


def prompt_flops(config, prompt_len):
    return prompt_len * token_flops(config, 0, False) + \\
        token_flops(config, 0, True) - token_flops(config, 0, False)
'''
TOY = {"name": "toy", "family": "toy", "hidden_size": 32, "expert_size": 16,
       "num_hidden_layers": 3, "vocab_size": 64, "torch_dtype": "bfloat16",
       "reduced": []}


def _bench_with(tmp_path, config, family_source=None):
    """The tiny benchmark under tmp_path with its configuration replaced,
    and a family file added where given."""
    bench = make_bench(tmp_path)
    base = bench.parent / "bench"
    (base / "configs" / "tiny.json").write_text(json.dumps(config))
    if family_source is not None:
        (base / "families" / f"{config['family']}.py").write_text(
            family_source)
    return bench


def _toy_forward(config, g, layers, token, signs):
    """The toy family in numpy: the next token after `token`."""
    f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
    rms = lambda x, w: x / np.sqrt(np.mean(x * x) + 1e-6) * w  # noqa: E731
    h = f32(g["embed"])[token]
    for lw, sign in zip(layers, signs):
        x = rms(h, f32(lw["norm"]))
        up, down = f32(lw["experts_up"]), f32(lw["experts_down"])
        h = h + sign * sum(np.tanh(x @ up[e]) @ down[e]
                           for e in range(len(up))) / len(up)
    return int(np.argmax(rms(h, f32(g["final_norm"])) @ f32(g["lm_head"])))


def test_toy_family_runs_through_weights_reference_and_work(tmp_path):
    bench = _bench_with(tmp_path, TOY, TOY_FAMILY)
    config = spec.load_cell("tiny.short", bench).config
    seed, n_layers = 2**31 + 9, TOY["num_hidden_layers"]

    w = weights.model(config, seed)
    g = weights.globals_(config, seed)
    assert set(g) == {"embed", "final_norm", "lm_head"}
    assert w["layers"]["experts_up"].shape == (n_layers, 4, 32, 16)
    layers = [weights.layer(config, seed, l) for l in range(n_layers)]
    for l, lw in enumerate(layers):
        for name, x in lw.items():
            assert np.array_equal(np.asarray(x),
                                  np.asarray(w["layers"][name][l])), name
    assert np.array_equal(np.asarray(g["lm_head"]), np.asarray(w["lm_head"]))

    # the reference gives each layer its own index: tokens served greedily
    # by the toy's equations lie at the reference's best, and tokens served
    # as if every layer were an even one do not
    def serve(prompt, signs, n=12):
        out = [_toy_forward(config, g, layers, prompt[-1], signs)]
        while len(out) < n:
            out.append(_toy_forward(config, g, layers, out[-1], signs))
        return out

    prompts = [[1, 2, 3], [40, 41]]
    right = [serve(p, [1, -1, 1]) for p in prompts]
    blind = [serve(p, [1, 1, 1]) for p in prompts]
    assert right != blind
    got, _ = reference.served_gaps(config, seed, prompts, right)
    assert [len(x) for x in got] == [12, 12]
    assert max(float(x.max()) for x in got) < 1e-3
    got, _ = reference.served_gaps(config, seed, prompts, blind)
    assert max(float(x.max()) for x in got) > 1e-2

    assert work.token_flops(config, 10, logits=True) == \
        2.0 * 3 * 2 * 4 * 32 * 16 + 2.0 * 32 * 64
    assert work.layer_matmul_params(config) == 2 * 4 * 32 * 16
    assert work.attention_work(config, 100) == (0.0, 0.0)
    assert not (ROOT / "bench" / "families" / "toy.py").exists()


def test_layer_index_reaches_the_family(tmp_path):
    """One compiled reference layer serves every layer: the index is an
    argument, and the toy's odd layers undo what its even layers add."""
    config = spec.load_cell(
        "tiny.short", _bench_with(tmp_path, TOY, TOY_FAMILY)).config
    layer = reference._fns(weights.freeze(config), "f32")[1]
    lw = weights.layer(config, 3, 0)
    h = jnp.asarray(np.random.default_rng(1).normal(size=(8, 32)),
                    jnp.float32)
    up, down = (np.asarray(layer(lw, h, jnp.int32(l))) for l in (0, 1))
    assert not np.allclose(up, down)
    np.testing.assert_allclose(up + down, 2 * np.asarray(h), rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("family", [None, "no_such_family"])
def test_config_must_name_a_known_family(tmp_path, family):
    config = {k: v for k, v in CONFIG.items() if k != "family"}
    if family is not None:
        config["family"] = family
    with pytest.raises(spec.SpecError, match="family"):
        spec.load_cell("tiny.short", _bench_with(tmp_path, config))
    with pytest.raises(spec.SpecError, match="family"):
        work.token_flops(config, 10, logits=True)

