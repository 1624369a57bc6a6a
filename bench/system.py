"""The system under test, built from a cell's files: the one place where the
benchmark calls into the program to make an engine.

It passes the deployment's sizes (slots, page size, pool pages, block-table
width, prefill window) and PAPI's path: the paged KV cache and the Attn-PIM
kernel.  It leaves the FC route policy (`alpha`) to the program.  The end of
sequence is set to an id outside the vocabulary, so that every request runs
to the output length its traffic drew for it: with random weights any
in-vocabulary id would end requests at random.
"""
from __future__ import annotations

import jax

NO_EOS = -1


def model_config(config: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig
    from bench import weights as W

    m = W.dims(config)
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


def program_params(cfg, w: dict):
    """The benchmark's weights (bench/weights.py) in the program's tree."""
    from repro.models import param_shapes

    lw = w["layers"]
    attn = {"w_q": lw["q"], "w_k": lw["k"], "w_v": lw["v"], "w_o": lw["o"]}
    if "q_bias" in lw:
        attn.update(b_q=lw["q_bias"], b_k=lw["k_bias"], b_v=lw["v_bias"])
    params = {
        "embed": {"w": w["embed"]},
        "final_norm": {"w": w["final_norm"]},
        "layers": {"norm1": lw["input_norm"], "attn": attn,
                   "norm2": lw["post_norm"],
                   "mlp": {"w_gate": lw["gate"], "w_up": lw["up"],
                           "w_down": lw["down"]}},
    }
    want = param_shapes(cfg)
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("benchmark weights do not match the program's "
                         f"parameter tree: {want} vs {got}")
    return params


def build_engine(cfg, params, cell: dict):
    """PapiEngine at the cell's deployment sizes, on PAPI's path."""
    from repro.serving import PapiEngine

    e = cell["engine"]
    return PapiEngine(
        cfg, params,
        max_slots=e["max_slots"], prefill_len=e["prefill_len"],
        kv_layout="paged", attn_pim=True, page_size=e["page_size"],
        num_pages=e["num_pages"], max_blocks=e["max_blocks"],
        eos_token=NO_EOS)
