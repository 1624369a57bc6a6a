"""The system under test, built from a cell's files: the one place where the
benchmark calls into the program to make an engine.

The program's model config and parameter tree come from the configuration's
family (bench/families/<family>.py); this module checks the tree against
the program's own shapes.  It passes the deployment's sizes (slots, page
size, pool pages, block-table width, prefill window) and PAPI's path: the
paged KV cache and the Attn-PIM kernel.  It leaves the FC route policy
(`alpha`) to the program.  The end of sequence is set to an id outside the
vocabulary, so that every request runs to the output length its traffic
drew for it: with random weights any in-vocabulary id would end requests at
random.
"""
from __future__ import annotations

import jax

from bench import spec

NO_EOS = -1


def model_config(config: dict):
    """The program's ModelConfig for a configuration file."""
    return spec.family(config).model_config(config)


def program_params(config: dict, cfg, w: dict):
    """The benchmark's weights (bench/weights.py) in the program's tree."""
    from repro.models import param_shapes

    params = spec.family(config).program_params(cfg, w)
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
