"""The FC execution-path hook — where PAPI's scheduling decision lands.

Model code routes every FC projection (QKV, out-proj, FFN — the paper's "FC
kernels") through `papi_linear`.  A context-local variant selects the
execution path:

  "pu"  (default) — XLA dot_general onto the MXU: the compute-bound path.
  "pim"           — the weight-streaming `fc_gemv` Pallas kernel: the
                    memory-bound path (FC-PIM analogue).

The serving engine sets the variant per decode iteration from
`core.scheduler.PapiScheduler`; both paths are numerically interchangeable
(tested) so flipping is free.  Outside a `fc_variant(...)` context the hook
is the plain einsum — training and the dry-run lower the XLA path.

Mesh execution (§5.3: FC-PIM banks)
-----------------------------------
Under `distributed.sharding.axis_rules(serve_rules(), mesh)` the two paths
split the FC weight over the tensor axis (the mesh axis the rules map the
logical "ffn" dim onto):

  * "pu" stays a plain einsum — GSPMD partitions it from the weight/activation
    sharding constraints;
  * "pim" cannot be auto-partitioned (a Pallas kernel is opaque to GSPMD), so
    it is wrapped in `shard_map`: each mesh shard streams its *local* weight
    bank through `fc_gemv`, which is exactly the paper's one-FC-PIM-bank-per-
    channel layout.  Column-split weights (`tp="col"`: QKV, gate/up) shard the
    output dim — no collective; row-split weights (`tp="row"`: out-proj, down)
    shard the contraction dim and `psum` the partial products, the analogue of
    the PIM channels' reduction tree.

Call sites declare which dim carries the tensor split via ``tp``, plus the
*logical* bank dim (``bank``: "ffn" for MLP weights, "heads"/"kv_heads" for
attention projections) and its unit count (``units``: head count for the
flattened QKV/out weights).  The split only engages when the rule table
actually maps that logical dim onto a mesh axis AND the unit count divides
it — the exact conditions under which `filter_spec_for_shape` shards the
stored weight — so the kernel's bank layout always matches the weight's
resident sharding and no per-call resharding is provoked.  Everything else
falls back to the replicated kernel.
"""
from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import fc_tensor_axis

_state = threading.local()


def current_fc_variant() -> str:
    return getattr(_state, "variant", "pu")


def current_fc_interpret() -> bool | None:
    return getattr(_state, "interpret", None)


@contextlib.contextmanager
def fc_variant(variant: str, interpret: bool | None = None):
    assert variant in ("pu", "pim"), variant
    prev = current_fc_variant()
    prev_i = getattr(_state, "interpret", None)
    _state.variant = variant
    _state.interpret = interpret
    try:
        yield
    finally:
        _state.variant = prev
        _state.interpret = prev_i


def _pim_gemv(x2: jax.Array, w: jax.Array, tp: str | None, bank: str,
              units: int | None) -> jax.Array:
    """[m, K] @ [K, N] through fc_gemv, sharded one bank per mesh shard."""
    from repro.kernels.fc_gemv import fc_gemv

    interpret = getattr(_state, "interpret", None)
    mesh, axis = fc_tensor_axis(bank)
    k, n = w.shape
    if units is None:
        units = n if tp == "col" else k
    if mesh is not None and axis is not None and units % mesh.shape[axis] == 0:
        size = mesh.shape[axis]
        if tp == "col" and n % size == 0:
            # output-dim banks: every shard produces its own slice, no
            # collective (QKV / gate / up projections)
            return jax.shard_map(
                lambda xs, ws: fc_gemv(xs, ws, interpret=interpret),
                mesh=mesh, in_specs=(P(), P(None, axis)),
                out_specs=P(None, axis), check_vma=False,
            )(x2, w)
        if tp == "row" and k % size == 0:
            # contraction-dim banks: shards hold partial products, reduced
            # over the tensor axis (out-proj / down projections)
            def _row(xs, ws):
                return jax.lax.psum(fc_gemv(xs, ws, interpret=interpret),
                                    axis)
            return jax.shard_map(
                _row, mesh=mesh, in_specs=(P(None, axis), P(axis, None)),
                out_specs=P(), check_vma=False,
            )(x2, w)
    return fc_gemv(x2, w, interpret=interpret)


def papi_linear(x: jax.Array, w: jax.Array, *, tp: str | None = None,
                bank: str = "ffn", units: int | None = None) -> jax.Array:
    """x: [..., K] @ w: [K, N] through the scheduled FC path.

    ``tp`` declares which weight dim carries the tensor-parallel split under
    a mesh: "col" (N is the sharded bank dim), "row" (K is; partials are
    psum-reduced), or None (always replicated); ``bank``/``units`` name the
    logical dim behind that split and its unit count so the split engages
    exactly when the stored weight is sharded (module docstring).  All
    ignored outside a mesh context.  Block sizes are left to `fc_gemv`'s
    auto-tuner, which sizes the tiles to the double-buffered VMEM budget
    instead of a fixed 512."""
    if current_fc_variant() == "pim":
        lead = x.shape[:-1]
        k, n = w.shape
        m = 1
        for d in lead:
            m *= d
        out = _pim_gemv(x.reshape(m, k), w, tp, bank, units)
        return out.reshape(*lead, n)
    return jnp.einsum("...k,kn->...n", x, w)
