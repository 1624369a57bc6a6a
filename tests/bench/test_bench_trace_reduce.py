"""bench/trace_reduce.py on a small trace recorded on the chip: a few
steps of `qwen2-0.5b.creative` with `--trace 1` (TPU v5 lite, one chip),
committed under bench/testdata, so that the reduction is checked without
a chip."""
import pytest

from tiny_cell import ROOT
from bench import trace_reduce

TRACE = ROOT / "bench/testdata/qwen2-0.5b.creative.xplane.pb.gz"
LAYERS = 24


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce_file(TRACE)


def test_busy_and_idle_share(summary):
    assert summary.chips == 1
    assert 0 < summary.busy_s < summary.window_s
    idle = sum(s for _, s in summary.gaps)
    # the gaps are exactly the window less the union of the ops
    assert idle + summary.busy_s == pytest.approx(summary.window_s, rel=1e-6)


def test_kernel_times(summary):
    fc = summary.kernel_seconds("papi_fc_gemv")
    attn = summary.kernel_seconds("papi_paged_decode_attention")
    assert 0 < fc < summary.busy_s and 0 < attn < summary.busy_s
    # one paged-attention call per layer per step, seven FC calls per layer
    # per step on the pim route (every step of this trace ran on it)
    n_attn = summary.kernel_count("papi_paged_decode_attention")
    n_fc = summary.kernel_count("papi_fc_gemv")
    assert n_attn % LAYERS == 0 and n_attn > 0
    assert n_fc == 7 * n_attn


def test_kernel_calls_carry_their_shapes(summary):
    """Each fc_gemv call's shapes come from its trace event: the decode
    steps of this trace multiply 32 rows, its two mixed waves 32 x 64, by
    the seven projections of a layer, whose operands XLA had staged in
    fast memory (layout S(1))."""
    import types

    from bench.metrics import fc_gemv_roofline

    calls = summary.kernel_calls["papi_fc_gemv"]
    assert len(calls) == summary.kernel_count("papi_fc_gemv")
    kn = {(ins[1][1][0], ins[1][1][1]) for _, _, ins in calls}
    assert kn == {(896, 896), (896, 128), (896, 4864), (4864, 896)}
    rows = [out[1][0] for _, out, _ in calls]
    assert rows.count(32) == 7 * 24 * 7 and rows.count(2048) == 2 * 7 * 24
    assert all(t[2] for _, out, ins in calls for t in [out] + ins)
    ctx = types.SimpleNamespace(trace=summary, device_kind="TPU v5 lite")
    assert 0 < fc_gemv_roofline.read(ctx) <= 100


def test_staged_weights_are_charged_to_the_fc_kernel(summary):
    """Each layer's gate, up and down weights (896 x 4864 elements) reach
    fast memory by an op of their own, one for each fc_gemv call that reads
    them; their time counts against the kernel, so the share is lower than
    over the kernel's own time."""
    import dataclasses
    import types

    from bench.metrics import fc_gemv_roofline

    calls = summary.kernel_calls["papi_fc_gemv"]
    mlp = [c for c in calls if c[2][1][1] in ((896, 4864), (4864, 896))]
    assert len(summary.fast_slices[("bf16", 896 * 4864)]) == len(mlp) > 0
    ctx = types.SimpleNamespace(trace=summary, device_kind="TPU v5 lite")
    unstaged = types.SimpleNamespace(
        trace=dataclasses.replace(summary, fast_slices={}),
        device_kind="TPU v5 lite")
    assert 0 < fc_gemv_roofline.read(ctx) < fc_gemv_roofline.read(unstaged)


def test_gap_attribution(summary):
    labels = {label for label, _ in summary.gaps}
    assert labels <= set(trace_reduce.HOST_SPANS) | {"outside harness spans"}
    assert "serve.next" in labels
    b = summary.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][1] >= b["device_ops"][-1][1]
