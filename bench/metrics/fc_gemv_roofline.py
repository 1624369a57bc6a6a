"""Kernels: roofline share of `papi_fc_gemv`, call by call from the device
trace.  Each call is an [M, K] x [K, N] product (shapes from its trace
event): 2 M K N FLOPs, and the bytes the algorithm needs, the weights read
once from HBM and the activations read and written once (bench/work.py).
XLA stages a layer's weights into the chip's fast on-core memory with ops
of its own (a slice of the stacked [layers, ...] array, layout `S(1)`)
before the kernel reads them there; the weights' trip from HBM then lies
in those ops.  So a call whose weights were staged is charged its own
device time plus the mean time of the staging ops of a slice of that size.
The share is the sum of the calls' least times on the chip (the larger of
FLOPs over peak and bytes over bandwidth) over the sum of those times."""
from statistics import fmean

from bench import work
from bench.peaks import roofline_s

KERNEL = "papi_fc_gemv"


def read(ctx):
    if ctx.trace is None or ctx.device_kind is None:
        return None
    calls = ctx.trace.kernel_calls.get(KERNEL)
    if not calls:
        return None
    staging = {key: fmean(s) for key, s in ctx.trace.fast_slices.items()}
    least = spent = 0.0
    for seconds, out, ins in calls:
        (m, n), k = out[1], ins[0][1][1]
        least += roofline_s(*work.fc_call(m, k, n), ctx.device_kind)[0]
        dtype, shape, fast = ins[1]
        spent += seconds
        if fast:
            spent += staging.get((dtype, k * n), 0.0)
    return 100.0 * least / spent
