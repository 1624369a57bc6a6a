"""Kernels: roofline share of `papi_paged_decode_attention` over the LIVE
context: the FLOPs and K/V bytes that the window's decode tokens and prompt
chunks (all but the first window of each prompt, which the admission
prefill computes without the kernel) need, not the blocks the kernel walks.
The least time of each iteration's call (one per layer) is the larger of
its summed FLOPs over peak and its summed bytes over bandwidth; their sum
over the window is divided by the summed device time of the kernel's
events in the trace."""
from collections import defaultdict

from bench import work
from bench.peaks import roofline_s
from bench.window import decode_tokens, prompts_finished, window_iterations

KERNEL = "papi_paged_decode_attention"


def read(ctx):
    if ctx.trace is None or ctx.device_kind is None:
        return None
    t = ctx.trace.kernel_seconds(KERNEL)
    if not t:
        return None
    per_iter = defaultdict(lambda: [0.0, 0.0])
    for p, j, it in decode_tokens(ctx):
        f, b = work.attention_work(ctx.config, p + j)
        per_iter[it][0] += f
        per_iter[it][1] += b
    chunk = ctx.cell["engine"]["prefill_len"]
    its = window_iterations(ctx)
    for p, last in prompts_finished(ctx):
        starts = list(range(chunk, p, chunk))
        for k, lo in enumerate(starts):
            it = last - (len(starts) - 1 - k)
            if it not in its:
                continue
            hi = min(lo + chunk, p)
            per_iter[it][0] += sum(work.attention_work(ctx.config, c + 1)[0]
                                   for c in range(lo, hi))
            per_iter[it][1] += work.attention_work(ctx.config, hi, hi - lo)[1]
    least = sum(roofline_s(f, b, ctx.device_kind)[0]
                for f, b in per_iter.values())
    return 100.0 * least / t
