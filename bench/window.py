"""What happened inside the measured window, for the per-layer readers.

A reader gets `ctx` with: config, cell, chips, device_kind (None in a
rehearsal), t0 / t_open / t_close (host clock), iters (the program's
IterStats of the window's iterations), routes (the FC route each of those
iterations ran on), rec (bench/run.py Recorder: due times, prompts, token
stamps and iterations, results), submitted (host time each request was
handed over) and trace (bench/trace_reduce.py TraceSummary, or None).
"""
from __future__ import annotations


def due_in_window(ctx) -> list[int]:
    return [i for i, d in ctx.rec.due.items()
            if ctx.t_open <= ctx.t0 + d < ctx.t_close]


def window_iterations(ctx) -> set[int]:
    return {st.iteration for st in ctx.iters}


def decode_tokens(ctx) -> list[tuple[int, int, int]]:
    """(prompt length, token index, iteration) of each token a decode step
    produced in the window's iterations (index 0 comes from the prompt's
    last chunk, not from a decode)."""
    its = window_iterations(ctx)
    out = []
    for i, iters in ctx.rec.iters.items():
        p = len(ctx.rec.prompt[i])
        out.extend((p, j, it) for j, it in enumerate(iters)
                   if j and it in its)
    return out


def prompts_finished(ctx) -> list[tuple[int, int]]:
    """(prompt length, iteration of the first token) of the requests whose
    first token came in the window's iterations: their prompts were (nearly
    all) computed in it.  A prompt of n windows ran its chunk waves on the
    n - 1 iterations up to that one."""
    its = window_iterations(ctx)
    return [(len(ctx.rec.prompt[i]), iters[0])
            for i, iters in ctx.rec.iters.items()
            if iters and iters[0] in its]
