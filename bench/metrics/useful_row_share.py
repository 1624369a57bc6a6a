"""Model step: token rows the window needed over the rows its programs
computed.  Needed: one row per decode token, and the prompt of every
request whose first token came in the window.  Computed: a mixed wave and
an admission prefill each compute max_slots x prefill_len rows, a decode
step max_slots rows."""
from bench.window import decode_tokens, prompts_finished


def read(ctx):
    e = ctx.cell["engine"]
    s, p = e["max_slots"], e["prefill_len"]
    computed = 0
    for st in ctx.iters:
        if st.prefill_slots:
            computed += s * p
        elif st.decode_slots:
            computed += s
        if st.admitted:
            computed += s * p
    if not computed:
        return None
    needed = len(decode_tokens(ctx)) + sum(p for p, _ in prompts_finished(ctx))
    return 100.0 * needed / computed
