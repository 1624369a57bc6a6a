"""Model step: model FLOPs of the tokens the traced window needed (bench/
work.py: 2 x matmul parameters per token, attention over its live context,
the LM head where logits are needed) over window seconds x chips x the
chip's peak bf16 rate."""
from bench import work
from bench.peaks import peaks
from bench.window import decode_tokens, prompts_finished


def read(ctx):
    if ctx.trace is None or ctx.device_kind is None:
        return None
    flops = sum(work.token_flops(ctx.config, p + j, logits=True)
                for p, j, _ in decode_tokens(ctx))
    flops += sum(work.prompt_flops(ctx.config, p)
                 for p, _ in prompts_finished(ctx))
    peak = peaks(ctx.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / (ctx.trace.window_s * ctx.chips * peak)
