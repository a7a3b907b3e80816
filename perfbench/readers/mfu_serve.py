"""2 x matrix parameters touched per token x every prompt and generated
token processed, over the serving time of the window (of its part before
the profiler started, in a traced run) x the chip's published bf16 peak,
in %."""

from perfbench import rooflines


def read(ctx, meta):
    c = ctx.cell.config
    per_token = rooflines.lm_flops_per_token(
        int(c["hidden_size"]), int(c["num_hidden_layers"]), int(c["vocab_size"])
    )
    untraced = ctx.facts.get("untraced")  # a traced run: before the profiler started
    if untraced and untraced["tokens"]:
        tokens, seconds = untraced["tokens"], untraced["seconds"]
    else:
        tokens = ctx.facts["prompt_tokens"] + ctx.facts["output_tokens"]
        seconds = ctx.facts["serve_s"]
    if not tokens:
        return None
    return 100.0 * per_token * tokens / (seconds * ctx.peaks["flops_bf16"])
