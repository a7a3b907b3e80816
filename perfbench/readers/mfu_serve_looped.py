"""mfu.serve's share for a model whose layers run several times: operations
a token needs, from the function the configuration's `flops` entry names
(under perfbench/, as readers/mfu_train.py has it), x every prompt and
generated token processed, over the serving time of the window (of its part
before the profiler started, in a traced run) x the chip's published bf16
peak, in %. Tokens and seconds are counted as readers/mfu_serve.py counts
them."""

import importlib


def read(ctx, meta):
    spec = ctx.cell.config.get("flops")
    if not spec:
        return None
    module = spec.get("module", "perfbench.rooflines")
    if not module.startswith("perfbench."):
        raise ValueError(f"FLOP counts live under perfbench/, not in {module!r}")
    per_token = getattr(importlib.import_module(module), spec["function"])(**spec["args"])
    untraced = ctx.facts.get("untraced")  # a traced run: before the profiler started
    if untraced and untraced["tokens"]:
        tokens, seconds = untraced["tokens"], untraced["seconds"]
    else:
        tokens = ctx.facts.get("prompt_tokens", 0) + ctx.facts.get("output_tokens", 0)
        seconds = ctx.facts.get("serve_s")
    if not tokens or not seconds:
        return None
    return 100.0 * per_token * tokens / (seconds * ctx.peaks["flops_bf16"])
