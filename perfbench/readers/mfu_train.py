"""Model FLOPs per item (recomputed work not counted) x items/s/chip of
this run's window (of its part before the profiler started, in a traced
run), over the chip's published bf16 peak, in %. The
configuration names the function that counts its model's FLOPs: in
perfbench/rooflines.py, or in a module of its own under perfbench/ that a
later PR adds ("flops": {"module": "perfbench.<file>", ...})."""

import importlib


def read(ctx, meta):
    spec = ctx.cell.config["flops"]
    module = spec.get("module", "perfbench.rooflines")
    if not module.startswith("perfbench."):
        raise ValueError(f"FLOP counts live under perfbench/, not in {module!r}")
    per_item = getattr(importlib.import_module(module), spec["function"])(**spec["args"])
    rate = ctx.facts.get("throughput_untraced", ctx.e2e["throughput"])
    return 100.0 * per_item * rate / ctx.peaks["flops_bf16"]
