"""Bytes a decode step MUST move over the HBM peak, over the measured
decode-only step, in %: the share that means something on a step that
bandwidth bounds. Bytes (hybrid_moe_counts.decode_step_bytes, the same work
whatever implements it): every held parameter once, the recurrent state of
every live slot read and written, the K/V of the live contexts read. Live
slots are the mean `slots` of the program's `serve.decode` spans that ended
in the counted window (readers/decode_live_slots.py's reading); the contexts
are the traced steps' where a run has them, and left out where not (a
hundredth of the bytes); the step is the median of the benchmark's own spans
around session.step() calls that decoded and ran no prefill
(readers/decode_step.py's). Nothing to read where the program records no
such span or the configuration is no such model."""

import statistics

from perfbench import hybrid_moe_counts
from perfbench.readers import decode_live_slots


def read(ctx, meta):
    c = ctx.cell.config
    steps = ctx.facts.get("decode_only_step_s")
    if not steps or "experts_held" not in c:
        return None
    slots = decode_live_slots.read(ctx, meta)
    if slots is None:
        return None
    traced = [sum(n + 1 for n in step) for step in ctx.facts.get("traced_contexts") or () if step]
    context_tokens = statistics.fmean(traced) if traced else 0.0
    least = hybrid_moe_counts.decode_step_bytes(c, slots, context_tokens) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / statistics.median(steps)
