"""Median, in ms, of the benchmark's own spans around session.step() calls
that decoded and ran no prefill (a median is allowed here: per-layer)."""

import statistics


def read(ctx, meta):
    spans = ctx.facts.get("decode_only_step_s")
    return 1e3 * statistics.median(spans) if spans else None
