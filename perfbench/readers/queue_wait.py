"""95th percentile, in ms, of the time from a request's DUE time to the
engine's own stamp of its admission to a slot (the scheduler's t_started):
the wait for the generator to come round, for a slot and for pages, without
the prefill that follows."""

from perfbench import arith


def read(ctx, meta):
    waits = ctx.facts.get("queue_wait_s")
    return 1e3 * arith.percentile(waits, 95) if waits else None
