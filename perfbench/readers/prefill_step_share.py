"""Engine steps of the window that ran a prefill (a request's first token
appeared in them) over all its engine steps, in %: how much of the gaps'
tail is a decode step waiting behind a prompt."""


def read(ctx, meta):
    steps = ctx.facts.get("steps")
    return 100.0 * ctx.facts["prefill_steps"] / steps if steps else None
