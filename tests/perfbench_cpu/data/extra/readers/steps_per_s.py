def read(ctx, meta):
    return ctx.facts["steps"] / ctx.facts["window_s"]
