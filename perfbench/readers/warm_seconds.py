"""compile_s: host clock around warm-up (loading or compiling every program
the window uses, and the first steps). The count of programs asked of the
compile cache, and compilations inside the window, are on info lines."""


def read(ctx, meta):
    return ctx.facts.get("warm_s")
