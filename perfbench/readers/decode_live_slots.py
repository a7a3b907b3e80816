"""Mean `slots` of the program's `serve.decode` flight spans (one a decode
step, around its dispatch and fetch) that ENDED in the counted window: the
batch over which a step's weight traffic is shared. The builder gives the
window's two edges on the ring's clock (`decode_window_ns`). Nothing to read
where the program records no such span, the ring dropped spans, or the
builder gave no window."""

from perfbench import spans

SPAN = "serve.decode"


def read(ctx, meta):
    edges = ctx.facts.get("decode_window_ns")
    if not edges:
        return None
    rows = spans.ring_rows()
    if rows is None:
        return None
    t0, t_end = edges
    slots = [
        (r[spans.ATTRS] or {}).get("slots") for r in rows
        if r[spans.NAME] == SPAN and t0 < r[spans.START] + r[spans.DUR] <= t_end
    ]
    slots = [s for s in slots if s is not None]
    return sum(slots) / len(slots) if slots else None
