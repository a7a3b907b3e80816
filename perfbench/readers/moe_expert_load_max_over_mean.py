"""The busiest held expert's tokens over the mean held expert's, a MoE layer,
averaged over the layers, over the whole drive: the imbalance the grouped
product absorbs (1 is an even router). From the program's device counters as
the builder read them around the drive (`moe_counted`); nothing where the
program has no such counter."""


def read(ctx, meta):
    counted = (ctx.facts.get("moe_counted") or {}).get("moe_expert_tokens")
    rows = [row for row in counted or () if sum(row)]
    if not rows:
        return None
    return sum(max(row) * len(row) / sum(row) for row in rows) / len(rows)
