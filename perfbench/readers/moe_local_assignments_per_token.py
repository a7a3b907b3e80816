"""Router assignments a token leaves on THIS rank's experts, mean over the
MoE layers: `num_experts_per_tok` x (landed here) / (landed here + went to an
absent expert), from the program's device counters as the builder read them
around the drive (`moe_counted`). Nothing where the program has no such
counter."""


def read(ctx, meta):
    counted = (ctx.facts.get("moe_counted") or {}).get("moe_assignments")
    if not counted:
        return None
    here = sum(row[0] for row in counted)
    everywhere = sum(row[0] + row[1] for row in counted)
    if not everywhere:
        return None
    return float(ctx.cell.config["num_experts_per_tok"]) * here / everywhere
