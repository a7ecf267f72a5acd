"""MoE stage: share of the decode capacity-buffer rows (experts x
capacity, per MoE layer per tick) that carry no routed copy of a live
request's token.  Live slot-steps times experts-per-token are the copies;
ticks are slot-steps over the batch."""


def read(ctx):
    d = ctx["delta"]
    if not d["slot_steps"]:
        return None
    live = d["slot_steps"] - d["wasted_slot_steps"]
    ticks = d["slot_steps"] / ctx["batch"]
    rows = ctx["experts"] * ctx["capacity"] * ticks
    return 100.0 * (1.0 - live * ctx["top_k"] / rows)
