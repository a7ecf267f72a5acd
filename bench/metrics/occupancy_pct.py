"""Scheduler: share of the decode slot-steps the window executed that
carried a live request (``ServeReport`` slot-step counters)."""


def read(ctx):
    d = ctx["delta"]
    if not d["slot_steps"]:
        return None
    return 100.0 * (1.0 - d["wasted_slot_steps"] / d["slot_steps"])
