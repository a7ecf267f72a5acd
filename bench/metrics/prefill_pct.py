"""Engine: share of the device's busy time in the window spent in the
engine's prefill programs (XLA modules named ``*prefill*``), from the
trace."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.busy_s <= 0:
        return None
    pre = sum(s for m, s in tr.module_s.items() if "prefill" in m)
    return 100.0 * pre / (tr.busy_s * tr.devices)
