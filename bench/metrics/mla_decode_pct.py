"""Attention: share of the device's busy time in the window spent in the
Pallas ``mla_decode`` kernel (absorbed latent-attention decode) outside
the engine's prefill programs, from the trace.  None where the trace holds
no such kernel."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.busy_s <= 0:
        return None
    secs = sum(s for k, s in tr.op_s.items()
               if k.rsplit(":", 1)[-1] == "mla_decode"
               and "prefill" not in k.rsplit(":", 1)[0])
    if secs <= 0:
        return None
    return 100.0 * secs / (tr.busy_s * tr.devices)
