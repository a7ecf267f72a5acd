"""Kernel: the Pallas ``expert_ffn`` kernel's decode calls, as a share of
their roofline.  The least time the chip could take is the larger of the
FLOPs over the bf16 peak and the bytes over the HBM bandwidth, for the
work the routed copies need (the model module's ``expert_ffn_work``,
given in ``ctx``: every row of the batch routed to ``top_k`` experts,
each hit expert's weights read once, each copy's row in and out); the
time is the kernel's device time in the trace, outside the prefill
programs.  Capacity rows and padding never enter the work."""


def read(ctx):
    tr, pk = ctx["trace"], ctx["peaks"]
    if tr is None or pk is None:
        return None
    name = ctx["kernel"]
    secs = sum(s for (k, m), s in tr.kernel_s_by_module.items()
               if k == name and "prefill" not in m)
    calls = sum(n for (k, m), n in tr.kernel_calls_by_module.items()
                if k == name and "prefill" not in m)
    if not calls or secs <= 0:
        return None
    copies = ctx["batch"] * ctx["top_k"]
    fl, by = ctx["expert_ffn_work"](ctx["dims"], copies,
                                    min(ctx["experts"], copies))
    t_min = calls * max(fl / pk["bf16_flops"], by / pk["hbm_bytes_per_s"])
    return 100.0 * t_min / secs
