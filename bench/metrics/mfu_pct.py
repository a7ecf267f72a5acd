"""Whole step: model FLOPs of the work the window completed (the unpadded
prompts it prefilled, each generated token at the context it attended;
the model module's ``prefill_flops`` and ``decode_flops``) over the
window's seconds, as a share of the chip's bf16 peak
(``bench/peaks.py``)."""


def read(ctx):
    if ctx["peaks"] is None or not ctx["work_flops"]:
        return None
    rate = ctx["work_flops"] / ctx["seconds"]
    return 100.0 * rate / (ctx["peaks"]["bf16_flops"] * ctx["chips"])
