"""Model FLOP utilisation of the whole step: the model FLOPs of the steps
the traced window completed (``bench/counts.py``) over the window's length,
the chips and the chips' bf16 peak."""


def read(ctx):
    tr = ctx["trace"]
    if tr.window_s <= 0:
        return None
    flops = ctx["units"] * ctx["counts"]["flops_per_unit"]
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / (tr.window_s * peak)
