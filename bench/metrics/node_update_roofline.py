"""Share of the HBM roofline that the fused node-update kernel reaches: the
bytes any implementation of the update must move in the traced window
(``bench/counts.py``) over the kernel's device time times the chips' HBM
bandwidth.  The time is the sum over the kernel's device events, found by
the instruction name Mosaic gives them (``dasha_update``,
``dasha_mvr_update``); a path that runs no such kernel reads nothing."""

from bench.trace import short_name

KERNELS = ("dasha_update", "dasha_mvr_update")


def read(ctx):
    tr = ctx["trace"]
    secs = tr.op_seconds(lambda n: short_name(n) in KERNELS)
    if secs <= 0:
        return None
    need = ctx["units"] * ctx["counts"]["update_bytes_per_unit"]
    return 100.0 * need / (secs * ctx["peaks"]["hbm_bytes_per_s"])
