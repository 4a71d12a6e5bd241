"""Backend compiles (``repro.analysis.recompile`` events) inside the
measured window."""


def read(ctx):
    return float(ctx["compiles_in_window"])
