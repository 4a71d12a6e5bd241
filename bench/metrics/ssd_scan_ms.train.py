"""Device milliseconds per step of the ops under the model's ``ssd.scan``
scope: the Mamba-2 layers' chunked SSD, forward and backward."""

from bench.subscopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "ssd.scan")
