"""Device milliseconds per step of the ops under the model's
``moe.experts`` scope: the held experts' grouped products, forward and
backward."""

from bench.subscopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "moe.experts")
