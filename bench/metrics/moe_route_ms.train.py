"""Device milliseconds per step of the ops under the model's ``moe.route``
scope: the router, its top-k, the sort of the (token, choice) rows into
held-expert groups and the weighted combine, forward and backward."""

from bench.subscopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "moe.route")
