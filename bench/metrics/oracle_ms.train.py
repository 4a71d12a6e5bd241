"""Device milliseconds per step of the ops under the program's
``dasha.oracle`` scope: the nodes' forward/backward passes (Alg. 1 line 8;
both gradients for MVR)."""

from bench.scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "dasha.oracle")
