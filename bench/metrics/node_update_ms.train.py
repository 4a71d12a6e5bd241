"""Device milliseconds per step of the ops under the program's
``dasha.node_update`` scope and no scope inside it: the fused update kernel
and the copies that pack its operands into lanes and unpack its results
(Alg. 1 lines 9-10)."""

from bench.scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "dasha.node_update")
