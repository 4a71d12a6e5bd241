"""Device milliseconds per step of the ops under the program's
``dasha.aggregate`` and ``dasha.server`` scopes: the mean of the messages
over the nodes, its addition to g (Alg. 1 line 14) and the server step
(line 4)."""

from bench.scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "dasha.aggregate", "dasha.server")
