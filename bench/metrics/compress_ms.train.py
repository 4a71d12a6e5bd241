"""Device milliseconds per step of the ops under the program's
``dasha.compress`` scope: the compression plan's mask draws and the cast of
each mask into the kernel's layout."""

from bench.scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "dasha.compress")
