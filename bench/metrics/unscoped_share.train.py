"""Share of the device's busy time in the traced window spent in ops under
no program scope (``bench/scopes.py``): what the per-scope metrics do not
see."""

from bench.scopes import UNSCOPED, live_split


def read(ctx):
    tr = ctx["trace"]
    split = live_split(tr)
    busy = tr.busy_s()
    if split is None or busy <= 0:
        return None
    return 100.0 * split.get(UNSCOPED, 0.0) / busy
