"""Share of the bf16 peak that the held experts' grouped matmul kernels
reach at the expected load: the FLOPs of the rows that a uniform router
would send to the held experts (k of the published experts a token, the
held ones of them; ``bench/counts_nemotron_h.py``), over the kernels'
device time times the chips' bf16 peak.  Not a roofline: the kernels
compute the rows actually routed, which the seed's weights make up to a
third more than expected, and the routed rows of the timed steps are not
read (PERF.md section 7).  It moves when the kernels do the same rows
faster.  The time is the sum over the events of megablox's ``gmm`` and
``tgmm`` kernels, found by their instructions' names (``gmm``,
``transpose_jvp_jit_gmm___``, ...); a path that runs no such kernel reads
nothing."""
import re

from bench.trace import short_name

_KERNEL = re.compile(r"(^|_)t?gmm(_|$)")


def read(ctx):
    flops = ctx["counts"].get("gmm_flops_per_unit")
    tr = ctx["trace"]
    secs = tr.op_seconds(lambda n: bool(_KERNEL.search(short_name(n))))
    if not flops or secs <= 0:
        return None
    return 100.0 * ctx["units"] * flops / (
        secs * ctx["peaks"]["bf16_flops_per_s"])
