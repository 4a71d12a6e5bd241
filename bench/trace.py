"""Reduction of a profiler trace to device busy time, idle gaps and kernel
time.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``.  A TPU trace holds one plane per chip
(``/device:TPU:<i>``) whose ``XLA Ops`` line has one event per HLO op run
(a Mosaic kernel is one such op); the host plane holds the benchmark's ``TraceAnnotation`` spans on its threads' lines, on the same
clock.  The window is the host span named :data:`WINDOW`.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]

#: the benchmark's span around its measured window
WINDOW = "bench.window"
#: prefix of the benchmark's own host spans around its calls into layers
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
#: opcodes whose event spans the ops nested in it: never counted as work
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge intervals into disjoint sorted ones."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def short_name(op: str) -> str:
    """``%dasha_update.140 = (f32[...]) custom-call(...)`` -> the HLO
    instruction's name without ``%`` and its ``.<n>`` suffix
    (``dasha_update``)."""
    head = op.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def opcode(op: str) -> str:
    """The HLO opcode of an op event's name (``custom-call``, ``fusion``,
    ``while``, ...), or the name itself when it has none."""
    rhs = op.split(" = ", 1)
    if len(rhs) < 2:
        return op
    m = _OPCODE.search(" " + rhs[1])
    return m.group(1) if m else op


def is_container(name: str) -> bool:
    return opcode(name) in CONTAINERS


class Trace:
    """The events of one trace that the per-layer metrics read.

    ``ops[chip]``: (name, start_ns, end_ns) of every device op that does
    work (a ``while`` or other container spans the ops nested in it and is
    left out); ``spans``: the benchmark's host spans (name, start_ns,
    end_ns); ``window``: the measured window (start_ns, end_ns)."""

    def __init__(self, ops: Dict[int, List[Tuple[str, int, int]]],
                 spans: List[Tuple[str, int, int]]):
        self.ops, self.spans = ops, spans
        win = [(s, e) for n, s, e in spans if n == WINDOW]
        if win:
            self.window = (min(s for s, _ in win), max(e for _, e in win))
        else:
            every = [(s, e) for evs in ops.values() for _, s, e in evs]
            self.window = (min(s for s, _ in every),
                           max(e for _, e in every)) if every else (0, 0)

    @property
    def chips(self) -> List[int]:
        return sorted(self.ops)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _in_window(self, evs, pred=None) -> List[Interval]:
        lo, hi = self.window
        return clip(((s, e) for n, s, e in evs
                     if pred is None or pred(n)), lo, hi)

    def busy(self, chip: int) -> List[Interval]:
        """Disjoint intervals in the window in which any op ran."""
        return union(self._in_window(self.ops.get(chip, [])))

    def busy_s(self) -> float:
        """Busy seconds in the window, mean over the chips traced."""
        if not self.ops:
            return 0.0
        return sum(total(self.busy(c)) for c in self.chips) \
            / len(self.chips) / 1e9

    def idle_share(self) -> Optional[float]:
        w = self.window_s
        if w <= 0 or not self.ops:
            return None
        return 1.0 - self.busy_s() / w

    def op_seconds(self, match) -> float:
        """Device seconds, summed over chips, of the window's ops whose name
        satisfies ``match`` (a predicate on the name)."""
        return sum(total(self._in_window(self.ops[c], match))
                   for c in self.chips) / 1e9

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` ops with the most device time in the window, summed
        over chips and over the runs of one HLO instruction:
        [["<instruction> <opcode>", seconds], ...]."""
        acc: Dict[str, int] = defaultdict(int)
        lo, hi = self.window
        for c in self.chips:
            for n, s, e in self.ops[c]:
                s, e = max(s, lo), min(e, hi)
                if e > s:
                    acc[f"{n.split(' = ', 1)[0].lstrip('%')} {opcode(n)}"] \
                        += e - s
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v / 1e9] for n, v in top]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest gaps of chip 0's busy time in the window, each
        named by the innermost benchmark span covering its midpoint:
        [["<span> @<ms into window>", seconds], ...]."""
        if not self.ops:
            return []
        lo, hi = self.window
        busy = self.busy(self.chips[0])
        gaps = subtract([(lo, hi)], busy)
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:k]:
            mid = (s + e) // 2
            cover = [(ss, ee, n) for n, ss, ee in self.spans
                     if n != WINDOW and ss <= mid < ee]
            name = min(cover, key=lambda t: t[1] - t[0])[2] if cover \
                else "outside any benchmark span"
            out.append([f"{name} @{(s - lo) / 1e6:.3f}ms", (e - s) / 1e9])
        return out


def from_profile(pd) -> Trace:
    """Collect the events a :class:`Trace` needs from a ProfileData."""
    ops: Dict[int, List[Tuple[str, int, int]]] = {}
    spans: List[Tuple[str, int, int]] = []
    for plane in pd.planes:
        name = plane.name
        if name.startswith(DEVICE_PREFIX):
            chip = int(name[len(DEVICE_PREFIX):].split(" ")[0])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[chip] = [(e.name, int(e.start_ns), int(e.end_ns))
                                 for e in line.events
                                 if not is_container(e.name)]
        elif name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.end_ns)))
    return Trace(ops, spans)


def load(directory: str) -> Trace:
    """The newest ``.xplane.pb`` under ``directory``, reduced."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return from_profile(ProfileData.from_file(max(files,
                                                  key=os.path.getmtime)))
