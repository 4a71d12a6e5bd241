"""Jaxpr/HLO audits for compiled callables — Layer 1 of ``repro.analysis``.

Three audits, each answering a question the repo used to answer with
hand-rolled one-off walks (DESIGN.md §15):

* :func:`large_outputs` / :func:`assert_large_outputs` — how many
  equation outputs at or above a byte threshold does the traced program
  materialize?  Generalizes the PR 5 inline n=4096 memory guard: on the
  sampled path only the two persistent (n, d) state scatters may be that
  large; any third O(n·d) temporary is a scaling regression.
* :func:`donation_report` — which declared ``donate_argnums`` buffers did
  XLA actually alias into outputs?  On CPU the answer is "none
  must-alias" (the carry-copy floor, DESIGN.md §13); the report makes
  that explicit instead of silently eating the copies.
* :func:`scan_carry_report` — per-scan carry byte accounting, so the
  O(tau·n·d) async in-flight ring (DESIGN.md §14) is a number in a
  report rather than an OOM surprise.

Plus :func:`hlo_collective_report`, which feeds the compiled module text
through :mod:`repro.analysis.hlo_parse` for loop-aware collective bytes.

All entry points accept the *uncompiled* callable plus example
arguments; they trace via ``jax.make_jaxpr`` / ``jax.jit(...).lower``
and never execute the function.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import numpy as np


def aval_bytes(aval: Any) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        # extended dtypes (key<fry>, float8 wrappers) expose itemsize
        itemsize = int(getattr(dtype, "itemsize", 0))
    return int(np.prod(shape, dtype=np.int64)) * itemsize


def iter_eqns(jaxpr: Any, *, recurse: bool = True) -> Iterator[Any]:
    """Yield equations of ``jaxpr`` (a ``Jaxpr`` or ``ClosedJaxpr``),
    recursing into sub-jaxprs carried in equation params (scan/while/cond
    bodies, custom-call jaxprs, pjit bodies)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        yield eqn
        if not recurse:
            continue
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, recurse=True)


def _sub_jaxprs(eqn: Any) -> Iterator[Any]:
    for val in eqn.params.values():
        for cand in (val if isinstance(val, (list, tuple)) else [val]):
            if hasattr(cand, "eqns") or hasattr(getattr(cand, "jaxpr", None),
                                                "eqns"):
                yield cand


@dataclasses.dataclass(frozen=True)
class LargeOutput:
    primitive: str
    shape: Tuple[int, ...]
    dtype: str
    nbytes: int

    def render(self) -> str:
        return (f"{self.primitive}: {self.dtype}{list(self.shape)} "
                f"({self.nbytes / 2**20:.2f} MiB)")


def _default_min_bytes(jaxpr: Any) -> int:
    """Largest input buffer: temporaries at or above it are 'large'."""
    invars = getattr(jaxpr, "jaxpr", jaxpr).invars
    return max((aval_bytes(v.aval) for v in invars), default=1) or 1


def large_outputs(fn: Callable, *args: Any,
                  min_bytes: Optional[int] = None,
                  recurse: bool = True, **kwargs: Any) -> List[LargeOutput]:
    """Equation outputs of the traced ``fn(*args)`` at least ``min_bytes``
    big.  ``min_bytes`` defaults to the largest input buffer, so on the
    sampled federated path "large" means O(n·d) and the expected hits are
    exactly the persistent-state scatters."""
    jaxpr = jax.make_jaxpr(fn, **kwargs)(*args)
    floor = _default_min_bytes(jaxpr) if min_bytes is None else min_bytes
    out: List[LargeOutput] = []
    for eqn in iter_eqns(jaxpr, recurse=recurse):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            nb = aval_bytes(aval)
            if nb >= floor:
                out.append(LargeOutput(str(eqn.primitive), tuple(aval.shape),
                                       str(aval.dtype), nb))
    return out


def assert_large_outputs(fn: Callable, *args: Any, max_big: int = 2,
                         min_bytes: Optional[int] = None,
                         **kwargs: Any) -> List[LargeOutput]:
    """Assert the traced program materializes at most ``max_big`` outputs
    at or above the threshold; returns the offending list for reporting."""
    big = large_outputs(fn, *args, min_bytes=min_bytes, **kwargs)
    if len(big) > max_big:
        lines = "\n  ".join(o.render() for o in big)
        raise AssertionError(
            f"{len(big)} large equation outputs (allowed {max_big}) — the "
            f"compiled step materializes extra full-size buffers:\n  {lines}")
    return big


# ---------------------------------------------------------------------------
# Compiled-module audits (memory / flops / donation)
# ---------------------------------------------------------------------------

def _compile(fn: Callable, *args: Any, **jit_kwargs: Any):
    return jax.jit(fn, **jit_kwargs).lower(*args).compile()


def compiled_temp_bytes(fn: Callable, *args: Any,
                        **jit_kwargs: Any) -> Optional[int]:
    """XLA's temp-allocation size for ``fn(*args)``; None when the backend
    does not report a memory analysis."""
    mem = _compile(fn, *args, **jit_kwargs).memory_analysis()
    if mem is None:
        return None
    return int(getattr(mem, "temp_size_in_bytes", 0))


def compiled_flops(fn: Callable, *args: Any,
                   **jit_kwargs: Any) -> Optional[float]:
    """XLA cost-analysis flops for ``fn(*args)``; None when unavailable."""
    cost = _compile(fn, *args, **jit_kwargs).cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not cost:
        return None
    return float(cost.get("flops", 0.0))


_ALIAS_BLOCK = re.compile(r"input_output_alias=\{(.*?)\}\s*[,)]", re.S)
_ALIAS_ENTRY = re.compile(
    r"\{([\d,\s]*)\}:\s*\((\d+),\s*\{([\d,\s]*)\},\s*([\w-]+)\)")


@dataclasses.dataclass(frozen=True)
class AliasEntry:
    output_index: Tuple[int, ...]
    param_number: int
    param_index: Tuple[int, ...]
    kind: str                      # "must-alias" | "may-alias"


@dataclasses.dataclass(frozen=True)
class DonationReport:
    donate_argnums: Tuple[int, ...]
    donated_leaves: int            # buffers declared donatable
    aliases: Tuple[AliasEntry, ...]

    @property
    def must_alias(self) -> int:
        return sum(1 for a in self.aliases if a.kind == "must-alias")

    @property
    def may_alias(self) -> int:
        return sum(1 for a in self.aliases if a.kind == "may-alias")

    @property
    def effective(self) -> bool:
        """True when every declared-donated buffer aliases an output."""
        return self.donated_leaves > 0 \
            and len(self.aliases) >= self.donated_leaves

    def render(self) -> str:
        return (f"declared {self.donated_leaves} donated buffers "
                f"(argnums {list(self.donate_argnums)}); XLA aliased "
                f"{len(self.aliases)} ({self.must_alias} must-alias, "
                f"{self.may_alias} may-alias)")


def _parse_index(text: str) -> Tuple[int, ...]:
    return tuple(int(t) for t in text.replace(",", " ").split())


def donation_report(fn: Callable, *args: Any,
                    donate_argnums: Sequence[int] = (0,)) -> DonationReport:
    """Compile ``fn`` with ``donate_argnums`` and report which buffers XLA
    actually aliased into outputs.  On CPU expect zero must-alias entries:
    that *is* the carry-copy floor (DESIGN.md §13), now measured instead
    of assumed."""
    donate = tuple(donate_argnums)
    leaves = sum(len(jax.tree_util.tree_leaves(args[i])) for i in donate
                 if i < len(args))
    compiled = _compile(fn, *args, donate_argnums=donate)
    txt = compiled.as_text() or ""
    m = _ALIAS_BLOCK.search(txt)
    aliases: List[AliasEntry] = []
    if m:
        for out_idx, pnum, pidx, kind in _ALIAS_ENTRY.findall(m.group(0)):
            aliases.append(AliasEntry(_parse_index(out_idx), int(pnum),
                                      _parse_index(pidx), kind))
    return DonationReport(donate, leaves, tuple(aliases))


# ---------------------------------------------------------------------------
# Scan-carry accounting
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ScanCarry:
    num_carry: int
    carry_bytes: int
    length: Optional[int]
    shapes: Tuple[Tuple[Tuple[int, ...], str], ...]

    def render(self) -> str:
        tail = ", ".join(f"{d}{list(s)}" for s, d in self.shapes[:6])
        more = "" if len(self.shapes) <= 6 else f", +{len(self.shapes) - 6}"
        return (f"scan(length={self.length}): carry {self.num_carry} bufs, "
                f"{self.carry_bytes / 2**20:.2f} MiB [{tail}{more}]")


def scan_carry_report(fn: Callable, *args: Any,
                      **kwargs: Any) -> List[ScanCarry]:
    """Byte accounting for every ``lax.scan`` carry in the traced program
    (recursive, so nested scans report too).  This is where the async
    ring's O(tau·n·d) in-flight buffers show up per-config."""
    jaxpr = jax.make_jaxpr(fn, **kwargs)(*args)
    out: List[ScanCarry] = []
    for eqn in iter_eqns(jaxpr, recurse=True):
        if str(eqn.primitive) != "scan":
            continue
        num_carry = int(eqn.params.get("num_carry", 0))
        num_consts = int(eqn.params.get("num_consts", 0))
        body = eqn.params.get("jaxpr")
        invars = getattr(body, "jaxpr", body).invars
        carry = invars[num_consts:num_consts + num_carry]
        shapes = tuple((tuple(v.aval.shape), str(v.aval.dtype))
                       for v in carry)
        out.append(ScanCarry(
            num_carry=num_carry,
            carry_bytes=sum(aval_bytes(v.aval) for v in carry),
            length=eqn.params.get("length"),
            shapes=shapes))
    return out


def hlo_collective_report(fn: Callable, *args: Any,
                          **jit_kwargs: Any) -> Dict[str, float]:
    """Loop-aware collective byte totals for the compiled module, via
    :mod:`repro.analysis.hlo_parse` (trip-count multipliers included)."""
    from repro.analysis import hlo_parse
    txt = _compile(fn, *args, **jit_kwargs).as_text() or ""
    return hlo_parse.collective_bytes_loop_aware(txt)
