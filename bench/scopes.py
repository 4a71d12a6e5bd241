"""Device time per program scope: which named scope of the program each
device op of a trace ran under.

The program wraps each line of its step in a ``jax.named_scope``
(:data:`SCOPES`); a scope edits only the op metadata of what it traces, so
every compiled instruction carries its scope path in ``op_name``.  An op
belongs to the innermost program scope of that path; a fusion's ``op_name``
is its root's, and an instruction the compiler made without one takes the
scope of what it serves (:func:`hlo_op_names`).  A trace's device events name their instruction (the HLO text
without its metadata), so the scope of an event is found by the
instruction's head, ``%<name> = <type> <opcode>``, in one of two sources:

* :func:`live_op_names` — the compiled modules of every executable this
  process still holds (what a benchmark run reads, while the program that
  it traced is alive);
* :func:`xplane_op_names` — the ``tf_op`` stat of the device planes' event
  metadata in an ``.xplane.pb`` file, read with the minimal protobuf reader
  below (no ``tensorflow`` import).

:func:`scope_split` then reduces a :class:`bench.trace.Trace` to device
seconds per scope.
"""
from __future__ import annotations

import functools
import re
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

from bench.trace import clip, total, union

#: the program's named scopes (DESIGN.md §17), each a layer of the step
SCOPES = ("dasha.server", "dasha.oracle", "dasha.compress",
          "dasha.node_update", "dasha.aggregate", "driver.data",
          "driver.metrics")
#: the split's key for device time under no program scope
UNSCOPED = ""

_SCOPE = re.compile(r"(?<![\w.])(" + "|".join(re.escape(s) for s in SCOPES)
                    + r")(?![\w.])")
_HEAD = re.compile(r"\s*(?:ROOT )?(%[^\s=]+ = .+? [a-z][a-z0-9_\-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=(%[^\s,]+)")
_OPERAND = re.compile(r"%[^\s,)]+")
#: an instruction whose head maps to more than one scope
AMBIGUOUS = object()


def innermost(op_name: str) -> str:
    """The innermost program scope in an ``op_name`` path (transformation
    wrappers such as ``transpose(jvp(dasha.oracle))`` included), or
    :data:`UNSCOPED`."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else UNSCOPED


def head(text: str) -> Optional[str]:
    """``%fusion.3 = f32[8]{0} fusion`` from an HLO instruction's text, as
    a trace event or a module's listing prints it."""
    m = _HEAD.match(text)
    return m.group(1) if m else None


def _add(out: Dict, key: Optional[str], scope: str) -> None:
    if key is None:
        return
    old = out.get(key, scope)
    out[key] = scope if old == scope else AMBIGUOUS


def _computations(text: str) -> Dict[str, List[Tuple]]:
    """computation -> (name, head, op_name, called computation, operand
    names) of each of its instructions, in the listing's order (operands
    before their users), from one module's listing."""
    comps: Dict[str, List[Tuple]] = {}
    body: List[Tuple] = []
    for line in text.splitlines():
        if line[:1] not in (" ", "") and line.rstrip().endswith("{"):
            words = line.split()
            body = comps.setdefault(
                words[1] if words[0] == "ENTRY" else words[0], [])
            continue
        m = _HEAD.match(line)
        if m is None:
            continue
        args = line[m.end():]
        op = _OP_NAME.search(line)
        callee = _CALLS.search(line)
        body.append((m.group(1).split(" = ", 1)[0], m.group(1),
                     op.group(1) if op else None,
                     callee.group(1) if callee else None,
                     _OPERAND.findall(args[:args.find(")")])))
    return comps


def hlo_op_names(module_texts: Iterable[str]) -> Dict[str, object]:
    """head -> innermost scope of every instruction in the HLO listings.

    An instruction the compiler made without metadata takes a scope from
    what it serves: a fusion built by merging ops, the one scope its body's
    instructions share; else (a layout copy, a prefetch, a buffer fill) the
    one scope its users share.  A head that two modules give different
    scopes maps to :data:`AMBIGUOUS`."""
    out: Dict[str, object] = {}
    for text in module_texts:
        comps = _computations(text)
        inner = {name: {innermost(i[2]) for i in body if i[2]} - {UNSCOPED}
                 for name, body in comps.items()}
        for body in comps.values():
            scope: Dict[str, Optional[str]] = {}
            users: Dict[str, List[str]] = {}
            for name, _, op, callee, operands in body:
                shared = inner.get(callee, set())
                scope[name] = innermost(op) if op is not None else \
                    next(iter(shared)) if len(shared) == 1 else None
                for o in operands:
                    users.setdefault(o, []).append(name)
            for name, key, *_ in reversed(body):    # users first
                if scope[name] is None:
                    found = {scope[u] for u in users.get(name, ())} \
                        - {None, UNSCOPED}
                    scope[name] = found.pop() if len(found) == 1 else None
                _add(out, key, scope[name] or UNSCOPED)
    return out


def live_op_names() -> Dict[str, object]:
    """:func:`hlo_op_names` over the compiled modules of every executable
    the process holds; empty where the backend does not list them."""
    from jax.errors import JaxRuntimeError
    from jax.extend.backend import get_backend
    unlisted = (AttributeError, NotImplementedError, JaxRuntimeError)
    texts: List[str] = []
    try:
        executables = get_backend().live_executables()
    except unlisted:
        return {}
    for exe in executables:
        try:
            texts += [m.to_string() for m in exe.hlo_modules()]
        except unlisted:        # its ops count as unscoped
            continue
    return hlo_op_names(texts)


def scope_split(trace, op_names: Dict[str, object]
                ) -> Optional[Dict[str, float]]:
    """Device seconds of the trace's window per program scope (mean over
    chips), :data:`UNSCOPED` for busy time under none; ``None`` when no op
    of the window lies in a program scope (a program without scopes).

    Each scope's time is the union of its ops' intervals, clipped to the
    window; an op whose instruction is not found, or is ambiguous, counts
    as unscoped."""
    lo, hi = trace.window
    acc: Dict[str, float] = {}
    scope_of: Dict[str, str] = {}        # an instruction runs many times
    for chip in trace.chips:
        per: Dict[str, list] = {}
        for name, s, e in trace.ops[chip]:
            scope = scope_of.get(name)
            if scope is None:
                scope = op_names.get(head(name), UNSCOPED)
                scope = scope_of[name] = \
                    UNSCOPED if scope is AMBIGUOUS else scope
            per.setdefault(scope, []).append((s, e))
        for scope, ivs in per.items():
            acc[scope] = acc.get(scope, 0.0) + total(union(clip(ivs, lo, hi)))
    if not any(acc.get(s, 0.0) > 0 for s in SCOPES):
        return None
    n = len(trace.chips)
    return {k: v / n / 1e9 for k, v in acc.items()}


@functools.lru_cache(maxsize=1)
def live_split(trace) -> Optional[Dict[str, float]]:
    """:func:`scope_split` of ``trace`` over :func:`live_op_names`, computed
    once per trace for all the metrics that read it."""
    return scope_split(trace, live_op_names()) if trace.ops else None


def ms_per_step(ctx, *scopes: str) -> Optional[float]:
    """Device milliseconds per step of the ops in ``scopes``; ``None`` when
    the program has no scopes or none of these ran."""
    split = live_split(ctx["trace"])
    if split is None or not ctx["units"]:
        return None
    secs = sum(split.get(s, 0.0) for s in scopes)
    return 1e3 * secs / ctx["units"] if secs > 0 else None


# ---------------------------------------------------------------------------
# the xplane's event metadata, by the protobuf wire format
# ---------------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for varint and fixed
    fields, bytes for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield num, val


def _map_value(entry: bytes) -> Optional[bytes]:
    """The message value (field 2) of a map entry."""
    for num, val in _fields(entry):
        if num == 2:
            return val
    return None


def _stats(stat_msgs: List[bytes], stat_names: Dict[int, str]
           ) -> Dict[str, object]:
    """XStat: metadata_id 1; str_value 5; ref_value 7 (an XStatMetadata
    id whose name is the value); the numeric values are not kept."""
    out: Dict[str, object] = {}
    for msg in stat_msgs:
        mid, val = None, None
        for num, v in _fields(msg):
            if num == 1:
                mid = v
            elif num == 5:
                val = v.decode("utf-8", "replace")
            elif num == 7:
                val = stat_names.get(v)
        if mid in stat_names and val is not None:
            out[stat_names[mid]] = val
    return out


def xplane_metadata(path: str, prefix: str = "/device:TPU:"
                    ) -> Dict[str, Dict[str, Dict[str, object]]]:
    """plane name -> event name -> its metadata's string stats (``tf_op``,
    ``hlo_category``, ...) for the planes whose name starts with
    ``prefix``.

    XSpace: planes 1.  XPlane: name 2, event_metadata 4 and stat_metadata
    5 (maps of id to message).  XEventMetadata: name 2, stats 5.
    XStatMetadata: id 1, name 2."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, Dict[str, Dict[str, object]]] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for pnum, val in _fields(plane):
            if pnum == 2:
                name = val.decode("utf-8", "replace")
            elif pnum == 4:
                events.append(_map_value(val))
            elif pnum == 5:
                meta = dict(_fields(_map_value(val) or b""))
                stat_names[meta.get(1, 0)] = \
                    meta.get(2, b"").decode("utf-8", "replace")
        if not name.startswith(prefix):
            continue
        table: Dict[str, Dict[str, object]] = {}
        for ev in events:
            ev_name, stat_msgs = "", []
            for enum, val in _fields(ev or b""):
                if enum == 2:
                    ev_name = val.decode("utf-8", "replace")
                elif enum == 5:
                    stat_msgs.append(val)
            table[ev_name] = _stats(stat_msgs, stat_names)
        out[name] = table
    return out


def xplane_op_names(path: str) -> Dict[str, object]:
    """head -> innermost scope of every device op in an ``.xplane.pb``,
    from its ``tf_op`` stat (the op_name path and ``:<op type>``)."""
    out: Dict[str, object] = {}
    for table in xplane_metadata(path).values():
        for ev_name, stats in table.items():
            tf_op = str(stats.get("tf_op", ""))
            _add(out, head(ev_name), innermost(tf_op.rsplit(":", 1)[0]))
    return out
