"""Device time per program sub-scope: the layers inside the model's
forward/backward that the program names under ``dasha.oracle``
(:data:`SUBSCOPES`).

:mod:`bench.scopes` gives every op its innermost scope of
:data:`bench.scopes.SCOPES`, so a sub-scope's ops count there under
``dasha.oracle``.  This module builds a second head -> scope map over
``SCOPES`` and the sub-scopes, by the same rules (:func:`hlo_op_names`,
reusing :mod:`bench.scopes`' listing parser), and hands it to
:func:`bench.scopes.scope_split`: an op under ``moe.route`` inside
``dasha.oracle`` counts under ``moe.route`` here.
"""
from __future__ import annotations

import functools
import re
from typing import Dict, Iterable, List, Optional

from bench import scopes

#: the model's named scopes (DESIGN.md §17), nested in ``dasha.oracle``
SUBSCOPES = ("moe.route", "moe.experts", "moe.shared", "ssd.scan",
             "attn.core")

_SCOPE = re.compile(r"(?<![\w.])("
                    + "|".join(re.escape(s) for s in scopes.SCOPES
                               + SUBSCOPES) + r")(?![\w.])")


def innermost(op_name: str) -> str:
    """The innermost program scope or sub-scope in an ``op_name`` path, or
    :data:`bench.scopes.UNSCOPED`."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else scopes.UNSCOPED


def hlo_op_names(module_texts: Iterable[str]) -> Dict[str, object]:
    """:func:`bench.scopes.hlo_op_names` with the sub-scopes: head ->
    innermost scope or sub-scope of every instruction in the listings."""
    out: Dict[str, object] = {}
    for text in module_texts:
        comps = scopes._computations(text)
        inner = {name: {innermost(i[2]) for i in body if i[2]}
                 - {scopes.UNSCOPED} for name, body in comps.items()}
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
                        - {None, scopes.UNSCOPED}
                    scope[name] = found.pop() if len(found) == 1 else None
                scopes._add(out, key, scope[name] or scopes.UNSCOPED)
    return out


def _live_texts() -> List[str]:
    """The compiled modules' listings of every executable the process
    holds; empty where the backend does not list them."""
    from jax.errors import JaxRuntimeError
    from jax.extend.backend import get_backend
    unlisted = (AttributeError, NotImplementedError, JaxRuntimeError)
    try:
        executables = get_backend().live_executables()
    except unlisted:
        return []
    texts: List[str] = []
    for exe in executables:
        try:
            texts += [m.to_string() for m in exe.hlo_modules()]
        except unlisted:
            continue
    return texts


@functools.lru_cache(maxsize=1)
def live_split(trace) -> Optional[Dict[str, float]]:
    """:func:`bench.scopes.scope_split` of ``trace`` over the sub-scope
    map of the live executables, once per trace."""
    return scopes.scope_split(trace, hlo_op_names(_live_texts())) \
        if trace.ops else None


def ms_per_step(ctx, *names: str) -> Optional[float]:
    """Device milliseconds per step of the ops in the sub-scopes
    ``names``; ``None`` where the program has none of them."""
    split = live_split(ctx["trace"])
    if split is None or not ctx["units"]:
        return None
    secs = sum(split.get(s, 0.0) for s in names)
    return 1e3 * secs / ctx["units"] if secs > 0 else None
