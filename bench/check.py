"""The numbers that decide ``correct``: gaps between what the timed path
produced and what the plain reference computes, each against its limit.

A gap of norms is taken leaf by leaf: ``| |prog_leaf| - |ref_leaf| |``
over the larger of the reference's norm of that leaf and of the median
leaf, and the worst leaf is the number.  Leaves whose reference gradient is
under a thousandth of the median leaf's move by round-off alone and are
left out, by that rule and never by name; so are, for the parameters'
change, leaves whose reference change is under half a unit in the last
place of their stored values (:func:`stepping_leaves`).

A gap of directions reads a tree through random projections
(:func:`project`): per leaf, the root mean square over :data:`DIRS`
directions of the projections' difference, over the reference leaf's norm,
estimates how far the two leaves lie apart relative to the reference's.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import numpy as np

#: a leaf whose reference first gradient is below this share of the median
#: leaf's is left out of the comparison
NOUGHT = 1e-3

#: a leaf whose reference change, root mean square per element, is under
#: this share of the root mean square ulp of its stored values moves by
#: rounding, and is left out of the parameters' change
ROUNDING = 0.5

#: random directions per leaf in :func:`project`
DIRS = 1


def moving_leaves(ref_grad_sq: Sequence[float]) -> np.ndarray:
    """Boolean mask of the leaves that count, from the reference's squared
    leaf norms of the first gradient."""
    norms = np.sqrt(np.asarray(ref_grad_sq, np.float64))
    return norms >= NOUGHT * float(np.median(norms))


def stepping_leaves(ref_dx_sq, ref_ulp_sq) -> np.ndarray:
    """Boolean mask of the leaves whose reference change is carried by
    their stored precision: squared change norm at least ``ROUNDING**2``
    times the sum of the squared ulps of the leaf's starting values
    (:func:`ulp_sq`)."""
    return np.asarray(ref_dx_sq, np.float64) >= \
        ROUNDING ** 2 * np.asarray(ref_ulp_sq, np.float64)


def worst_leaf_gap(prog_sq, ref_sq, keep: np.ndarray) -> float:
    """Worst relative gap of leaf norms (inputs are squared norms), each
    over the larger of its reference norm and the median leaf's."""
    p = np.sqrt(np.asarray(prog_sq, np.float64))[keep]
    r = np.sqrt(np.asarray(ref_sq, np.float64))[keep]
    if p.size == 0:
        return float("nan")
    den = np.maximum(r, float(np.median(r)))
    den = np.where(den > 0, den, 1.0)
    return float(np.max(np.abs(p - r) / den))


def ulp_sq(tree):
    """Sum over each leaf's elements of the squared unit in the last place
    of the element in the leaf's own dtype (0 for an element that is 0):
    (leaves,) f32."""
    import jax
    import jax.numpy as jnp
    out = []
    for x in jax.tree_util.tree_leaves(tree):
        nmant = jnp.finfo(x.dtype).nmant
        _, e = jnp.frexp(x.astype(jnp.float32))
        u = jnp.where(x != 0, jnp.exp2((e - 1 - nmant).astype(jnp.float32)),
                      0.0)
        out.append(jnp.sum(u * u))
    return jnp.stack(out)


def project(tree, key, dirs: int = DIRS):
    """Inner products of every leaf with ``dirs`` standard normal
    directions of its own, drawn from ``key``: (leaves, dirs) f32.  Two
    trees whose leaves differ in direction, not only in norm, read
    differently."""
    import jax
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_leaves(tree)
    keys = jax.random.split(key, len(leaves))

    def one(k, x):
        x = x.astype(jnp.float32)
        return jax.lax.map(
            lambda kk: jnp.sum(x * jax.random.normal(kk, x.shape,
                                                     jnp.float32)),
            jax.random.split(k, dirs))
    return jnp.stack([one(k, x) for k, x in zip(keys, leaves)])


def direction_gaps(prog, ref, ref_sq) -> np.ndarray:
    """Per leaf: the root mean square over directions of the difference of
    the projections (:func:`project`), over the reference leaf's norm."""
    p = np.asarray(prog, np.float64)
    r = np.asarray(ref, np.float64)
    den = np.sqrt(np.asarray(ref_sq, np.float64))
    den = np.where(den > 0, den, 1.0)
    return np.sqrt(np.mean(np.square(p - r), axis=-1)) / den


def worst_projection_gap(prog, ref, ref_sq, keep: np.ndarray) -> float:
    """The worst kept leaf's :func:`direction_gaps`."""
    g = direction_gaps(prog, ref, ref_sq)[keep]
    return float(np.max(g)) if g.size else float("nan")


def rms_projection_gap(prog, ref, ref_sq, keep: np.ndarray) -> float:
    """The root mean square of the kept leaves' :func:`direction_gaps`."""
    g = direction_gaps(prog, ref, ref_sq)[keep]
    return float(np.sqrt(np.mean(np.square(g)))) if g.size \
        else float("nan")


def load_limits(root: Path, cell: str) -> Dict[str, Optional[float]]:
    path = root / "bench" / "limits" / f"{cell}.json"
    if not path.exists():
        return {}
    return {k: v for k, v in json.loads(path.read_text())["limits"].items()}


def verdict(values: Dict[str, float], limits: Dict[str, Optional[float]]
            ) -> tuple:
    """(correct, [{"name", "value", "limit"}]): correct when every number
    is finite and at or under its limit, and every number has one."""
    rows: List[Dict] = []
    ok = bool(values)
    for name, v in values.items():
        lim = limits.get(name)
        rows.append({"name": name, "value": v, "limit": lim})
        if lim is None or not math.isfinite(v) or v > lim:
            ok = False
    return ok, rows
