"""Checkpointing: npz payload + json meta (no external deps).

Two layers:

* the generic pytree save/load of the seed (``save_checkpoint`` /
  ``load_checkpoint``) — kept for params-only snapshots;
* the VERSIONED full-state format (``save_state`` / ``load_state``, v2):
  when the saved tree is a NamedTuple (``MethodState``, optimizer states
  nest freely inside), the meta records per-field leaf spans so restore
  is matched BY FIELD NAME — a checkpoint written with extra retired
  fields (the seed-era ``prev_params``) restores into today's state by
  dropping them, and missing-field mismatches fail loudly instead of
  loading garbage.

Restore is bit-identical for every dtype npz can hold natively; bfloat16
is stored as float32 (a lossless widening) and cast back on load.  This
lifts the seed's "checkpointing is params-only" restriction: the driver
(DESIGN.md §10) checkpoints the complete ``MethodState``
(x / g / g_local / h_local / opt_state / key / t / bits_sent), and a
restored run continues bit-identically (tested in tests/test_driver.py).

v1 checkpoints (no ``version`` in meta) and non-NamedTuple trees load
positionally.

Multi-host sharded checkpointing (array-serialization per shard) remains
out of scope for the CPU container.
"""
from __future__ import annotations

import json
import os
from typing import Any, Optional

import jax
import numpy as np

#: current on-disk format version (meta.json "version")
FORMAT_VERSION = 2


def _write(path: str, leaves, meta: dict) -> None:
    os.makedirs(path, exist_ok=True)
    # npz has no bfloat16: store as float32 (lossless) and restore on load
    arrays, dtypes = {}, []
    for i, l in enumerate(leaves):
        a = np.asarray(l)
        dtypes.append(str(a.dtype))
        if a.dtype.name == "bfloat16":
            a = a.astype(np.float32)
        arrays[f"leaf_{i}"] = a
    np.savez(os.path.join(path, "arrays.npz"), **arrays)
    meta = dict(meta, num_leaves=len(leaves), dtypes=dtypes)
    with open(os.path.join(path, "meta.json"), "w") as f:
        json.dump(meta, f)


def _read(path: str):
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    data = np.load(os.path.join(path, "arrays.npz"))
    leaves = [data[f"leaf_{i}"] for i in range(meta["num_leaves"])]
    return leaves, meta


def _cast_into(saved, like_leaves):
    import jax.numpy as jnp
    if len(saved) != len(like_leaves):
        raise ValueError(f"checkpoint leaf count mismatch: saved "
                         f"{len(saved)} vs expected {len(like_leaves)}")
    out = []
    for got, want in zip(saved, like_leaves):
        w = np.asarray(want)
        assert got.shape == w.shape, \
            f"checkpoint shape mismatch: {got.shape} vs {w.shape}"
        out.append(jnp.asarray(got).astype(w.dtype))
    return out


# ---------------------------------------------------------------------------
# seed API (generic pytree; params-only snapshots)
# ---------------------------------------------------------------------------

def save_checkpoint(path: str, tree: Any, step: int = 0) -> None:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    _write(path, leaves, {"version": FORMAT_VERSION,
                          "treedef": str(treedef), "step": step})


def load_checkpoint(path: str, like: Any) -> Any:
    """Restore into the structure of ``like`` (shape/dtype template)."""
    saved, _ = _read(path)
    like_leaves, treedef = jax.tree_util.tree_flatten(like)
    return jax.tree_util.tree_unflatten(treedef,
                                        _cast_into(saved, like_leaves))


def checkpoint_step(path: str) -> int:
    with open(os.path.join(path, "meta.json")) as f:
        return json.load(f)["step"]


def checkpoint_meta(path: str) -> dict:
    """The full meta dict (version / step / fields / extra)."""
    with open(os.path.join(path, "meta.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# versioned full-state format (v2)
# ---------------------------------------------------------------------------

def _field_spans(tree) -> Optional[list]:
    """[{name, leaves}] per NamedTuple field, in field order."""
    if not hasattr(tree, "_fields"):
        return None
    return [{"name": f,
             "leaves": len(jax.tree_util.tree_leaves(getattr(tree, f)))}
            for f in tree._fields]


def save_state(path: str, state: Any, *, step: Optional[int] = None,
               extra: Optional[dict] = None) -> None:
    """Save a full state pytree in the versioned (v2) format.

    When ``state`` is a NamedTuple the meta records per-field leaf spans,
    enabling field-name-matched restore across state-layout revisions.
    ``step`` defaults to the state's round counter ``t`` (0 without one).
    """
    if step is None:
        step = int(np.asarray(getattr(state, "t", 0)))
    leaves, treedef = jax.tree_util.tree_flatten(state)
    _write(path, leaves, {"version": FORMAT_VERSION,
                          "treedef": str(treedef), "step": step,
                          "fields": _field_spans(state),
                          "extra": extra or {}})


def load_state(path: str, like: Any) -> Any:
    """Restore a v2 (or v1) state checkpoint into the structure of ``like``:
    a restored ``MethodState`` continues bit-identically under the driver
    (same data keys via ``fold_in(data_key, t)``, same method RNG via the
    restored ``key``).

    v2 + NamedTuple: fields are matched by NAME — saved fields absent from
    ``like`` (retired fields such as ``prev_params``) are dropped; fields
    of ``like`` absent from the save raise.  Otherwise: positional.
    """
    saved, meta = _read(path)
    like_leaves, treedef = jax.tree_util.tree_flatten(like)
    fields = meta.get("fields")
    if fields and hasattr(like, "_fields"):
        spans, off = {}, 0
        for f in fields:
            spans[f["name"]] = saved[off:off + f["leaves"]]
            off += f["leaves"]
        missing = [n for n in like._fields if n not in spans]
        if missing:
            raise ValueError(f"checkpoint at {path!r} lacks state fields "
                             f"{missing} (saved: {sorted(spans)})")
        picked = []
        for name in like._fields:
            want = len(jax.tree_util.tree_leaves(getattr(like, name)))
            got = spans[name]
            if len(got) != want:
                raise ValueError(f"field {name!r}: saved {len(got)} leaves"
                                 f" vs expected {want}")
            picked.extend(got)
        return jax.tree_util.tree_unflatten(treedef,
                                            _cast_into(picked, like_leaves))
    # v1 / non-NamedTuple: positional restore
    return jax.tree_util.tree_unflatten(treedef,
                                        _cast_into(saved, like_leaves))
