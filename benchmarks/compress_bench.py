"""Compression backend bench: dense vs sparse vs fused, RandK/PermK/QDither.

For each compressor x d in {1e5, 1e6, 1e7} x backend, times one full
"communication round" on the (n, d) message matrix — drift + plan +
compress + g_local update + server aggregate, identical work through
``estimator_update`` for every backend so rows are comparable — and
reports the coords a node message actually moves.  The headline numbers (DESIGN.md §5-§6):

* sparse RandK moves <= 2K coords per message (K values + K indices; K only
  when the support is derivable from the shared seed) vs d for dense — the
  `bits sent` plots stop being fictional;
* the fused Pallas path runs every compressor in one HBM pass (off a TPU
  it executes in interpret mode, so fused wall-times are NOT meaningful
  there — structural numbers only).

Env: REPRO_BENCH_QUICK=1 shrinks to d=1e4 for CI smoke runs.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.compress import REGISTRY, make_round_compressor

N_NODES = 4


def _reps(d: int) -> int:
    return 5 if d <= 1_000_000 else 2


def _sizes():
    if os.environ.get("REPRO_BENCH_QUICK"):
        return [10_000]
    return [100_000, 1_000_000, 10_000_000]


def _round_fn(rc):
    """One communication round, identical work for every backend:
    drift + compress + g_i update (estimator_update) + server aggregate."""
    def fn(key, h_new, h, g_local):
        msgs, _, gl = rc.estimator_update(key, h_new, h, g_local, 0.1)
        return gl, msgs.mean()
    return jax.jit(fn)


def _time(fn, reps, *args) -> float:
    out = fn(*args)                       # compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def run():
    rows = []
    key = jax.random.PRNGKey(0)
    for d in _sizes():
        k = max(1, d // 64)
        deltas = jax.random.normal(key, (N_NODES, d), jnp.float32)
        g_local = jnp.zeros((N_NODES, d), jnp.float32)
        cases = [("randk", dict(k=k), "independent"),
                 ("randk", dict(k=k), "shared_coords"),
                 ("permk", {}, "permk"),
                 ("qdither", dict(s=15), "independent")]
        for name, kw, mode in cases:
            for backend in ("dense", "sparse", "fused"):
                rc = make_round_compressor(name, d, N_NODES, mode=mode,
                                           backend=backend, **kw)
                fn = _round_fn(rc)
                h = jnp.zeros((N_NODES, d), jnp.float32)
                dt = _time(fn, _reps(d), key, deltas, h, g_local)
                wire = rc.wire_per_node
                is_sparse = (backend == "sparse"
                             and REGISTRY[name].supports_sparse)
                rows.append({
                    "bench": "compress", "comp": name, "mode": mode,
                    "backend": backend, "d": d, "k": k,
                    "step_ms": f"{dt * 1e3:.2f}",
                    "wire_coords_per_msg": round(float(wire)),
                    "agg_bytes_per_round": round(4.0 * float(wire)
                                                 * N_NODES),
                    "sparse_format": is_sparse,
                    "note": ("interpret-mode kernel; TPU-only timing"
                             if backend == "fused" else ""),
                })
    # headline sanity printed with the rows: RandK sparse <= 2K vs d dense
    for r in rows:
        if r["comp"] == "randk" and r["backend"] == "sparse" \
                and r["mode"] == "independent":
            assert r["wire_coords_per_msg"] <= 2 * r["k"], r
        if r["comp"] == "randk" and r["backend"] == "dense":
            assert r["wire_coords_per_msg"] == r["d"], r
    return rows


if __name__ == "__main__":
    emit(run())
