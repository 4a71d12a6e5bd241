"""Driver throughput: the old per-step Python experiment loops vs the
chunked compiled driver (DESIGN.md §10).

Cases (all at CPU-container scale; emitted to ``BENCH_driver.json``):

* ``smoke_lm_tune`` — the HEADLINE case: the paper's powers-of-two
  stepsize tune (8 gammas) at the smoke LM config.  Old harness: one
  Python loop per gamma — a fresh ``jax.jit(make_method(...).step)`` per
  stepsize (each gamma recompiles), eager per-step batch generation, a
  host ``float()`` read per run.  New: ONE vmapped chunked sweep
  (``driver.sweep``) — compiles once, draws data in-jit, runs all lanes
  as a single batched scan.  steps/sec = aggregate method-steps/sec.
* ``smoke_lm_single`` — a single training run, old ``launch/train.py``
  loop shape (eager batch gen + jitted step + eval_loss/metric ``float()``
  casts on log steps) vs the driver.  On CPU the step compute dominates a
  single run, so this gap is modest; on accelerators the per-step host
  round-trip it removes is the serialization bottleneck.
* ``flat_1e6`` — a flat d=1e6 stochastic problem, research-loop shape
  (per-step jitted ``method.step`` + a host metric read per round) vs the
  driver.

Env: ``REPRO_BENCH_QUICK=1`` shrinks gammas/steps/d for CI smoke runs.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.analysis import recompile
from repro.compress import make_round_compressor
from repro.configs import get_smoke_config
from repro.core.oracles import StochasticProblem
from repro.data.pipeline import SyntheticTextConfig, make_node_batches
from repro.methods import FlatSubstrate, Hyper, Method
from repro.methods.driver import Driver, Sweeper
from repro.models import init_params, lm
from repro.optim.distributed import DashaTrainConfig, make_method

QUICK = bool(os.environ.get("REPRO_BENCH_QUICK"))
REPS = 1 if QUICK else 3     # best-of-N timing (the container is noisy)
N_NODES = 4
BATCH, SEQ = 1, 32            # the tune case (keeps 8 lanes x 30 steps fast)
BATCH_1, SEQ_1 = 2, 64        # the single-run case (train.py-like shape)
LOG_EVERY = 10
N_GAMMAS = 4 if QUICK else 8
STEPS_TUNE = 10 if QUICK else 30
STEPS_LM = 20 if QUICK else 40
D_FLAT = int(1e5) if QUICK else int(1e6)
STEPS_FLAT = 20 if QUICK else 50


def _best_sps(fn, steps: int, reps: int = REPS) -> float:
    best = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = max(best, steps / (time.perf_counter() - t0))
    return best


def _lm_setup(seq: int = SEQ):
    cfg = get_smoke_config("starcoder2-3b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    tcfg = SyntheticTextConfig(vocab_size=cfg.vocab_size, seq_len=seq)

    def node_loss(p, b):
        return lm.loss_fn(cfg, p, b)[0]

    eval_loss = jax.jit(lambda p, b: lm.loss_fn(
        cfg, p, jax.tree_util.tree_map(
            lambda x: x.reshape((-1,) + x.shape[2:]), b))[1]["loss"])
    return cfg, params, tcfg, node_loss, eval_loss


def _dcfg(gamma):
    return DashaTrainConfig(gamma=gamma, compression=1 / 32,
                            n_nodes=N_NODES, server_opt="adam")


def _bench_smoke_lm_tune() -> Dict:
    """The 8-gamma stepsize tune: sequential Python loops (per-gamma
    recompile) vs ONE vmapped chunked sweep."""
    cfg, params, tcfg, node_loss, eval_loss = _lm_setup()
    gammas = tuple(0.0005 * 2 ** i for i in range(N_GAMMAS))
    total = len(gammas) * STEPS_TUNE

    def old_tune():
        best = None
        for g in gammas:
            method = make_method(_dcfg(g), node_loss)
            st = method.init(params, jax.random.PRNGKey(1), init_mode="zeros")
            step = jax.jit(method.step)
            k = jax.random.PRNGKey(2)
            for _ in range(STEPS_TUNE):
                k, kb = jax.random.split(k)
                st = step(st, make_node_batches(kb, tcfg, N_NODES, BATCH))
            fl = float(eval_loss(
                st.x, make_node_batches(k, tcfg, N_NODES, BATCH)))
            if best is None or fl < best:
                best = fl
        return best

    t0 = time.perf_counter()
    old_tune()
    py_sps = total / (time.perf_counter() - t0)       # incl. the per-gamma
    # recompiles — they are inherent to the old harness (a fresh jitted
    # step closure per stepsize)

    def method_fn(gamma):
        return make_method(_dcfg(gamma), node_loss)

    ms0 = method_fn(gammas[0]).init(params, jax.random.PRNGKey(1),
                                    init_mode="zeros")

    def data_fn(k, t):
        return make_node_batches(k, tcfg, N_NODES, BATCH)

    sweeper = Sweeper(method_fn, data_fn=data_fn, chunk=LOG_EVERY)

    def new_tune():
        fin, _ = sweeper.run(jnp.array(gammas), ms0, STEPS_TUNE,
                             data_key=jax.random.PRNGKey(2))
        jax.block_until_ready(fin.x)

    t0 = time.perf_counter()
    new_tune()                                        # incl. its ONE compile
    drv_first = total / (time.perf_counter() - t0)
    with recompile.watch("lm_tune_steady") as region:
        t0 = time.perf_counter()
        new_tune()
        drv_sps = total / (time.perf_counter() - t0)
    return {"case": "smoke_lm_tune", "gammas": len(gammas),
            "steps": STEPS_TUNE,
            "python_loop_steps_per_s": round(py_sps, 3),
            "driver_steps_per_s": round(drv_sps, 3),
            "driver_steps_per_s_incl_compile": round(drv_first, 3),
            "speedup": round(drv_sps / py_sps, 2),
            "steady_state_compiles": region.count}


def _bench_smoke_lm_single() -> Dict:
    cfg, params, tcfg, node_loss, eval_loss = _lm_setup(SEQ_1)
    dcfg = _dcfg(0.003)

    method = make_method(dcfg, node_loss)
    ms0 = method.init(params, jax.random.PRNGKey(1), init_mode="zeros")

    # OLD: the pre-driver launch/train.py Python loop
    step = jax.jit(method.step)
    g_norm_sq = jax.jit(lambda g: sum(jnp.sum(jnp.square(x))
                                      for x in jax.tree_util.tree_leaves(g)))

    def py_loop(state, k_data, steps):
        for t in range(steps):
            k_data, k_b = jax.random.split(k_data)
            batch = make_node_batches(k_b, tcfg, N_NODES, BATCH_1)
            state = step(state, batch)
            if t % LOG_EVERY == 0 or t == steps - 1:
                float(eval_loss(state.x, batch))
                float(g_norm_sq(state.g))
        return state

    py_loop(ms0, jax.random.PRNGKey(9), 2)             # warm up jits
    py_sps = _best_sps(
        lambda: jax.block_until_ready(
            py_loop(ms0, jax.random.PRNGKey(2), STEPS_LM).x),
        STEPS_LM)

    # NEW: the chunked compiled driver, data drawn in-jit

    def data_fn(k, t):
        return make_node_batches(k, tcfg, N_NODES, BATCH_1)

    drv = Driver(method, data_fn=data_fn,
                 metrics={"loss": lambda s, d: lm.loss_fn(
                     cfg, s.x, jax.tree_util.tree_map(
                         lambda x: x.reshape((-1,) + x.shape[2:]), d)
                 )[1]["loss"],
                     "g_norm_sq": lambda s, d: sum(
                         jnp.sum(jnp.square(x))
                         for x in jax.tree_util.tree_leaves(s.g))},
                 metric_every=LOG_EVERY, chunk=LOG_EVERY)
    fin, _ = drv.run(ms0, STEPS_LM, data_key=jax.random.PRNGKey(9))
    jax.block_until_ready(fin.x)                       # warm up chunk jits
    with recompile.watch("lm_single_steady") as region:
        drv_sps = _best_sps(
            lambda: jax.block_until_ready(
                drv.run(ms0, STEPS_LM, data_key=jax.random.PRNGKey(2))[0].x),
            STEPS_LM)
    return {"case": "smoke_lm_single", "steps": STEPS_LM,
            "d": sum(int(x.size)
                     for x in jax.tree_util.tree_leaves(params)),
            "python_loop_steps_per_s": round(py_sps, 3),
            "driver_steps_per_s": round(drv_sps, 3),
            "speedup": round(drv_sps / py_sps, 2),
            "steady_state_compiles": region.count}


def _flat_problem(d: int) -> StochasticProblem:
    diag = jnp.linspace(1.0, 2.0, d)
    b = jax.random.normal(jax.random.PRNGKey(3), (d,)) / jnp.sqrt(d)

    def loss(x, xi, i):
        return 0.5 * jnp.sum(diag * x * x) - b @ x + xi @ x

    def sample(k, i, batch):
        return 0.1 * jax.random.normal(k, (batch, d)) / jnp.sqrt(d)

    return StochasticProblem(loss=loss, sample=sample, n=N_NODES,
                             true_grad=lambda x: diag * x - b)


def _bench_flat(d: int) -> Dict:
    problem = _flat_problem(d)
    comp = make_round_compressor("randk", d, N_NODES, k=max(d // 100, 1))
    hp = Hyper(gamma=0.1, a=0.5, variant="mvr", b=0.2)
    m = Method.build("mvr", comp, FlatSubstrate(problem, N_NODES, d), hp)
    st0 = m.init(jnp.zeros(d), jax.random.PRNGKey(1), init_mode="stoch")
    metric = lambda s: jnp.sum(jnp.square(s.g))

    # OLD: per-step jitted step + a host metric read per round
    jstep = jax.jit(m.step)
    jmetric = jax.jit(metric)

    def py_loop(st, steps):
        trace = []
        for _ in range(steps):
            st = jstep(st)
            trace.append(float(jmetric(st)))
        return st, trace

    py_loop(st0, 2)                                    # warm up jits
    py_sps = _best_sps(
        lambda: jax.block_until_ready(py_loop(st0, STEPS_FLAT)[0].x),
        STEPS_FLAT)

    # NEW: chunked driver (metric traced in-scan, one host sync per chunk)
    drv = Driver(m, metrics={"metric": lambda s, d_: metric(s)}, chunk=10)
    fin, _ = drv.run(st0, STEPS_FLAT)
    jax.block_until_ready(fin.x)                       # warm up chunk jits
    with recompile.watch("flat_steady") as region:
        drv_sps = _best_sps(
            lambda: jax.block_until_ready(drv.run(st0, STEPS_FLAT)[0].x),
            STEPS_FLAT)
    return {"case": f"flat_d{d:.0e}", "steps": STEPS_FLAT, "d": d,
            "python_loop_steps_per_s": round(py_sps, 3),
            "driver_steps_per_s": round(drv_sps, 3),
            "speedup": round(drv_sps / py_sps, 2),
            "steady_state_compiles": region.count}


def run() -> List[Dict]:
    cases = [_bench_smoke_lm_tune(), _bench_smoke_lm_single(),
             _bench_flat(D_FLAT)]
    recompile_free = all(c["steady_state_compiles"] == 0 for c in cases)
    payload = {"bench": "driver", "quick": QUICK,
               "steady_state_recompile_free": recompile_free,
               "backend": jax.default_backend(),
               "note": ("smoke_lm_tune: the paper's stepsize tune — "
                        "sequential per-gamma Python loops (each gamma "
                        "recompiles a fresh jitted step; eager batch gen) "
                        "vs ONE vmapped chunked sweep. smoke_lm_single / "
                        "flat: per-step dispatch with host metric reads "
                        "vs the chunked donated scan with in-jit data "
                        "(DESIGN.md §10)."),
               "cases": cases}
    with open("BENCH_driver.json", "w") as f:
        json.dump(payload, f, indent=2)
    if QUICK:
        # CI smoke gate: a warmed driver loop must never recompile —
        # a nonzero count is the identity-keyed-closure bug class the
        # recompile sentinels (DESIGN.md §15) exist to catch
        assert recompile_free, \
            f"warmed driver runs triggered backend compiles: {cases}"
    return [dict(bench="driver_bench",
                 **{k: v for k, v in c.items()}) for c in cases]


if __name__ == "__main__":
    emit(run())
