"""Smoke run of both deployments on a TPU chip, through their entry points.

    python chip_smoke.py             # one chip: phases train, nemotron,
                                     # kernel-vs-jnp, fed
    python chip_smoke.py --phase nemotron   # one phase of them
    python chip_smoke.py --chips 4   # four chips: the node axis on a mesh only

One process owns the chip(s) and starts no other.  Every phase checks its
result and raises on a failure, so the script exits non-zero; only when
every phase passed is the last line of standard output the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Off a TPU it
exits non-zero before any phase runs.

Phases (one chip):

* ``train``: ``repro.launch.train.main`` on mamba2-780m at its published
  widths, depth cut to :data:`LAYERS`, 4 nodes vmapped on the chip, for
  ``dasha`` and ``mvr`` on the compiled fused node-update kernel; the loss
  and ``|g|^2`` of every logged step must be finite.
* ``nemotron``: ``repro.launch.train.main`` on one chip's share of
  NVIDIA-Nemotron-3-Nano-30B-A3B (:data:`NEMOTRON_ARGS`: layers 0-6, 8 of
  128 experts, 16384 of 131072 vocabulary rows, one node, 8192 tokens) for
  one 5-step chunk on the fused kernel; the chip-share line is printed,
  and the logged loss must be finite and no routed token dropped.
* ``kernel-vs-jnp``: one DASHA step with the fused kernel and one with the
  jnp path from the same state and key; the messages m_i, h_i and g_i must
  agree to f32 rounding.  Then the keyed kernels, which draw the mask
  themselves, against the explicit-mask kernels fed ``draw_mask``'s mask,
  at every leaf size of the nodes' state: m_i, h_i and g_i must be
  bit-equal (max |diff| = 0).
* ``fed``: the sampled ``VecFedSim`` campaign of
  ``benchmarks/fed_scale_bench.py`` (n=1e5 clients, cohort C=64, d=64) for
  a few 200-round chunks through the compiled slab writeback, then one
  chunk's writeback replayed through the kernel and through XLA's scatter,
  which must leave bit-equal stores.

With ``--chips 4``, phase ``node-mesh`` runs the trainer (f32 params and
f32 matmuls, the fused kernel on each chip's shard) with its 4 nodes on the ``data`` axis of
the four chips and again with the 4 nodes vmapped on chip 0, same seed and
steps, and compares the loss at every logged step and the final params.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

#: depth cut of mamba2-780m (48 layers published): the fused MVR step with
#: 4 nodes then compiles to a 13.5 GB peak, 79% of the chip's 16 GiB
LAYERS = 4
#: depth of the two comparison phases: kernel-vs-jnp holds two steps'
#: temporaries (13.6 GB peak), node-mesh keeps f32 params and all 4 nodes
#: on chip 0 for its reference run (12.5 GB peak)
CMP_LAYERS = 2
#: the trainer's smoke configuration: published widths, one chip's share
TRAIN_ARGS = ["--arch", "mamba2-780m", "--full", "--layers", str(LAYERS),
              "--nodes", "4", "--batch", "1", "--seq", "2048",
              "--server-opt", "sgd", "--steps", "4", "--log-every", "2"]
#: the Nemotron-H chip share (bench/configs/nemotron3-nano.l7.e8.n1.chip1)
NEMOTRON_ARGS = ["--arch", "nemotron-3-nano-30b-a3b", "--full", "--layers",
                 "7", "--experts", "8", "--vocab", "16384", "--nodes", "1",
                 "--batch", "1", "--seq", "8192", "--server-opt", "sgd",
                 "--steps", "5", "--log-every", "5", "--use-kernel",
                 "--devices", "1"]
#: relative agreement asked of two f32 computations of the same values
#: that differ only in fusion or reduction order
F32_RTOL = 1e-5
#: the sampled federated campaign: clients, cohort size, rounds per chunk
FED_N, FED_C, FED_CHUNK = 100_000, 64, 200


def _phase(name: str, compiles: list):
    """Print a phase's header; return a closure printing its footer with
    the phase's wall and compile seconds."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[smoke] phase {name}: start, device bytes_in_use="
          f"{stats.get('bytes_in_use', 'not reported')}", flush=True)
    t0, c0 = time.perf_counter(), len(compiles)

    def done(result: str) -> None:
        comp = compiles[c0:]
        print(f"[smoke] phase {name}: PASS {result} "
              f"(wall {time.perf_counter() - t0:.1f} s, {len(comp)} compiles,"
              f" {sum(comp):.1f} s compiling)", flush=True)
    return done


def _finite(x: float) -> bool:
    return math.isfinite(float(x))


def _assert_kernel(lowered, what: str) -> None:
    """Fail unless the lowered program holds a compiled Mosaic kernel."""
    if "tpu_custom_call" not in lowered.as_text():
        raise AssertionError(f"{what} holds no compiled kernel")


def _max_diff(a, b):
    """Largest |a - b| and largest |b| over the leaves of two pytrees."""
    import jax
    import jax.numpy as jnp
    diffs = jax.tree_util.tree_map(
        lambda x, y: jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))), a, b)
    refs = jax.tree_util.tree_map(
        lambda y: jnp.max(jnp.abs(y.astype(jnp.float32))), b)
    return (jnp.max(jnp.stack(jax.tree_util.tree_leaves(diffs))),
            jnp.max(jnp.stack(jax.tree_util.tree_leaves(refs))))


def phase_train(compiles: list) -> None:
    from repro.launch import train
    for variant in ("dasha", "mvr"):
        done = _phase(f"train/{variant}", compiles)
        run = train.main(TRAIN_ARGS + ["--variant", variant, "--use-kernel",
                                       "--devices", "1"])
        if len(run.log) != 2:
            raise AssertionError(f"expected 2 logged steps, got {run.log}")
        for rec in run.log:
            if not (_finite(rec["loss"]) and _finite(rec["g_norm_sq"])):
                raise AssertionError(f"non-finite train record {rec}")
        last = run.log[-1]
        del run
        done(f"layers={LAYERS} loss={last['loss']:.6f} |g|^2={last['g_norm_sq']:.6e} "
             f"at step {last['step']}")


def phase_nemotron(compiles: list) -> None:
    from repro.launch import train
    done = _phase("nemotron", compiles)
    run = train.main(NEMOTRON_ARGS)
    last = run.log[-1]
    del run
    if not (_finite(last["loss"]) and _finite(last["g_norm_sq"])):
        raise AssertionError(f"non-finite train record {last}")
    if last["dropped"] != 0:
        raise AssertionError(f"{last['dropped']} routed tokens dropped")
    done(f"loss={last['loss']:.6f} |g|^2={last['g_norm_sq']:.6e} "
         f"routed per held expert {last['expert_tokens']} dropped 0")


def phase_kernel_vs_jnp(compiles: list) -> None:
    import jax

    from repro.data.pipeline import SyntheticTextConfig, make_node_batches
    from repro.launch import train
    from repro.models import init_params, lm
    from repro.optim.distributed import DashaTrainConfig, make_method

    done = _phase("kernel-vs-jnp", compiles)
    args = train.parse_args(TRAIN_ARGS)
    cfg = train.arch_config(args.arch, args.full, CMP_LAYERS)

    def node_loss(p, b):
        return lm.loss_fn(cfg, p, b)[0]

    def method(use_kernel: bool):
        return make_method(DashaTrainConfig(
            gamma=args.gamma, compression=args.compression, variant="dasha",
            n_nodes=args.nodes, server_opt=args.server_opt,
            use_kernel=use_kernel), node_loss)

    fused, plain = method(True), method(False)
    k_init, k_state, k_data = jax.random.split(jax.random.PRNGKey(0), 3)
    tcfg = SyntheticTextConfig(vocab_size=cfg.vocab_size, seq_len=args.seq)
    b0, b1 = (make_node_batches(k, tcfg, args.nodes, args.batch)
              for k in jax.random.split(k_data))
    state = jax.jit(lambda k, km: plain.init(init_params(cfg, k), km,
                                             init_mode="zeros"))(k_init,
                                                                 k_state)
    # one step first, so that h_i and g_i are not zero when compared
    state = jax.jit(plain.step, donate_argnums=0)(state, b0)

    def compare(s, b):
        fk, fj = fused.step(s, b), plain.step(s, b)
        msg = jax.tree_util.tree_map(lambda new, old: new - old,
                                     fk.g_local, s.g_local)
        msg_j = jax.tree_util.tree_map(lambda new, old: new - old,
                                       fj.g_local, s.g_local)
        return {"m": _max_diff(msg, msg_j),
                "h": _max_diff(fk.h_local, fj.h_local),
                "g_i": _max_diff(fk.g_local, fj.g_local)}

    lowered = jax.jit(compare).lower(state, b1)
    _assert_kernel(lowered, "the fused step")
    got = jax.device_get(lowered.compile()(state, b1))
    out = []
    for name, (diff, ref) in got.items():
        if not float(diff) <= F32_RTOL * max(float(ref), 1.0):
            raise AssertionError(f"{name}: fused and jnp steps differ by "
                                 f"{float(diff):.3e} (max |ref| "
                                 f"{float(ref):.3e})")
        out.append(f"{name} max|diff|={float(diff):.3e} "
                   f"(max|ref|={float(ref):.3e})")
    del state, lowered, b0, b1
    out.append(_keyed_vs_mask(cfg, args.nodes, args.compression))
    done("; ".join(out))


def _keyed_vs_mask(cfg, nodes: int, p: float) -> str:
    """The keyed kernels (mask drawn inside) against the explicit-mask
    kernels fed ``draw_mask``'s mask under the same key, in the (rows,
    cols) view the fused update streams each leaf of the nodes' state in
    (its own layout, else lane rows): m, h_i and g_i must be bit-equal,
    for DASHA and MVR."""
    import jax
    import jax.numpy as jnp

    from repro.compress.plan import draw_mask, u8_threshold
    from repro.kernels import dasha_update as kern
    from repro.kernels.ops import node_update_view
    from repro.models import init_params

    thresh = u8_threshold(p)
    if thresh is None:
        raise AssertionError(f"compression {p} is no multiple of 1/256")
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    views = sorted({node_update_view((nodes,) + x.shape)
                    or (-(-nodes * x.size // kern.LANE), kern.LANE)
                    for x in jax.tree_util.tree_leaves(shapes)})

    def bits(x):
        return jax.lax.bitcast_convert_type(x, jnp.uint32)

    @functools.partial(jax.jit, static_argnums=1)
    def inputs(key, view):
        mask = draw_mask(key, view, p).astype(jnp.float32)
        return tuple(jax.random.normal(k, view) for k in
                     jax.random.split(jax.random.fold_in(key, 1), 3)) + (mask,)

    # the mask comes in as an argument, as in the step: drawn in the same
    # program, an interpreted kernel would let XLA fold it into a select
    @functools.partial(jax.jit, static_argnums=5)
    def check(g, h, gl, mask, key, variant):
        words = key.astype(jnp.uint32)
        if variant == "mvr":   # g_local stands in for the old gradient
            keyed = kern.dasha_mvr_update_keyed_pallas(
                g, gl, h, gl, words, 0.2, 0.1, 1 / p, thresh,
                interpret=False)
            masked = kern.dasha_mvr_update_pallas(g, gl, h, gl, mask, 0.2,
                                                  0.1, 1 / p,
                                                  interpret=False)
        else:
            keyed = kern.dasha_update_keyed_pallas(
                g, h, gl, words, 0.2, 1 / p, thresh, interpret=False)
            masked = kern.dasha_update_pallas(g, h, gl, mask, 0.2, 1 / p,
                                              interpret=False)
        return (jnp.max(jnp.stack([jnp.max(jnp.abs(a - b))
                                   for a, b in zip(keyed, masked)])),
                sum(jnp.sum(bits(a) != bits(b))
                    for a, b in zip(keyed, masked)))

    key = jax.random.PRNGKey(7)
    worst = 0.0
    for variant in ("dasha", "mvr"):
        for i, view in enumerate(views):
            k = jax.random.fold_in(key, i)
            diff, unequal = jax.device_get(
                check(*inputs(k, view), k, variant))
            worst = max(worst, float(diff))
            if int(unequal):
                raise AssertionError(
                    f"keyed {variant} kernel at {view}: {int(unequal)} "
                    f"elements differ from the explicit mask's, max|diff| "
                    f"{float(diff):.3e}")
    return (f"keyed vs mask kernels (dasha, mvr) in {len(views)} leaf "
            f"views: max|diff|={worst:.3e}, bit-equal")


def phase_fed(compiles: list) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.fed_scale_bench import _sampled_sim
    from repro.kernels import ops

    n, c = FED_N, FED_C
    done = _phase("fed", compiles)
    vec, state, metric = _sampled_sim(n, c)
    vec.chunk = FED_CHUNK
    rounds = 3 * vec.chunk
    res = vec.run(state, rounds, metric_fn=metric)
    trace = res.traces["metric"]
    if trace.shape != (rounds,) or not np.all(np.isfinite(trace)):
        raise AssertionError(f"campaign metric trace not finite: {trace}")

    # replay the next chunk: gather its slab, run its scan, write back
    from repro.methods.substrates import slab_layout
    st = res.state
    sels = vec.substrate.cohort_schedule(st.key, vec.chunk)
    uniq, loc = slab_layout(sels, n)
    ones = jnp.ones((vec.chunk, c), jnp.float32)
    slab, full_h, _ = vec._slab_enter(st, uniq)
    slab, _ = vec._chunk_fn_slab(vec.chunk, metric)(
        slab, ones, ones, jnp.asarray(sels), jnp.asarray(loc))
    idx = jnp.asarray(uniq)
    _assert_kernel(ops.slab_writeback.lower(full_h, idx, slab.h_local),
                   "the slab writeback")
    for accumulate in (False, True):
        stores = [np.asarray(ops.slab_writeback(
            full_h, idx, slab.h_local, accumulate=accumulate,
            use_kernel=use_kernel)) for use_kernel in (True, False)]
        if stores[0].tobytes() != stores[1].tobytes():
            bad = int(np.sum(np.any(stores[0] != stores[1], axis=1)))
            raise AssertionError(f"slab writeback (accumulate={accumulate})"
                                 f": kernel and scatter differ in {bad} rows")
    done(f"n={n} C={c} d={full_h.shape[1]} rounds={rounds} "
         f"final metric={float(trace[-1]):.6e}; writeback of "
         f"{int(np.sum(uniq < n))} rows bit-equal to XLA scatter "
         f"(set and accumulate)")


def phase_node_mesh(compiles: list) -> None:
    import jax
    import numpy as np

    from repro.launch import train

    done = _phase("node-mesh", compiles)
    argv = TRAIN_ARGS + ["--layers", str(CMP_LAYERS), "--variant", "dasha",
                         "--use-kernel", "--dtype", "float32"]
    # a TPU's default precision rounds f32 matmul operands to bf16, and the
    # two placements tile the matmuls differently: compare true f32 runs
    with jax.default_matmul_precision("highest"):
        mesh_run = train.main(argv)
        if mesh_run.mesh is None or mesh_run.mesh.devices.size != 4:
            raise AssertionError("the trainer did not build a 4-device mesh")
        stats = [(d.id, (d.memory_stats() or {}).get("peak_bytes_in_use"))
                 for d in jax.devices()]
        print("[smoke] node-mesh peak_bytes_in_use per device: " + ", ".join(
            f"{i}:{'not reported' if p is None else f'{p / 2**30:.3f} GiB'}"
            for i, p in stats), flush=True)
        mesh_log, mesh_x = mesh_run.log, jax.device_get(mesh_run.state.x)
        del mesh_run
        one_run = train.main(argv + ["--devices", "1"])
        one_log, one_x = one_run.log, jax.device_get(one_run.state.x)
        del one_run

    worst_loss = 0.0
    for a, b in zip(mesh_log, one_log, strict=True):
        rel = abs(a["loss"] - b["loss"]) / max(abs(b["loss"]), 1.0)
        worst_loss = max(worst_loss, rel)
        if rel > F32_RTOL or a["step"] != b["step"]:
            raise AssertionError(f"loss differs: mesh {a} vs one chip {b}")
    leaves = zip(jax.tree_util.tree_leaves(mesh_x),
                 jax.tree_util.tree_leaves(one_x), strict=True)
    diff = ref = 0.0
    for a, b in leaves:
        diff = max(diff, float(np.max(np.abs(a - b))))
        ref = max(ref, float(np.max(np.abs(b))))
    if diff > F32_RTOL * max(ref, 1.0):
        raise AssertionError(f"final params differ by {diff:.3e} "
                             f"(max |param| {ref:.3e})")
    done(f"{len(mesh_log)} logged steps; largest relative loss difference "
         f"{worst_loss:.3e}; largest param difference {diff:.3e} "
         f"(max |param| {ref:.3e})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the node axis across four chips")
    ap.add_argument("--phase", default=None,
                    choices=["train", "nemotron", "kernel-vs-jnp", "fed"],
                    help="run this one-chip phase alone")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"[smoke] device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("[smoke] no TPU found: nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"[smoke] --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    from repro.analysis import recompile
    from repro.launch.train import enable_compile_cache
    enable_compile_cache()
    compiles: list = []
    recompile.subscribe(lambda event, seconds: compiles.append(seconds))

    one_chip = {"train": phase_train, "nemotron": phase_nemotron,
                "kernel-vs-jnp": phase_kernel_vs_jnp, "fed": phase_fed}
    phases = [phase_node_mesh] if args.chips == 4 else \
        [one_chip[args.phase]] if args.phase else list(one_chip.values())
    for phase in phases:
        phase(compiles)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
