"""End-to-end DASHA training driver.

    PYTHONPATH=src python -m repro.launch.train --arch starcoder2-3b \
        --steps 200 --nodes 4 --batch 2 --seq 128 [--full [--layers N]] \
        --compression 0.03125 --variant dasha \
        [--ckpt out/ckpt --ckpt-every 1 --resume]

The whole experiment runs through the compiled driver (DESIGN.md §10):
batches are drawn INSIDE the jitted scan (``data_fn``), so the host only
wakes up once per ``--chunk`` rounds to log and checkpoint.  Checkpoints
hold the FULL ``MethodState`` (params, h_i, g_i, optimizer state, RNG key,
round counter), so ``--resume`` continues bit-identically with the same
data stream (per-round data keys are ``fold_in(data_seed, t)``).

By default the driver runs the reduced (smoke) config of the selected
architecture family.  ``--full`` selects the published config; ``--layers
N`` cuts its depth to N layers, ``--experts N`` the routed experts it holds
(the router still scores all of them) and ``--vocab N`` its vocabulary to
the first N rows, so that one chip's share of a deployment fits one chip.
A cut config prints its chip share, and a held-expert model the tokens its
experts were routed and dropped at each log.

Placement: with one visible device the n nodes are vmapped on it.  With
several (``--devices`` caps how many are used), the nodes go onto a
``("data", "model")`` mesh of those devices with ``data`` = device count:
each device holds its nodes' h_i, g_i and batch, and params, g and the
server optimizer are replicated.  ``REPRO_EXAMPLE_ROUNDS`` overrides
``--steps`` for CI smoke jobs.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import os
import time
from pathlib import Path
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.checkpoint.io import checkpoint_step, load_state, save_state
from repro.compress.treelevel import (kernel_draw_count,
                                      kernel_grad_dtype_count,
                                      kernel_layout_count)
from repro.configs import get_config, get_smoke_config
from repro.data.pipeline import SyntheticTextConfig, make_node_batches
from repro.methods import MethodState
from repro.methods.driver import Driver
from repro.models import init_params, lm
from repro.models.sharding import dp_axes, param_specs, to_shardings
from repro.optim.base import AdamState
from repro.optim.distributed import (DashaTrainConfig, make_method,
                                     payload_frac)

#: the compile cache's home when JAX_COMPILATION_CACHE_DIR is unset: one
#: fixed directory in the checkout (the path is part of the cache key)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed; otherwise the cache goes to :data:`CACHE_DIR`."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))


class TrainRun(NamedTuple):
    """What :func:`main` leaves behind: the final state, the mesh it lived
    on (``None`` on one device) and one ``{"step", "loss", "g_norm_sq"}``
    record per logged step."""

    state: MethodState
    mesh: Optional[jax.sharding.Mesh]
    log: List[Dict[str, float]]


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--full", action="store_true",
                    help="use the published config instead of the smoke one")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the config's depth to this many layers")
    ap.add_argument("--experts", type=int, default=None,
                    help="routed experts this chip holds (ids 0..N-1)")
    ap.add_argument("--vocab", type=int, default=None,
                    help="vocabulary rows this chip holds (ids 0..N-1)")
    ap.add_argument("--dtype", default=None, choices=["bfloat16", "float32"],
                    help="parameter dtype (default: the config's)")
    ap.add_argument("--steps", type=int,
                    default=int(os.environ.get("REPRO_EXAMPLE_ROUNDS", 100)))
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--devices", type=int, default=None,
                    help="devices for the node mesh (default: all visible)")
    ap.add_argument("--batch", type=int, default=2, help="per-node batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--gamma", type=float, default=0.003)
    ap.add_argument("--compression", type=float, default=1 / 32)
    ap.add_argument("--mode", default="independent",
                    choices=["independent", "permk"])
    ap.add_argument("--variant", default="dasha",
                    choices=["dasha", "mvr", "page", "sync_mvr"])
    ap.add_argument("--mvr-b", type=float, default=0.1)
    ap.add_argument("--coin-p", type=float, default=0.25,
                    help="PAGE / SYNC-MVR sync-round probability")
    ap.add_argument("--server-opt", default="adam", choices=["sgd", "adam"])
    ap.add_argument("--use-kernel", action="store_true",
                    help="fused Pallas dasha_update path")
    ap.add_argument("--ckpt", default=None,
                    help="full-MethodState checkpoint directory")
    ap.add_argument("--ckpt-every", type=int, default=1,
                    help="checkpoint cadence in chunks")
    ap.add_argument("--resume", action="store_true",
                    help="continue from --ckpt (bit-identical to an "
                         "uninterrupted run)")
    ap.add_argument("--chunk", type=int, default=None,
                    help="scan-segment length (default: --log-every)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def arch_config(arch: str, full: bool, layers: Optional[int] = None,
                dtype: Optional[str] = None, experts: Optional[int] = None,
                vocab: Optional[int] = None):
    """The model config of ``--arch``/``--full``, its depth cut to
    ``layers``, the routed experts it holds to ``experts``, its vocabulary
    to ``vocab`` rows and its parameter dtype set to ``dtype`` when given
    (no other field changes)."""
    cfg = get_config(arch) if full else get_smoke_config(arch)
    cut = {"num_layers": layers, "experts_held": experts,
           "vocab_size": vocab, "dtype": dtype}
    cut = {k: v for k, v in cut.items() if v is not None}
    if experts is not None and not cfg.num_experts:
        raise SystemExit(f"--experts: {cfg.name} has no routed experts")
    return dataclasses.replace(cfg, **cut) if cut else cfg


def chip_share(cfg, uncut) -> str:
    """What of the uncut config this one holds: layers, routed experts and
    vocabulary rows."""
    parts = [f"layers {cfg.num_layers}/{uncut.num_layers}"]
    if cfg.num_experts:
        parts.append(f"experts {cfg.held_experts}/{cfg.num_experts}")
    parts.append(f"vocab rows {cfg.vocab_size}/{uncut.vocab_size}")
    return " ".join(parts)


def node_mesh(n_nodes: int, n_devices: Optional[int] = None):
    """The ``("data", "model")`` mesh the nodes are spread over, or
    ``None`` when one device holds them all."""
    devices = jax.devices()[:n_devices]
    if len(devices) == 1:
        return None
    if n_nodes % len(devices):
        raise SystemExit(f"--nodes {n_nodes} is not a multiple of the "
                         f"{len(devices)} devices")
    return jax.make_mesh((len(devices), 1), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto),
                         devices=devices)


def node_state_specs(cfg, mesh, dasha: DashaTrainConfig,
                     state_s: MethodState) -> MethodState:
    """PartitionSpecs of the trainer's ``MethodState`` on ``mesh``, for
    the state ``state_s`` (shapes only).

    Params, g and the server optimizer's moments follow the param rule
    (:func:`repro.models.sharding.param_specs`); each node's h_i / g_i
    puts its node axis on the data axes, the param rule after it; key,
    round counter and payload count are replicated.  The ``x`` specs are
    also the ones pinned onto each node's gradient."""
    dp = dp_axes(mesh)
    p_specs = param_specs(cfg, state_s.x, mesh)
    per_node = jax.tree_util.tree_map(
        lambda s: P(dp, *tuple(s)), p_specs,
        is_leaf=lambda x: isinstance(x, P))
    if dasha.server_opt == "adam":
        opt_specs: Any = AdamState(mu=p_specs, nu=p_specs, count=P())
    else:
        opt_specs = jax.tree_util.tree_map(lambda x: P(), state_s.opt_state)
    return MethodState(x=p_specs, g=p_specs, g_local=per_node,
                       h_local=per_node, opt_state=opt_specs, key=P(),
                       t=P(), bits_sent=P())


def main(argv=None) -> TrainRun:
    args = parse_args(argv)
    enable_compile_cache()
    cfg = arch_config(args.arch, args.full, args.layers, args.dtype,
                      args.experts, args.vocab)
    mesh = node_mesh(args.nodes, args.devices)
    key = jax.random.PRNGKey(args.seed)
    k_init, k_state, k_data = jax.random.split(key, 3)

    params_s = jax.eval_shape(lambda k: init_params(cfg, k), k_init)
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params_s))
    cut = "" if args.layers is None else \
        f" (depth cut from {arch_config(args.arch, args.full).num_layers})"
    print(f"[train] arch={cfg.name} layers={cfg.num_layers}{cut} "
          f"d_model={cfg.d_model} vocab={cfg.vocab_size} dtype={cfg.dtype} "
          f"params={n_params/1e6:.2f}M nodes={args.nodes} "
          f"devices={1 if mesh is None else mesh.devices.size} "
          f"tokens/step={args.nodes*args.batch*args.seq}")
    if (args.layers, args.experts, args.vocab) != (None, None, None):
        print("[train] chip share: " + chip_share(
            cfg, arch_config(args.arch, args.full)))

    dasha = DashaTrainConfig(
        gamma=args.gamma, compression=args.compression, mode=args.mode,
        variant=args.variant, b=args.mvr_b, p=args.coin_p,
        n_nodes=args.nodes,
        server_opt=args.server_opt, use_kernel=args.use_kernel,
        spmd_axes=None if mesh is None else ("data",))

    if args.use_kernel:
        # the nodes' gradients, in the dtypes the oracle makes them in
        per_node = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((args.nodes,) + x.shape,
                                           x.dtype), params_s)
        specs = None if mesh is None else jax.tree_util.tree_map(
            lambda _: P("data"), per_node)
        print("[train] mask draw: " + str(kernel_draw_count(
            per_node, mode=args.mode, p=args.compression, specs=specs,
            mesh=mesh)))
        print("[train] node update: " + str(kernel_layout_count(
            per_node, specs=specs, mesh=mesh)))
        print("[train] gradient: " + str(kernel_grad_dtype_count(
            per_node)))

    def node_loss(p, b):
        return lm.loss_fn(cfg, p, b)[0]

    method = make_method(dasha, node_loss)

    def init_state(k_params, k_method):
        return method.init(init_params(cfg, k_params), k_method,
                           init_mode="zeros")

    if mesh is None:
        state = jax.jit(init_state)(k_init, k_state)
    else:
        state_s = jax.eval_shape(init_state, k_init, k_state)
        specs = node_state_specs(cfg, mesh, dasha, state_s)
        state = jax.jit(init_state, out_shardings=to_shardings(specs, mesh))(
            k_init, k_state)
        method = make_method(dasha, node_loss, grad_specs=specs.x)
    done = 0
    if args.resume:
        if not args.ckpt:
            raise SystemExit("--resume requires --ckpt")
        state = load_state(args.ckpt, state)
        done = checkpoint_step(args.ckpt)
        print(f"[train] resumed from {args.ckpt} at step {done}")

    tcfg = SyntheticTextConfig(vocab_size=cfg.vocab_size, seq_len=args.seq)
    data_kw: Dict[str, Any] = {}
    if cfg.arch_type == "vlm":
        data_kw = dict(with_images=cfg.num_image_tokens,
                       d_model=cfg.d_model, dtype=cfg.jax_dtype)
    if cfg.arch_type == "audio":
        data_kw = dict(with_frames=cfg.num_audio_frames,
                       d_model=cfg.d_model, dtype=cfg.jax_dtype)
    node_axis = None if mesh is None else NamedSharding(mesh, P("data"))

    def data_fn(k, t):
        batch = make_node_batches(k, tcfg, args.nodes, args.batch, **data_kw)
        if node_axis is not None:
            batch = jax.lax.with_sharding_constraint(batch, node_axis)
        return batch

    def g_norm_sq(s, b):
        return sum(jnp.sum(jnp.square(x))
                   for x in jax.tree_util.tree_leaves(s.g))

    # held-out eval batch, evaluated once per chunk at the logged step
    # (fresh — not a scan-held value from the chunk's first round)
    k_data, k_eval = jax.random.split(k_data)
    eval_batch = jax.tree_util.tree_map(
        lambda x: x.reshape((-1,) + x.shape[2:]),
        make_node_batches(k_eval, tcfg, args.nodes, args.batch, **data_kw))
    # the held-out loss, and for a held-expert model the tokens its experts
    # were routed and dropped on that batch, from one forward pass
    eval_fn = jax.jit(lambda p: lm.loss_fn(cfg, p, eval_batch)[1])

    frac = payload_frac(dasha)
    chunk = args.chunk or args.log_every
    drv = Driver(method, data_fn=data_fn,
                 metrics={"g_norm_sq": g_norm_sq}, chunk=chunk)
    log: List[Dict[str, float]] = []
    t0 = time.time()

    def hook(ms, t, tr):
        ev = jax.device_get(eval_fn(ms.x))
        rec = {"step": done + t, "loss": float(ev["loss"]),
               "g_norm_sq": float(tr["g_norm_sq"][-1])}
        experts = ""
        if "dropped" in ev:
            rec.update(expert_tokens=ev["expert_tokens"].tolist(),
                       dropped=int(ev["dropped"]))
            experts = (f"routed/expert={ev['expert_tokens'].sum(0).tolist()} "
                       f"dropped={rec['dropped']} ")
        log.append(rec)
        print(f"[train] step {rec['step']:5d} "
              f"loss={rec['loss']:.4f} "
              f"|g|^2={rec['g_norm_sq']:.3e} "
              f"payload={frac:.4f} "
              f"coords/node={float(ms.bits_sent):.3e} {experts}"
              f"({time.time()-t0:.1f}s)")
        if args.ckpt:
            save_state(args.ckpt, ms)

    remaining = args.steps - done
    if remaining <= 0:
        print(f"[train] checkpoint already at step {done} >= {args.steps}")
        return TrainRun(state, mesh, log)
    # the sharding specs inside the step name mesh axes: trace under it
    with contextlib.nullcontext() if mesh is None else jax.set_mesh(mesh):
        state, _ = drv.run(state, remaining, data_key=k_data,
                           checkpoint=hook, checkpoint_every=args.ckpt_every,
                           donate_input=True)
    jax.block_until_ready(state)
    if args.ckpt:
        print(f"[train] saved full method state to {args.ckpt}")
    sps = remaining / max(time.time() - t0, 1e-9)
    print(f"[train] done: {remaining} rounds at {sps:.2f} steps/s "
          f"(compilation included)")
    return TrainRun(state, mesh, log)


if __name__ == "__main__":
    main()
