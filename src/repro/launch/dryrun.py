import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first init.

DOC = """Multi-pod dry-run: lower + compile every (architecture x input-shape) on
the production mesh, print memory/cost analysis, and emit roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch starcoder2-3b \
        --shape train_4k [--multi-pod] [--all] [--json out.json]

Success criterion (deliverable e): ``.lower().compile()`` succeeds for the
16x16 single-pod mesh AND the 2x16x16 multi-pod mesh for every supported
(arch x shape) pair.
"""

import argparse
import dataclasses
import json
import sys
import time
from typing import Dict, Optional

import jax

from repro.configs import all_arch_ids, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import memory_per_device
from repro.launch.specs import SHAPES, input_specs, shape_supported
from repro.optim.distributed import DashaTrainConfig


def dryrun_one(arch: str, shape: str, *, multi_pod: bool = False,
               dasha: Optional[DashaTrainConfig] = None,
               moe_dispatch: Optional[str] = None,
               serve_attn_hd_shard: bool = True,
               verbose: bool = True) -> Dict:
    """Lower+compile one (arch, shape) pair; returns the roofline row."""
    cfg = get_config(arch)
    if moe_dispatch and cfg.num_experts:
        cfg = dataclasses.replace(cfg, moe_dispatch=moe_dispatch)
    ok, why = shape_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape, "status": "skip", "why": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    try:
        from repro.models.sharding import to_shardings
        spec = input_specs(cfg, shape, mesh, dasha=dasha,
                           serve_attn_hd_shard=serve_attn_hd_shard)
        # donate the train/decode state (params+estimators / KV cache) so XLA
        # aliases it in-place instead of double-buffering ~2x the state.
        donate = (0,) if spec.static.get("kind") == "train" else \
            ((1,) if spec.static.get("kind") == "decode" else ())
        with jax.set_mesh(mesh):
            jitted = jax.jit(spec.fn,
                             in_shardings=to_shardings(spec.in_shardings,
                                                       mesh),
                             out_shardings=to_shardings(spec.out_shardings,
                                                        mesh),
                             donate_argnums=donate)
            lowered = jitted.lower(*spec.args)
            compiled = lowered.compile()
    except Exception as e:  # a failure here is a bug in our sharding config
        return {"arch": arch, "shape": shape, "status": "FAIL",
                "error": f"{type(e).__name__}: {e}"[:500]}
    dt = time.time() - t0

    import numpy as _np

    from repro.launch import analytic
    from repro.launch.hlo_parse import collective_bytes_loop_aware
    from repro.launch.roofline import Roofline  # noqa: local to keep the
    # module import light for --help

    def _tree_bytes(tree):
        return float(sum(_np.prod(l.shape) * l.dtype.itemsize
                         for l in jax.tree_util.tree_leaves(tree)))

    mem = memory_per_device(compiled)
    n_active = cfg.active_param_count()
    kind = spec.static.get("kind")
    tokens = spec.static.get("tokens", 0)
    info = SHAPES[shape]
    if kind == "train":
        state_s = spec.args[0]
        params_bytes = _tree_bytes(state_s.params)
        state_bytes = (_tree_bytes(state_s.h_local)
                       + _tree_bytes(state_s.g_local)
                       + _tree_bytes(state_s.g))
        ana = analytic.train_analytics(
            cfg, seq=info["seq"], global_batch=info["global_batch"],
            n_active=n_active, params_bytes=params_bytes,
            state_bytes=state_bytes,
            state_itemsize=4)
    elif kind == "prefill":
        ana = analytic.prefill_analytics(
            cfg, seq=info["seq"], global_batch=info["global_batch"],
            n_active=n_active, params_bytes=_tree_bytes(spec.args[0]))
    else:
        ana = analytic.decode_analytics(
            cfg, seq=info["seq"], global_batch=info["global_batch"],
            n_active=n_active, params_bytes=_tree_bytes(spec.args[0]),
            cache_bytes=_tree_bytes(spec.args[1]))

    mult = 6.0 if kind == "train" else 2.0
    model_flops = mult * n_active * tokens
    det = collective_bytes_loop_aware(compiled.as_text())
    coll = float(sum(v for k, v in det.items() if not k.endswith("_count")))
    rl = Roofline(flops=ana["flops"], hbm_bytes=ana["hbm_bytes"],
                  coll_bytes=coll, chips=chips, coll_detail=det,
                  model_flops=model_flops)

    # raw cost_analysis kept for reference (undercounts loops; see
    # hlo_parse.py docstring)
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]

    row = {"arch": arch, "shape": shape, "status": "ok",
           "mesh": "x".join(str(s) for s in mesh.devices.shape),
           "chips": chips, "compile_s": round(dt, 1),
           "kind": kind, "tokens": tokens,
           "model_gflops": model_flops / 1e9,
           "hlo_raw_gflops": float(cost.get("flops", 0.0)) / 1e9,
           **mem, **rl.row(),
           "coll_detail": {k: round(v) for k, v in rl.coll_detail.items()
                           if v}}
    if verbose:
        print(f"[dryrun] {arch} x {shape} mesh={row['mesh']} "
              f"compile={dt:.1f}s peak={mem['peak_gb']:.2f}GB/dev "
              f"bottleneck={row['bottleneck']} "
              f"t=(C {row['t_compute_s']:.3e}, M {row['t_memory_s']:.3e}, "
              f"X {row['t_collective_s']:.3e})s")
        print(f"  memory_analysis: {compiled.memory_analysis()}")
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + [None], help="input shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true",
                    help="run single-pod AND multi-pod")
    ap.add_argument("--json", default=None, help="write rows to this file")
    ap.add_argument("--compression", type=float, default=1 / 32)
    ap.add_argument("--mode", default="independent",
                    choices=["independent", "permk"])
    ap.add_argument("--variant", default="dasha", choices=["dasha", "mvr"])
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--state-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--server-opt", default="sgd", choices=["sgd", "adam"])
    ap.add_argument("--moe-dispatch", default=None,
                    choices=[None, "gather", "einsum"])
    ap.add_argument("--serve-attn-replicate", action="store_true",
                    help="replicate attention weights on serve paths for "
                         "non-divisible head counts (kills the per-layer "
                         "hd-partial all-reduces)")
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else all_arch_ids()
    shapes = [args.shape] if args.shape else list(SHAPES)
    pods = [args.multi_pod] if not args.both_meshes else [False, True]

    rows, failures = [], 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                dasha = DashaTrainConfig(
                    gamma=0.01, compression=args.compression, mode=args.mode,
                    variant=args.variant, seq_shard=args.seq_shard,
                    fsdp=args.fsdp, state_dtype=args.state_dtype,
                    server_opt=args.server_opt)
                row = dryrun_one(
                    arch, shape, multi_pod=mp, dasha=dasha,
                    moe_dispatch=args.moe_dispatch,
                    serve_attn_hd_shard=not args.serve_attn_replicate)
                rows.append(row)
                if row["status"] == "FAIL":
                    failures += 1
                    print(f"[dryrun] FAIL {arch} x {shape}: {row['error']}",
                          file=sys.stderr)
                elif row["status"] == "skip":
                    print(f"[dryrun] skip {arch} x {shape}: {row['why']}")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1, default=str)
        print(f"[dryrun] wrote {len(rows)} rows to {args.json}")
    n_ok = sum(r["status"] == "ok" for r in rows)
    print(f"[dryrun] {n_ok} ok / {sum(r['status']=='skip' for r in rows)} "
          f"skip / {failures} FAIL")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
