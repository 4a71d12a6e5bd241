"""Cells that train a Nemotron-H chip share (Mamba-2, held-expert MoE and
attention layers) with DASHA through the program's compiled run driver.

As :mod:`bench.kinds.train`, whose timed path, readings and comparison
this kind keeps: set-up builds ``make_method`` on the config that
``launch/train.py``'s ``arch_config`` gives for the depth, experts held
and vocabulary rows of the configuration, hands one ``Driver`` the token
stream and the trainer's log hook, and runs the first chunk.  What
differs: the weights (:mod:`bench.gen_nemotron_h`), the reference
(:mod:`bench.ref_nemotron_h`), the counts (:mod:`bench.counts_nemotron_h`)
and the log hook, which also reads the tokens routed to each held expert
on the held-out batch from the same forward pass as the held-out loss.
"""
from __future__ import annotations

import sys
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, gen, gen_nemotron_h
from bench import counts_nemotron_h as counts
from bench.kinds import train

#: configuration key -> the program's ArchConfig attribute, which must agree
_ARCH_KEYS = {"hidden_size": "d_model", "num_hidden_layers": "num_layers",
              "vocab_size": "vocab_size", "mamba_num_heads": "ssm_nheads",
              "mamba_head_dim": "ssm_headdim", "n_groups": "ssm_ngroups",
              "ssm_state_size": "ssm_state", "conv_kernel": "conv_width",
              "chunk_size": "ssd_chunk",
              "published_n_routed_experts": "num_experts",
              "n_routed_experts": "held_experts",
              "expert_first": "expert_first",
              "num_experts_per_tok": "experts_per_token",
              "moe_intermediate_size": "d_ff",
              "moe_shared_expert_intermediate_size": "shared_expert_ff",
              "routed_scaling_factor": "routed_scale",
              "num_attention_heads": "num_heads",
              "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
              "norm_eps": "norm_eps", "dtype": "dtype"}

#: faults the calibration plants in this kind's reference
FAULTS = ("unchanged", "capacity", "softmax", "one_group")

#: leaves whose second axis counts experts
_EXPERT_LEAVES = ("w_in", "w_out")


def _piece_axes(path) -> int:
    """How many leading axes of the leaf at ``path`` count its pieces: a
    layer stack's layer axis, and a held-expert leaf's expert axis too."""
    keys = [getattr(k, "key", None) for k in path]
    if keys[0] not in gen_nemotron_h.KINDS.values():
        return 0
    return 2 if keys[0] == "moe" and keys[-1] in _EXPERT_LEAVES else 1


def _pieces(path, x) -> int:
    """How many pieces :func:`slices` cuts the leaf at ``path`` into."""
    return int(np.prod(x.shape[:_piece_axes(path)], dtype=np.int64))


def slices(tree) -> list:
    """The pieces every reading is taken over, flattened: each leaf of a
    layer stack cut into its layers, a held-expert leaf into its layers'
    experts, so that a fault in one layer or one expert is not averaged
    away by the rest of its stack."""
    return [piece for path, x in jax.tree_util.tree_leaves_with_path(tree)
            for piece in x.reshape(_pieces(path, x), -1)]


def piece_sq(tree) -> jax.Array:
    """The squared norms of :func:`slices`' pieces, (pieces,) f32, summed
    over each piece's own axes: the timed steps' metric, which must not
    lay a stacked leaf out again to flatten its pieces."""
    return jnp.concatenate([
        jnp.sum(jnp.square(x.astype(jnp.float32)),
                axis=tuple(range(_piece_axes(path), x.ndim))).reshape(-1)
        for path, x in jax.tree_util.tree_leaves_with_path(tree)])


def owners(tree) -> np.ndarray:
    """The leaf index of each of :func:`slices`' pieces."""
    pieces = [_pieces(path, x)
              for path, x in jax.tree_util.tree_leaves_with_path(tree)]
    return np.repeat(np.arange(len(pieces)), pieces)


class Cell(train.Cell):
    def __init__(self, config: Dict, traffic: Dict, seed: int, chips: int):
        from repro.launch import train as launch
        from repro.methods.driver import Driver
        from repro.models import init_params, lm
        from repro.optim.distributed import DashaTrainConfig, make_method

        self.config, self.traffic = config, traffic
        cfg = launch.arch_config(
            config["arch"], bool(config["published"]),
            int(config["num_hidden_layers"]), config["dtype"],
            int(config["n_routed_experts"]), int(config["vocab_size"]))
        for key, attr in _ARCH_KEYS.items():
            if getattr(cfg, attr) != config[key]:
                raise ValueError(f"the program's {config['arch']} has "
                                 f"{attr}={getattr(cfg, attr)!r}, the "
                                 f"configuration {key}={config[key]!r}")
        if cfg.pattern != gen_nemotron_h.pattern(config) or cfg.use_rope \
                or cfg.tie_embeddings:
            raise ValueError("the program's layers are not the "
                             "configuration's")
        self.cfg = cfg
        nodes = self.nodes = int(config["nodes"])
        self.chunk = int(traffic["chunk"])
        self.tokens_per_step = nodes * int(traffic["batch_per_node"]) \
            * int(traffic["seq"])
        if chips != 1:
            raise ValueError("a train cell runs its nodes on one chip")
        k_params, self.k_method, self.k_data, k_eval, self.k_proj = \
            jax.random.split(train.seed_key(seed), 5)
        self._make_params = jax.jit(
            lambda k: gen_nemotron_h.params(k, config))
        self.k_params = k_params
        params = self._make_params(k_params)
        want = jax.eval_shape(lambda k: init_params(cfg, k), k_params)
        got = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        if want != got:
            raise ValueError("the benchmark's weights do not have the "
                             "program's parameter layout")
        self.owners = owners(want)

        dasha = DashaTrainConfig(
            gamma=float(config["gamma"]),
            compression=float(traffic["compression"]),
            mode=traffic["mode"], variant=traffic["variant"],
            b=float(traffic.get("mvr_b", 0.1)), n_nodes=nodes,
            server_opt=config["server_opt"],
            use_kernel=bool(config["use_kernel"]),
            state_dtype=config["state_dtype"])

        def node_loss(p, b):
            return lm.loss_fn(cfg, p, b)[0]

        method = make_method(dasha, node_loss)
        self.state = jax.jit(
            lambda p, k: method.init(p, k, init_mode="zeros"),
            donate_argnums=0)(params, self.k_method)
        del params
        vocab = cfg.vocab_size
        self.drv = Driver(method,
                          data_fn=lambda k, t: gen.node_batches(
                              k, traffic, vocab, nodes),
                          metrics={"g_sq": lambda s, b: piece_sq(s.g)},
                          chunk=self.chunk)
        eval_batch = gen.node_batches(k_eval, traffic, vocab, nodes)
        self.eval_batch = {k: v.reshape((-1,) + v.shape[2:])
                           for k, v in eval_batch.items()}

        def held_out(p):
            # the log hook's forward is the run driver's metric of the run
            with jax.named_scope("driver.metrics"):
                return lm.loss_fn(cfg, p, self.eval_batch)[1]
        self.eval_fn = jax.jit(held_out)
        self.log = []

    def _hook(self, ms, t, tr):
        """The trainer's log hook: held-out loss, |g|^2 and the tokens
        routed to each held expert (per MoE layer) on the host."""
        with jax.profiler.TraceAnnotation("bench.log_hook"):
            ev = jax.device_get(self.eval_fn(ms.x))
            self.log.append({"step": int(t), "loss": float(ev["loss"]),
                             "g_norm_sq": float(jnp.sum(tr["g_sq"][-1])),
                             "expert_tokens": np.asarray(
                                 ev["expert_tokens"]),
                             "dropped": int(ev["dropped"])})

    def warm(self) -> None:
        """The first chunk, which compiles; keep what the check reads,
        over :func:`slices`' pieces."""
        self.call()
        g_sq = np.asarray(jax.device_get(self._traces["g_sq"]))
        x0 = self._make_params(self.k_params)
        dx = jax.jit(lambda a, b: train._sq_leaves(
            [u.astype(jnp.float32) - v.astype(jnp.float32)
             for u, v in zip(slices(a), slices(b))]))(self.state.x, x0)
        proj = jax.jit(lambda g, k: check.project(slices(g), k))(
            self.state.g, self.k_proj)
        self.readings = {"g_sq": g_sq, "dx_sq": np.asarray(dx),
                         "g_proj": np.asarray(proj)}
        del x0

    def counts(self) -> Dict[str, float]:
        variant, seq = self.traffic["variant"], int(self.traffic["seq"])
        out = {"flops_per_unit": counts.train_step_flops(
                   self.config, variant, seq, self.tokens_per_step),
               "update_bytes_per_unit": counts.node_update_min_bytes(
                   self.config, variant, self.nodes),
               "gmm_flops_per_unit": counts.held_expert_flops(
                   self.config, variant, self.tokens_per_step)}
        if self.log:
            # per MoE layer: the busiest held expert over the held mean
            tok = self.log[-1]["expert_tokens"].astype(np.float64)
            mean = tok.mean(-1)
            out["moe_imbalance"] = float(np.max(
                tok.max(-1) / np.where(mean > 0, mean, 1.0)))
            # the seed's routing sets the grouped products' rows, and so
            # part of the step's time: logged beside the run's throughput
            expected = counts.expected_held_rows(self.config,
                                                 self.tokens_per_step)
            print(f"[nemotron] rows routed to the held experts per MoE "
                  f"layer on the held-out batch at the last log: "
                  f"{tok.sum(-1).astype(int).tolist()} (expected "
                  f"{expected:.0f})", file=sys.stderr, flush=True)
        return out

    def reference(self, *, cache=None, precision=None, fault: str = ""):
        """The reference's readings over the first chunk, from the seed
        (as :meth:`bench.kinds.train.Cell.reference`)."""
        from bench import ref_nemotron_h
        mm = jnp.dtype(precision or self.config["reference_matmul"])
        cache = {} if cache is None else cache
        ref = cache.get((mm, fault))
        if ref is None:
            ref = cache[(mm, fault)] = ref_nemotron_h.Reference(
                self.config, self.traffic, mm_dtype=mm, fault=fault)
        x0 = self._make_params(self.k_params)
        ulp = jax.jit(lambda x: check.ulp_sq(slices(x)))(x0)
        g_sq, dx_sq, g = ref.follow(x0, self.k_method, self.k_data,
                                    self.chunk, split=slices)
        proj = jax.jit(lambda g, k: check.project(slices(g), k))(
            g, self.k_proj)
        return {"g_sq": np.asarray(g_sq), "dx_sq": np.asarray(dx_sq),
                "g_proj": np.asarray(proj), "ulp_sq": np.asarray(ulp)}

    def compare(self, prog: Dict, ref: Dict) -> Dict[str, float]:
        """The numbers ``correct`` is decided by (PERF.md gives why these):
        :meth:`bench.kinds.train.Cell.compare`'s four over whole leaves,
        the pieces' readings summed back into their leaves, and over the
        pieces of :func:`slices` (one layer, or one held expert of one
        layer):

        * ``grad_piece``: the first gradient, the worst piece's norm gap;
        * ``g_rms_piece``: g at the end of the chunk, the root mean square
          over the pieces of their gaps of directions."""
        own = np.eye(self.owners.max() + 1)[self.owners]   # piece -> leaf

        def leaves(r):
            return {k: own.T @ v if k == "g_proj" else v @ own
                    for k, v in r.items()}
        out = super().compare(leaves(prog), leaves(ref))
        keep = check.moving_leaves(ref["g_sq"][0])
        out["grad_piece"] = check.worst_leaf_gap(prog["g_sq"][0],
                                                 ref["g_sq"][0], keep)
        out["g_rms_piece"] = check.rms_projection_gap(
            prog["g_proj"], ref["g_proj"], ref["g_sq"][-1], keep)
        return out
