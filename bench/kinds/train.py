"""Cells that train a language model with DASHA through the program's
compiled run driver.

Set-up builds the program's method (``optim.distributed.make_method``) on
the configuration that ``launch/train.py``'s ``arch_config`` gives, with
the nodes vmapped on one chip, and hands one ``methods.driver.Driver`` the
benchmark's token stream (``data_fn``), a per-leaf ``|g|^2`` metric and
the trainer's log hook (held-out loss and ``|g|^2`` between chunks).  The
first chunk runs in set-up: it compiles every program the window calls,
and its results are what the reference checks.  The window then calls the
same driver, one whole chunk at a time, on the state the chunk before left.
"""
from __future__ import annotations

import gc
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, counts, gen

#: the model config keys that must match the program's ArchConfig
_ARCH_KEYS = {"d_model": "d_model", "n_layer": "num_layers",
              "vocab_size": "vocab_size", "d_state": "ssm_state",
              "headdim": "ssm_headdim", "expand": "ssm_expand",
              "d_conv": "conv_width", "chunk_size": "ssd_chunk",
              "ngroups": "ssm_ngroups", "tie_embeddings": "tie_embeddings",
              "norm_eps": "norm_eps", "dtype": "dtype"}


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"--seed {seed} is outside [0, 2**64)")
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, seed >> 32)
    return jax.random.fold_in(k, seed & 0xFFFFFFFF)


def _sq_leaves(tree) -> jax.Array:
    return jnp.stack([jnp.sum(jnp.square(x.astype(jnp.float32)))
                      for x in jax.tree_util.tree_leaves(tree)])


class Cell:
    unit = "steps"

    def __init__(self, config: Dict, traffic: Dict, seed: int, chips: int):
        from repro.launch import train as launch
        from repro.methods.driver import Driver
        from repro.models import init_params, lm
        from repro.optim.distributed import DashaTrainConfig, make_method

        self.config, self.traffic = config, traffic
        cfg = launch.arch_config(config["arch"], bool(config["published"]),
                                 int(config["n_layer"]), config["dtype"])
        for key, attr in _ARCH_KEYS.items():
            if key in config and getattr(cfg, attr) != config[key]:
                raise ValueError(f"the program's {config['arch']} has "
                                 f"{attr}={getattr(cfg, attr)!r}, the "
                                 f"configuration {key}={config[key]!r}")
        self.cfg = cfg
        nodes = int(config["nodes"])
        self.nodes = nodes
        self.chunk = int(traffic["chunk"])
        self.tokens_per_step = nodes * int(traffic["batch_per_node"]) \
            * int(traffic["seq"])
        if chips != 1:
            raise ValueError("a train cell runs its nodes on one chip")
        k_params, self.k_method, self.k_data, k_eval, self.k_proj = \
            jax.random.split(seed_key(seed), 5)
        self._make_params = jax.jit(lambda k: gen.mamba2_params(k, config))
        self.k_params = k_params
        params = self._make_params(k_params)
        want = jax.eval_shape(lambda k: init_params(cfg, k), k_params)
        got = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        if jax.tree_util.tree_structure(want) != \
                jax.tree_util.tree_structure(got) or any(
                    a.shape != b.shape or a.dtype != b.dtype for a, b in zip(
                        jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got))):
            raise ValueError("the benchmark's weights do not have the "
                             "program's parameter layout")

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
                          metrics={"g_sq": lambda s, b: _sq_leaves(s.g)},
                          chunk=self.chunk)
        eval_batch = gen.node_batches(k_eval, traffic, vocab, nodes)
        self.eval_batch = {k: v.reshape((-1,) + v.shape[2:])
                           for k, v in eval_batch.items()}
        self.eval_loss = jax.jit(
            lambda p: lm.loss_fn(cfg, p, self.eval_batch)[1]["loss"])
        self.log = []

    # -- the timed path ----------------------------------------------------
    def _hook(self, ms, t, tr):
        """The trainer's log hook: held-out loss and |g|^2 on the host."""
        with jax.profiler.TraceAnnotation("bench.log_hook"):
            self.log.append({"step": int(t),
                             "loss": float(self.eval_loss(ms.x)),
                             "g_norm_sq": float(jnp.sum(tr["g_sq"][-1]))})

    def call(self) -> int:
        """One whole chunk through the driver; returns the steps it ran."""
        with jax.profiler.TraceAnnotation("bench.chunk"):
            self.state, self._traces = self.drv.run(
                self.state, self.chunk, data_key=self.k_data,
                checkpoint=self._hook, donate_input=True)
        return self.chunk

    def warm(self) -> None:
        """The first chunk, which compiles; keep what the check reads."""
        self.call()
        g_sq = np.asarray(jax.device_get(self._traces["g_sq"]))
        x0 = self._make_params(self.k_params)
        dx = jax.jit(lambda a, b: _sq_leaves(jax.tree_util.tree_map(
            lambda u, v: u.astype(jnp.float32) - v.astype(jnp.float32),
            a, b)))(self.state.x, x0)
        proj = jax.jit(check.project)(self.state.g, self.k_proj)
        self.readings = {"g_sq": g_sq, "dx_sq": np.asarray(dx),
                         "g_proj": np.asarray(proj)}
        del x0

    def sync(self) -> None:
        jax.block_until_ready(self.state)

    # -- what the window reports -------------------------------------------
    def end_to_end(self, steps: int, seconds: float) -> Dict[str, float]:
        return {"tokens_per_s": steps * self.tokens_per_step / seconds}

    def counts(self) -> Dict[str, float]:
        variant = self.traffic["variant"]
        return {
            "flops_per_unit": counts.train_step_flops(
                self.config, variant, self.tokens_per_step),
            "update_bytes_per_unit": counts.node_update_min_bytes(
                self.config, variant, self.nodes)}

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        self.state = self._traces = None
        self.drv = None
        gc.collect()

    # -- correctness ---------------------------------------------------------
    def reference(self, *, cache=None, precision=None, fault: str = ""):
        """The reference's readings over the first chunk, from the seed.
        ``precision`` replaces the configuration's matmul precision (the
        control); ``cache`` keeps compiled references across seeds."""
        from bench import ref_lm
        mm = jnp.dtype(precision or self.config["reference_matmul"])
        cache = {} if cache is None else cache
        ref = cache.get((mm, fault))
        if ref is None:
            ref = cache[(mm, fault)] = ref_lm.Reference(
                self.config, self.traffic, mm_dtype=mm, fault=fault)
        x0 = self._make_params(self.k_params)
        ulp = jax.jit(check.ulp_sq)(x0)
        g_sq, dx_sq, g = ref.follow(x0, self.k_method, self.k_data,
                                    self.chunk)
        proj = jax.jit(check.project)(g, self.k_proj)
        return {"g_sq": np.asarray(g_sq), "dx_sq": np.asarray(dx_sq),
                "g_proj": np.asarray(proj), "ulp_sq": np.asarray(ulp)}

    def compare(self, prog: Dict, ref: Dict) -> Dict[str, float]:
        """The numbers ``correct`` is decided by (PERF.md gives why these):

        * ``grad``: the first gradient the optimizer gets (g after step 1),
          the worst leaf's norm gap;
        * ``step``: the parameters' change over the chunk (as stored), the
          worst leaf's norm gap, over the leaves whose change their stored
          precision carries;
        * ``g_proj``: g at the end of the chunk, the worst leaf's gap of
          directions: a message whose sign or support is wrong moves it,
          however its norm reads;
        * ``g_rms``: the same gap, root mean square over the leaves: the
          precision every leaf's gradient was computed in."""
        keep = check.moving_leaves(ref["g_sq"][0])
        steps = keep & check.stepping_leaves(ref["dx_sq"], ref["ulp_sq"])
        args = (prog["g_proj"], ref["g_proj"], ref["g_sq"][-1], keep)
        return {"grad": check.worst_leaf_gap(prog["g_sq"][0],
                                             ref["g_sq"][0], keep),
                "step": check.worst_leaf_gap(prog["dx_sq"], ref["dx_sq"],
                                             steps),
                "g_proj": check.worst_projection_gap(*args),
                "g_rms": check.rms_projection_gap(*args)}
