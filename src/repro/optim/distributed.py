"""The trainer's configuration and its Method.

:class:`DashaTrainConfig` holds what a training run sets of the paper's
algorithm family: the server stepsize, the compressor (independent |
shared_coords | permk Bernoulli-RandP, the fraction it sends), the
variant (``dasha`` | ``mvr`` | ``page`` | ``sync_mvr``), the node count,
the server optimizer, the fused Pallas path and the dtype of each node's
h_i / g_i.  :func:`make_method` turns it and a per-node loss into the
engine's :class:`repro.methods.Method` (DESIGN.md §7): the variant's rule
over a :class:`repro.methods.TreeSubstrate`, whose oracle derives each
node's gradient from the loss, with compression through
:class:`repro.methods.TreeCompression`.  Every quantity of the method
(h_i, g_i, messages) is a pytree shaped like the params with a leading
node axis.  ``method.init`` then ``methods.driver.Driver`` run it.

``use_kernel=True`` routes every mode x variant through the fused Pallas
path; the MVR/SARAH h-update is recomputed inside the kernel pass
(:class:`repro.methods.rules.MvrFusion`), so each step reads and writes
the node state once.  :func:`payload_frac` is the static expected
fraction of coordinates sent, SYNC-MVR's dense sync rounds included.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.compress import omega_bernoulli, omega_permk
from repro.methods import (BatchLossOracle, Hyper, Method, TreeCompression,
                           TreeSubstrate, expected_payload_frac, get_rule)
from repro.optim.base import SGD, Adam

PyTree = Any


@dataclasses.dataclass(frozen=True)
class DashaTrainConfig:
    gamma: float                      # server stepsize
    compression: float = 0.03125     # fraction of coords sent (1/32)
    mode: str = "independent"        # independent | shared_coords | permk
    variant: str = "dasha"           # dasha | mvr | page | sync_mvr
    b: float = 0.1                   # MVR momentum
    p: float = 0.25                  # PAGE / SYNC-MVR coin probability
    n_nodes: int = 1
    server_opt: str = "sgd"          # sgd | adam (adam = beyond-paper)
    use_kernel: bool = False         # fused Pallas path (all modes/variants)
    # --- memory / sharding knobs (beyond-paper TPU adaptation) ------------
    state_dtype: str = "float32"     # h_i/g_i storage: float32 | bfloat16
    spmd_axes: Optional[Tuple[str, ...]] = None  # vmap spmd_axis_name

    @property
    def omega(self) -> float:
        if self.mode == "permk":
            return omega_permk(self.n_nodes)
        # independent & shared_coords Bernoulli-RandP
        return omega_bernoulli(self.compression)

    @property
    def a(self) -> float:
        return 1.0 / (2.0 * self.omega + 1.0)

    @property
    def jax_state_dtype(self):
        return {"float32": jnp.float32,
                "bfloat16": jnp.bfloat16}[self.state_dtype]

    @property
    def hyper(self) -> Hyper:
        return Hyper(gamma=self.gamma, a=self.a, variant=self.variant,
                     b=self.b, p=self.p)


def _server_opt(cfg: DashaTrainConfig):
    if cfg.server_opt == "adam":
        return Adam(lr=cfg.gamma)
    return SGD(lr=cfg.gamma)


def make_method(cfg: DashaTrainConfig,
                loss_fn: Callable[[PyTree, Any], jax.Array],
                grad_specs: Optional[PyTree] = None) -> Method:
    """The trainer's Method (variant rule x TreeCompression x
    TreeSubstrate) as a first-class object, for direct use with the
    compiled run driver (:mod:`repro.methods.driver`, DESIGN.md §10):
    ``method.init(params, key, init_mode="zeros")`` then
    ``driver.run(method, state, rounds, data_fn=..., ...)``.

    ``loss_fn(params, node_batch) -> scalar``; steps take ``batch`` with a
    leading node axis (n, ...) sharded over ("pod","data").
    ``grad_specs``: optional per-param PartitionSpecs (no node axis) pinned
    onto each node's gradient so the scan-backward accumulators compile
    sharded (the vmap spmd_axis_name lifts in the node axis).
    """
    # full specs (node axis + per-param spec) for pinning mask RNG sharding
    node_full_specs = None
    if grad_specs is not None and cfg.spmd_axes:
        from jax.sharding import PartitionSpec as P
        node_full_specs = jax.tree_util.tree_map(
            lambda s_: P(cfg.spmd_axes, *tuple(s_)), grad_specs,
            is_leaf=lambda x: isinstance(x, P))

    oracle = BatchLossOracle(loss_fn=loss_fn, spmd_axes=cfg.spmd_axes,
                             grad_specs=grad_specs,
                             state_dtype=cfg.jax_state_dtype)
    substrate = TreeSubstrate(oracle=oracle, n=cfg.n_nodes,
                              server_opt=_server_opt(cfg),
                              state_dtype=cfg.jax_state_dtype)
    comp = TreeCompression(mode=cfg.mode, p=cfg.compression, n=cfg.n_nodes,
                           use_kernel=cfg.use_kernel, specs=node_full_specs)
    return Method.build(cfg.variant, comp, substrate, cfg.hyper)


def payload_frac(cfg: DashaTrainConfig) -> float:
    """Static E[coords sent]/d: the compressor's fraction
    (TreeCompression.static_frac — the ONE mode->fraction rule) + the sync
    rounds' dense uploads (SYNC-MVR's prob-p megabatch), via the ONE
    accounting helper."""
    comp = TreeCompression(mode=cfg.mode, p=cfg.compression,
                           n=cfg.n_nodes)
    return expected_payload_frac(get_rule(cfg.variant), cfg.hyper,
                                 comp.static_frac)
