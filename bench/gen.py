"""Inputs made from the seed: token batches and weights.

The yardstick's own generators.  The token stream is a copy of the
trainer's synthetic text (``repro.data.pipeline.make_lm_batch``), so that a
change to it leaves the benchmark's inputs as they are.  Nothing here
imports the program.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp


def lm_batch(key: jax.Array, vocab: int, seq: int, batch: int,
             copy_period: int, noise: float) -> Dict[str, jax.Array]:
    """``{"tokens", "labels"}`` of shape (batch, seq): a period-
    ``copy_period`` repeating stream with a ``noise`` share of its tokens
    replaced by uniform draws, so that the loss has signal to learn."""
    k1, k2, k3, _, _ = jax.random.split(key, 5)
    base = jax.random.randint(k1, (batch, copy_period), 1, vocab)
    reps = -(-seq // copy_period) + 1
    stream = jnp.tile(base, (1, reps))
    rand = jax.random.randint(k2, (batch, seq + 1), 1, vocab)
    noisy = jax.random.bernoulli(k3, noise, (batch, seq + 1))
    s = jnp.where(noisy, rand, stream[:, :seq + 1])
    return {"tokens": s[:, :seq], "labels": s[:, 1:]}


def node_batches(key: jax.Array, traffic: Dict, vocab: int,
                 nodes: int) -> Dict[str, jax.Array]:
    """One step's batch with a leading node axis: (nodes, batch, seq)."""
    b = int(traffic["batch_per_node"])
    out = lm_batch(key, vocab, int(traffic["seq"]), nodes * b,
                   int(traffic["copy_period"]), float(traffic["noise"]))
    return {k: v.reshape((nodes, b) + v.shape[1:]) for k, v in out.items()}


def padded_vocab(vocab: int) -> int:
    """Rows of the embedding as the program stores them (a multiple of
    256)."""
    return -(-vocab // 256) * 256


def mamba2_param_shapes(cfg: Dict):
    """Leaf name -> (shape, dtype name, fan_in or None) of the Mamba2 LM's
    parameters, layers stacked on a leading axis (the program's layout)."""
    d, L = int(cfg["d_model"]), int(cfg["n_layer"])
    inner = int(cfg["expand"]) * d
    P = int(cfg["headdim"])
    H = inner // P
    N, W = int(cfg["d_state"]), int(cfg["d_conv"])
    cd = inner + 2 * int(cfg.get("ngroups", 1)) * N
    dt = cfg["dtype"]
    layers = {
        "ln": ((L, d), dt, None),
        "w_z": ((L, d, H, P), dt, d),
        "w_xbc": ((L, d, cd), dt, d),
        "w_dt": ((L, d, H), dt, d),
        "dt_bias": ((L, H), dt, "dt_bias"),
        "conv_w": ((L, W, cd), dt, W),
        "conv_b": ((L, cd), dt, None),
        "A_log": ((L, H), "float32", None),
        "D": ((L, H), "float32", "ones"),
        "norm": ((L, inner), dt, None),
        "w_out": ((L, inner, d), dt, inner),
    }
    return {"embed": ((padded_vocab(int(cfg["vocab_size"])), d), dt, d),
            "final_norm": ((d,), dt, None), "layers": layers}


def mamba2_params(key: jax.Array, cfg: Dict):
    """The weights, from ``key``, in the dtype they are trained in.  Call
    under ``jax.jit``: one program makes every leaf on the device."""
    shapes = mamba2_param_shapes(cfg)

    def leaf(k, shape, dtype, fan):
        if fan is None:
            x = jnp.zeros(shape, jnp.float32)
        elif fan == "ones":
            x = jnp.ones(shape, jnp.float32)
        elif fan == "dt_bias":                 # softplus(dt_bias) = 1
            x = jnp.full(shape, math.log(math.e - 1), jnp.float32)
        else:
            x = jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan)
        return x.astype(dtype)

    flat = [("embed",) + shapes["embed"], ("final_norm",)
            + shapes["final_norm"]]
    flat += [(name,) + v for name, v in sorted(shapes["layers"].items())]
    keys = jax.random.split(key, len(flat))
    vals = {name: leaf(k, s, dt, fan)
            for k, (name, s, dt, fan) in zip(keys, flat)}
    return {"embed": vals.pop("embed"), "final_norm": vals.pop("final_norm"),
            "layers": vals}
