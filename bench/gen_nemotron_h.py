"""Weights of a Nemotron-H (Mamba-2 + MoE + attention) chip share, made
from the seed in the program's parameter layout.

The yardstick's own generator: it reads the benchmark configuration's keys
(the published ``config.json`` names) and imports nothing of the program.
Layers of one kind are stacked on a leading axis in pattern order:
``mamba`` (``M``), ``moe`` (``E``, the held experts only, the router over
every expert) and ``attn`` (``*``).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench.gen import padded_vocab

#: layer letter -> stack name
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


def pattern(cfg: Dict) -> str:
    """The letters of the layers held here."""
    return cfg["hybrid_override_pattern"][:int(cfg["num_hidden_layers"])]


def param_shapes(cfg: Dict):
    """Stack -> leaf -> (shape, dtype name, init); init is a fan-in, or
    one of ``zeros``, ``ones``, ``dt_bias``, ``a_log``."""
    d, dt = int(cfg["hidden_size"]), cfg["dtype"]
    V = padded_vocab(int(cfg["vocab_size"]))
    H, P = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    G, N = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    W = int(cfg["conv_kernel"])
    inner, cd = H * P, H * P + 2 * G * N
    E, Eh = int(cfg["published_n_routed_experts"]), int(cfg["n_routed_experts"])
    f, fs = int(cfg["moe_intermediate_size"]), \
        int(cfg["moe_shared_expert_intermediate_size"])
    Ha, Ga = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    hd = int(cfg["head_dim"])
    stacks = {
        "mamba": {"ln": ((d,), dt, "zeros"), "w_z": ((d, H, P), dt, d),
                  "w_xbc": ((d, cd), dt, d), "w_dt": ((d, H), dt, d),
                  "dt_bias": ((H,), dt, "dt_bias"),
                  "conv_w": ((W, cd), dt, W), "conv_b": ((cd,), dt, "zeros"),
                  "A_log": ((H,), "float32", "a_log"),
                  "D": ((H,), "float32", "ones"),
                  "norm": ((inner,), dt, "zeros"),
                  "w_out": ((inner, d), dt, inner)},
        "moe": {"ln": ((d,), dt, "zeros"),
                "router": ((d, E), "float32", d),
                "w_in": ((Eh, d, f), dt, d), "w_out": ((Eh, f, d), dt, f),
                "shared_w_in": ((d, fs), dt, d),
                "shared_w_out": ((fs, d), dt, fs)},
        "attn": {"ln": ((d,), dt, "zeros"), "wq": ((d, Ha, hd), dt, d),
                 "wk": ((d, Ga, hd), dt, d), "wv": ((d, Ga, hd), dt, d),
                 "wo": ((Ha, hd, d), dt, Ha * hd)},
    }
    letters = pattern(cfg)
    out = {"embed": ((V, d), dt, d), "final_norm": ((d,), dt, "zeros"),
           "lm_head": ((d, V), dt, d)}
    for letter, name in KINDS.items():
        n = letters.count(letter)
        if n:
            out[name] = {k: ((n,) + s, t, i)
                         for k, (s, t, i) in stacks[name].items()}
    return out


def _leaf(k, shape, dtype, init, cfg):
    if init == "zeros":
        x = jnp.zeros(shape, jnp.float32)
    elif init == "ones":
        x = jnp.ones(shape, jnp.float32)
    elif init == "a_log":                   # A = -(1 .. H) per head
        x = jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1,
                                                dtype=jnp.float32)), shape)
    elif init == "dt_bias":                 # softplus(dt_bias) = dt
        lo = math.log(float(cfg["time_step_min"]))
        hi = math.log(float(cfg["time_step_max"]))
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi))
        dt = jnp.maximum(dt, float(cfg["time_step_floor"]))
        x = dt + jnp.log(-jnp.expm1(-dt))
    else:
        x = jax.random.normal(k, shape, jnp.float32) / math.sqrt(init)
    return x.astype(dtype)


def params(key: jax.Array, cfg: Dict):
    """The weights, from ``key``, in the dtype they are trained in.  Call
    under ``jax.jit``: one program makes every leaf on the device."""
    shapes = param_shapes(cfg)
    flat = [((name,), v) for name, v in shapes.items() if name in
            ("embed", "final_norm", "lm_head")]
    flat += [((name, leaf), v) for name, stack in sorted(shapes.items())
             if isinstance(stack, dict) for leaf, v in sorted(stack.items())]
    keys = jax.random.split(key, len(flat))
    out: Dict = {}
    for k, (path, (shape, dtype, init)) in zip(keys, flat):
        val = _leaf(k, shape, dtype, init, cfg)
        if len(path) == 1:
            out[path[0]] = val
        else:
            out.setdefault(path[0], {})[path[1]] = val
    return out
