"""Parameter initialisation for every architecture family.

Layers are STACKED along a leading axis (scanned at apply time) so a model
compiles one layer body regardless of depth.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from repro.models.common import ArchConfig


def _dense_init(key, shape, dtype, fan_in=None):
    fan = fan_in if fan_in is not None else shape[0]
    scale = 1.0 / math.sqrt(max(fan, 1))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _stack(key, n, fn):
    return jax.vmap(fn)(jax.random.split(key, n))


def _mlp_params(key, cfg: ArchConfig, d, ff, dt) -> Dict:
    ks = jax.random.split(key, 4)
    if cfg.mlp_type == "gelu":
        return {"w_in": _dense_init(ks[0], (d, ff), dt),
                "b_in": jnp.zeros((ff,), dt),
                "w_out": _dense_init(ks[1], (ff, d), dt, ff),
                "b_out": jnp.zeros((d,), dt)}
    return {"w_gate": _dense_init(ks[0], (d, ff), dt),
            "w_in": _dense_init(ks[1], (d, ff), dt),
            "w_out": _dense_init(ks[2], (ff, d), dt, ff)}


def _gqa_params(key, cfg: ArchConfig, dt) -> Dict:
    d, H, G, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {"wq": _dense_init(ks[0], (d, H, hd), dt, d),
         "wk": _dense_init(ks[1], (d, G, hd), dt, d),
         "wv": _dense_init(ks[2], (d, G, hd), dt, d),
         "wo": _dense_init(ks[3], (H, hd, d), dt, H * hd)}
    if cfg.qkv_bias:
        p.update(bq=jnp.zeros((H, hd), dt), bk=jnp.zeros((G, hd), dt),
                 bv=jnp.zeros((G, hd), dt))
    return p


def _mla_params(key, cfg: ArchConfig, dt) -> Dict:
    d, H = cfg.d_model, cfg.num_heads
    dn, dr, dv, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim, cfg.kv_lora_rank)
    ks = jax.random.split(key, 6)
    return {"wq": _dense_init(ks[0], (d, H, dn + dr), dt, d),
            "w_dkv": _dense_init(ks[1], (d, r), dt, d),
            "w_krope": _dense_init(ks[2], (d, dr), dt, d),
            "w_uk": _dense_init(ks[3], (r, H, dn), dt, r),
            "w_uv": _dense_init(ks[4], (r, H, dv), dt, r),
            "wo": _dense_init(ks[5], (H, dv, d), dt, H * dv)}


def _moe_params(key, cfg: ArchConfig, dt) -> Dict:
    d, E, ff = cfg.d_model, cfg.num_experts, cfg.d_ff
    ks = jax.random.split(key, 7)
    p = {"router": _dense_init(ks[0], (d, E), jnp.float32, d),
         "w_gate": jax.vmap(lambda k: _dense_init(k, (d, ff), dt))(
             jax.random.split(ks[1], E)),
         "w_in": jax.vmap(lambda k: _dense_init(k, (d, ff), dt))(
             jax.random.split(ks[2], E)),
         "w_out": jax.vmap(lambda k: _dense_init(k, (ff, d), dt, ff))(
             jax.random.split(ks[3], E))}
    if cfg.num_shared_experts:
        sf = ff * cfg.num_shared_experts
        p.update(shared_w_gate=_dense_init(ks[4], (d, sf), dt),
                 shared_w_in=_dense_init(ks[5], (d, sf), dt),
                 shared_w_out=_dense_init(ks[6], (sf, d), dt, sf))
    return p


def _block_params(key, cfg: ArchConfig, dt) -> Dict:
    """One dense/moe transformer block."""
    k_attn, k_ffn = jax.random.split(key)
    p = {"ln1": jnp.zeros((cfg.d_model,), dt),
         "ln2": jnp.zeros((cfg.d_model,), dt)}
    p["attn"] = _mla_params(k_attn, cfg, dt) if cfg.use_mla \
        else _gqa_params(k_attn, cfg, dt)
    p["ffn"] = _moe_params(k_ffn, cfg, dt) if cfg.num_experts \
        else _mlp_params(k_ffn, cfg, cfg.d_model, cfg.d_ff, dt)
    return p


def _mamba_params(key, cfg: ArchConfig, dt) -> Dict:
    d = cfg.d_model
    H, P, N, W = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state, cfg.conv_width
    cd = H * P + 2 * cfg.ssm_ngroups * N
    ks = jax.random.split(key, 6)
    return {"ln": jnp.zeros((d,), dt),
            "w_z": _dense_init(ks[0], (d, H, P), dt, d),
            "w_xbc": _dense_init(ks[1], (d, cd), dt, d),
            "w_dt": _dense_init(ks[2], (d, H), dt, d),
            "dt_bias": jnp.full((H,), math.log(math.e - 1), dt),  # softplus=1
            "conv_w": _dense_init(ks[3], (W, cd), dt, W),
            "conv_b": jnp.zeros((cd,), dt),
            "A_log": jnp.zeros((H,), jnp.float32),                # A = -1
            "D": jnp.ones((H,), jnp.float32),
            "norm": jnp.zeros((H * P,), dt),
            "w_out": _dense_init(ks[4], (H * P, d), dt, H * P)}


def _held_moe_params(key, cfg: ArchConfig, dt) -> Dict:
    """One held-expert MoE layer (moe.moe_held): pre-norm, the router over
    every expert (f32), the held experts' relu^2 MLPs and the shared one."""
    d, E, Eh, ff = cfg.d_model, cfg.num_experts, cfg.held_experts, cfg.d_ff
    sf = cfg.shared_expert_ff
    ks = jax.random.split(key, 5)
    return {"ln": jnp.zeros((d,), dt),
            "router": _dense_init(ks[0], (d, E), jnp.float32, d),
            "w_in": jax.vmap(lambda k: _dense_init(k, (d, ff), dt))(
                jax.random.split(ks[1], Eh)),
            "w_out": jax.vmap(lambda k: _dense_init(k, (ff, d), dt, ff))(
                jax.random.split(ks[2], Eh)),
            "shared_w_in": _dense_init(ks[3], (d, sf), dt),
            "shared_w_out": _dense_init(ks[4], (sf, d), dt, sf)}


def _attn_params(key, cfg: ArchConfig, dt) -> Dict:
    """One pre-norm attention layer of a ``pattern`` stack."""
    return {"ln": jnp.zeros((cfg.d_model,), dt),
            **_gqa_params(key, cfg, dt)}


#: a ``pattern`` stack's layer kinds: letter -> (params key, init)
PATTERN_KINDS = {"M": ("mamba", _mamba_params),
                 "E": ("moe", _held_moe_params),
                 "*": ("attn", _attn_params)}


def _cross_block_params(key, cfg: ArchConfig, dt) -> Dict:
    k_attn, k_ffn = jax.random.split(key)
    return {"ln1": jnp.zeros((cfg.d_model,), dt),
            "ln2": jnp.zeros((cfg.d_model,), dt),
            "attn": _gqa_params(k_attn, cfg, dt),
            "ffn": _mlp_params(k_ffn, cfg, cfg.d_model, cfg.d_ff, dt),
            "attn_gate": jnp.zeros((1,), dt),
            "mlp_gate": jnp.zeros((1,), dt)}


def init_params(cfg: ArchConfig, key: jax.Array) -> Dict:
    dt = cfg.jax_dtype
    keys = jax.random.split(key, 8)
    params: Dict = {
        "embed": _dense_init(keys[0], (cfg.padded_vocab, cfg.d_model),
                             dt, cfg.d_model),
        "final_norm": jnp.zeros((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(keys[1],
                                        (cfg.d_model, cfg.padded_vocab), dt)

    at = cfg.arch_type
    if at == "pattern":
        # one stack per layer kind, in the order the pattern lists them
        for i, (letter, (name, fn)) in enumerate(PATTERN_KINDS.items()):
            count = cfg.pattern.count(letter)
            if count:
                params[name] = _stack(jax.random.fold_in(keys[2], i), count,
                                      lambda k, fn=fn: fn(k, cfg, dt))
    elif at == "ssm":
        params["layers"] = _stack(keys[2], cfg.num_layers,
                                  lambda k: _mamba_params(k, cfg, dt))
    elif at == "hybrid":
        params["layers"] = _stack(keys[2], cfg.num_layers,
                                  lambda k: _mamba_params(k, cfg, dt))
        params["shared_attn"] = _block_params(keys[3], cfg, dt)
    elif at == "vlm":
        params["layers"] = _stack(keys[2], cfg.num_layers,
                                  lambda k: _block_params(k, cfg, dt))
        n_cross = cfg.num_layers // cfg.cross_attn_every
        params["cross_layers"] = _stack(
            keys[3], n_cross, lambda k: _cross_block_params(k, cfg, dt))
    elif at == "audio":
        params["enc_layers"] = _stack(keys[2], cfg.num_encoder_layers,
                                      lambda k: _block_params(k, cfg, dt))
        params["enc_norm"] = jnp.zeros((cfg.d_model,), dt)
        params["layers"] = _stack(keys[3], cfg.num_layers,
                                  lambda k: _block_params(k, cfg, dt))
        params["cross_layers"] = _stack(
            keys[4], cfg.num_layers, lambda k: _cross_block_params(k, cfg, dt))
    elif cfg.global_every:  # gemma3-style local/global groups
        n_groups = cfg.num_layers // cfg.global_every
        n_local = cfg.global_every - 1
        params["local_layers"] = _stack(
            keys[2], n_groups,
            lambda k: _stack(k, n_local, lambda kk: _block_params(kk, cfg, dt)))
        params["global_layers"] = _stack(
            keys[3], n_groups, lambda k: _block_params(k, cfg, dt))
    else:  # homogeneous dense / moe stack
        params["layers"] = _stack(keys[2], cfg.num_layers,
                                  lambda k: _block_params(k, cfg, dt))
    return params
