"""Sharding policy: PartitionSpecs for params and decode caches.

Megatron-style 2D: batch over ("pod","data"), tensor dims over "model" —
but only when the dimension is divisible by the model-axis size; otherwise the
tensor is replicated.  Stacked-layer leading axes are always unsharded (they
are scanned).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.common import ArchConfig

DP_AXES = ("pod", "data")   # logical batch axes (pod may be absent)


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in DP_AXES if a in mesh.axis_names)


def dp_size(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in dp_axes(mesh)]))


def tp_size(mesh: Mesh) -> int:
    return int(mesh.shape.get("model", 1))


def _div(n: int, tp: int) -> bool:
    return tp > 1 and n % tp == 0


def param_specs(cfg: ArchConfig, params: Any, mesh: Mesh) -> Any:
    """Mirror the params pytree with PartitionSpecs (path-name rules)."""
    tp = tp_size(mesh)
    H, G = cfg.num_heads, cfg.num_kv_heads
    Hs = cfg.ssm_nheads if cfg.ssm_state else 0
    E = cfg.num_experts

    def model_if(ok: bool):
        return "model" if ok else None

    hd_ok = _div(cfg.head_dim or 0, tp)

    def qkv_spec(n_heads: int) -> Tuple:
        """(d, heads, hd) weight: shard heads when divisible, else fall back
        to sharding head_dim (keeps few-kv-head archs from replicating the
        whole attention stack on a 16-wide model axis)."""
        if _div(n_heads, tp):
            return (None, "model", None)
        if hd_ok:
            return (None, None, "model")
        return (None, None, None)

    def o_spec(n_heads: int) -> Tuple:
        if _div(n_heads, tp):
            return ("model", None, None)
        if hd_ok:
            return (None, "model", None)
        return (None, None, None)

    def bias_spec(n_heads: int) -> Tuple:
        if _div(n_heads, tp):
            return ("model", None)
        if hd_ok:
            return (None, "model")
        return (None, None)

    # base specs keyed by leaf name; rank excludes stacked leading dims
    base: Dict[str, Tuple] = {
        "embed": ("model", None),
        "lm_head": (None, "model"),
        "final_norm": (None,), "enc_norm": (None,),
        "ln": (None,), "ln1": (None,), "ln2": (None,),
        "attn_gate": (None,), "mlp_gate": (None,),
        # attention
        "wq": qkv_spec(H),
        "wk": qkv_spec(G),
        "wv": qkv_spec(G),
        "wo": o_spec(H),
        "bq": bias_spec(H),
        "bk": bias_spec(G),
        "bv": bias_spec(G),
        # MLA (latent dims shard over model when divisible; the ckv cache
        # uses the same rule so decode einsums stay aligned)
        "w_dkv": (None, model_if(cfg.kv_lora_rank % tp == 0 and tp > 1
                                 and cfg.kv_lora_rank >= tp)),
        "w_krope": (None, None),
        "w_uk": (None, model_if(_div(H, tp)), None),
        "w_uv": (None, model_if(_div(H, tp)), None),
        # dense mlp
        "w_gate": (None, model_if(_div(cfg.d_ff, tp))),
        "w_in": (None, model_if(_div(cfg.d_ff, tp))),
        "w_out": (model_if(_div(cfg.d_ff, tp)), None),
        "b_in": (model_if(_div(cfg.d_ff, tp)),), "b_out": (None,),
        # moe (leaf names overlap mlp: expert variants matched by rank below)
        "router": (None, None),
        # mamba
        "w_z": (None, model_if(_div(Hs, tp)), None),
        "w_xbc": (None, model_if(_div(Hs, tp) and cfg.ssm_state % tp == 0)),
        "w_dt": (None, model_if(_div(Hs, tp))),
        "dt_bias": (model_if(_div(Hs, tp)),),
        "conv_w": (None, model_if(_div(Hs, tp) and cfg.ssm_state % tp == 0)),
        "conv_b": (model_if(_div(Hs, tp) and cfg.ssm_state % tp == 0),),
        "A_log": (model_if(_div(Hs, tp)),), "D": (model_if(_div(Hs, tp)),),
        "norm": (model_if(_div(Hs, tp)),),
    }
    moe_expert = {
        "w_gate": (model_if(_div(E, tp)), None, None),
        "w_in": (model_if(_div(E, tp)), None, None),
        "w_out": (model_if(_div(E, tp)), None, None),
    }
    if cfg.num_shared_experts:
        sf = cfg.d_ff * cfg.num_shared_experts
        base.update({
            "shared_w_gate": (None, model_if(_div(sf, tp))),
            "shared_w_in": (None, model_if(_div(sf, tp))),
            "shared_w_out": (model_if(_div(sf, tp)), None)})
    if cfg.ssm_state and cfg.arch_type in ("ssm", "hybrid"):
        # mamba w_out: (H*P, d)
        base["w_out"] = (model_if(_div(Hs, tp)), None)
        if cfg.arch_type == "hybrid":
            pass  # shared_attn mlp w_out handled by rank disambiguation below

    def rule(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        is_expert = E > 0 and name in moe_expert and leaf.ndim >= 3 and \
            any(getattr(p, "key", None) == "ffn" for p in path) and \
            leaf.shape[-3 if name != "w_out" else -3] == E
        spec = moe_expert[name] if is_expert else base.get(name)
        if spec is None:
            spec = (None,) * leaf.ndim
            return P(*spec)
        # hybrid: shared_attn's dense mlp w_out is (ff, d) while mamba w_out
        # is (HP, d) — same rank; disambiguate via path.
        if (name == "w_out" and cfg.arch_type in ("ssm", "hybrid")
                and any(getattr(p, "key", None) in ("shared_attn", "ffn",
                                                    "cross_layers")
                        for p in path) and not is_expert):
            spec = (model_if(_div(cfg.d_ff, tp)), None)
        if (name in ("w_gate", "w_in") and not is_expert):
            spec = (None, model_if(_div(cfg.d_ff, tp)))
        lead = leaf.ndim - len(spec)
        return P(*((None,) * lead), *spec)

    return jax.tree_util.tree_map_with_path(rule, params)


def cache_specs(cfg: ArchConfig, cache: Any, mesh: Mesh,
                batch_size: int) -> Any:
    """Decode-cache specs.  Batch axis over ("pod","data") when divisible;
    otherwise (long_500k, B=1) the cache SEQUENCE axis is sharded over "data"
    (context-parallel decode) and SSM states stay replicated."""
    dp = dp_axes(mesh)
    tp = tp_size(mesh)
    batch_ok = batch_size % dp_size(mesh) == 0
    G = cfg.num_kv_heads

    def rule(path, leaf):
        names = [getattr(p, "key", "") for p in path]
        nd = leaf.ndim
        if "cross" in names:
            # cross-attn K/V over image/audio tokens: (n, B, T_src, G, hd);
            # T_src (1601/1500) is not shardable — batch + heads/hd only.
            spec = [None] * nd
            if batch_ok:
                spec[nd - 4] = dp
            if _div(G, tp):
                spec[nd - 2] = "model"
            elif (cfg.head_dim or 0) % tp == 0 and tp > 1:
                spec[nd - 1] = "model"
            return P(*spec)
        if "ssm" in names or "conv" in names:     # (L,B,...) mamba states
            spec = [None] * nd
            if batch_ok:
                spec[1] = dp
            if "ssm" in names and _div(cfg.ssm_nheads, tp):
                spec[2] = "model"                 # (L,B,H,N,P)
            if "conv" in names and _div(cfg.ssm_nheads, tp) \
                    and cfg.ssm_state % tp == 0:
                spec[-1] = "model"                # channel dim
            return P(*spec)
        # attention caches: (..., B, T, G, hd) or MLA (..., B, T, r)
        spec = [None] * nd
        b_idx = nd - 4 if nd >= 4 else nd - 3     # works for kv and mla ranks
        if "ckv" in names or "krope" in names:    # (L,B,T,r)
            b_idx = 1
            if batch_ok:
                spec[b_idx] = dp
            elif "data" in (mesh.axis_names or ()) and \
                    leaf.shape[2] % mesh.shape["data"] == 0:
                spec[2] = "data"
            if "ckv" in names and cfg.kv_lora_rank % tp == 0 and tp > 1:
                spec[-1] = "model"                # latent dim (512 % 16 == 0)
            return P(*spec)
        # kv caches: locate (B, T, G, hd) as last four dims
        if batch_ok:
            spec[nd - 4] = dp
        elif "data" in mesh.axis_names and \
                leaf.shape[nd - 3] % mesh.shape["data"] == 0:
            spec[nd - 3] = "data"                 # shard sequence
        if _div(G, tp):
            spec[nd - 2] = "model"
        elif (cfg.head_dim or 0) % tp == 0 and tp > 1:
            spec[nd - 1] = "model"  # few kv heads: shard head_dim instead
        return P(*spec)

    return jax.tree_util.tree_map_with_path(rule, cache)


def to_shardings(tree_specs: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                  tree_specs,
                                  is_leaf=lambda x: isinstance(x, P))
