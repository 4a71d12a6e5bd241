"""ShapeDtypeStruct stand-ins + shardings for every (arch x input-shape).

``input_specs(cfg, shape, mesh, ...)`` returns a ``LoweredSpec``: the function
to lower, abstract arguments, and in/out shardings — no device allocation.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import init_params, lm
from repro.models.common import ArchConfig
from repro.models.sharding import (cache_specs, dp_axes, dp_size,
                                   expert_sharding, param_specs)
from repro.optim.distributed import (DashaTrainConfig, dasha_train_init,
                                     make_train_step)

SHAPES: Dict[str, Dict] = {
    "train_4k":    dict(kind="train",  seq=4_096,   global_batch=256),
    "prefill_32k": dict(kind="prefill", seq=32_768, global_batch=32),
    "decode_32k":  dict(kind="decode", seq=32_768,  global_batch=128),
    "long_500k":   dict(kind="decode", seq=524_288, global_batch=1),
}

#: long_500k eligibility (DESIGN.md §4): SSM / hybrid / sliding-window.
def long_context_supported(cfg: ArchConfig) -> bool:
    return cfg.is_subquadratic and cfg.arch_type != "audio"


def shape_supported(cfg: ArchConfig, shape: str) -> Tuple[bool, str]:
    if shape == "long_500k" and not long_context_supported(cfg):
        return False, ("full-attention arch (no sub-quadratic variant); "
                       "skip per DESIGN.md §4")
    return True, ""


@dataclasses.dataclass
class LoweredSpec:
    fn: Callable
    args: Tuple
    in_shardings: Any
    out_shardings: Any
    static: Dict


def _abstract(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _batch_struct(cfg: ArchConfig, batch: int, seq: int,
                  node_axis: Optional[int] = None) -> Dict:
    """Abstract LM batch; optional leading node axis (DASHA training)."""
    lead = (node_axis, batch // node_axis) if node_axis else (batch,)
    tok = jax.ShapeDtypeStruct(lead + (seq,), jnp.int32)
    out = {"tokens": tok, "labels": tok}
    if cfg.arch_type == "vlm":
        out["image_embeds"] = jax.ShapeDtypeStruct(
            lead + (cfg.num_image_tokens, cfg.d_model), cfg.jax_dtype)
    if cfg.arch_type == "audio":
        out["frames"] = jax.ShapeDtypeStruct(
            lead + (cfg.num_audio_frames, cfg.d_model), cfg.jax_dtype)
    return out


def _batch_sharding(cfg: ArchConfig, mesh: Mesh, batch: int,
                    node_axis: bool) -> Dict:
    dp = dp_axes(mesh)
    b = dp if (batch % dp_size(mesh) == 0 or node_axis) else None
    lead = (b, None) if node_axis else (b,)
    out = {"tokens": P(*lead, None), "labels": P(*lead, None)}
    if cfg.arch_type == "vlm":
        out["image_embeds"] = P(*lead, None, None)
    if cfg.arch_type == "audio":
        out["frames"] = P(*lead, None, None)
    return out


def node_state_specs(cfg: ArchConfig, params_s: Any, mesh: Mesh,
                     dasha: DashaTrainConfig, opt_state_s: Any):
    """PartitionSpecs of the DASHA trainer state on ``mesh``.

    Returns ``(param_specs, state_param_specs, per_node_specs, opt_specs)``:
    the per-param specs pinned onto each node's gradient; the specs of
    params and g (FSDP over the data axes when ``dasha.fsdp``); each
    node's h_i / g_i with the node axis on the data axes (plain specs after
    it — the node axis already occupies the data axes); and the server
    optimizer's state, shaped like ``opt_state_s``."""
    dp = dp_axes(mesh)
    p_specs = param_specs(cfg, params_s, mesh)
    p_specs_f = param_specs(cfg, params_s, mesh, fsdp=dasha.fsdp)
    per_node = jax.tree_util.tree_map(
        lambda s: P(dp, *tuple(s)), p_specs,
        is_leaf=lambda x: isinstance(x, P))
    if dasha.server_opt == "adam":
        from repro.optim.base import AdamState
        opt_specs: Any = AdamState(mu=p_specs_f, nu=p_specs_f, count=P())
    else:
        opt_specs = jax.tree_util.tree_map(lambda x: P(), opt_state_s)
    return p_specs, p_specs_f, per_node, opt_specs


# ---------------------------------------------------------------------------
# train (DASHA data-parallel nodes x tensor parallel)
# ---------------------------------------------------------------------------

def train_spec(cfg: ArchConfig, mesh: Mesh, *, seq: int, global_batch: int,
               dasha: Optional[DashaTrainConfig] = None) -> LoweredSpec:
    n = dp_size(mesh)
    dasha = dasha or DashaTrainConfig(gamma=0.01, compression=1 / 32,
                                      n_nodes=n)
    if dasha.n_nodes != n:
        dasha = dataclasses.replace(dasha, n_nodes=n)
    dp = dp_axes(mesh)
    tp = mesh.shape.get("model", 1)
    if dasha.spmd_axes is None and dp:
        dasha = dataclasses.replace(dasha, spmd_axes=dp)
    key = jax.random.PRNGKey(0)
    params_s = jax.eval_shape(lambda: init_params(cfg, key))
    state_s = jax.eval_shape(
        lambda p: dasha_train_init(p, dasha, key), params_s)
    batch_s = _batch_struct(cfg, global_batch, seq, node_axis=n)

    seq_axis = "model" if (dasha.seq_shard and tp > 1 and seq % tp == 0) \
        else None
    exp_axis = "model" if (cfg.num_experts and tp > 1
                           and cfg.num_experts % tp == 0) else None

    def node_loss(p, b):
        with expert_sharding(exp_axis):
            return lm.loss_fn(cfg, p, b, seq_shard=seq_axis)[0]

    p_specs, p_specs_f, per_node, opt_specs = node_state_specs(
        cfg, params_s, mesh, dasha, state_s.opt_state)
    step = make_train_step(dasha, node_loss, grad_specs=p_specs)

    from repro.optim.distributed import DashaTrainState
    state_specs = DashaTrainState(
        params=p_specs_f,
        g=p_specs_f,
        h_local=per_node,
        g_local=per_node,
        opt_state=opt_specs,
        key=P(), step=P())
    batch_specs_ = _batch_sharding(cfg, mesh, global_batch, node_axis=True)
    out_specs = (state_specs, {"g_norm_sq": P(), "payload_frac": P(),
                               "payload_coords": P()})
    return LoweredSpec(fn=step, args=(state_s, batch_s),
                       in_shardings=(state_specs, batch_specs_),
                       out_shardings=out_specs,
                       static=dict(kind="train", n_nodes=n,
                                   tokens=global_batch * seq,
                                   dasha=dataclasses.asdict(dasha)))


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def prefill_spec(cfg: ArchConfig, mesh: Mesh, *, seq: int,
                 global_batch: int,
                 serve_attn_hd_shard: bool = True) -> LoweredSpec:
    key = jax.random.PRNGKey(0)
    params_s = jax.eval_shape(lambda: init_params(cfg, key))
    batch_s = _batch_struct(cfg, global_batch, seq)

    tp = mesh.shape.get("model", 1)
    exp_axis = "model" if (cfg.num_experts and tp > 1
                           and cfg.num_experts % tp == 0) else None

    def prefill(params, batch):
        with expert_sharding(exp_axis):
            logits, _ = lm.forward(cfg, params, batch["tokens"],
                                   image_embeds=batch.get("image_embeds"),
                                   frames=batch.get("frames"),
                                   remat=False, last_only=True)
        return logits  # (B, 1, V)

    p_specs = param_specs(cfg, params_s, mesh,
                          hd_fallback=serve_attn_hd_shard)
    b_specs = _batch_sharding(cfg, mesh, global_batch, node_axis=False)
    b_axis = b_specs["tokens"][0]
    return LoweredSpec(fn=prefill, args=(params_s, batch_s),
                       in_shardings=(p_specs, b_specs),
                       out_shardings=P(b_axis, None, None),
                       static=dict(kind="prefill",
                                   tokens=global_batch * seq))


# ---------------------------------------------------------------------------
# decode (serve_step: ONE token against a seq-long cache)
# ---------------------------------------------------------------------------

def decode_spec(cfg: ArchConfig, mesh: Mesh, *, seq: int,
                global_batch: int) -> LoweredSpec:
    key = jax.random.PRNGKey(0)
    params_s = jax.eval_shape(lambda: init_params(cfg, key))

    def make_cache():
        image_kv = enc_kv = None
        if cfg.arch_type == "vlm":
            G, hd = cfg.num_kv_heads, cfg.head_dim
            n_cross = cfg.num_layers // cfg.cross_attn_every
            image_kv = {"k": jnp.zeros((n_cross, global_batch,
                                        cfg.num_image_tokens, G, hd),
                                       cfg.jax_dtype)}
            image_kv["v"] = image_kv["k"]
        if cfg.arch_type == "audio":
            G, hd = cfg.num_kv_heads, cfg.head_dim
            enc_kv = {"k": jnp.zeros((cfg.num_layers, global_batch,
                                      cfg.num_audio_frames, G, hd),
                                     cfg.jax_dtype)}
            enc_kv["v"] = enc_kv["k"]
        return lm.init_cache(cfg, global_batch, seq, image_kv=image_kv,
                             enc_kv=enc_kv)

    cache_s = jax.eval_shape(make_cache)
    token_s = jax.ShapeDtypeStruct((global_batch,), jnp.int32)
    t_s = jax.ShapeDtypeStruct((), jnp.int32)

    tp = mesh.shape.get("model", 1)
    exp_axis = "model" if (cfg.num_experts and tp > 1
                           and cfg.num_experts % tp == 0) else None

    def serve_step(params, cache, token, t):
        with expert_sharding(exp_axis):
            return lm.decode_step(cfg, params, cache, token, t)

    p_specs = param_specs(cfg, params_s, mesh)
    c_specs = cache_specs(cfg, cache_s, mesh, global_batch)
    b_ok = global_batch % dp_size(mesh) == 0
    tok_spec = P(dp_axes(mesh)) if b_ok else P(None)
    logits_spec = P(tok_spec[0] if b_ok else None, None)
    return LoweredSpec(
        fn=serve_step, args=(params_s, cache_s, token_s, t_s),
        in_shardings=(p_specs, c_specs, tok_spec, P()),
        out_shardings=(logits_spec, c_specs),
        static=dict(kind="decode", tokens=global_batch))


def input_specs(cfg: ArchConfig, shape: str, mesh: Mesh,
                dasha: Optional[DashaTrainConfig] = None,
                serve_attn_hd_shard: bool = True) -> LoweredSpec:
    info = SHAPES[shape]
    ok, why = shape_supported(cfg, shape)
    if not ok:
        raise ValueError(f"{cfg.name} x {shape}: {why}")
    if info["kind"] == "train":
        return train_spec(cfg, mesh, seq=info["seq"],
                          global_batch=info["global_batch"], dasha=dasha)
    if info["kind"] == "prefill":
        return prefill_spec(cfg, mesh, seq=info["seq"],
                            global_batch=info["global_batch"],
                            serve_attn_hd_shard=serve_attn_hd_shard)
    return decode_spec(cfg, mesh, seq=info["seq"],
                       global_batch=info["global_batch"])
