"""Language-model assembly for every architecture family.

Public API (used by launch/, tests/, examples/):

    forward(cfg, params, tokens, *, image_embeds=None, frames=None) -> logits
    loss_fn(cfg, params, batch) -> (scalar, metrics)
    init_cache(cfg, batch, seq) -> cache pytree (decode)
    decode_step(cfg, params, cache, token, t, ...) -> (logits, cache)

Layers are scanned; heterogeneous structure (gemma3 local/global groups,
zamba2 shared attention, VLM cross blocks) is handled inside the scan body
with `lax.cond` + dynamic indexing so each family still compiles ONE body.
The ``pattern`` stack (Nemotron-H: Mamba-2, MoE and attention layers in
one stack, DESIGN.md §19) unrolls its layers in pattern order instead.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models.blocks import (block_decode, block_prefill, cross_block,
                                 mamba_block_decode, mamba_block_prefill)
from repro.models.common import ArchConfig, rms_norm
from repro.models.init import PATTERN_KINDS
from repro.models.moe import moe_held


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _embed(cfg: ArchConfig, params: Dict, tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]
    if cfg.arch_type == "dense" and cfg.global_every:   # gemma-style scaling
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def _logits(cfg: ArchConfig, params: Dict, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", x, params["embed"])
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"])


def _positions(B: int, S: int) -> jax.Array:
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))


def _maybe_remat(fn, use_remat: bool):
    return jax.checkpoint(fn) if use_remat else fn


# ---------------------------------------------------------------------------
# pattern stack (nemotron-h): x + mixer(rmsnorm(x)), the mixer by letter
# ---------------------------------------------------------------------------

def _pattern_layer(letter: str, cfg: ArchConfig, pos: jax.Array):
    """One layer of a ``pattern`` stack: (params, x) -> (x, tokens routed
    to each held expert or None, dropped assignments)."""
    none = jnp.int32(0)
    if letter == "M":
        return lambda lp, x: (mamba_block_prefill(lp, x, cfg), None, none)
    if letter == "E":
        def moe(lp, x):
            y, routed, dropped = moe_held(
                lp, rms_norm(x, lp["ln"], cfg.norm_eps), cfg)
            return x + y, routed, dropped
        return moe
    if letter == "*":
        return lambda lp, x: (x + attn_lib.gqa_prefill(
            lp, rms_norm(x, lp["ln"], cfg.norm_eps), pos, cfg), None, none)
    raise ValueError(f"layer letter {letter!r} in {cfg.layer_pattern!r}")


def pattern_forward(cfg: ArchConfig, params: Dict, tokens: jax.Array, *,
                    remat: bool = True
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The ``pattern`` stack's hidden states before the final norm:
    returns (x (B,S,d), tokens routed to each held expert per MoE layer
    (n_moe, E_h) int32, dropped assignments () int32).  Layers of one kind
    are stacked on a leading axis (:data:`repro.models.init.PATTERN_KINDS`)
    and sliced in pattern order; each layer is rematerialised."""
    B, S = tokens.shape
    pos = _positions(B, S)
    x = params["embed"][tokens]
    seen = {name: 0 for name, _ in PATTERN_KINDS.values()}
    routed, dropped = [], jnp.int32(0)
    for letter in cfg.pattern:
        name = PATTERN_KINDS[letter][0]
        lp = jax.tree_util.tree_map(lambda a, i=seen[name]: a[i],
                                    params[name])
        seen[name] += 1
        x, r, dr = _maybe_remat(_pattern_layer(letter, cfg, pos), remat)(
            lp, x)
        if r is not None:
            routed.append(r)
        dropped = dropped + dr
    routed = jnp.stack(routed) if routed else \
        jnp.zeros((0, cfg.held_experts), jnp.int32)
    return x, routed, dropped


# ---------------------------------------------------------------------------
# prefill / train forward
# ---------------------------------------------------------------------------

def forward(cfg: ArchConfig, params: Dict, tokens: jax.Array, *,
            image_embeds: Optional[jax.Array] = None,
            frames: Optional[jax.Array] = None,
            remat: bool = True,
            last_only: bool = False
            ) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits (B,S,V_padded), aux_loss scalar).  ``last_only`` slices
    the hidden states to the final position BEFORE the vocab projection
    (serving prefill: avoids materialising (B,S,V))."""
    B, S = tokens.shape
    at = cfg.arch_type
    if at == "pattern":
        x, _, _ = pattern_forward(cfg, params, tokens, remat=remat)
        if last_only:
            x = x[:, -1:]
        return _logits(cfg, params, x), jnp.float32(0)
    x = _embed(cfg, params, tokens)
    pos = _positions(B, S)

    if at == "ssm":
        def body(carry, lp):
            return mamba_block_prefill(lp, carry, cfg), None
        x, _ = jax.lax.scan(_maybe_remat(body, remat), x, params["layers"])
        aux = jnp.float32(0)

    elif at == "hybrid":
        every = cfg.hybrid_attn_every

        def body(carry, inp):
            lp, idx = inp
            def with_attn(h):
                out, _ = block_prefill(params["shared_attn"], h, pos, cfg)
                return out
            h = jax.lax.cond(idx % every == 0, with_attn, lambda h: h, carry)
            return mamba_block_prefill(lp, h, cfg), None

        xs = (params["layers"], jnp.arange(cfg.num_layers))
        x, _ = jax.lax.scan(_maybe_remat(body, remat), x, xs)
        aux = jnp.float32(0)

    elif at == "vlm":
        every = cfg.cross_attn_every

        def body(carry, inp):
            lp, idx = inp
            h, aux = block_prefill(lp, carry, pos, cfg)
            def with_cross(hh):
                cp = jax.tree_util.tree_map(
                    lambda a: a[idx // every], params["cross_layers"])
                return cross_block(cp, hh, image_embeds, cfg)
            h = jax.lax.cond(idx % every == every - 1, with_cross,
                             lambda hh: hh, h)
            return h, aux

        xs = (params["layers"], jnp.arange(cfg.num_layers))
        x, auxs = jax.lax.scan(_maybe_remat(body, remat), x, xs)
        aux = jnp.sum(auxs)

    elif at == "audio":
        enc = _encoder_forward(cfg, params, frames, remat)

        def body(carry, inp):
            lp, cp = inp
            h, aux = block_prefill(lp, carry, pos, cfg)
            h = cross_block(cp, h, enc, cfg)
            return h, aux

        x, auxs = jax.lax.scan(_maybe_remat(body, remat), x,
                               (params["layers"], params["cross_layers"]))
        aux = jnp.sum(auxs)

    elif cfg.global_every:   # gemma3 grouped local/global
        W = cfg.sliding_window

        def group(carry, inp):
            locals_p, global_p = inp

            def local_body(h, lp):
                out, a = block_prefill(lp, h, pos, cfg, window=W)
                return out, a
            h, a1 = jax.lax.scan(local_body, carry, locals_p)
            h, a2 = block_prefill(global_p, h, pos, cfg, window=0)
            return h, jnp.sum(a1) + a2

        x, auxs = jax.lax.scan(_maybe_remat(group, remat), x,
                               (params["local_layers"],
                                params["global_layers"]))
        aux = jnp.sum(auxs)

    else:  # homogeneous dense / moe stack (uniform window)
        W = cfg.sliding_window

        def body(carry, lp):
            h, a = block_prefill(lp, carry, pos, cfg, window=W)
            return h, a

        x, auxs = jax.lax.scan(_maybe_remat(body, remat), x, params["layers"])
        aux = jnp.sum(auxs)

    if last_only:
        x = x[:, -1:]
    return _logits(cfg, params, x), aux


def _encoder_forward(cfg: ArchConfig, params: Dict, frames: jax.Array,
                     remat: bool) -> jax.Array:
    """Whisper encoder over stubbed frame embeddings (B, F, d):
    bidirectional self-attention (window=0, no causal mask trick: we reuse the
    causal path but encoders in this repro attend causally — noted in
    DESIGN.md as a stub simplification kept symmetric for the oracle)."""
    B, F, _ = frames.shape
    pos = _positions(B, F)

    def body(carry, lp):
        h, _ = block_prefill(lp, carry, pos, cfg)
        return h, None

    x, _ = jax.lax.scan(_maybe_remat(body, remat), frames,
                        params["enc_layers"])
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# loss / train step
# ---------------------------------------------------------------------------

def loss_fn(cfg: ArchConfig, params: Dict, batch: Dict, *,
            remat: bool = True) -> Tuple[jax.Array, Dict]:
    counters = {}
    if cfg.arch_type == "pattern":
        x, routed, dropped = pattern_forward(cfg, params, batch["tokens"],
                                             remat=remat)
        logits, aux = _logits(cfg, params, x), jnp.float32(0)
        counters = {"expert_tokens": routed, "dropped": dropped}
    else:
        logits, aux = forward(cfg, params, batch["tokens"],
                              image_embeds=batch.get("image_embeds"),
                              frames=batch.get("frames"), remat=remat)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    nll = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    mask = (labels >= 0) & (labels < cfg.vocab_size)
    nll = jnp.where(mask, nll, 0.0)
    loss = jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1)
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux": aux, **counters}



# ---------------------------------------------------------------------------
# decode caches
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, seq: int,
               image_kv: Optional[Dict] = None,
               enc_kv: Optional[Dict] = None) -> Dict:
    """Allocate the decode cache for ``seq`` total positions."""
    dt = cfg.jax_dtype
    L, B = cfg.num_layers, batch
    G, hd = cfg.num_kv_heads, cfg.head_dim
    at = cfg.arch_type

    def kv(n_layers, T):
        return {"k": jnp.zeros((n_layers, B, T, G, hd), dt),
                "v": jnp.zeros((n_layers, B, T, G, hd), dt)}

    if at == "ssm":
        return _ssm_cache(cfg, B)
    if at == "hybrid":
        n_attn = (cfg.num_layers + cfg.hybrid_attn_every - 1) \
            // cfg.hybrid_attn_every
        return {"mamba": _ssm_cache(cfg, B), "attn": kv(n_attn, seq)}
    if at == "vlm":
        n_cross = cfg.num_layers // cfg.cross_attn_every
        assert image_kv is not None
        return {"kv": kv(L, seq), "cross": image_kv}
    if at == "audio":
        assert enc_kv is not None
        return {"kv": kv(L, seq), "cross": enc_kv}
    if cfg.use_mla:
        return {"ckv": jnp.zeros((L, B, seq, cfg.kv_lora_rank), dt),
                "krope": jnp.zeros((L, B, seq, cfg.qk_rope_head_dim), dt)}
    if cfg.global_every:
        n_groups = cfg.num_layers // cfg.global_every
        n_local = cfg.global_every - 1
        Wr = min(cfg.sliding_window, seq)
        return {"local": {"k": jnp.zeros((n_groups, n_local, B, Wr, G, hd), dt),
                          "v": jnp.zeros((n_groups, n_local, B, Wr, G, hd), dt)},
                "global": kv(n_groups, seq)}
    if cfg.sliding_window:
        return kv(L, min(cfg.sliding_window, seq))   # ring buffers
    return kv(L, seq)


def _ssm_cache(cfg: ArchConfig, B: int) -> Dict:
    H, P, N, W = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state, cfg.conv_width
    cd = H * P + 2 * N
    L = cfg.num_layers
    return {"conv": jnp.zeros((L, B, W - 1, cd), cfg.jax_dtype),
            "ssm": jnp.zeros((L, B, H, N, P), jnp.float32)}


def make_image_kv(cfg: ArchConfig, params: Dict,
                  image_embeds: jax.Array) -> Dict:
    """Precompute cross-attn K/V per cross layer for decode."""
    return jax.vmap(lambda cp: attn_lib.cross_kv(cp["attn"], image_embeds,
                                                 cfg))(params["cross_layers"])


def make_enc_kv(cfg: ArchConfig, params: Dict, frames: jax.Array) -> Dict:
    enc = _encoder_forward(cfg, params, frames, remat=False)
    return jax.vmap(lambda cp: attn_lib.cross_kv(cp["attn"], enc, cfg))(
        params["cross_layers"])


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------

def decode_step(cfg: ArchConfig, params: Dict, cache: Dict,
                token: jax.Array, t: jax.Array) -> Tuple[jax.Array, Dict]:
    """token: (B,) int32; t: scalar absolute position.  Returns
    (logits (B, V_padded), new cache)."""
    B = token.shape[0]
    x = _embed(cfg, params, token[:, None])
    at = cfg.arch_type

    if at == "ssm":
        def body(carry, inp):
            lp, lc = inp
            h, nc = mamba_block_decode(lp, carry, lc, cfg)
            return h, nc
        x, new = jax.lax.scan(body, x, (params["layers"], cache))
        cache = new

    elif at == "hybrid":
        every = cfg.hybrid_attn_every

        def body(carry, inp):
            h, attn_cache = carry
            lp, mc, idx = inp

            def with_attn(args):
                hh, ac = args
                a_idx = idx // every
                lc = jax.tree_util.tree_map(lambda c: c[a_idx], ac)
                out, lc_new = block_decode(params["shared_attn"], hh, t, lc,
                                           cfg)
                ac = jax.tree_util.tree_map(
                    lambda c, n: jax.lax.dynamic_update_index_in_dim(
                        c, n.astype(c.dtype), a_idx, 0), ac, lc_new)
                return out, ac

            h, attn_cache = jax.lax.cond(idx % every == 0, with_attn,
                                         lambda a: a, (h, attn_cache))
            h, mc_new = mamba_block_decode(lp, h, mc, cfg)
            return (h, attn_cache), mc_new

        xs = (params["layers"], cache["mamba"],
              jnp.arange(cfg.num_layers))
        (x, attn_new), mamba_new = jax.lax.scan(body, (x, cache["attn"]), xs)
        cache = {"mamba": mamba_new, "attn": attn_new}

    elif at == "audio":
        def body(carry, inp):
            lp, lc, cp, ckv = inp
            h, nc = block_decode(lp, carry, t, lc, cfg)
            h = cross_block(cp, h, None, cfg, kv=ckv)
            return h, nc

        xs = (params["layers"], cache["kv"], params["cross_layers"],
              cache["cross"])
        x, kv_new = jax.lax.scan(body, x, xs)
        cache = dict(cache, kv=kv_new)

    elif at == "vlm":
        every = cfg.cross_attn_every
        cross_kv_all = cache["cross"]   # (n_cross, B, T_img, G, hd) x2

        def body(carry, inp):
            lp, lc, idx = inp
            h, nc = block_decode(lp, carry, t, lc, cfg)

            def with_cross(hh):
                cp = jax.tree_util.tree_map(
                    lambda a: a[idx // every], params["cross_layers"])
                kv_i = jax.tree_util.tree_map(
                    lambda a: a[idx // every], cross_kv_all)
                return cross_block(cp, hh, None, cfg, kv=kv_i)

            h = jax.lax.cond(idx % every == every - 1, with_cross,
                             lambda hh: hh, h)
            return h, nc

        xs = (params["layers"], cache["kv"], jnp.arange(cfg.num_layers))
        x, kv_new = jax.lax.scan(body, x, xs)
        cache = dict(cache, kv=kv_new)

    elif cfg.global_every:
        W = cfg.sliding_window

        def group(carry, inp):
            locals_p, global_p, lc_local, lc_global = inp

            def local_body(h, lin):
                lp, lc = lin
                out, nc = block_decode(lp, h, t, lc, cfg, ring=True)
                return out, nc
            h, nc_local = jax.lax.scan(local_body, carry,
                                       (locals_p, lc_local))
            h, nc_global = block_decode(global_p, h, t, lc_global, cfg)
            return h, (nc_local, nc_global)

        xs = (params["local_layers"], params["global_layers"],
              cache["local"], cache["global"])
        x, (local_new, global_new) = jax.lax.scan(group, x, xs)
        cache = {"local": local_new, "global": global_new}

    else:
        ring = bool(cfg.sliding_window)

        def body(carry, inp):
            lp, lc = inp
            h, nc = block_decode(lp, carry, t, lc, cfg,
                                 window=cfg.sliding_window, ring=ring)
            return h, nc

        x, new = jax.lax.scan(body, x, (params["layers"], cache))
        cache = new

    logits = _logits(cfg, params, x)[:, 0]
    return logits, cache
