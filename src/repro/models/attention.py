"""Attention variants: GQA (+RoPE, sliding window, logit softcap, QKV bias),
MLA (DeepSeek-V2 latent attention with absorbed decode), and cross-attention.

Two entry points per variant: ``*_prefill`` (full sequence, causal) and
``*_decode`` (1 new token against a fixed-size KV cache written at position
``t``).  Caches are dense fixed-shape arrays so they shard cleanly under pjit;
for long_500k the cache *sequence* axis is sharded over "data" and the softmax
reductions over that axis are handled by GSPMD (context-parallel decode).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ArchConfig, rope, softcap

NEG_INF = -2.0e38


def _gqa_logits(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B,S,G,R,hd), k: (B,T,G,hd) -> (B,G,R,S,T)."""
    return jnp.einsum("bsgrk,btgk->bgrst", q, k)


def _causal_window_mask(q_pos: jax.Array, k_pos: jax.Array,
                        window) -> jax.Array:
    """True where attention is allowed. q_pos: (S,), k_pos: (T,).  ``window``
    may be a python int or a traced scalar (0 => full causal)."""
    causal = k_pos[None, :] <= q_pos[:, None]
    w = jnp.asarray(window, jnp.int32)
    win_ok = (w <= 0) | ((q_pos[:, None] - k_pos[None, :]) < w)
    return causal & win_ok


#: sequences at or above this length use the double-blocked streaming softmax
#: so no (S, T) logits matrix is ever materialised — neither in the forward
#: pass nor in the scan's saved backward residuals (each block body is
#: jax.checkpoint'ed, so the backward recomputes block probs from q/k/v).
QBLOCK_THRESHOLD = 2048
QBLOCK = 512
KBLOCK = 512


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array, q_pos: jax.Array,
          k_pos: jax.Array, window, cap: float, scale: float) -> jax.Array:
    """q: (B,Sq,G,R,hd); k/v: (B,T,G,hd) -> (B,Sq,G,R,hd)."""
    logits = _gqa_logits(q, k) * scale
    logits = softcap(logits, cap)
    mask = _causal_window_mask(q_pos, k_pos, window)
    logits = jnp.where(mask[None, None, None], logits.astype(jnp.float32),
                       NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrst,btgk->bsgrk", probs, v)


def _flash_sdpa(q: jax.Array, k: jax.Array, v: jax.Array, q_pos: jax.Array,
                k_pos: jax.Array, window, cap: float,
                scale: float) -> jax.Array:
    """Streaming (online-softmax) attention for one q block.

    q: (B,Q,G,R,hd); k/v: (B,T,G,hd) with T % KBLOCK == 0.  The scan walks
    k-blocks carrying (acc, running max, running denom); the checkpointed
    body keeps live memory at one (B,G,R,Q,KBLOCK) logits block.
    """
    B, Q, G, R, hd = q.shape
    T = k.shape[1]
    nkb = T // KBLOCK
    f32 = jnp.float32
    kb = jnp.moveaxis(k.reshape(B, nkb, KBLOCK, G, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nkb, KBLOCK, G, hd), 1, 0)
    pb = k_pos.reshape(nkb, KBLOCK)

    def body(carry, inp):
        acc, mx, den = carry                   # (B,G,R,Q,hd), (B,G,R,Q) x2
        kblk, vblk, kpos = inp
        logits = jnp.einsum("bqgrk,btgk->bgrqt", q, kblk).astype(f32) * scale
        logits = softcap(logits, cap)
        mask = _causal_window_mask(q_pos, kpos, window)    # (Q, KBLOCK)
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
        blk_max = jnp.max(logits, -1)
        new_mx = jnp.maximum(mx, blk_max)
        # new_mx == NEG_INF only while no key is visible yet; keep alpha/p
        # finite there (the row contributes nothing).
        safe_mx = jnp.where(new_mx <= NEG_INF, 0.0, new_mx)
        alpha = jnp.exp(jnp.where(mx <= NEG_INF, NEG_INF, mx) - safe_mx)
        p = jnp.exp(logits - safe_mx[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        den = den * alpha + jnp.sum(p, -1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bgrqt,btgk->bgrqk", p.astype(q.dtype), vblk).astype(f32)
        return (acc, new_mx, den), None

    init = (jnp.zeros((B, G, R, Q, hd), f32),
            jnp.full((B, G, R, Q), NEG_INF, f32),
            jnp.zeros((B, G, R, Q), f32))
    (acc, _, den), _ = jax.lax.scan(jax.checkpoint(body), init, (kb, vb, pb))
    out = acc / jnp.maximum(den, 1e-30)[..., None]
    return jnp.moveaxis(out, 3, 1).astype(q.dtype)    # (B,Q,G,R,hd)


def gqa_prefill(p: Dict, x: jax.Array, positions: jax.Array,
                cfg: ArchConfig, *, window: int = 0,
                scale: Optional[float] = None) -> jax.Array:
    """x: (B,S,d) -> (B,S,d)."""
    B, S, _ = x.shape
    H, G, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    R = H // G
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dgk->bsgk", x, p["wk"])
    v = jnp.einsum("bsd,dgk->bsgk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = q.reshape(B, S, G, R, hd)
    sc = scale or hd ** -0.5
    k_pos = positions[0]
    with jax.named_scope("attn.core"):
        if S < QBLOCK_THRESHOLD or S % QBLOCK != 0 or S % KBLOCK != 0:
            out = _sdpa(q, k, v, positions[0], k_pos, window,
                        cfg.attn_logit_softcap, sc)
        else:
            nb = S // QBLOCK
            q_blocks = jnp.moveaxis(
                q.reshape(B, nb, QBLOCK, G, R, hd), 1, 0)   # (nb,B,Q,G,R,hd)
            pos_blocks = k_pos.reshape(nb, QBLOCK)

            def body(_, inp):
                qb, pb = inp
                ob = _flash_sdpa(qb, k, v, pb, k_pos, window,
                                 cfg.attn_logit_softcap, sc)
                return None, ob

            _, out_blocks = jax.lax.scan(jax.checkpoint(body), None,
                                         (q_blocks, pos_blocks))
            out = jnp.moveaxis(out_blocks, 0, 1).reshape(B, S, G, R, hd)
    out = out.reshape(B, S, H, hd)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def gqa_decode(p: Dict, x: jax.Array, t: jax.Array, cache: Dict,
               cfg: ArchConfig, *, window: int = 0, ring: bool = False,
               scale: Optional[float] = None) -> Tuple[jax.Array, Dict]:
    """x: (B,1,d); cache {"k","v"}: (B,T,G,hd); t: scalar ABSOLUTE position.

    ``ring=True`` treats the cache as a rolling buffer of the last T tokens
    (sliding-window decode: write at ``t % T``; keys carry their absolute RoPE
    phase so the mask is just 'slot already written')."""
    B, _, _ = x.shape
    H, G, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    R = H // G
    T = cache["k"].shape[1]
    write_at = jax.lax.rem(t, T) if ring else t
    pos = jnp.full((B, 1), t, jnp.int32)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dgk->bsgk", x, p["wk"])
    v = jnp.einsum("bsd,dgk->bsgk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)
    k_cache = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                           (0, write_at, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                           (0, write_at, 0, 0))
    q = q.reshape(B, 1, G, R, hd)
    logits = _gqa_logits(q, k_cache) * (scale or hd ** -0.5)
    logits = softcap(logits, cfg.attn_logit_softcap)
    k_pos = jnp.arange(T)
    ok = k_pos <= t                       # ring: all-true once t >= T
    if not ring:
        w = jnp.asarray(window, jnp.int32)
        ok &= (w <= 0) | ((t - k_pos) < w)
    logits = jnp.where(ok[None, None, None, None, :],
                       logits.astype(jnp.float32), NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    out = jnp.einsum("bgrst,btgk->bsgrk", probs, v_cache).reshape(B, 1, H, hd)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# MLA — DeepSeek-V2 multi-head latent attention
# ---------------------------------------------------------------------------

def mla_prefill(p: Dict, x: jax.Array, positions: jax.Array,
                cfg: ArchConfig) -> jax.Array:
    B, S, _ = x.shape
    H = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])          # (B,S,H,dn+dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    ckv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])       # (B,S,r)
    k_rope = jnp.einsum("bsd,dk->bsk", x, p["w_krope"])[:, :, None, :]
    k_rope = rope(k_rope, positions, cfg.rope_theta)     # (B,S,1,dr)
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uv"])
    scale = (dn + dr) ** -0.5
    k_rope_s = k_rope.reshape(B, S, dr)
    k_pos = positions[0]

    def attend(qn, qr, q_pos):
        logits = (jnp.einsum("bshk,bthk->bhst", qn, k_nope)
                  + jnp.einsum("bshk,btk->bhst", qr, k_rope_s)) * scale
        mask = _causal_window_mask(q_pos, k_pos, 0)
        logits = jnp.where(mask[None, None], logits.astype(jnp.float32),
                           NEG_INF)
        probs = jax.nn.softmax(logits, -1).astype(x.dtype)
        return jnp.einsum("bhst,bthk->bshk", probs, v)

    def attend_flash(qn, qr, q_pos):
        """Streaming softmax over T blocks; qn/qr: (B,Q,H,*)."""
        Q = qn.shape[1]
        nkb = S // KBLOCK
        f32 = jnp.float32
        knb = jnp.moveaxis(k_nope.reshape(B, nkb, KBLOCK, H, dn), 1, 0)
        krb = jnp.moveaxis(k_rope_s.reshape(B, nkb, KBLOCK, dr), 1, 0)
        vb = jnp.moveaxis(v.reshape(B, nkb, KBLOCK, H, dv), 1, 0)
        pb = k_pos.reshape(nkb, KBLOCK)

        def body(carry, inp):
            acc, mx, den = carry
            knblk, krblk, vblk, kpos = inp
            logits = (jnp.einsum("bqhk,bthk->bhqt", qn, knblk)
                      + jnp.einsum("bqhk,btk->bhqt", qr, krblk)
                      ).astype(f32) * scale
            mask = _causal_window_mask(q_pos, kpos, 0)
            logits = jnp.where(mask[None, None], logits, NEG_INF)
            new_mx = jnp.maximum(mx, jnp.max(logits, -1))
            safe_mx = jnp.where(new_mx <= NEG_INF, 0.0, new_mx)
            alpha = jnp.exp(jnp.where(mx <= NEG_INF, NEG_INF, mx) - safe_mx)
            pr = jnp.exp(logits - safe_mx[..., None])
            pr = jnp.where(mask[None, None], pr, 0.0)
            den = den * alpha + jnp.sum(pr, -1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqt,bthk->bhqk", pr.astype(x.dtype), vblk).astype(f32)
            return (acc, new_mx, den), None

        init = (jnp.zeros((B, H, Q, dv), f32),
                jnp.full((B, H, Q), NEG_INF, f32),
                jnp.zeros((B, H, Q), f32))
        (acc, _, den), _ = jax.lax.scan(jax.checkpoint(body), init,
                                        (knb, krb, vb, pb))
        out = acc / jnp.maximum(den, 1e-30)[..., None]
        return jnp.moveaxis(out, 2, 1).astype(x.dtype)     # (B,Q,H,dv)

    dv = cfg.v_head_dim
    if S < QBLOCK_THRESHOLD or S % QBLOCK != 0 or S % KBLOCK != 0:
        out = attend(q_nope, q_rope, k_pos)
    else:
        nb = S // QBLOCK

        def body(_, inp):
            qn, qr, pb = inp
            return None, attend_flash(qn, qr, pb)

        _, blocks = jax.lax.scan(
            jax.checkpoint(body), None,
            (jnp.moveaxis(q_nope.reshape(B, nb, QBLOCK, H, dn), 1, 0),
             jnp.moveaxis(q_rope.reshape(B, nb, QBLOCK, H, dr), 1, 0),
             k_pos.reshape(nb, QBLOCK)))
        out = jnp.moveaxis(blocks, 0, 1).reshape(B, S, H, cfg.v_head_dim)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def mla_decode(p: Dict, x: jax.Array, t: jax.Array, cache: Dict,
               cfg: ArchConfig) -> Tuple[jax.Array, Dict]:
    """Absorbed-matrices decode: attention runs in the r-dim latent space, the
    cache stores only (c_kv, k_rope) — this is MLA's memory win."""
    B = x.shape[0]
    H = cfg.num_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    T = cache["ckv"].shape[1]
    pos = jnp.full((B, 1), t, jnp.int32)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope(q_rope, pos, cfg.rope_theta)
    ckv_new = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])
    krope_new = rope(jnp.einsum("bsd,dk->bsk", x, p["w_krope"])[:, :, None, :],
                     pos, cfg.rope_theta)[:, :, 0, :]
    ckv = jax.lax.dynamic_update_slice(
        cache["ckv"], ckv_new.astype(cache["ckv"].dtype), (0, t, 0))
    krope = jax.lax.dynamic_update_slice(
        cache["krope"], krope_new.astype(cache["krope"].dtype), (0, t, 0))
    # absorb W_uk into q: q_lat (B,1,H,r)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"])
    scale = (dn + dr) ** -0.5
    logits = (jnp.einsum("bshr,btr->bhst", q_lat, ckv)
              + jnp.einsum("bshk,btk->bhst", q_rope, krope)) * scale
    ok = jnp.arange(T) <= t
    logits = jnp.where(ok[None, None, None], logits.astype(jnp.float32),
                       NEG_INF)
    probs = jax.nn.softmax(logits, -1).astype(x.dtype)
    out_lat = jnp.einsum("bhst,btr->bshr", probs, ckv)   # latent-space output
    out = jnp.einsum("bshr,rhk->bshk", out_lat, p["w_uv"])
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, {"ckv": ckv, "krope": krope}


# ---------------------------------------------------------------------------
# cross-attention (VLM image layers / whisper decoder)
# ---------------------------------------------------------------------------

def cross_attn(p: Dict, x: jax.Array, kv_src: jax.Array,
               cfg: ArchConfig) -> jax.Array:
    """x: (B,S,d) queries; kv_src: (B,T,d) encoder/image states."""
    B, S, _ = x.shape
    H, G, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    R = H // G
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"]).reshape(B, S, G, R, hd)
    k = jnp.einsum("btd,dgk->btgk", kv_src, p["wk"])
    v = jnp.einsum("btd,dgk->btgk", kv_src, p["wv"])
    logits = _gqa_logits(q, k) * hd ** -0.5
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(x.dtype)
    out = jnp.einsum("bgrst,btgk->bsgrk", probs, v).reshape(B, S, H, hd)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def cross_attn_cached(p: Dict, x: jax.Array, kv: Dict,
                      cfg: ArchConfig) -> jax.Array:
    """Decode-path cross attention against precomputed K/V (B,T,G,hd)."""
    B, S, _ = x.shape
    H, G, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    R = H // G
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"]).reshape(B, S, G, R, hd)
    logits = _gqa_logits(q, kv["k"]) * hd ** -0.5
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(x.dtype)
    out = jnp.einsum("bgrst,btgk->bsgrk", probs, kv["v"]).reshape(B, S, H, hd)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def cross_kv(p: Dict, kv_src: jax.Array, cfg: ArchConfig) -> Dict:
    return {"k": jnp.einsum("btd,dgk->btgk", kv_src, p["wk"]),
            "v": jnp.einsum("btd,dgk->btgk", kv_src, p["wv"])}
