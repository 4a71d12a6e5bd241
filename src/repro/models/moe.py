"""Mixture-of-Experts FFN: top-k router, capacity-based dense dispatch
(Shazeer-style einsum dispatch — maps onto expert parallelism over the
"model" mesh axis), optional shared experts (DeepSeek-V2); and
:func:`moe_held`, the dropless held-expert layer of the ``pattern`` stack.

Dispatch is the classic dropping formulation: each expert processes at most
``capacity = ceil(cf * tokens * k / E)`` tokens; overflow tokens fall through
to the residual (plus shared experts).  Aux load-balance loss is returned for
training.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ops import grouped_matmul
from repro.models.common import ArchConfig, mlp_apply, relu2


def _capacity(cfg: ArchConfig, num_tokens: int) -> int:
    cap = int(cfg.capacity_factor * num_tokens * cfg.experts_per_token
              / cfg.num_experts)
    return max(cap, 1)


def moe_ffn(p: Dict, x: jax.Array, cfg: ArchConfig,
            dropless: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,d) -> (out (B,S,d), aux_loss scalar).

    ``dropless=True`` sets capacity = num_tokens (an expert can never
    overflow) — used on the decode path so decode == prefill semantics don't
    depend on batch composition.

    Dispatch mode (``cfg.moe_dispatch``):
    * ``gather``  — slot->token gather dispatch (cheapest FLOPs; backward
      contains scatters which GSPMD shards poorly on big meshes).
    * ``einsum``  — Switch-Transformer one-hot matmul dispatch over token
      chunks (MXU-friendly, no scatters anywhere in fwd/bwd; costs extra
      dispatch FLOPs ~ 2*E*C/ (3*K*ff) of the expert GEMMs).
    """
    if cfg.moe_dispatch == "einsum" and not dropless:
        return _moe_ffn_einsum(p, x, cfg)
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    N = B * S
    xt = x.reshape(N, d)
    C = N if dropless else _capacity(cfg, N)

    logits = (xt @ p["router"]).astype(jnp.float32)            # (N, E)
    probs = jax.nn.softmax(logits, -1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)              # (N, K)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    # position of each (token, k) inside its expert's buffer
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)      # (N, K, E)
    flat = onehot.reshape(N * K, E)
    pos_in_expert = (jnp.cumsum(flat, 0) - flat).reshape(N, K, E)
    pos = jnp.sum(pos_in_expert * onehot, -1)                  # (N, K)
    keep = pos < C
    # Gather-based dispatch (GSPMD-friendly: the expert dim of every large
    # tensor shards over "model"; only small int32 index maps are scattered).
    tok_idx = jnp.broadcast_to(jnp.arange(N)[:, None], (N, K))
    e_flat = gate_idx.reshape(-1)
    c_flat = jnp.where(keep, pos, C).reshape(-1)               # C = dropped slot
    t_flat = tok_idx.reshape(-1)
    # slot -> token map (E, C+1); sentinel N points at an all-zero pad row
    slot_tok = jnp.full((E, C + 1), N, jnp.int32)
    slot_tok = slot_tok.at[e_flat, c_flat].set(t_flat, mode="drop")
    xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], 0)
    buffers = xt_pad[slot_tok[:, :C]]                          # (E, C, d)

    # expert computation: (E, C, d) x (E, d, ff)
    h = jnp.einsum("ecd,edf->ecf", buffers, p["w_gate"])
    h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", buffers, p["w_in"])
    y = jnp.einsum("ecf,efd->ecd", h, p["w_out"])              # (E, C, d)

    # combine back: one (N, d) gather per k (never materialise (N*K, d))
    y_pad = jnp.concatenate([y, jnp.zeros((E, 1, d), y.dtype)], 1)
    out = jnp.zeros((N, d), xt.dtype)
    e_nk = gate_idx                                            # (N, K)
    c_nk = jnp.where(keep, pos, C)                             # (N, K)
    for k in range(K):
        w_k = (gate_vals[:, k] * keep[:, k]).astype(xt.dtype)  # (N,)
        out = out + y_pad[e_nk[:, k], c_nk[:, k]] * w_k[:, None]

    if cfg.num_shared_experts:
        out = out + mlp_apply({"w_gate": p["shared_w_gate"],
                               "w_in": p["shared_w_in"],
                               "w_out": p["shared_w_out"]}, xt, "swiglu")

    # Switch-style load-balance aux loss
    me = jnp.mean(probs, 0)                                    # (E,)
    ce = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32), 0)
    aux = E * jnp.sum(me * ce)
    return out.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Switch-style chunked einsum dispatch (no scatters: GSPMD-friendly)
# ---------------------------------------------------------------------------

def _moe_ffn_einsum(p: Dict, x: jax.Array, cfg: ArchConfig
                    ) -> Tuple[jax.Array, jax.Array]:
    """One-hot matmul dispatch over token chunks (Switch Transformer / Mesh
    dispatch).  Capacity is per-chunk: C = ceil(cf * chunk * K / E)."""
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    N = B * S
    G = min(cfg.moe_chunk, N)              # tokens per dispatch group
    n_chunks = -(-N // G)
    pad = n_chunks * G - N
    xt = x.reshape(N, d)
    if pad:
        xt = jnp.concatenate([xt, jnp.zeros((pad, d), xt.dtype)], 0)
    C = max(int(cfg.capacity_factor * G * K / E), 1)

    logits_all = (xt @ p["router"]).astype(jnp.float32)        # (N', E)
    xc = xt.reshape(n_chunks, G, d)
    lc = logits_all.reshape(n_chunks, G, E)

    def chunk(carry, inp):
        xg, lg = inp                                           # (G,d),(G,E)
        probs = jax.nn.softmax(lg, -1)
        gate_vals, gate_idx = jax.lax.top_k(probs, K)          # (G, K)
        gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True),
                                         1e-9)
        oh_e = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)    # (G, K, E)
        flat = oh_e.reshape(G * K, E)
        pos = jnp.sum(((jnp.cumsum(flat, 0) - flat).reshape(G, K, E)) * oh_e,
                      -1)                                      # (G, K)
        keep = pos < C
        oh_c = jax.nn.one_hot(jnp.where(keep, pos, C), C + 1,
                              dtype=xg.dtype)[..., :C]         # (G, K, C)
        disp = jnp.einsum("gke,gkc->gec", oh_e.astype(xg.dtype),
                          oh_c)                                # (G, E, C)
        buf = jnp.einsum("gec,gd->ecd", disp, xg)              # (E, C, d)
        h = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
        h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", buf, p["w_in"])
        y = jnp.einsum("ecf,efd->ecd", h, p["w_out"])          # (E, C, d)
        comb = jnp.einsum("gke,gkc,gk->gec", oh_e.astype(xg.dtype), oh_c,
                          (gate_vals * keep).astype(xg.dtype))
        out = jnp.einsum("gec,ecd->gd", comb, y)
        # Switch aux loss per chunk
        me = jnp.mean(probs, 0)
        ce = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32), 0)
        return carry, (out, E * jnp.sum(me * ce))

    _, (outs, auxs) = jax.lax.scan(chunk, None, (xc, lc))
    out = outs.reshape(n_chunks * G, d)[:N]

    if cfg.num_shared_experts:
        out = out + mlp_apply({"w_gate": p["shared_w_gate"],
                               "w_in": p["shared_w_in"],
                               "w_out": p["shared_w_out"]}, xt[:N], "swiglu")
    return out.reshape(B, S, d), jnp.mean(auxs)


# ---------------------------------------------------------------------------
# held experts, dropless (the chip's share of an expert-parallel layer)
# ---------------------------------------------------------------------------

def dropped_assignments(sizes: jax.Array, rows: jax.Array,
                        groups: jax.Array) -> jax.Array:
    """Assignments to a held expert (``groups`` below ``len(sizes) - 1``)
    whose output row ``rows`` lies outside that expert's group of rows, as
    consecutive groups of ``sizes`` give them: int32 count."""
    row_group = jnp.searchsorted(jnp.cumsum(sizes), rows, side="right")
    held = groups < sizes.shape[0] - 1
    return jnp.sum((held & (row_group != groups)).astype(jnp.int32))


def moe_held(p: Dict, x: jax.Array, cfg: ArchConfig
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The part of a routed-expert layer that this chip's experts give, plus
    its shared expert.  x: (B,S,d) -> (out (B,S,d), tokens routed to each
    held expert (E_h,) int32, dropped assignments () int32).

    The router scores all ``cfg.num_experts`` experts by a sigmoid, picks
    the top ``experts_per_token`` by score (the score-correction bias is
    held at its initial 0, so it adds nothing), weighs the chosen scores
    normalised to sum 1 times ``routed_scale``, and the chip computes
    its experts ``expert_first ..`` + ``experts_held``: relu^2 MLPs over
    every token routed to them, by grouped products
    (:func:`repro.kernels.ops.grouped_matmul`) over the (token, choice) rows
    sorted by expert, so that no token is dropped.  What the absent
    experts would add is left out.  A held (token, choice) assignment is
    dropped when the row that its output is read back from was not computed
    by its own expert's group of the products; the layer has no capacity,
    so that count is 0.  The routing, sort and combine run under
    the ``moe.route`` named scope, the grouped products under
    ``moe.experts``, the shared expert under ``moe.shared``."""
    B, S, d = x.shape
    K, Eh, e0 = cfg.experts_per_token, cfg.held_experts, cfg.expert_first
    N = B * S
    f32 = jnp.float32
    xt = x.reshape(N, d)
    with jax.named_scope("moe.route"):
        logits = jnp.dot(xt.astype(f32), p["router"].astype(f32),
                         precision=jax.lax.Precision.HIGHEST)   # (N, E)
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(s, K)
        w = jnp.take_along_axis(s, idx, -1)                     # (N, K)
        w = w / jnp.sum(w, -1, keepdims=True) * cfg.routed_scale
        local = idx - e0
        held = (local >= 0) & (local < Eh)
        grp = jnp.where(held, local, Eh).reshape(N * K)         # Eh = absent
        order = jnp.argsort(grp, stable=True)
        sizes = jnp.sum(jax.nn.one_hot(grp, Eh + 1, dtype=jnp.int32), 0)
        # rows padded to whole 128-row tiles; the pad joins the absent group
        M = -(-N * K // 128) * 128
        sizes = sizes.at[Eh].add(M - N * K)
        rows = jnp.pad(jnp.take(xt, order // K, axis=0),
                       ((0, M - N * K), (0, 0)))                # (M, d)
    with jax.named_scope("moe.experts"):
        h = relu2(grouped_matmul(rows, p["w_in"], sizes))
        y = grouped_matmul(h, p["w_out"], sizes)                # (M, d)
    with jax.named_scope("moe.route"):
        back = jnp.zeros((N * K,), jnp.int32).at[order].set(
            jnp.arange(N * K, dtype=jnp.int32))
        y = jnp.take(y, back, axis=0).reshape(N, K, d)
        out = jnp.einsum("nkd,nk->nd", y, (w * held).astype(y.dtype))
        routed = sizes[:Eh]
        dropped = dropped_assignments(sizes, back, grp)
    with jax.named_scope("moe.shared"):
        out = out + mlp_apply({"w_in": p["shared_w_in"],
                               "w_out": p["shared_w_out"]}, xt, "relu2")
    return out.reshape(B, S, d), routed, dropped
