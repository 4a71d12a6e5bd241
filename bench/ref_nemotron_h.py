"""Plain reference of DASHA training of a Nemotron-H chip share (Mamba-2,
held-expert MoE and attention layers in one stack).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
written from the published description (the layer equations are in
``DESIGN.md``) and importing nothing of the program:

* pre-norm layers ``x + mixer(rmsnorm(x))`` in ``hybrid_override_pattern``
  order, each rematerialised;
* Mamba-2: the SSD in its quadratic form, y = (L o C B^T) (dt x) with
  L[q, k] = exp(sum_{j=k+1..q} a_j), head h reading group h // (H/G)'s B
  and C, computed one block of queries at a time against every key; the
  decay exponents come from block-local cumulative sums plus whole-block
  totals, so that f32 keeps them to a few ulps at any position; then the
  D skip and the gated RMSNorm over each group's channels;
* MoE: sigmoid scores over every expert, the top k by score (the
  correction bias held at 0), weights normalised to sum 1 times the routed
  scale, and each held expert run over every token with its own weight
  column (zero where the token did not choose it), plus the shared expert;
* attention: causal softmax attention, one block of queries at a time;
* the untied head and the mean next-token cross-entropy over the held
  vocabulary, one block of positions at a time.

DASHA's step, its keys and the message replay are
:class:`bench.ref_lm.Reference`'s, with this model's gradient.
``mm_dtype`` rounds every matmul operand to a lower precision first (the
control).  ``fault`` plants a fault the check has to catch: ``unchanged``,
``capacity`` (tokens past capacity factor 1.25 dropped, as the program's
older MoE did), ``softmax`` (softmax router scores), ``one_group`` (every
head reads group 0's B and C).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from bench import ref_lm
from bench.gen_nemotron_h import KINDS, pattern
from bench.ref_lm import F32, _mm, _rms, _silu

#: faults planted in the model (``unchanged`` is the step's)
MODEL_FAULTS = ("capacity", "softmax", "one_group")

#: query positions per block of the SSD and of attention, and positions per
#: block of the loss
SSD_BLOCK, ATTN_BLOCK, LOSS_BLOCK = 256, 512, 2048


def _blocks(S: int, T: int) -> int:
    return T if S % T == 0 and S > T else S


def _mamba(lp, x, cfg, mm, fault):
    S = x.shape[0]
    H, P = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    G, N = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    inner = H * P
    eps = float(cfg["norm_eps"])
    h = _rms(x, lp["ln"], eps)
    z = mm("sd,dhp->shp", h, lp["w_z"]).reshape(S, inner)
    xbc = mm("sd,dc->sc", h, lp["w_xbc"])
    dt = jax.nn.softplus(mm("sd,dh->sh", h, lp["w_dt"])
                         + lp["dt_bias"].astype(F32))
    w = lp["conv_w"].astype(F32)
    W = w.shape[0]
    pad = jnp.pad(xbc, ((W - 1, 0), (0, 0)))
    xbc = _silu(sum(pad[i:i + S] * w[i] for i in range(W))
                + lp["conv_b"].astype(F32))
    xs = xbc[:, :inner].reshape(S, H, P)
    b = xbc[:, inner:inner + G * N].reshape(S, G, N)
    c = xbc[:, inner + G * N:].reshape(S, G, N)
    if fault == "one_group":
        b = jnp.broadcast_to(b[:, :1], b.shape)
        c = jnp.broadcast_to(c[:, :1], c.shape)
    a = dt * (-jnp.exp(lp["A_log"].astype(F32)))            # (S, H)
    xdt = xs * dt[..., None]

    T = _blocks(S, SSD_BLOCK)
    nb = S // T
    loc = jnp.cumsum(a.reshape(nb, T, H), 1)                # in-block sums
    tot = loc[:, -1]                                        # (nb, H)
    blk = jnp.arange(nb)
    # between[i, j] = sum of the totals of blocks j .. i-1 (0 for j >= i)
    span = (blk[None, :, None] <= blk[None, None, :]) \
        & (blk[None, None, :] < blk[:, None, None])         # (i, j, m)
    between = jnp.einsum("ijm,mh->ijh", span.astype(F32), tot,
                         precision="highest")
    pos = jnp.arange(S)
    locs = loc.reshape(S, H)

    def query_block(i):
        q0 = i * T
        lq = jax.lax.dynamic_slice_in_dim(locs, q0, T)      # (T, H)
        expo = (between[i][jnp.repeat(blk, T)][None]        # (1, S, H)
                + lq[:, None, :] - locs[None])              # (T, S, H)
        qpos = q0 + jnp.arange(T)
        causal = pos[None, :] <= qpos[:, None]
        decay = jnp.exp(jnp.where(causal[..., None], expo, -jnp.inf))
        cq = jax.lax.dynamic_slice_in_dim(c, q0, T)         # (T, G, N)
        scores = mm("qgn,kgn->gqk", cq, b)                  # (G, T, S)
        scores = jnp.repeat(scores, H // G, axis=0)         # (H, T, S)
        return mm("hqk,khp->qhp", jnp.moveaxis(decay, -1, 0) * scores, xdt)

    y = jax.lax.map(jax.checkpoint(query_block), jnp.arange(nb))
    y = y.reshape(S, H, P) + xs * lp["D"].astype(F32)[None, :, None]
    y = (y.reshape(S, inner) * _silu(z)).reshape(S, G, inner // G)
    y = _rms(y, lp["norm"].reshape(G, inner // G), eps).reshape(S, inner)
    return x + mm("sc,cd->sd", y, lp["w_out"])


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _moe(lp, x, cfg, mm, fault):
    S = x.shape[0]
    E = int(cfg["published_n_routed_experts"])
    Eh, e0 = int(cfg["n_routed_experts"]), int(cfg["expert_first"])
    K = int(cfg["num_experts_per_tok"])
    h = _rms(x, lp["ln"], float(cfg["norm_eps"]))
    logits = mm("sd,de->se", h, lp["router"])
    s = jax.nn.softmax(logits, -1) if fault == "softmax" \
        else jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s, K)                            # bias b = 0
    wts = jnp.take_along_axis(s, idx, -1)
    wts = wts / jnp.sum(wts, -1, keepdims=True) \
        * float(cfg["routed_scaling_factor"])
    if fault == "capacity":
        # the place of each (token, choice) in its expert's buffer, in
        # token order; past int(1.25 S K / E) it is dropped
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32).reshape(S * K, E)
        place = jnp.sum((jnp.cumsum(onehot, 0) - onehot) * onehot, -1)
        wts = wts * (place.reshape(S, K) < int(1.25 * S * K / E))
    out = mm("sf,fd->sd", _relu2(mm("sd,df->sf", h, lp["shared_w_in"])),
             lp["shared_w_out"])
    for e in range(Eh):
        col = jnp.sum(jnp.where(idx == e0 + e, wts, 0.0), -1)   # (S,)
        y = mm("sf,fd->sd", _relu2(mm("sd,df->sf", h, lp["w_in"][e])),
               lp["w_out"][e])
        out = out + col[:, None] * y
    return x + out


def _attn(lp, x, cfg, mm, fault):
    S = x.shape[0]
    Ha, Ga = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    hd = int(cfg["head_dim"])
    h = _rms(x, lp["ln"], float(cfg["norm_eps"]))
    q = mm("sd,dhk->shk", h, lp["wq"])
    k = jnp.repeat(mm("sd,dgk->sgk", h, lp["wk"]), Ha // Ga, axis=1)
    v = jnp.repeat(mm("sd,dgk->sgk", h, lp["wv"]), Ha // Ga, axis=1)
    T = _blocks(S, ATTN_BLOCK)
    pos = jnp.arange(S)

    def query_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * T, T)
        logits = mm("qhk,shk->hqs", qb, k) / jnp.sqrt(float(hd))
        causal = pos[None, :] <= (i * T + jnp.arange(T))[:, None]
        probs = jax.nn.softmax(jnp.where(causal[None], logits, -jnp.inf), -1)
        return mm("hqs,shk->qhk", probs, v)

    o = jax.lax.map(jax.checkpoint(query_block), jnp.arange(S // T))
    return x + mm("shk,hkd->sd", o.reshape(S, Ha, hd), lp["wo"])


MIXERS = {"M": _mamba, "E": _moe, "*": _attn}


def seq_loss(params, tokens, labels, cfg, mm_dtype=F32, fault=""):
    """Mean next-token cross-entropy of one sequence over the vocabulary
    rows the params hold."""
    mm = _mm(mm_dtype)
    x = params["embed"][tokens].astype(F32)
    seen = {name: 0 for name in KINDS.values()}
    for letter in pattern(cfg):
        name = KINDS[letter]
        lp = jax.tree_util.tree_map(lambda a, i=seen[name]: a[i],
                                    params[name])
        seen[name] += 1
        x = jax.checkpoint(lambda lp_, x_, f=MIXERS[letter]:
                           f(lp_, x_, cfg, mm, fault))(lp, x)
    x = _rms(x, params["final_norm"], float(cfg["norm_eps"]))
    S = x.shape[0]
    T = _blocks(S, LOSS_BLOCK)

    def block(i):
        xb = jax.lax.dynamic_slice_in_dim(x, i * T, T)
        lb = jax.lax.dynamic_slice_in_dim(labels, i * T, T)
        logits = mm("sd,dv->sv", xb, params["lm_head"])
        tgt = jnp.take_along_axis(logits, lb[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - tgt)

    return jnp.sum(jax.lax.map(jax.checkpoint(block),
                               jnp.arange(S // T))) / S


def batch_loss(params, batch, cfg, mm_dtype=F32, fault=""):
    """Mean loss over a (B, S) batch, one sequence at a time."""
    return jnp.mean(jax.lax.map(
        lambda tl: seq_loss(params, tl[0], tl[1], cfg, mm_dtype, fault),
        (batch["tokens"], batch["labels"])))


class Reference(ref_lm.Reference):
    """:class:`bench.ref_lm.Reference`'s DASHA / DASHA-MVR step with this
    model's gradient."""

    def __init__(self, cfg: Dict, traffic: Dict, *, mm_dtype=F32,
                 fault: str = ""):
        super().__init__(cfg, traffic, mm_dtype=mm_dtype,
                         fault="" if fault in MODEL_FAULTS else fault)
        model_fault = fault if fault in MODEL_FAULTS else ""
        b = float(traffic.get("mvr_b", 0.0))
        mvr = traffic["variant"] == "mvr"

        def grad(x, batch):
            g = jax.grad(batch_loss)(x, batch, cfg, mm_dtype, model_fault)
            return jax.tree_util.tree_map(lambda t: t.astype(F32), g)

        def h_new(x, x_old, batch, h_i):
            gn = grad(x, batch)
            if not mvr:
                return gn
            go = grad(x_old, batch)
            return jax.tree_util.tree_map(
                lambda gn_, h_, go_: gn_ + (1.0 - b) * (h_ - go_),
                gn, h_i, go)

        self._h_new = jax.jit(h_new)
        # the running sums' first operand is never read again: reuse it
        self._add = jax.jit(lambda t, u, s: jax.tree_util.tree_map(
            lambda x, y: x + s * y, t, u), donate_argnums=0)

    def follow(self, params, method_key, data_key, steps: int, *,
               split=jax.tree_util.tree_leaves):
        """As :meth:`bench.ref_lm.Reference.follow`, the squared norms taken
        over the arrays ``split`` cuts a tree into."""
        n = self.nodes
        zeros = jax.jit(lambda p: jax.tree_util.tree_map(
            lambda w: jnp.zeros(w.shape, F32), p))
        st = {"x": params, "key": method_key, "t": jnp.zeros((), jnp.int32),
              "g": zeros(params), "h": [zeros(params) for _ in range(n)],
              "gl": [zeros(params) for _ in range(n)]}
        norms = jax.jit(lambda t: ref_lm.sq_norms(split(t)))
        g_sq = []
        for _ in range(steps):
            batch = ref_lm.gen.node_batches(
                jax.random.fold_in(data_key, st["t"]), self.traffic,
                int(self.cfg["vocab_size"]), n)
            st = self.step(st, batch)
            g_sq.append(norms(st["g"]))
        dx = jax.jit(lambda a, b: ref_lm.sq_norms(split(
            jax.tree_util.tree_map(lambda u, v: u.astype(F32)
                                   - v.astype(F32), a, b))))(st["x"], params)
        return jnp.stack(g_sq), dx, st["g"]
