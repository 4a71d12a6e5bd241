"""Plain reference of DASHA / DASHA-MVR training of a Mamba2 LM.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
written from the published description and importing nothing of the
program:

* the model: pre-norm Mamba2 blocks (arXiv:2405.21060, SSD with one B/C
  group) with the SSD in its quadratic form over the whole sequence -- the
  masked semiseparable matrix, not the chunked scan the program runs -- a
  tied LM head and the mean next-token cross-entropy;
* one step of Alg. 1: SGD on the server estimator (params kept in their
  stated dtype), per-node gradients, Bernoulli(p) masks scaled by 1/p, the
  g_i / h_i recursions and the mean of the messages over nodes, and
  DASHA-MVR's momentum h-update (Theorem 6.7).

Its random draws replay the method's stated RNG contract from the seed
(``key, k_h, k_c, k_coin = split(key, 4)`` per step; one mask key per leaf
from ``k_c`` in leaf order; a mask is ``uint8 bits < 256 p``), so it follows
the program's trajectory.  ``mm_dtype`` rounds every matmul operand to a
lower precision first: that is the control.  ``fault`` plants one of the
faults the check has to catch; only the tests set it.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench import gen

F32 = jnp.float32


def _rounder(dtype):
    """x rounded to ``dtype`` after a per-tensor scale that maps its largest
    magnitude to the dtype's largest finite value (as fp8 matmuls are run),
    and back to f32; the cotangent is rounded the same way on the way back.
    Matmuls of such operands, accumulated in f32, are the control."""
    top = float(jnp.finfo(dtype).max)

    def q(x):
        s = jax.lax.stop_gradient(jnp.max(jnp.abs(x)) / top)
        s = jnp.where(s > 0, s, 1.0)
        return (x / s).astype(dtype).astype(F32) * s

    @jax.custom_vjp
    def rnd(x):
        return q(x)

    rnd.defvjp(lambda x: (q(x), None), lambda _, g: (q(g),))
    return rnd


def _mm(mm_dtype):
    """Matmul of operands rounded to ``mm_dtype``, accumulated in f32."""
    rnd = (lambda x: x.astype(F32)) if jnp.dtype(mm_dtype) == F32 \
        else _rounder(mm_dtype)

    def mm(spec, *ops):
        return jnp.einsum(spec, *[rnd(o.astype(F32)) for o in ops],
                          precision="highest")
    return mm


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w.astype(F32))


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _layer(lp, x, cfg, mm):
    """x + Mamba2 mixer(RMSNorm(x)) for one sequence, x: (S, d)."""
    S = x.shape[0]
    inner = int(cfg["expand"]) * int(cfg["d_model"])
    P = int(cfg["headdim"])
    H, N = inner // P, int(cfg["d_state"])
    eps = float(cfg["norm_eps"])
    h = _rms(x, lp["ln"], eps)
    z = mm("sd,dhp->shp", h, lp["w_z"]).reshape(S, inner)
    xbc = mm("sd,dc->sc", h, lp["w_xbc"])
    dt = jax.nn.softplus(mm("sd,dh->sh", h, lp["w_dt"])
                         + lp["dt_bias"].astype(F32))
    w = lp["conv_w"].astype(F32)
    W = w.shape[0]
    pad = jnp.pad(xbc, ((W - 1, 0), (0, 0)))
    conv = sum(pad[i:i + S] * w[i] for i in range(W))
    xbc = _silu(conv + lp["conv_b"].astype(F32))
    xs = xbc[:, :inner].reshape(S, H, P)
    b, c = xbc[:, inner:inner + N], xbc[:, inner + N:]
    a = dt * (-jnp.exp(lp["A_log"].astype(F32)))            # (S, H)
    cs = jnp.cumsum(a, 0).T                                  # (H, S)
    causal = jnp.tril(jnp.ones((S, S), bool))[None]
    decay = jnp.exp(jnp.where(causal, cs[:, :, None] - cs[:, None, :],
                              -jnp.inf))                     # (H, S, S)
    scores = mm("qn,kn->qk", c, b)
    y = mm("hqk,khp->qhp", decay * scores[None], xs * dt[..., None])
    y = y + xs * lp["D"].astype(F32)[None, :, None]
    y = y.reshape(S, inner) * _silu(z)
    y = _rms(y, lp["norm"], eps)
    return x + mm("sc,cd->sd", y, lp["w_out"])


def seq_loss(params, tokens, labels, cfg, mm_dtype=F32):
    """Mean next-token cross-entropy of one sequence; the softmax runs over
    every embedding row the params hold."""
    mm = _mm(mm_dtype)
    x = params["embed"][tokens].astype(F32)
    layer = jax.checkpoint(lambda lp, x_: _layer(lp, x_, cfg, mm))
    for i in range(int(cfg["n_layer"])):
        x = layer(jax.tree_util.tree_map(lambda a: a[i], params["layers"]),
                  x)
    x = _rms(x, params["final_norm"], float(cfg["norm_eps"]))
    logits = mm("sd,vd->sv", x, params["embed"])
    lse = jax.nn.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.mean(lse - tgt)


def batch_loss(params, batch, cfg, mm_dtype=F32):
    """Mean loss over a (B, S) batch, one sequence at a time."""
    losses = jax.lax.map(
        lambda tl: seq_loss(params, tl[0], tl[1], cfg, mm_dtype),
        (batch["tokens"], batch["labels"]))
    return jnp.mean(losses)


def sq_norms(tree) -> jax.Array:
    """Squared f32 norm of every leaf, in leaf order."""
    return jnp.stack([jnp.sum(jnp.square(x.astype(F32)))
                      for x in jax.tree_util.tree_leaves(tree)])


class Reference:
    """The reference's compiled pieces for one configuration and mix."""

    def __init__(self, cfg: Dict, traffic: Dict, *, mm_dtype=F32,
                 fault: str = ""):
        self.cfg, self.traffic, self.fault = cfg, traffic, fault
        self.nodes = int(cfg["nodes"])
        p = float(traffic["compression"])
        a = 1.0 / (2.0 * (1.0 / p - 1.0) + 1.0)
        thresh = round(p * 256)
        gamma = float(cfg["gamma"])
        b = float(traffic.get("mvr_b", 0.0))
        mvr = traffic["variant"] == "mvr"
        n = self.nodes

        def grad(x, batch):
            g = jax.grad(batch_loss)(x, batch, cfg, mm_dtype)
            return jax.tree_util.tree_map(lambda t: t.astype(F32), g)

        def h_new(x, x_old, batch, h_i):
            gn = grad(x, batch)
            if not mvr:
                return gn
            go = grad(x_old, batch)
            return jax.tree_util.tree_map(
                lambda gn_, h_, go_: gn_ + (1.0 - b) * (h_ - go_),
                gn, h_i, go)

        def message(k_c, i, hn, h, gl):
            """Node i's message m_i and new g_i: its slice of each leaf's
            mask over all n nodes, the drift scaled by 1/p."""
            leaves, treedef = jax.tree_util.tree_flatten(hn)
            keys = jax.random.split(k_c, len(leaves))
            ms, gls = [], []
            for k, hn_, h_, gl_ in zip(keys, leaves,
                                       jax.tree_util.tree_leaves(h),
                                       jax.tree_util.tree_leaves(gl)):
                bits = jax.random.bits(k, (n,) + hn_.shape, jnp.uint8)
                mask = jax.lax.dynamic_index_in_dim(bits, i, 0, False) \
                    < jnp.uint8(thresh)
                m = jnp.where(mask, (hn_ - h_ - a * (gl_ - h_)) * (1.0 / p),
                              0.0)
                ms.append(m)
                gls.append(gl_ + m)
            unflat = lambda ls: jax.tree_util.tree_unflatten(treedef, ls)  # noqa
            return unflat(ms), unflat(gls)

        self._server = jax.jit(lambda x, g: jax.tree_util.tree_map(
            lambda w, gg: (w.astype(F32) - gamma * gg).astype(w.dtype), x, g))
        self._h_new = jax.jit(h_new)
        self._message = jax.jit(message, donate_argnums=(4,))
        self._add = jax.jit(lambda t, u, s: jax.tree_util.tree_map(
            lambda x, y: x + s * y, t, u))

    def step(self, st: Dict, batch: Dict) -> Dict:
        """One step; ``batch`` leaves carry the node axis (n, B, S)."""
        if self.fault == "unchanged":
            return dict(st, t=st["t"] + 1)
        n = self.nodes
        key, _, k_c, _ = jax.random.split(st["key"], 4)
        x = self._server(st["x"], st["g"])
        g, h, gl = st["g"], [], []
        agg = None
        for i in range(n):
            b_i = {k: v[i] for k, v in batch.items()}
            hn = self._h_new(x, st["x"], b_i, st["h"][i])
            m, gl_i = self._message(k_c, jnp.int32(i), hn, st["h"][i],
                                    st["gl"][i])
            h.append(hn)
            if self.fault == "altered" and i == 0:
                # node 0's message negated where it is produced
                m = jax.tree_util.tree_map(jnp.negative, m)
                gl_i = self._add(gl_i, m, 2.0)
            gl.append(gl_i)
            if self.fault == "half_batch" and i >= n // 2:
                continue
            agg = m if agg is None else self._add(agg, m, 1.0)
        share = 1.0 / (n // 2 if self.fault == "half_batch" else n)
        g = self._add(g, agg, share)
        return {"x": x, "g": g, "h": h, "gl": gl, "key": key,
                "t": st["t"] + 1}

    def follow(self, params, method_key, data_key,
               steps: int) -> Tuple[jax.Array, jax.Array, Dict]:
        """``steps`` steps from the start.  Returns the squared leaf norms of
        g after each step (steps, leaves), those of the parameters' change,
        and g at the end."""
        n = self.nodes
        zeros = jax.jit(lambda p: jax.tree_util.tree_map(
            lambda w: jnp.zeros(w.shape, F32), p))
        st = {"x": params, "key": method_key, "t": jnp.zeros((), jnp.int32),
              "g": zeros(params), "h": [zeros(params) for _ in range(n)],
              "gl": [zeros(params) for _ in range(n)]}
        vocab = int(self.cfg["vocab_size"])
        g_sq = []
        for _ in range(steps):
            batch = gen.node_batches(jax.random.fold_in(data_key, st["t"]),
                                     self.traffic, vocab, n)
            st = self.step(st, batch)
            g_sq.append(sq_norms(st["g"]))
        dx = jax.tree_util.tree_map(
            lambda a, b: a.astype(F32) - b.astype(F32), st["x"], params)
        return jnp.stack(g_sq), sq_norms(dx), st["g"]
