"""Mamba2 / SSD (state-space duality, arXiv:2405.21060) in pure JAX.

Chunked SSD for training/prefill (intra-chunk attention-like einsums +
inter-chunk ``lax.scan`` over chunk states) and an O(1)-per-token recurrent
decode step — this is what makes the long_500k shape tractable for the
ssm/hybrid architectures.

Layout: d_inner = H * P (heads x headdim); B/C are per-group (G groups,
state size N; head h reads group h // (H / G)); the scalar-per-head A
follows Mamba2.  With G > 1 the gated RMSNorm normalises each group's
d_inner / G channels on their own.  The scan runs under the
``ssd.scan`` named scope.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ArchConfig, rms_norm


def _segsum(a: jax.Array) -> jax.Array:
    """a: (..., Q) log-decays -> (..., Q, Q) with [q,k] = sum_{j=k+1..q} a_j
    for q >= k, -inf otherwise."""
    cs = jnp.cumsum(a, -1)
    diff = cs[..., :, None] - cs[..., None, :]
    q = a.shape[-1]
    mask = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, b: jax.Array,
                c: jax.Array, D: jax.Array, chunk: int,
                s0: jax.Array | None = None
                ) -> Tuple[jax.Array, jax.Array]:
    """SSD over G B/C groups, head h reading group h // (H / G).

    x: (B,S,H,P), dt: (B,S,H) (post-softplus), A: (H,) (negative),
    b/c: (B,S,G,N), or (B,S,N) for one group, D: (H,), s0: (B,H,N,P).
    Returns (y: (B,S,H,P), final_state: (B,H,N,P)).  With G > 1 each
    group's H / G heads run as one more sequence of the batch, reading the
    group's B and C and their own A and D (:func:`_ssd_one_group`).
    """
    if b.ndim == 3 or b.shape[2] == 1:
        N = b.shape[-1]
        return _ssd_one_group(x, dt, A, b.reshape(b.shape[:2] + (N,)),
                              c.reshape(c.shape[:2] + (N,)), D, chunk, s0)
    Bb, S, H, P = x.shape
    G, N = b.shape[2:]
    R = H // G

    def fold(t):                # (B,S,G*R,...) -> (B*G,S,R,...)
        t = t.reshape((Bb, S, G, R) + t.shape[3:])
        return jnp.moveaxis(t, 2, 1).reshape((Bb * G, S, R) + t.shape[4:])

    def per_group(v):           # (H,) -> (B*G, 1, R): one row per sequence
        return jnp.broadcast_to(v.reshape(1, G, 1, R),
                                (Bb, G, 1, R)).reshape(Bb * G, 1, R)

    bc = [jnp.moveaxis(t, 2, 1).reshape(Bb * G, S, N) for t in (b, c)]
    y, final = _ssd_one_group(
        fold(x), fold(dt), per_group(A), *bc, per_group(D)[..., None],
        chunk, None if s0 is None else s0.reshape(Bb * G, R, N, P))
    y = jnp.moveaxis(y.reshape(Bb, G, S, R, P), 1, 2).reshape(Bb, S, H, P)
    return y, final.reshape(Bb, H, N, P)


def _ssd_one_group(x: jax.Array, dt: jax.Array, A: jax.Array, b: jax.Array,
                   c: jax.Array, D: jax.Array, chunk: int,
                   s0: jax.Array | None = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Single-group chunked SSD.

    x: (B,S,H,P), dt: (B,S,H) (post-softplus), A: (H,) or per sequence
    (B,1,H) (negative), b/c: (B,S,N), D: (H,) or (B,1,H,1).  Returns
    (y: (B,S,H,P), final_state: (B,H,N,P)).
    """
    Bb, S, H, P = x.shape
    N = b.shape[-1]
    nc = S // chunk
    f32 = jnp.float32
    if A.ndim == 1:
        A, D = A[None, None, :], D[None, None, :, None]
    xv = (x * dt[..., None]).astype(f32)                    # dt-weighted input
    a = (dt * A).astype(f32)                                # (B,S,H) log decay

    xc = xv.reshape(Bb, nc, chunk, H, P)
    ac = a.reshape(Bb, nc, chunk, H)
    bc = b.astype(f32).reshape(Bb, nc, chunk, N)
    cc = c.astype(f32).reshape(Bb, nc, chunk, N)

    acs = jnp.cumsum(ac, 2)                                 # (B,nc,Q,H) incl.
    L = jnp.exp(_segsum(jnp.moveaxis(ac, -1, -2)))          # (B,nc,H,Q,Q)
    scores = jnp.einsum("bnqs,bnks->bnqk", cc, bc)          # (B,nc,Q,Q)
    y_diag = jnp.einsum("bnhqk,bnqk,bnkhp->bnqhp",
                        L, scores, xc)

    # states contributed by each chunk: decay to end of chunk
    decay_end = jnp.exp(acs[:, :, -1:, :] - acs)            # (B,nc,Q,H)
    chunk_states = jnp.einsum("bnks,bnkh,bnkhp->bnhsp",
                              bc, decay_end, xc)            # (B,nc,H,N,P)

    # inter-chunk recurrence
    decay_chunk = jnp.exp(acs[:, :, -1, :])                 # (B,nc,H)

    def scan_fn(s, inp):
        st, dk = inp                                        # (B,H,N,P), (B,H)
        out = s
        s = s * dk[..., None, None] + st
        return s, out

    init = jnp.zeros((Bb, H, N, P), f32) if s0 is None else s0.astype(f32)
    final, prev_states = jax.lax.scan(
        scan_fn, init,
        (jnp.moveaxis(chunk_states, 1, 0), jnp.moveaxis(decay_chunk, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)           # (B,nc,H,N,P)

    state_decay = jnp.exp(acs)                              # (B,nc,Q,H)
    y_off = jnp.einsum("bnqs,bnqh,bnhsp->bnqhp",
                       cc, state_decay, prev_states)
    y = (y_diag + y_off).reshape(Bb, S, H, P)
    y = y + x.astype(f32) * D
    return y.astype(x.dtype), final


def ssd_decode(x: jax.Array, dt: jax.Array, A: jax.Array, b: jax.Array,
               c: jax.Array, D: jax.Array, state: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """One token: x (B,H,P), dt (B,H), b/c (B,N), state (B,H,N,P)."""
    f32 = jnp.float32
    a = jnp.exp((dt * A[None, :]).astype(f32))              # (B,H)
    upd = jnp.einsum("bn,bhp->bhnp", b.astype(f32),
                     (x * dt[..., None]).astype(f32))
    state = state * a[..., None, None] + upd
    y = jnp.einsum("bn,bhnp->bhp", c.astype(f32), state)
    y = y + x.astype(f32) * D[None, :, None]
    return y.astype(x.dtype), state


# ---------------------------------------------------------------------------
# full Mamba2 mixer layer
# ---------------------------------------------------------------------------

def _conv1d_prefill(xbc: jax.Array, w: jax.Array, bias: jax.Array
                    ) -> jax.Array:
    """Causal depthwise conv. xbc: (B,S,Cd); w: (W,Cd)."""
    W = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1]] * w[i][None, None]
              for i in range(W))
    return jax.nn.silu(out + bias[None, None])


def mamba_mixer_prefill(p: Dict, x: jax.Array, cfg: ArchConfig,
                        s0=None) -> jax.Array:
    """x: (B,S,d) -> (B,S,d)."""
    B, S, _ = x.shape
    H, P, N = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state
    G = cfg.ssm_ngroups
    z = jnp.einsum("bsd,dhp->bshp", x, p["w_z"])
    xbc = jnp.einsum("bsd,dc->bsc", x, p["w_xbc"])   # (B,S,HP+2GN)
    dt = jax.nn.softplus(jnp.einsum("bsd,dh->bsh", x, p["w_dt"]) + p["dt_bias"])
    xbc = _conv1d_prefill(xbc, p["conv_w"], p["conv_b"])
    xs = xbc[..., :H * P].reshape(B, S, H, P)
    bmat = xbc[..., H * P:H * P + G * N]
    cmat = xbc[..., H * P + G * N:]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    chunk = min(cfg.ssd_chunk, S)
    with jax.named_scope("ssd.scan"):
        if cfg.use_ssd_kernel and G == 1 and s0 is None \
                and S % chunk == 0:
            from repro.kernels.ops import ssd_chunk_scan
            y, _ = ssd_chunk_scan(xs, dt, A, bmat, cmat, p["D"], chunk)
        else:
            y, _ = ssd_chunked(xs, dt, A, bmat.reshape(B, S, G, N),
                               cmat.reshape(B, S, G, N), p["D"], chunk, s0)
    y = y * jax.nn.silu(z)
    if G == 1:
        # the norm over all of d_inner; a one-group reshape around it would
        # compile to a different program (more bytes) for the same result
        y = rms_norm(y.reshape(B, S, H * P), p["norm"], cfg.norm_eps)
    else:                       # each group's d_inner / G channels alone
        y = rms_norm(y.reshape(B, S, G, H * P // G),
                     p["norm"].reshape(G, H * P // G),
                     cfg.norm_eps).reshape(B, S, H * P)
    return jnp.einsum("bsc,cd->bsd", y, p["w_out"])


def mamba_mixer_decode(p: Dict, x: jax.Array, cache: Dict, cfg: ArchConfig
                       ) -> Tuple[jax.Array, Dict]:
    """x: (B,1,d); cache: {"conv": (B,W-1,Cd), "ssm": (B,H,N,P)}; one B/C
    group."""
    if cfg.ssm_ngroups != 1:
        raise NotImplementedError("grouped SSD decode")
    B = x.shape[0]
    H, P, N = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state
    xt = x[:, 0]
    z = jnp.einsum("bd,dhp->bhp", xt, p["w_z"])
    xbc = jnp.einsum("bd,dc->bc", xt, p["w_xbc"])
    dt = jax.nn.softplus(xt @ p["w_dt"] + p["dt_bias"])      # (B,H)
    # conv cache: window of last W-1 inputs
    conv_in = jnp.concatenate([cache["conv"], xbc[:, None]], 1)  # (B,W,Cd)
    w = p["conv_w"]                                          # (W,Cd)
    conv_out = jax.nn.silu(jnp.einsum("bwc,wc->bc", conv_in, w) + p["conv_b"])
    new_conv = conv_in[:, 1:]
    xs = conv_out[:, :H * P].reshape(B, H, P)
    bmat = conv_out[:, H * P:H * P + N]
    cmat = conv_out[:, H * P + N:]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, new_ssm = ssd_decode(xs, dt, A, bmat, cmat, p["D"],
                            cache["ssm"].astype(jnp.float32))
    y = y * jax.nn.silu(z)
    y = rms_norm(y.reshape(B, 1, H * P), p["norm"], cfg.norm_eps)
    out = jnp.einsum("bsc,cd->bsd", y, p["w_out"])
    return out, {"conv": new_conv, "ssm": new_ssm.astype(cache["ssm"].dtype)}
