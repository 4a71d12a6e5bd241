"""Pallas TPU kernel: Mamba2/SSD intra-chunk block (state-space duality).

The SSD training/prefill pass (models/ssm.py `ssd_chunked`) splits the
sequence into chunks; per chunk the heavy work is attention-like:

    L      = exp(segsum(a))               (Q, Q) lower-triangular decays
    y_diag = (L * (c @ b^T)) @ x_dt       intra-chunk output
    state  = b^T @ (decay_end * x_dt)     chunk's contribution to the
                                          inter-chunk recurrence

This is exactly one (Q=chunk)-square block of a linear-attention kernel —
the natural Pallas unit: grid over (batch*heads, n_chunks), each program
holds one chunk's (Q,N)/(Q,P)/(Q,Q) tiles in VMEM (Q=256, N,P<=128 =>
~1.3 MB working set, MXU-aligned when Q,N,P are multiples of 128/8).

The O(n_chunks) inter-chunk recurrence stays a lax.scan outside the kernel
(sequential by construction); ops.ssd_chunk_scan composes both and matches
models/ssm.ssd_chunked (the oracle) to float tolerance.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_chunk_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,
                      y_ref, state_ref, decay_ref, acs_ref):
    """One (batch*head, chunk) block.

    Block shapes (leading grid dims are 1): x (1,1,Q,P); dt (1,1,Q);
    a (1,); b/c (1,1,Q,N).  Outputs: y (1,1,Q,P) intra-chunk part,
    state (1,1,N,P) chunk contribution, decay (1,1) chunk total decay,
    acs (1,1,Q) inclusive cumulative log-decay (for the combine step).
    """
    x = x_ref[0, 0].astype(jnp.float32)            # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)          # (Q,)
    b = b_ref[0, 0].astype(jnp.float32)            # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)
    A = a_ref[0]

    xdt = x * dt[:, None]                          # (Q, P)
    a = dt * A                                     # (Q,) log decays
    acs = jnp.cumsum(a)                            # inclusive
    # L[q, k] = exp(acs[q] - acs[k]) for q >= k else 0
    diff = acs[:, None] - acs[None, :]
    q = a.shape[0]
    tri = jnp.tril(jnp.ones((q, q), jnp.bool_))
    L = jnp.where(tri, jnp.exp(diff), 0.0)         # (Q, Q)
    scores = c @ b.T                               # (Q, Q)
    y_ref[0, 0] = ((L * scores) @ xdt).astype(y_ref.dtype)

    decay_end = jnp.exp(acs[-1] - acs)             # (Q,)
    state_ref[0, 0] = (b.T @ (decay_end[:, None] * xdt)).astype(
        state_ref.dtype)
    decay_ref[0, 0] = jnp.exp(acs[-1])
    acs_ref[0, 0] = acs.astype(acs_ref.dtype)


def ssd_chunk_pallas(x: jax.Array, dt: jax.Array, A: jax.Array,
                     b: jax.Array, c: jax.Array, *, interpret: bool = True
                     ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Batched intra-chunk pass.

    x: (G, nc, Q, P) where G = batch*heads; dt: (G, nc, Q); A: (G,);
    b/c: (G, nc, Q, N).  Returns (y_diag, chunk_states, chunk_decays, acs)
    with shapes ((G,nc,Q,P), (G,nc,N,P), (G,nc), (G,nc,Q)).
    """
    G, nc, Q, P = x.shape
    N = b.shape[-1]
    f32 = jnp.float32
    grid = (G, nc)
    def t4(d):
        return pl.BlockSpec((1, 1, Q, d), lambda i, j: (i, j, 0, 0))


    t3 = pl.BlockSpec((1, 1, Q), lambda i, j: (i, j, 0))
    ta = pl.BlockSpec((1,), lambda i, j: (i,))

    return pl.pallas_call(
        _ssd_chunk_kernel,
        grid=grid,
        in_specs=[t4(P), t3, ta, t4(N), t4(N)],
        out_specs=(t4(P),
                   pl.BlockSpec((1, 1, N, P), lambda i, j: (i, j, 0, 0)),
                   pl.BlockSpec((1, 1), lambda i, j: (i, j)),
                   t3),
        out_shape=(jax.ShapeDtypeStruct((G, nc, Q, P), f32),
                   jax.ShapeDtypeStruct((G, nc, N, P), f32),
                   jax.ShapeDtypeStruct((G, nc), f32),
                   jax.ShapeDtypeStruct((G, nc, Q), f32)),
        interpret=interpret,
        name="ssd_chunk",
    )(x, dt, A, b, c)
