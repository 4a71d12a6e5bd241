"""jit'd public wrappers around the Pallas kernels.

The fused node update streams each tensor in its own row-major layout: the
ops layer views a tensor of shape ``(..., cols)`` as ``(rows, cols)``, rows
the product of all dims but the last (:func:`node_update_view`).  That
merges leading dims only, so where the second-to-last dim is a multiple of
8 it is a bitcast on the TPU, and the outputs come back by the same
reshape.  A tensor whose view no kernel block tiles (a 1-D vector; a row
too wide for one block whose width is no multiple of 128; see
:func:`repro.kernels.dasha_update.node_update_block`) takes the lane path
instead: flattened, padded to (R, 128) lane rows and cut back after the
kernel, which costs a relayout each way.  Where a call runs is decided
when it is traced, from the default backend: on a TPU every kernel
compiles to Mosaic (and a kernel the chip refuses raises); on any other
backend the kernel body runs in the Pallas interpreter.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.custom_batching import sequential_vmap

from repro.kernels.dasha_update import (LANE, dasha_mvr_update_keyed_pallas,
                                        dasha_mvr_update_pallas,
                                        dasha_update_keyed_pallas,
                                        dasha_update_pallas,
                                        node_update_block, quantize_pallas)


def _interpret() -> bool:
    """Interpret the kernels unless they are traced for a TPU."""
    return jax.default_backend() != "tpu"


#: megablox's (m, k, n) tile: 128 rows, so that a group's partial tile wastes
#: at most 127; k and n need not divide (its last tiles are masked)
GMM_TILING = (128, 512, 1024)


def _gmm(lhs: jax.Array, rhs: jax.Array, sizes: jax.Array) -> jax.Array:
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    return gmm(lhs, rhs, sizes, lhs.dtype, GMM_TILING, jnp.int32(0), None,
               False, _interpret())


@jax.custom_vjp
def grouped_matmul(lhs: jax.Array, rhs: jax.Array, sizes: jax.Array
                   ) -> jax.Array:
    """Rows of lhs (M, k) in consecutive groups of ``sizes`` (G + 1,) times
    rhs (G, k, n): group g's rows times rhs[g]; the last group's rows, and
    rows past all groups, give 0 and are never computed.  M is a multiple
    of 128.  megablox's grouped matmul (Pallas), whose backward is its own
    (``gmm`` for lhs, ``tgmm`` for rhs, the same groups).  It cannot be
    batched with per-example group sizes, so under ``vmap`` (the nodes'
    oracle) it and its backward map over the batch one example at a time."""
    return sequential_vmap(_gmm)(lhs, rhs, sizes)


def _grouped_fwd(lhs, rhs, sizes):
    return grouped_matmul(lhs, rhs, sizes), (lhs, rhs, sizes)


def _grouped_bwd(res, g):
    def vjp(lhs, rhs, sizes, g):
        return jax.vjp(lambda a, b: _gmm(a, b, sizes), lhs, rhs)[1](g)
    return (*sequential_vmap(vjp)(*res, g), None)


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


def node_update_view(shape) -> Optional[Tuple[int, int]]:
    """The (rows, cols) view in which the fused node update streams a
    tensor of ``shape`` in its own layout, or ``None`` where it takes the
    lane path."""
    if len(shape) < 2:
        return None
    view = (math.prod(shape[:-1]), shape[-1])
    return view if node_update_block(*view) else None


def _to_lanes(x: jax.Array) -> jax.Array:
    flat = x.reshape(-1)
    return jnp.pad(flat, (0, (-flat.shape[0]) % LANE)).reshape(-1, LANE)


def _from_lanes(x2: jax.Array, shape) -> jax.Array:
    return x2.reshape(-1)[:math.prod(shape)].reshape(shape)


def _key_words(key: jax.Array) -> jax.Array:
    """The (2,) uint32 words of a threefry key, typed or raw."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return key.astype(jnp.uint32)


def _fused(kernel, tensors, *scalars, mask=None, key=None):
    """Run a node-update kernel on same-shape ``tensors`` as f32 in their
    own layout's view (else in lane rows), then the ``mask`` in that view
    or the ``key``'s words, then the static ``scalars``.  Returns (m,
    h_new, g_local_new) with the first tensor's shape and dtype."""
    shape, dtype = tensors[0].shape, tensors[0].dtype
    view = node_update_view(shape)

    def to_view(x):
        x = x.astype(jnp.float32)
        return _to_lanes(x) if view is None else x.reshape(view)

    if mask is None:
        extra = _key_words(key)
    else:
        # the mask cast into the kernel's view belongs to the mask draw:
        # it runs under the compression plan's named scope
        with jax.named_scope("dasha.compress"):
            extra = to_view(mask)
    outs = kernel(*map(to_view, tensors), extra, *scalars,
                  interpret=_interpret())
    return tuple((_from_lanes(t, shape) if view is None else t.reshape(shape)
                  ).astype(dtype) for t in outs)


@functools.partial(jax.jit, static_argnames=("a", "scale"))
def dasha_update(grad: jax.Array, h: jax.Array, g_local: jax.Array,
                 mask: jax.Array, a: float, scale: float
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused DASHA update on arbitrary-shape tensors (see kernel docstring).

    Returns (m, h_new, g_local_new) with the input shape/dtype.
    """
    return _fused(dasha_update_pallas, (grad, h, g_local), a, scale,
                  mask=mask)


@functools.partial(jax.jit, static_argnames=("a", "scale", "thresh"))
def dasha_update_keyed(grad: jax.Array, h: jax.Array, g_local: jax.Array,
                       key: jax.Array, a: float, scale: float, thresh: int
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`dasha_update` with the mask ``jax.random.bits(key,
    grad.shape, uint8) < thresh`` drawn inside the kernel (threefry keys,
    typed or raw; ``grad.size`` at most 2**32)."""
    return _fused(dasha_update_keyed_pallas, (grad, h, g_local), a, scale,
                  thresh, key=key)


@functools.partial(jax.jit, static_argnames=("a", "b", "scale"))
def dasha_mvr_update(grad_new: jax.Array, grad_old: jax.Array, h: jax.Array,
                     g_local: jax.Array, mask: jax.Array, a: float, b: float,
                     scale: float) -> Tuple[jax.Array, jax.Array, jax.Array]:
    return _fused(dasha_mvr_update_pallas, (grad_new, grad_old, h, g_local),
                  a, b, scale, mask=mask)


@functools.partial(jax.jit, static_argnames=("a", "b", "scale", "thresh"))
def dasha_mvr_update_keyed(grad_new: jax.Array, grad_old: jax.Array,
                           h: jax.Array, g_local: jax.Array, key: jax.Array,
                           a: float, b: float, scale: float, thresh: int
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`dasha_mvr_update` with the mask drawn inside the kernel (see
    :func:`dasha_update_keyed`)."""
    return _fused(dasha_mvr_update_keyed_pallas,
                  (grad_new, grad_old, h, g_local), a, b, scale, thresh,
                  key=key)


@functools.partial(jax.jit, static_argnames=("accumulate", "use_kernel"))
def slab_writeback(full: jax.Array, idx: jax.Array, rows: jax.Array, *,
                   accumulate: bool = False,
                   use_kernel: bool | None = None) -> jax.Array:
    """Write a chunk slab back into the persistent (n, d) store.

    ``idx`` (U,) int32 — sorted-unique global row ids padded with the
    sentinel ``n`` (dropped); ``rows`` (U, d) — the slab.  On a TPU this is
    the aliased Pallas kernel (:mod:`repro.kernels.slab_writeback`), which
    touches only the addressed rows of the store.  Elsewhere the default is
    XLA's drop-mode scatter — the interpreter would serialize U Python
    iterations per chunk — and the tests force the kernel with
    ``use_kernel=True``.  Both paths produce identical bytes (same update,
    same drop semantics), so store contents never depend on the
    dispatch."""
    from repro.kernels.slab_writeback import slab_writeback_pallas
    if use_kernel is None:
        use_kernel = not _interpret()
    if not use_kernel:
        if accumulate:
            return full.at[idx].add(rows, mode="drop")
        return full.at[idx].set(rows, mode="drop")
    return slab_writeback_pallas(full, idx, rows, accumulate=accumulate,
                                 interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("levels",))
def quantize(x: jax.Array, key: jax.Array, levels: int = 15) -> jax.Array:
    """Unbiased row-wise stochastic quantization of x: (R, C)."""
    u = jax.random.uniform(key, x.shape, jnp.float32)
    return quantize_pallas(x, u, levels, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("levels",))
def quantize_with_u(x: jax.Array, u: jax.Array, levels: int = 15
                    ) -> jax.Array:
    """Row-wise quantization with EXTERNAL uniforms (the compress plan layer
    draws them once so dense and fused backends dither identically)."""
    return quantize_pallas(x, u, levels, interpret=_interpret())


def ssd_chunk_scan(x: jax.Array, dt: jax.Array, A: jax.Array, b: jax.Array,
                   c: jax.Array, D: jax.Array, chunk: int
                   ) -> Tuple[jax.Array, jax.Array]:
    """Pallas-kernel SSD forward (drop-in for models.ssm.ssd_chunked).

    x: (B,S,H,P), dt: (B,S,H), A: (H,), b/c: (B,S,N), D: (H,).
    Intra-chunk blocks run in the Pallas kernel; the O(S/chunk) inter-chunk
    recurrence is a lax.scan; the off-diagonal combine is two einsums.
    """
    from repro.kernels.ssd_chunk import ssd_chunk_pallas

    B, S, H, P = x.shape
    N = b.shape[-1]
    nc = S // chunk
    G = B * H
    # flatten (batch, head) -> G; broadcast per-batch b/c across heads
    xg = (jnp.moveaxis(x, 2, 1)               # (B,H,S,P)
          .reshape(G, nc, chunk, P))
    dtg = jnp.moveaxis(dt, 2, 1).reshape(G, nc, chunk)
    Ag = jnp.broadcast_to(A[None], (B, H)).reshape(G)
    bg = jnp.broadcast_to(b[:, None], (B, H, S, N)).reshape(G, nc, chunk, N)
    cg = jnp.broadcast_to(c[:, None], (B, H, S, N)).reshape(G, nc, chunk, N)

    y_diag, states, decays, acs = ssd_chunk_pallas(
        xg, dtg, Ag, bg, cg, interpret=_interpret())

    def scan_fn(s, inp):
        st, dk = inp                               # (G,N,P), (G,)
        out = s
        s = s * dk[:, None, None] + st
        return s, out

    init = jnp.zeros((G, N, P), jnp.float32)
    final, prev = jax.lax.scan(
        scan_fn, init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(decays, 1, 0)))
    prev = jnp.moveaxis(prev, 0, 1)                # (G,nc,N,P)

    # off-diagonal: y_off[q] = exp(acs[q]) * (c[q] @ prev_state)
    y_off = jnp.exp(acs)[..., None] * jnp.einsum("gnqs,gnsp->gnqp", cg,
                                                 prev)
    yg = y_diag + y_off + xg.astype(jnp.float32) \
        * jnp.broadcast_to(D[None], (B, H)).reshape(G)[:, None, None, None]
    y = jnp.moveaxis(yg.reshape(B, H, S, P), 1, 2).astype(x.dtype)
    final_state = final.reshape(B, H, N, P)
    return y, final_state
