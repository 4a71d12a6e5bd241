"""Pallas TPU kernel: fused DASHA node update.

Why a kernel: DASHA's per-round node work (Alg. 1 lines 8-10) is *pure
streaming* over the d-dimensional parameter space (d ~ 1e7-1e11 in the
paper's DNN experiment and our assigned architectures).  Written naively it
is 4-6 separate elementwise HLO ops = 4-6 round trips through HBM for
tensors that are each ~4d bytes.  The fused kernel makes exactly ONE pass:
read (grad, h, g_local, mask), write (m, h_new, g_local_new) — turning an
optimizer step that is ~6x memory-bound into the minimal 4-read/3-write
stream.  This is the TPU adaptation of the paper's "send compressed vectors
only" insight: compression (masking+scaling) happens in VMEM registers while
the state tensors stream through, so the compressed message m is produced
for free on top of the mandatory estimator update traffic.

Tiling: inputs are reshaped to (R, 128) by the ops layer; the grid walks R in
blocks of ``block_rows`` rows.  The pipeline double-buffers every operand, so
a program holds ``2 x tensors x block_rows x 128 x 4 B`` of VMEM: DASHA
streams 7 tensors (4 in, 3 out) and MVR 8, so block_rows=1024 takes 7 MiB
and 8 MiB, inside the 16 MiB of scoped VMEM that Mosaic grants a kernel on
TPU v5e by default.  At 2048 (14 and 16 MiB plus the compiler's own scratch)
the v5e compiler refuses both kernels for running out of VMEM.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128          # TPU vector lane width: last dim of every block
DEFAULT_BLOCK_ROWS = 1024


def _dasha_update_kernel(a_ref, scale_ref, grad_ref, h_ref, gl_ref, mask_ref,
                         m_ref, h_out_ref, gl_out_ref):
    a = a_ref[0]
    scale = scale_ref[0]
    grad = grad_ref[...]
    h = h_ref[...]
    gl = gl_ref[...]
    delta = grad - h - a * (gl - h)
    m = mask_ref[...] * delta * scale
    m_ref[...] = m
    h_out_ref[...] = grad
    gl_out_ref[...] = gl + m


def _dasha_mvr_update_kernel(a_ref, b_ref, scale_ref, gn_ref, go_ref, h_ref,
                             gl_ref, mask_ref, m_ref, h_out_ref, gl_out_ref):
    a = a_ref[0]
    b = b_ref[0]
    scale = scale_ref[0]
    h = h_ref[...]
    gl = gl_ref[...]
    h_new = gn_ref[...] + (1.0 - b) * (h - go_ref[...])
    delta = h_new - h - a * (gl - h)
    m = mask_ref[...] * delta * scale
    m_ref[...] = m
    h_out_ref[...] = h_new
    gl_out_ref[...] = gl + m


def _grid_specs(rows: int, block_rows: int, n_scalars: int, n_tensors: int):
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    tens = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    scal = pl.BlockSpec((1,), lambda i: (0,))
    return grid, [scal] * n_scalars + [tens] * n_tensors, [tens] * 3


def dasha_update_pallas(grad: jax.Array, h: jax.Array, g_local: jax.Array,
                        mask: jax.Array, a: float, scale: float, *,
                        block_rows: int = DEFAULT_BLOCK_ROWS,
                        interpret: bool = True
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """All array args: (R, 128) float32.  Returns (m, h_new, g_local_new)."""
    rows = grad.shape[0]
    grid, in_specs, out_specs = _grid_specs(rows, block_rows, 2, 4)
    shape = jax.ShapeDtypeStruct(grad.shape, grad.dtype)
    return pl.pallas_call(
        _dasha_update_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=(shape, shape, shape),
        interpret=interpret,
        name="dasha_update",
    )(jnp.full((1,), a, grad.dtype), jnp.full((1,), scale, grad.dtype),
      grad, h, g_local, mask)


def dasha_mvr_update_pallas(grad_new: jax.Array, grad_old: jax.Array,
                            h: jax.Array, g_local: jax.Array,
                            mask: jax.Array, a: float, b: float,
                            scale: float, *,
                            block_rows: int = DEFAULT_BLOCK_ROWS,
                            interpret: bool = True
                            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """MVR variant; all array args (R, 128) float32."""
    rows = grad_new.shape[0]
    grid, in_specs, out_specs = _grid_specs(rows, block_rows, 3, 5)
    shape = jax.ShapeDtypeStruct(grad_new.shape, grad_new.dtype)
    dt = grad_new.dtype
    return pl.pallas_call(
        _dasha_mvr_update_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=(shape, shape, shape),
        interpret=interpret,
        name="dasha_mvr_update",
    )(jnp.full((1,), a, dt), jnp.full((1,), b, dt), jnp.full((1,), scale, dt),
      grad_new, grad_old, h, g_local, mask)


# ---------------------------------------------------------------------------
# row-wise stochastic quantizer (QSGD / QDither compressor)
# ---------------------------------------------------------------------------

def _quantize_kernel(levels_ref, x_ref, u_ref, out_ref):
    s = levels_ref[0]
    x = x_ref[...].astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    safe = jnp.where(norm > 0, norm, 1.0)
    y = jnp.abs(x) / safe * s
    lo = jnp.floor(y)
    q = lo + (u_ref[...] < (y - lo)).astype(jnp.float32)
    out = jnp.sign(x) * q * safe / s
    out_ref[...] = jnp.where(norm > 0, out, 0.0).astype(out_ref.dtype)


def quantize_pallas(x: jax.Array, u: jax.Array, levels: int, *,
                    block_rows: int = 256, interpret: bool = True
                    ) -> jax.Array:
    """Row-quantize x: (R, C) with external uniforms u: (R, C).

    The row (= quantization group) must fit one block, so blocks are
    (block_rows, C) and the grid walks rows only.
    """
    rows, cols = x.shape
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    tens = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    scal = pl.BlockSpec((1,), lambda i: (0,))
    return pl.pallas_call(
        _quantize_kernel,
        grid=grid,
        in_specs=[scal, tens, tens],
        out_specs=tens,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        name="quantize",
    )(jnp.full((1,), levels, jnp.float32), x, u)
