"""Pallas TPU kernel: fused DASHA node update.

Why a kernel: DASHA's per-round node work (Alg. 1 lines 8-10) is *pure
streaming* over the d-dimensional parameter space (d ~ 1e7-1e11 in the
paper's DNN experiment and our assigned architectures).  Written naively it
is 4-6 separate elementwise HLO ops = 4-6 round trips through HBM for
tensors that are each ~4d bytes.  The fused kernel makes exactly ONE pass:
read (grad, h, g_local), write (m, h_new, g_local_new) — the minimal
3-read/3-write stream.  This is the TPU adaptation of the paper's "send
compressed vectors only" insight: compression (masking+scaling) happens in
VMEM registers while the state tensors stream through, so the compressed
message m is produced for free on top of the mandatory estimator update
traffic.

Two forms of each kernel share one update arithmetic:

* **keyed** (:func:`dasha_update_keyed_pallas`): the kernel draws the
  Bernoulli mask itself from the leaf key's two u32 words (in SMEM), so
  the mask never exists in HBM.  The draw is the one
  ``jax.random.bits(key, shape, uint8) < thresh`` makes with the
  partitionable threefry: the element at flat row-major index ``i`` of the
  unpadded tensor keeps its coordinate iff
  ``low8(x0 ^ x1) < thresh`` for ``(x0, x1) = threefry2x32(key, (0, i))``.
  The ops layer flattens row-major and pads at the end, so the element at
  ``(r, c)`` of the (R, 128) lane layout has ``i = r * 128 + c`` and each
  block computes its own counters from ``program_id`` and two iotas (u32
  arithmetic: exact while ``R * 128 <= 2**32``).  A block walks its rows in
  strips of ``strip_rows`` (``lax.fori_loop``), so the 20-round hash of a
  strip stays in vector registers.
* **explicit** (:func:`dasha_update_pallas`): the mask is a fourth f32
  input, for callers whose masks are not such a draw (PermK ownership,
  shared coordinates, p not a multiple of 1/256, shards of a leaf).

Both multiply the same f32 {0, 1} mask into the same expression, so they
give bit-equal outputs for the same mask.

Tiling: inputs are reshaped to (R, 128) by the ops layer; the grid walks R in
blocks of ``block_rows`` rows.  The pipeline double-buffers every operand, so
a program holds ``2 x tensors x block_rows x 128 x 4 B`` of VMEM: keyed
DASHA streams 6 tensors (3 in, 3 out) and keyed MVR 7, so block_rows=1024
takes 6 MiB and 7 MiB plus one strip's hash (a few vregs at 32 rows); the
explicit forms stream one tensor more (7 and 8 MiB).  All sit inside the
16 MiB of scoped VMEM that Mosaic grants a kernel on TPU v5e by default; at
2048 rows (14 and 16 MiB plus the compiler's own scratch) the v5e compiler
refuses the explicit kernels for running out of VMEM.
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.random import threefry2x32_p

LANE = 128          # TPU vector lane width: last dim of every block
DEFAULT_BLOCK_ROWS = 1024
#: rows of a block that the keyed kernels hash and update at a time
DEFAULT_STRIP_ROWS = 32
#: the bit pattern of f32 1.0
_ONE_F32_BITS = np.uint32(0x3F800000)


def _dasha_math(a, scale, grad, h, gl, mask):
    delta = grad - h - a * (gl - h)
    m = mask * delta * scale
    return m, grad, gl + m


def _dasha_mvr_math(a, b, scale, gn, go, h, gl, mask):
    h_new = gn + (1.0 - b) * (h - go)
    delta = h_new - h - a * (gl - h)
    m = mask * delta * scale
    return m, h_new, gl + m


def _explicit_kernel(math: Callable, n_scalars: int, *refs):
    """Inputs: scalars, tensors, then the f32 mask; three outputs."""
    scalars = [r[0] for r in refs[:n_scalars]]
    ins, outs = refs[n_scalars:-3], refs[-3:]
    for o, v in zip(outs, math(*scalars, *(r[...] for r in ins))):
        o[...] = v


def strip_mask(k1, k2, first_row, rows: int, thresh: int) -> jax.Array:
    """The f32 {0, 1} mask of ``rows`` lane-layout rows from ``first_row``:
    element (r, c) keeps iff ``low8(x0 ^ x1) < thresh`` for
    ``(x0, x1) = threefry2x32((k1, k2), (0, (first_row + r) * 128 + c))``.
    """
    shape = (rows, LANE)
    row = lax.broadcasted_iota(jnp.int32, shape, 0).astype(jnp.uint32)
    lane = lax.broadcasted_iota(jnp.int32, shape, 1).astype(jnp.uint32)
    lo = (first_row + row) * jnp.uint32(LANE) + lane
    x0, x1 = threefry2x32_p.bind(jnp.full(shape, k1), jnp.full(shape, k2),
                                 jnp.zeros(shape, jnp.uint32), lo)
    # bit 31 of (low8 - thresh) is the compare; times the bits of 1.0 it
    # makes the f32 mask by integer ops, which a compiler cannot fold into
    # a select (that would give +0 for the explicit form's -0 and change
    # how the update rounds)
    keep = (((x0 ^ x1) & jnp.uint32(255)) - jnp.uint32(thresh)) \
        >> jnp.uint32(31)
    return lax.bitcast_convert_type(keep * _ONE_F32_BITS, jnp.float32)


def _keyed_kernel(math: Callable, n_scalars: int, thresh: int,
                  strip_rows: int, key_ref, *refs):
    """Inputs: the key's two u32 words (SMEM), scalars, tensors; three
    outputs.  Draws each strip's mask and applies ``math`` to the strip;
    strips tile the block, and a block they do not tile is one strip."""
    k1, k2 = key_ref[0], key_ref[1]
    scalars = [r[0] for r in refs[:n_scalars]]
    ins, outs = refs[n_scalars:-3], refs[-3:]
    block_rows = ins[0].shape[0]
    if block_rows % strip_rows:
        strip_rows = block_rows
    block_row = (pl.program_id(0) * block_rows).astype(jnp.uint32)

    def strip(s, carry):
        r0 = pl.multiple_of(s * strip_rows, strip_rows)
        rows = pl.ds(r0, strip_rows)
        mask = strip_mask(k1, k2, block_row + r0.astype(jnp.uint32),
                          strip_rows, thresh)
        for o, v in zip(outs, math(*scalars, *(r[rows, :] for r in ins),
                                   mask)):
            o[rows, :] = v
        return carry

    lax.fori_loop(0, block_rows // strip_rows, strip, 0)


def _node_update_call(kernel: Callable, name: str, scalars: Sequence[float],
                      tensors: Sequence[jax.Array], block_rows: int,
                      interpret: bool, key: jax.Array | None = None
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One pass over (R, 128) f32 ``tensors`` in blocks of ``block_rows``
    (at most R), with f32 ``scalars`` and, for a keyed kernel, the (2,) u32
    ``key`` in SMEM first."""
    x = tensors[0]
    dt = x.dtype
    block_rows = min(block_rows, x.shape[0])
    grid = (pl.cdiv(x.shape[0], block_rows),)
    tens = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    scal = pl.BlockSpec((1,), lambda i: (0,))
    in_specs = [scal] * len(scalars) + [tens] * len(tensors)
    operands = [jnp.full((1,), s, dt) for s in scalars] + list(tensors)
    if key is not None:
        in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + in_specs
        operands = [key] + operands
    shape = jax.ShapeDtypeStruct(x.shape, dt)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[tens] * 3,
        out_shape=(shape, shape, shape),
        interpret=interpret,
        name=name,
    )(*operands)


def dasha_update_pallas(grad: jax.Array, h: jax.Array, g_local: jax.Array,
                        mask: jax.Array, a: float, scale: float, *,
                        block_rows: int = DEFAULT_BLOCK_ROWS,
                        interpret: bool = True
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """All array args: (R, 128) float32.  Returns (m, h_new, g_local_new)."""
    kernel = functools.partial(_explicit_kernel, _dasha_math, 2)
    return _node_update_call(kernel, "dasha_update", (a, scale),
                             (grad, h, g_local, mask), block_rows, interpret)


def dasha_update_keyed_pallas(grad: jax.Array, h: jax.Array,
                              g_local: jax.Array, key: jax.Array, a: float,
                              scale: float, thresh: int, *,
                              block_rows: int = DEFAULT_BLOCK_ROWS,
                              strip_rows: int = DEFAULT_STRIP_ROWS,
                              interpret: bool = True
                              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`dasha_update_pallas` with the mask drawn in the kernel from
    ``key`` ((2,) uint32 threefry words) at ``thresh`` in (0, 256)."""
    kernel = functools.partial(_keyed_kernel, _dasha_math, 2, thresh,
                               strip_rows)
    return _node_update_call(kernel, "dasha_update", (a, scale),
                             (grad, h, g_local), block_rows, interpret,
                             key=key)


def dasha_mvr_update_pallas(grad_new: jax.Array, grad_old: jax.Array,
                            h: jax.Array, g_local: jax.Array,
                            mask: jax.Array, a: float, b: float,
                            scale: float, *,
                            block_rows: int = DEFAULT_BLOCK_ROWS,
                            interpret: bool = True
                            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """MVR variant; all array args (R, 128) float32."""
    kernel = functools.partial(_explicit_kernel, _dasha_mvr_math, 3)
    return _node_update_call(kernel, "dasha_mvr_update", (a, b, scale),
                             (grad_new, grad_old, h, g_local, mask),
                             block_rows, interpret)


def dasha_mvr_update_keyed_pallas(grad_new: jax.Array, grad_old: jax.Array,
                                  h: jax.Array, g_local: jax.Array,
                                  key: jax.Array, a: float, b: float,
                                  scale: float, thresh: int, *,
                                  block_rows: int = DEFAULT_BLOCK_ROWS,
                                  strip_rows: int = DEFAULT_STRIP_ROWS,
                                  interpret: bool = True
                                  ) -> Tuple[jax.Array, jax.Array,
                                             jax.Array]:
    """:func:`dasha_mvr_update_pallas` with the mask drawn in the kernel
    (see :func:`dasha_update_keyed_pallas`)."""
    kernel = functools.partial(_keyed_kernel, _dasha_mvr_math, 3, thresh,
                               strip_rows)
    return _node_update_call(kernel, "dasha_mvr_update", (a, b, scale),
                             (grad_new, grad_old, h, g_local), block_rows,
                             interpret, key=key)


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
