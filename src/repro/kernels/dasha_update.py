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
  tensor keeps its coordinate iff ``low8(x0 ^ x1) < thresh`` for
  ``(x0, x1) = threefry2x32(key, (0, i))``.  The kernel sees the tensor as
  a row-major (rows, cols) view, so the element at ``(r, c)`` has
  ``i = r * cols + c``, and each block computes its own counters from its
  two ``program_id``s and two iotas (u32 arithmetic: exact while
  ``rows * cols <= 2**32``).  A block walks its rows in strips of
  ``strip_rows`` and its columns in strips of 128 lanes
  (``lax.fori_loop``), so the 20-round hash of a strip stays in a few
  vector registers whatever the width.
* **explicit** (:func:`dasha_update_pallas`): the mask is a fourth f32
  input, for callers whose masks are not such a draw (PermK ownership,
  shared coordinates, p not a multiple of 1/256, shards of a leaf).

Both multiply the same f32 {0, 1} mask into the same expression, so they
give bit-equal outputs for the same mask.

Dtypes: each tensor operand may be f32 or bf16 on its own, and the kernel
casts every block to f32 as it reads it from VMEM, so the arithmetic is
f32 whatever the operands: the training step hands its bf16 gradients in
as the backward pass made them, and no f32 copy of a gradient is written
to HBM for the kernel to read back.  The outputs take h's dtype.

Tiling: every operand is one 2-D (rows, cols) view, and the grid walks
it in (block_rows, block_cols) blocks, partial at the edges (their
out-of-range writes are dropped).  :func:`node_update_block` sizes the
block from the view's shape alone, to ``BLOCK_ELEMENTS`` (1024 x 128)
elements of each operand, lanes padded to 128, whatever the operands'
dtypes: the v5e compiler takes a bf16 operand in the same blocks as an
f32 one, row blocks that are no multiple of 16 (8, 24, 40) included.
The pipeline double-buffers every operand, so a program holds at most
``2 x tensors x 512 KiB`` of VMEM (less for a bf16 operand): keyed DASHA
streams 6 tensors (3 in,
3 out), keyed MVR 7, the explicit forms one more, 6 to 8 MiB plus one
strip's hash, inside the 16 MiB of scoped VMEM that Mosaic grants a kernel
on TPU v5e by default.  The v5e compiler refuses twice that (2048 x 128
for the explicit kernels; 256 x 1536 rows for keyed DASHA).  A width that
is a multiple of 128 takes equal column blocks of at most 4096 lanes, so
that a block holds at least 32 rows; any other width is one full-width
block column, where 8 of its rows, padded to 128 lanes, fit the budget.
A view with no such block is not given to the kernels: the ops layer
packs it into (R, 128) lane rows instead (:mod:`repro.kernels.ops`).
h_new and g_local_new overwrite h and g_local in place
(``input_output_aliases``).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.random import threefry2x32_p

LANE = 128          # TPU vector lane width
SUBLANE = 8         # rows of an f32 vector register
#: f32 elements of one operand's block (lanes padded to 128)
BLOCK_ELEMENTS = 1024 * LANE
#: rows of a block that the keyed kernels hash and update at a time
DEFAULT_STRIP_ROWS = 32
#: the widest column block: a block holds at least a strip's rows
MAX_BLOCK_COLS = BLOCK_ELEMENTS // DEFAULT_STRIP_ROWS
#: the bit pattern of f32 1.0
_ONE_F32_BITS = np.uint32(0x3F800000)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def node_update_block(rows: int, cols: int) -> Optional[Tuple[int, int]]:
    """The (block_rows, block_cols) in which the node-update kernels stream
    a (rows, cols) f32 view, or ``None`` where no block both tiles the view
    and fits ``BLOCK_ELEMENTS`` (see the module docstring)."""
    if cols % LANE == 0:
        n_col_blocks = -(-cols // MAX_BLOCK_COLS)
        bc = _round_up(-(-cols // n_col_blocks), LANE)
    else:
        bc = cols
    fit = BLOCK_ELEMENTS // _round_up(bc, LANE)
    if rows <= fit:
        return rows, bc
    if fit < SUBLANE:
        return None
    return fit // SUBLANE * SUBLANE, bc


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
    """Inputs: scalars, tensors, then the f32 mask; three outputs.  Each
    block is cast to f32 as it is read (an identity for an f32 operand,
    exact for a bf16 one), each output to its own dtype as it is written."""
    scalars = [r[0] for r in refs[:n_scalars]]
    ins, outs = refs[n_scalars:-3], refs[-3:]
    blocks = (r[...].astype(jnp.float32) for r in ins)
    for o, v in zip(outs, math(*scalars, *blocks)):
        o[...] = v.astype(o.dtype)


def strip_mask(k1, k2, row, col, shape: Tuple[int, int], cols: int,
               thresh: int) -> jax.Array:
    """The f32 {0, 1} mask of a ``shape`` strip at (``row``, ``col``) of a
    row-major view ``cols`` wide: element (r, c) keeps iff
    ``low8(x0 ^ x1) < thresh`` for ``(x0, x1) = threefry2x32((k1, k2),
    (0, (row + r) * cols + col + c))``.
    """
    r = lax.broadcasted_iota(jnp.int32, shape, 0).astype(jnp.uint32)
    c = lax.broadcasted_iota(jnp.int32, shape, 1).astype(jnp.uint32)
    lo = (row + r) * jnp.uint32(cols) + (col + c)
    x0, x1 = threefry2x32_p.bind(jnp.full(shape, k1), jnp.full(shape, k2),
                                 jnp.zeros(shape, jnp.uint32), lo)
    # bit 31 of (low8 - thresh) is the compare; times the bits of 1.0 it
    # makes the f32 mask by integer ops, which a compiler cannot fold into
    # a select (that would give +0 for the explicit form's -0 and change
    # how the update rounds)
    keep = (((x0 ^ x1) & jnp.uint32(255)) - jnp.uint32(thresh)) \
        >> jnp.uint32(31)
    return lax.bitcast_convert_type(keep * _ONE_F32_BITS, jnp.float32)


def _walk(extent: int, step: int, body: Callable) -> None:
    """``body(start, size)`` over ``[0, extent)``: the whole ``step``s in a
    loop, then the static rest."""
    whole, rest = divmod(extent, step)
    if whole:
        def one(s, carry):
            body(pl.multiple_of(s * step, step), step)
            return carry
        lax.fori_loop(0, whole, one, 0)
    if rest:
        body(whole * step, rest)


def _keyed_kernel(math: Callable, n_scalars: int, thresh: int, cols: int,
                  strip_rows: int, key_ref, *refs):
    """Inputs: the key's two u32 words (SMEM), scalars, tensors; three
    outputs.  Draws each strip's mask and applies ``math`` to the strip,
    cast to f32 as in :func:`_explicit_kernel`;
    strips of ``strip_rows`` x 128 tile the block, the last row and column
    strips as wide as what is left."""
    k1, k2 = key_ref[0], key_ref[1]
    scalars = [r[0] for r in refs[:n_scalars]]
    ins, outs = refs[n_scalars:-3], refs[-3:]
    block_rows, block_cols = ins[0].shape
    row0 = (pl.program_id(0) * block_rows).astype(jnp.uint32)
    col0 = (pl.program_id(1) * block_cols).astype(jnp.uint32)

    def strip(r0, nr, c0, nc):
        at = (pl.ds(r0, nr), pl.ds(c0, nc))
        mask = strip_mask(k1, k2, row0 + jnp.asarray(r0).astype(jnp.uint32),
                          col0 + jnp.asarray(c0).astype(jnp.uint32),
                          (nr, nc), cols, thresh)
        blocks = (r[at].astype(jnp.float32) for r in ins)
        for o, v in zip(outs, math(*scalars, *blocks, mask)):
            o[at] = v.astype(o.dtype)

    _walk(block_rows, strip_rows, lambda r0, nr: _walk(
        block_cols, LANE, lambda c0, nc: strip(r0, nr, c0, nc)))


def _node_update_call(kernel: Callable, name: str, scalars: Sequence[float],
                      tensors: Sequence[jax.Array], interpret: bool,
                      block: Optional[Tuple[int, int]] = None,
                      key: jax.Array | None = None
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One pass over same-shape (rows, cols) ``tensors`` in ``block``s
    (default :func:`node_update_block`), with f32 ``scalars`` and, for a
    keyed kernel, the (2,) u32 ``key`` in SMEM first.  ``tensors`` end
    with (h, g_local) (then the mask, for an explicit kernel), which
    h_new and g_local_new overwrite in place: where the caller's h and
    g_local die with the update, as a step's state does, XLA then needs
    no buffer, and no copy into the step's state, for the outputs.  Each
    tensor may have its own float dtype (a bf16 gradient beside the f32
    state): the kernel casts every block to f32 in VMEM, and the outputs
    take h's dtype."""
    x = tensors[0]
    rows, cols = x.shape
    block = block or node_update_block(rows, cols)
    if block is None:
        raise ValueError(f"no node-update block tiles a {x.shape} view")
    grid = (pl.cdiv(rows, block[0]), pl.cdiv(cols, block[1]))
    tens = pl.BlockSpec(block, lambda i, j: (i, j))
    scal = pl.BlockSpec((1,), lambda i, j: (0,))
    in_specs = [scal] * len(scalars) + [tens] * len(tensors)
    operands = [jnp.full((1,), s, jnp.float32) for s in scalars] \
        + list(tensors)
    if key is not None:
        in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + in_specs
        operands = [key] + operands
    h_at = len(operands) - (3 if key is None else 2)
    shape = jax.ShapeDtypeStruct(x.shape, operands[h_at].dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[tens] * 3,
        out_shape=(shape, shape, shape),
        input_output_aliases={h_at: 1, h_at + 1: 2},
        interpret=interpret,
        name=name,
    )(*operands)


def dasha_update_pallas(grad: jax.Array, h: jax.Array, g_local: jax.Array,
                        mask: jax.Array, a: float, scale: float, *,
                        block: Optional[Tuple[int, int]] = None,
                        interpret: bool = True
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """All array args: (rows, cols), f32 or bf16 (see the module
    docstring).  Returns (m, h_new, g_local_new) in h's dtype."""
    kernel = functools.partial(_explicit_kernel, _dasha_math, 2)
    return _node_update_call(kernel, "dasha_update", (a, scale),
                             (grad, h, g_local, mask), interpret, block)


def dasha_update_keyed_pallas(grad: jax.Array, h: jax.Array,
                              g_local: jax.Array, key: jax.Array, a: float,
                              scale: float, thresh: int, *,
                              block: Optional[Tuple[int, int]] = None,
                              strip_rows: int = DEFAULT_STRIP_ROWS,
                              interpret: bool = True
                              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`dasha_update_pallas` with the mask drawn in the kernel from
    ``key`` ((2,) uint32 threefry words) at ``thresh`` in (0, 256)."""
    kernel = functools.partial(_keyed_kernel, _dasha_math, 2, thresh,
                               grad.shape[1], strip_rows)
    return _node_update_call(kernel, "dasha_update", (a, scale),
                             (grad, h, g_local), interpret, block, key=key)


def dasha_mvr_update_pallas(grad_new: jax.Array, grad_old: jax.Array,
                            h: jax.Array, g_local: jax.Array,
                            mask: jax.Array, a: float, b: float,
                            scale: float, *,
                            block: Optional[Tuple[int, int]] = None,
                            interpret: bool = True
                            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """MVR variant; array args as :func:`dasha_update_pallas`'s."""
    kernel = functools.partial(_explicit_kernel, _dasha_mvr_math, 3)
    return _node_update_call(kernel, "dasha_mvr_update", (a, b, scale),
                             (grad_new, grad_old, h, g_local, mask),
                             interpret, block)


def dasha_mvr_update_keyed_pallas(grad_new: jax.Array, grad_old: jax.Array,
                                  h: jax.Array, g_local: jax.Array,
                                  key: jax.Array, a: float, b: float,
                                  scale: float, thresh: int, *,
                                  block: Optional[Tuple[int, int]] = None,
                                  strip_rows: int = DEFAULT_STRIP_ROWS,
                                  interpret: bool = True
                                  ) -> Tuple[jax.Array, jax.Array,
                                             jax.Array]:
    """:func:`dasha_mvr_update_pallas` with the mask drawn in the kernel
    (see :func:`dasha_update_keyed_pallas`)."""
    kernel = functools.partial(_keyed_kernel, _dasha_mvr_math, 3, thresh,
                               grad_new.shape[1], strip_rows)
    return _node_update_call(kernel, "dasha_mvr_update", (a, b, scale),
                             (grad_new, grad_old, h, g_local), interpret,
                             block, key=key)


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
