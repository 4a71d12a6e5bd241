"""Pallas kernels vs the pure-jnp oracles (ref.py): shape/dtype sweeps in
interpret mode + hypothesis property checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypcompat import given, settings, st

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("d", [1, 100, 128, 129, 1000, 4096, 128 * 300 + 7])
@pytest.mark.parametrize("a,scale", [(0.1, 32.0), (1.0, 1.0), (0.011, 8.0)])
def test_dasha_update_matches_ref(d, a, scale):
    ks = jax.random.split(KEY, 4)
    grad, h, gl = (jax.random.normal(k, (d,)) for k in ks[:3])
    mask = jax.random.bernoulli(ks[3], 1.0 / scale, (d,)).astype(jnp.float32)
    out = ops.dasha_update(grad, h, gl, mask, a, scale)
    expect = ref.dasha_update_ref(grad, h, gl, mask, a, scale)
    for x, y in zip(out, expect):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", [(64,), (8, 32), (3, 5, 7)])
def test_dasha_update_arbitrary_shapes(shape):
    ks = jax.random.split(KEY, 4)
    grad, h, gl = (jax.random.normal(k, shape) for k in ks[:3])
    mask = jax.random.bernoulli(ks[3], 0.5, shape).astype(jnp.float32)
    m, hn, gln = ops.dasha_update(grad, h, gl, mask, 0.2, 2.0)
    assert m.shape == shape and hn.shape == shape and gln.shape == shape
    e_m, e_hn, e_gln = ref.dasha_update_ref(grad, h, gl, mask, 0.2, 2.0)
    np.testing.assert_allclose(np.asarray(gln), np.asarray(e_gln),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(d=st.integers(1, 2000), a=st.floats(0.001, 1.0),
       b=st.floats(0.0, 1.0))
def test_dasha_mvr_update_matches_ref(d, a, b):
    ks = jax.random.split(jax.random.PRNGKey(d), 5)
    gn, go, h, gl = (jax.random.normal(k, (d,)) for k in ks[:4])
    mask = jax.random.bernoulli(ks[4], 0.3, (d,)).astype(jnp.float32)
    out = ops.dasha_mvr_update(gn, go, h, gl, mask, a, b, 1 / 0.3)
    expect = ref.dasha_mvr_update_ref(gn, go, h, gl, mask, a, b, 1 / 0.3)
    for x, y in zip(out, expect):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-4, atol=1e-4)


def test_kernel_invariant_g_local_update():
    """g_local_new - g_local == m exactly (Alg. 1 line 10)."""
    d = 777
    ks = jax.random.split(KEY, 4)
    grad, h, gl = (jax.random.normal(k, (d,)) for k in ks[:3])
    mask = jax.random.bernoulli(ks[3], 0.25, (d,)).astype(jnp.float32)
    m, _, gln = ops.dasha_update(grad, h, gl, mask, 0.04, 4.0)
    np.testing.assert_allclose(np.asarray(gln - gl), np.asarray(m),
                               rtol=1e-5, atol=1e-6)
    # compressed support: m is zero off-mask
    assert float(jnp.max(jnp.abs(m * (1 - mask)))) == 0.0


@pytest.mark.parametrize("rows,cols", [(1, 128), (16, 256), (7, 100),
                                       (300, 64)])
@pytest.mark.parametrize("levels", [1, 7, 15])
def test_quantize_matches_ref(rows, cols, levels):
    x = jax.random.normal(KEY, (rows, cols))
    key = jax.random.PRNGKey(3)
    q = ops.quantize(x, key, levels)
    u = jax.random.uniform(key, x.shape, jnp.float32)
    expect = ref.quantize_ref(x, u, levels)
    np.testing.assert_allclose(np.asarray(q), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_quantize_unbiased():
    x = jax.random.normal(KEY, (4, 64))
    keys = jax.random.split(jax.random.PRNGKey(7), 1024)
    est = jnp.mean(jnp.stack([ops.quantize(x, k, 7) for k in keys[:256]]), 0)
    np.testing.assert_allclose(np.asarray(est), np.asarray(x), atol=0.15)


def test_quantize_zero_rows_passthrough():
    x = jnp.zeros((3, 64))
    q = ops.quantize(x, KEY, 15)
    assert float(jnp.max(jnp.abs(q))) == 0.0


# ---------------------------------------------------------------------------
# keyed kernels: the mask drawn inside from the leaf key
# ---------------------------------------------------------------------------

#: (4, 5): one row; (4, 37, 300): 347 rows, one block that strips do not
#: tile, size not a multiple of 128; (4, 300, 130): 1219 rows, two blocks,
#: the last partial; (4, 1024, 64): exactly two blocks
KEYED_SHAPES = [(4, 5), (4, 37, 300), (4, 300, 130), (4, 1024, 64)]


def _same_bits(got, want):
    for x, y in zip(got, want):
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x).view(np.uint32),
                                      np.asarray(y).view(np.uint32))


def _keyed_inputs(shape, n=4):
    ks = jax.random.split(jax.random.PRNGKey(sum(shape)), n)
    return [jax.random.normal(k, shape) for k in ks]


#: the gradient dtypes the kernels read as they are: the f32 of the flat
#: problems and the bf16 that the trainer's backward pass makes
GRAD_DTYPES = ["float32", "bfloat16"]


def _same_as_f32_grads(update, grads, state):
    """``update(*grads, *state)`` keeps the state's dtype and is bit-equal
    to the update given the gradients cast to f32."""
    got = update(*grads, *state)
    assert all(x.dtype == state[0].dtype for x in got)
    _same_bits(got, update(*(g.astype(jnp.float32) for g in grads), *state))
    return got


@pytest.mark.parametrize("grad_dtype", GRAD_DTYPES)
@pytest.mark.parametrize("thresh", [1, 8, 255])
@pytest.mark.parametrize("shape", KEYED_SHAPES)
def test_keyed_dasha_update_equals_explicit_mask(shape, thresh, grad_dtype):
    """The keyed kernel's m, h_new and g_i are bit-equal to the
    explicit-mask kernel's fed ``draw_mask(k, shape, thresh / 256)``, and
    a bf16 gradient gives what its f32 cast gives."""
    from repro.compress.plan import draw_mask
    grad, h, gl = _keyed_inputs(shape, 3)
    grad = grad.astype(grad_dtype)
    key = jax.random.PRNGKey(11)
    mask = draw_mask(key, shape, thresh / 256).astype(jnp.float32)
    scale = 256 / thresh
    got = _same_as_f32_grads(lambda g, h_, gl_: ops.dasha_update_keyed(
        g, h_, gl_, key, 0.2, scale, thresh), (grad,), (h, gl))
    _same_bits(got, ops.dasha_update(grad, h, gl, mask, 0.2, scale))


@pytest.mark.parametrize("grad_dtype", GRAD_DTYPES)
@pytest.mark.parametrize("thresh", [1, 8, 255])
@pytest.mark.parametrize("shape", KEYED_SHAPES)
def test_keyed_dasha_mvr_update_equals_explicit_mask(shape, thresh,
                                                     grad_dtype):
    from repro.compress.plan import draw_mask
    gn, go, h, gl = _keyed_inputs(shape)
    gn, go = gn.astype(grad_dtype), go.astype(grad_dtype)
    key = jax.random.PRNGKey(12)
    mask = draw_mask(key, shape, thresh / 256).astype(jnp.float32)
    scale = 256 / thresh
    got = _same_as_f32_grads(
        lambda gn_, go_, h_, gl_: ops.dasha_mvr_update_keyed(
            gn_, go_, h_, gl_, key, 0.2, 0.3, scale, thresh),
        (gn, go), (h, gl))
    _same_bits(got, ops.dasha_mvr_update(gn, go, h, gl, mask, 0.2, 0.3,
                                         scale))


@pytest.mark.parametrize("variant", ["dasha", "mvr"])
def test_keyed_kernels_take_typed_keys(variant):
    """A typed threefry key draws what its raw words draw, and what
    ``jax.random.bits`` draws from it."""
    from repro.compress.plan import draw_mask
    shape = (4, 37, 300)
    gn, go, h, gl = _keyed_inputs(shape)
    typed = jax.random.key(13)
    raw = jax.random.key_data(typed)
    mask = draw_mask(typed, shape, 8 / 256).astype(jnp.float32)
    if variant == "mvr":
        outs = [ops.dasha_mvr_update_keyed(gn, go, h, gl, k, 0.2, 0.3, 32.0,
                                           8) for k in (typed, raw)]
        want = ops.dasha_mvr_update(gn, go, h, gl, mask, 0.2, 0.3, 32.0)
    else:
        outs = [ops.dasha_update_keyed(gn, h, gl, k, 0.2, 32.0, 8)
                for k in (typed, raw)]
        want = ops.dasha_update(gn, h, gl, mask, 0.2, 32.0)
    for got in outs:
        _same_bits(got, want)


@pytest.mark.parametrize("block_rows,strip_rows", [(64, 16), (64, 8),
                                                   (48, 32)])
def test_keyed_kernel_counts_rows_across_blocks_and_strips(block_rows,
                                                           strip_rows):
    """Every block and strip hashes its own rows' flat indices: 300 rows in
    blocks of 64 (the last partial) and strips of 16 or 8, and blocks of
    48 that strips of 32 do not tile."""
    from repro.compress.plan import draw_mask
    from repro.kernels.dasha_update import (dasha_update_keyed_pallas,
                                            dasha_update_pallas)
    grad, h, gl = _keyed_inputs((300, 128), 3)
    key = jax.random.PRNGKey(14)
    mask = draw_mask(key, (300, 128), 8 / 256).astype(jnp.float32)
    block = (block_rows, 128)
    _same_bits(dasha_update_keyed_pallas(grad, h, gl, key, 0.2, 32.0, 8,
                                         block=block,
                                         strip_rows=strip_rows),
               dasha_update_pallas(grad, h, gl, mask, 0.2, 32.0,
                                   block=block))


@pytest.mark.parametrize("shape,block,strip_rows", [
    ((40, 1536), (16, 512), 8),     # 3 x 3 blocks, strips of 4 columns
    ((50, 1856), (24, 1856), 16),   # full width, 14 strips and a 64-wide
    ((33, 48), (16, 48), 8),        # narrower than a lane strip
    ((20, 4224), (8, 2176), 8),     # a partial edge column block
])
def test_keyed_kernel_counts_columns_across_blocks_and_strips(
        shape, block, strip_rows):
    """Every block and strip of a wide view hashes ``row * cols + col``:
    column blocks and 128-lane strips, the last of each partial."""
    from repro.compress.plan import draw_mask
    from repro.kernels.dasha_update import (dasha_update_keyed_pallas,
                                            dasha_update_pallas)
    grad, h, gl = _keyed_inputs(shape, 3)
    key = jax.random.PRNGKey(15)
    mask = draw_mask(key, shape, 8 / 256).astype(jnp.float32)
    _same_bits(dasha_update_keyed_pallas(grad, h, gl, key, 0.2, 32.0, 8,
                                         block=block,
                                         strip_rows=strip_rows),
               dasha_update_pallas(grad, h, gl, mask, 0.2, 32.0,
                                   block=block))


def _strip_mask_near_2_to_the_32(cols, col, width):
    """The mask of a 4-row strip ``width`` wide at column ``col`` of a view
    ``cols`` wide, whose last element has flat index just below 2**32,
    against threefry over the indices a u64 count gives."""
    from jax.extend.random import threefry2x32_p

    from repro.kernels.dasha_update import strip_mask
    k1, k2 = np.uint32(0x12345678), np.uint32(0x9ABCDEF0)
    first = (2 ** 32 - 1 - (col + width - 1)) // cols - 3
    idx = ((first + np.arange(4, dtype=np.uint64))[:, None] * cols
           + col + np.arange(width, dtype=np.uint64)[None, :])
    assert 2 ** 32 - cols <= idx.max() < 2 ** 32 and idx.min() > 2 ** 31
    x0, x1 = threefry2x32_p.bind(
        jnp.full((4, width), k1), jnp.full((4, width), k2),
        jnp.zeros((4, width), jnp.uint32),
        jnp.asarray(idx.astype(np.uint32)))
    want = (((x0 ^ x1) & 255) < 100).astype(jnp.float32)
    got = strip_mask(jnp.uint32(k1), jnp.uint32(k2), jnp.uint32(first),
                     jnp.uint32(col), (4, width), cols, 100)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert 0 < float(jnp.sum(got)) < got.size


def test_strip_mask_counts_past_2_to_the_31():
    """The strip's u32 counters stay exact up to 2**32 elements: the rows
    just below it hash the flat indices a u64 count gives."""
    _strip_mask_near_2_to_the_32(128, 0, 128)


@pytest.mark.parametrize("cols,col,width", [(1536, 1408, 128),
                                            (1856, 1792, 64), (48, 0, 48),
                                            (16384, 4096, 128)])
def test_strip_mask_counts_wide_rows_past_2_to_the_31(cols, col, width):
    """As above in views of other widths: ``row * cols + col`` in u32."""
    _strip_mask_near_2_to_the_32(cols, col, width)


# ---------------------------------------------------------------------------
# own layout against the lane path
# ---------------------------------------------------------------------------

#: the leaf classes of both configurations at cut leading dims, by width:
#: mamba2's embed / w_out (1536), w_xbc (3328), w_z (64), w_dt (48);
#: Nemotron's experts' w_in (1856), its d_model rows (2688).  Then rows
#: that the block does not divide, a width cut into column blocks with a
#: partial edge, and the lane path: a 1-D vector and a row too wide for a
#: block whose width is no multiple of 128
LAYOUT_SHAPES = [(2, 20, 1536), (2, 100, 1536), (2, 8, 3328),
                 (2, 16, 48, 64), (2, 64, 48), (1, 2, 40, 1856),
                 (1, 3, 16, 2688), (2, 4, 4224), (1000,), (9, 16500)]


def _lane_path(kernel, tensors, extra, *scalars):
    """The node update through (R, 128) lane rows: each tensor flattened
    and padded, the kernel's outputs cut back to the tensors' shape."""
    shape = tensors[0].shape
    outs = kernel(*map(ops._to_lanes, tensors), extra, *scalars,
                  interpret=True)
    return tuple(ops._from_lanes(t, shape) for t in outs)


@pytest.mark.parametrize("grad_dtype", GRAD_DTYPES)
@pytest.mark.parametrize("form", ["keyed", "explicit"])
@pytest.mark.parametrize("variant", ["dasha", "mvr"])
@pytest.mark.parametrize("shape", LAYOUT_SHAPES, ids=str)
def test_own_layout_is_bit_equal_to_the_lane_path(shape, variant, form,
                                                  grad_dtype):
    """m, h_new and g_local_new in each leaf's own layout are bit-equal to
    the (R, 128) lane path's, keyed and explicit, DASHA and MVR; a bf16
    gradient (packed into bf16 lane rows on the lane path) gives what its
    f32 cast gives."""
    from repro.compress.plan import draw_mask
    from repro.kernels import dasha_update as kern
    view = ops.node_update_view(shape)
    assert (view is None) == (shape in [(1000,), (9, 16500)])
    gn, go, h, gl = _keyed_inputs(shape)
    gn, go = gn.astype(grad_dtype), go.astype(grad_dtype)
    key = jax.random.PRNGKey(16)
    mvr = variant == "mvr"
    grads = (gn, go) if mvr else (gn,)
    scalars = (0.2, 0.3, 32.0) if mvr else (0.2, 32.0)
    if form == "keyed":
        op = ops.dasha_mvr_update_keyed if mvr else ops.dasha_update_keyed
        got = _same_as_f32_grads(lambda *ts: op(*ts, key, *scalars, 8),
                                 grads, (h, gl))
        kernel = (kern.dasha_mvr_update_keyed_pallas if mvr
                  else kern.dasha_update_keyed_pallas)
        want = _lane_path(kernel, (*grads, h, gl), ops._key_words(key),
                          *scalars, 8)
    else:
        mask = draw_mask(key, shape, 8 / 256).astype(jnp.float32)
        op = ops.dasha_mvr_update if mvr else ops.dasha_update
        got = _same_as_f32_grads(lambda *ts: op(*ts, mask, *scalars),
                                 grads, (h, gl))
        kernel = (kern.dasha_mvr_update_pallas if mvr
                  else kern.dasha_update_pallas)
        want = _lane_path(kernel, (*grads, h, gl), ops._to_lanes(mask),
                          *scalars)
    _same_bits(got, want)


@pytest.mark.parametrize("rows,cols,block", [
    (201728, 1536, (80, 1536)), (24576, 3328, (32, 3328)),
    (64512, 1856, (64, 1856)), (2688, 16384, (32, 4096)),
    (8064, 6144, (40, 3072)), (1179648, 64, (1024, 64)), (16, 48, (16, 48)),
    (4, 4224, (4, 2176)), (9, 16500, None), (100, 16384 + 64, None)])
def test_node_update_block_is_sized_from_the_width(rows, cols, block):
    """Blocks of at most 1024 x 128 f32 elements, lanes padded to 128: full
    width up to 4096 lanes where it is a multiple of 128, else full width
    where 8 padded rows fit, else none (the lane path)."""
    from repro.kernels.dasha_update import BLOCK_ELEMENTS, node_update_block
    got = node_update_block(rows, cols)
    assert got == block
    if got is not None:
        br, bc = got
        assert br * -(-bc // 128) * 128 <= BLOCK_ELEMENTS
        assert br == rows or br % 8 == 0
        assert bc == cols or bc % 128 == 0
