"""Fused Pallas coverage at the training layer (optim.distributed).

The seed restricted ``use_kernel=True`` to mode=independent x variant=dasha;
the unified subsystem routes EVERY mode (independent | shared_coords |
permk) x variant (dasha | mvr) through
:func:`repro.compress.treelevel.fused_tree_update`.  These tests pin the
fused trajectories to the dense reference under a shared RNG.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compress import fused_tree_update, permk_compress
from repro.optim.distributed import DashaTrainConfig, make_method

KEY = jax.random.PRNGKey(0)


def _mlp_problem():
    params = {"w1": jax.random.normal(KEY, (8, 16)) * 0.3,
              "b1": jnp.zeros((16,)),
              "w2": jax.random.normal(jax.random.PRNGKey(1), (16, 4)) * 0.3}
    target_w = jax.random.normal(jax.random.PRNGKey(2), (8, 4))

    def loss(p, batch):
        x = batch["x"]
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        pred = h @ p["w2"]
        return jnp.mean((pred - batch["y"]) ** 2)

    def make_batch(key, n_nodes, b=16):
        x = jax.random.normal(key, (n_nodes, b, 8))
        y = jnp.einsum("nbi,io->nbo", x, target_w)
        return {"x": x, "y": y}

    return params, loss, make_batch


@pytest.mark.parametrize("mode,variant", [
    ("independent", "dasha"),        # the seed's only fused combination
    ("independent", "mvr"),          # NEW: fused MVR kernel
    ("shared_coords", "dasha"),      # NEW: shared-mask fused path
    ("shared_coords", "mvr"),
    ("permk", "dasha"),              # NEW: fused PermK ownership masks
    ("permk", "mvr"),
])
def test_kernel_path_matches_reference_path(mode, variant):
    """use_kernel=True matches the dense path under the same RNG, for every
    mode x variant (the seed's `not permk and not mvr` guard is gone)."""
    params, loss, make_batch = _mlp_problem()
    batches = [make_batch(jax.random.PRNGKey(10 + i), 2) for i in range(4)]
    outs = []
    for uk in (False, True):
        cfg = DashaTrainConfig(gamma=0.05, compression=0.5, n_nodes=2,
                               mode=mode, variant=variant, b=0.3,
                               use_kernel=uk)
        method = make_method(cfg, loss)
        state = method.init(params, jax.random.PRNGKey(5), init_mode="zeros")
        step = jax.jit(method.step)
        for b in batches:
            state = step(state, b)
        outs.append(state)
    for name, tree_a, tree_b in (("params", outs[0].x, outs[1].x),
                                 ("g", outs[0].g, outs[1].g),
                                 ("h", outs[0].h_local, outs[1].h_local),
                                 ("g_local", outs[0].g_local,
                                  outs[1].g_local)):
        for a, b in zip(jax.tree_util.tree_leaves(tree_a),
                        jax.tree_util.tree_leaves(tree_b)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=1e-6, err_msg=name)


def test_fused_permk_masks_partition_every_leaf():
    """Fused PermK messages have disjoint per-node supports tiling each
    leaf, exactly like the dense permk_compress path."""
    n = 4
    tree = {"a": jax.random.normal(KEY, (n, 3, 8)),
            "b": jax.random.normal(jax.random.PRNGKey(1), (n, 10))}
    zeros = jax.tree_util.tree_map(jnp.zeros_like, tree)
    m, h_new, gl = fused_tree_update(jax.random.PRNGKey(2), tree, zeros,
                                     zeros, mode="permk", a=1.0, p=1.0, n=n)
    m_ref, agg_ref = permk_compress(jax.random.PRNGKey(2), tree, n)
    for name in tree:
        np.testing.assert_allclose(np.asarray(m[name]),
                                   np.asarray(m_ref[name]),
                                   rtol=1e-6, atol=1e-7)
        supp = np.asarray(m[name] != 0).reshape(n, -1).astype(int)
        assert (supp.sum(0) <= 1).all()
        np.testing.assert_allclose(np.asarray(jnp.mean(m[name], 0)),
                                   np.asarray(agg_ref[name]),
                                   rtol=1e-5, atol=1e-6)


def test_fused_mvr_kernel_updates_h_with_momentum():
    """Fused MVR h-update: h_new = gn + (1-b)(h - go), computed in-kernel."""
    n, b = 2, 0.25
    gn = {"w": jax.random.normal(KEY, (n, 12))}
    go = {"w": jax.random.normal(jax.random.PRNGKey(1), (n, 12))}
    h = {"w": jax.random.normal(jax.random.PRNGKey(2), (n, 12))}
    gl = {"w": jax.random.normal(jax.random.PRNGKey(3), (n, 12))}
    m, h_new, gl_new = fused_tree_update(
        jax.random.PRNGKey(4), gn, h, gl, mode="independent", a=0.2, p=0.5,
        n=n, variant="mvr", b=b, grads_old=go)
    expect_h = gn["w"] + (1.0 - b) * (h["w"] - go["w"])
    np.testing.assert_allclose(np.asarray(h_new["w"]), np.asarray(expect_h),
                               rtol=1e-5, atol=1e-6)
    # g_local_new - g_local == m exactly (Alg. 1 line 10)
    np.testing.assert_allclose(np.asarray(gl_new["w"] - gl["w"]),
                               np.asarray(m["w"]), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mode,variant", [("permk", "mvr"),
                                          ("shared_coords", "dasha")])
def test_fused_training_reduces_loss(mode, variant):
    """The newly-covered fused combinations actually train."""
    params, loss, make_batch = _mlp_problem()
    cfg = DashaTrainConfig(gamma=0.01, compression=0.25, mode=mode,
                           variant=variant, b=0.2, n_nodes=4,
                           server_opt="adam", use_kernel=True)
    method = make_method(cfg, loss)
    state = method.init(params, jax.random.PRNGKey(3), init_mode="zeros")
    step = jax.jit(method.step)
    key = jax.random.PRNGKey(4)
    b0 = make_batch(key, 4)
    flat = jax.tree_util.tree_map(
        lambda x: x.reshape((-1,) + x.shape[2:]), b0)
    l0 = float(loss(params, flat))
    for _ in range(200):
        key, kb = jax.random.split(key)
        state = step(state, make_batch(kb, 4))
    assert float(loss(state.x, flat)) < 0.6 * l0


# ---------------------------------------------------------------------------
# the mask drawn inside the kernel (keyed path) vs the drawn mask
# ---------------------------------------------------------------------------

def _mamba_like(n=4):
    """Leaves shaped like a cut mamba2's per-node state: a vocab x width
    embedding, stacked layer weights with odd widths, small vectors."""
    shapes = {"embed": (n, 50, 24), "final_norm": (n, 24),
              "layers": {"w_xbc": (n, 2, 24, 43), "dt_bias": (n, 2, 3),
                         "w_z": (n, 2, 24, 3, 4)}}
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(jax.random.PRNGKey(21), 4 * len(leaves))
    trees = []
    for j in range(4):
        trees.append(jax.tree_util.tree_unflatten(treedef, [
            jax.random.normal(keys[4 * i + j], s)
            for i, s in enumerate(leaves)]))
    return trees


def _mask_path(key, gn, h, gl, *, mode, p, n, variant, b=0.3, go=None):
    """The explicit-mask fused update: ``tree_masks`` then the kernel."""
    from repro.compress import tree_masks
    from repro.kernels import ops
    masks, scale = tree_masks(key, gn, mode=mode, p=p, n=n)

    def one(mk, gn_, h_, gl_, go_=None):
        if variant == "mvr":
            return ops.dasha_mvr_update(gn_, go_, h_, gl_, mk, 0.2, b, scale)
        return ops.dasha_update(gn_, h_, gl_, mk, 0.2, scale)

    extra = (go,) if variant == "mvr" else ()
    trips = jax.tree_util.tree_map(one, masks, gn, h, gl, *extra)
    return tuple(jax.tree_util.tree_map(
        lambda t: t[i], trips, is_leaf=lambda t: isinstance(t, tuple))
        for i in range(3))


def _assert_same_bits(got, want):
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(a).view(np.uint32),
                                      np.asarray(b).view(np.uint32))


@pytest.mark.parametrize("variant", ["dasha", "mvr"])
@pytest.mark.parametrize("key", [jax.random.PRNGKey(5), jax.random.key(5)],
                         ids=["raw", "typed"])
def test_keyed_fused_update_equals_mask_and_dense_paths(variant, key):
    """At the cell's setting (independent, p = 1/32) every leaf draws in
    the kernel, and the update is bit-equal to the explicit-mask kernels
    fed ``tree_masks``; against the dense ``bernoulli_compress`` path the
    messages have the same support and agree to f32 rounding (the two
    programs may contract the update's multiply-adds differently)."""
    from repro.compress import bernoulli_compress
    n, p, a, b = 4, 1 / 32, 0.2, 0.3
    gn, h, gl, go = _mamba_like(n)
    kw = dict(mode="independent", a=a, p=p, n=n, variant=variant)
    if variant == "mvr":
        kw.update(b=b, grads_old=go)
    got = fused_tree_update(key, gn, h, gl, **kw)
    _assert_same_bits(got, _mask_path(key, gn, h, gl, mode="independent",
                                      p=p, n=n, variant=variant, b=b, go=go))

    h_new = gn if variant == "dasha" else jax.tree_util.tree_map(
        lambda g_, h_, o_: g_ + (1.0 - b) * (h_ - o_), gn, h, go)
    delta = jax.tree_util.tree_map(lambda hn, hh, g_: hn - hh - a * (g_ - hh),
                                   h_new, h, gl)
    m = bernoulli_compress(key, delta, p)
    for x, y in zip(jax.tree_util.tree_leaves(got[0]),
                    jax.tree_util.tree_leaves(m), strict=True):
        np.testing.assert_array_equal(np.asarray(x) != 0, np.asarray(y) != 0)
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5,
                                   atol=1e-6)
    for x, y, z in zip(jax.tree_util.tree_leaves(got[2]),
                       jax.tree_util.tree_leaves(gl),
                       jax.tree_util.tree_leaves(m), strict=True):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y + z),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mode,p", [("permk", 1 / 32),
                                    ("shared_coords", 1 / 32),
                                    ("independent", 0.3),
                                    ("independent", 1.0)])
def test_mask_path_modes_keep_the_drawn_mask(mode, p):
    """Where the kernel cannot replay the draw, ``fused_tree_update`` is
    the explicit-mask kernels fed ``tree_masks``, bit for bit."""
    n = 4
    gn, h, gl, _ = _mamba_like(n)
    key = jax.random.PRNGKey(6)
    got = fused_tree_update(key, gn, h, gl, mode=mode, a=0.2, p=p, n=n)
    _assert_same_bits(got, _mask_path(key, gn, h, gl, mode=mode, p=p, n=n,
                                      variant="dasha"))


def test_kernel_draw_decision():
    """The keyed path is taken for the cell's setting only: the mask path
    for permk, shared_coords, p not a multiple of 1/256 (and p = 1), a leaf
    split over a mesh, and a key of another generator."""
    from jax.sharding import PartitionSpec as P

    from repro.compress.treelevel import (kernel_draw_count,
                                          kernel_draw_threshold)
    x = jax.ShapeDtypeStruct((4, 50, 24), jnp.float32)
    mesh = jax.make_mesh((1,), ("data",))

    def thresh(**kw):
        kw = {"mode": "independent", "p": 1 / 32, **kw}
        return kernel_draw_threshold(x, kw.pop("spec", None), **kw)

    assert thresh() == 8
    assert thresh(p=255 / 256) == 255 and thresh(p=1 / 256) == 1
    assert thresh(spec=P("data")) == 8          # no mesh: not split
    assert thresh(spec=P("data"), mesh=mesh) is None
    assert thresh(spec=None, mesh=mesh) == 8
    assert thresh(mode="permk") is None
    assert thresh(mode="shared_coords") is None
    assert thresh(p=0.3) is None and thresh(p=1.0) is None
    rbg = jax.random.key(0, impl="rbg")
    assert thresh(key=rbg) is None
    assert thresh(key=jax.random.key(0)) == 8
    big = jax.ShapeDtypeStruct((4, 2 ** 30 + 1), jnp.float32)
    assert kernel_draw_threshold(big, None, mode="independent",
                                 p=1 / 32) is None

    tree = _mamba_like()[0]
    total = sum(int(t.size) for t in jax.tree_util.tree_leaves(tree))
    cell = kernel_draw_count(tree, mode="independent", p=1 / 32)
    assert tuple(cell) == (5, 5, total, total)
    assert str(cell) == (f"in kernel 5/5 leaves, {total / 1e6:.1f}M/"
                         f"{total / 1e6:.1f}M elements")
    for kw in (dict(mode="permk", p=1 / 32),
               dict(mode="shared_coords", p=1 / 32),
               dict(mode="independent", p=0.3),
               dict(mode="independent", p=1 / 32, mesh=mesh,
                    specs=jax.tree_util.tree_map(lambda _: P("data"),
                                                 tree))):
        assert tuple(kernel_draw_count(tree, **kw)) == (0, 5, 0, total)


def test_cell_draws_every_mask_in_the_kernel():
    """mamba2-780m cut to 4 layers with 4 nodes (the benchmark's cell):
    13 of 13 leaves, 544.2M elements, draw in the kernel."""
    from repro.compress.treelevel import kernel_draw_count
    from repro.launch.train import arch_config
    from repro.models import init_params
    cfg = arch_config("mamba2-780m", True, 4)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    per_node = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((4,) + s.shape, jnp.float32), shapes)
    count = kernel_draw_count(per_node, mode="independent", p=1 / 32)
    assert count.leaves == count.of_leaves == 13
    assert count.elements == count.of_elements
    assert str(count) == "in kernel 13/13 leaves, 544.2M/544.2M elements"


def test_kernel_layout_count():
    """Own layout where a kernel block tiles the leaf's (rows, cols) view;
    the lane path for a 1-D leaf and for rows too wide for a block whose
    width is no multiple of 128; a shard's view under a mesh; and in the
    mamba2 cell every leaf in its own layout."""
    from jax.sharding import PartitionSpec as P

    from repro.compress.treelevel import kernel_layout_count
    from repro.launch.train import arch_config
    from repro.models import init_params

    def leaf(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    tree = {"embed": leaf(4, 64, 256), "w_z": leaf(4, 16, 48, 64),
            "row": leaf(4, 16500), "scale": leaf(4,),
            "wide": leaf(4, 3, 16500)}
    own = 4 * 64 * 256 + 4 * 16 * 48 * 64 + 4 * 16500
    total = own + 4 + 4 * 3 * 16500
    count = kernel_layout_count(tree)
    assert tuple(count) == (3, 5, own, total)
    assert str(count) == (f"own layout 3/5 leaves, {own / 1e6:.1f}M/"
                          f"{total / 1e6:.1f}M elements")
    mesh = jax.make_mesh((1,), ("data",))
    specs = jax.tree_util.tree_map(lambda _: P("data"), tree)
    assert kernel_layout_count(tree, specs=specs, mesh=mesh) == count

    cfg = arch_config("mamba2-780m", True, 4)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    per_node = jax.tree_util.tree_map(lambda s: leaf(4, *s.shape), shapes)
    assert str(kernel_layout_count(per_node)) \
        == "own layout 13/13 leaves, 544.2M/544.2M elements"


# ---------------------------------------------------------------------------
# the gradient handed to the node update in the oracle's own dtype
# ---------------------------------------------------------------------------

def _smoke_run(variant, use_kernel, state_dtype="float32",
               init_mode="zeros"):
    """Two steps of the trainer's method on the mamba2 smoke config (bf16
    and f32 parameters, 2 nodes): the state after init (``"exact"``: from
    the oracle's gradient on the first batch) and each step.  The
    step is compiled without excess precision, so that a bf16 value is
    rounded to bf16 wherever the program makes one: otherwise the compiler
    may keep a bf16 gradient that the next op casts back to f32 at f32, in
    one program and not in another."""
    from repro.launch.train import arch_config
    from repro.models import init_params, lm
    cfg = arch_config("mamba2-780m", False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    method = make_method(
        DashaTrainConfig(gamma=0.01, compression=0.25, n_nodes=2,
                         variant=variant, use_kernel=use_kernel,
                         state_dtype=state_dtype),
        lambda p, b: lm.loss_fn(cfg, p, b)[0])
    batches = []
    for i in range(2):
        toks = jax.random.randint(jax.random.PRNGKey(10 + i), (2, 1, 33), 1,
                                  cfg.vocab_size)
        batches.append({"tokens": toks[..., :-1], "labels": toks[..., 1:]})
    states = [method.init(params, jax.random.PRNGKey(1), init_mode=init_mode,
                          data=batches[0])]
    step = jax.jit(method.step,
                   compiler_options={"xla_allow_excess_precision": False})
    for b in batches:
        states.append(step(states[-1], b))
    return states


def _dtypes(state):
    return jax.tree_util.tree_map(lambda x: x.dtype, state)


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("variant", ["dasha", "mvr", "page", "sync_mvr"])
def test_bf16_gradient_keeps_the_state_and_its_bits(monkeypatch, variant,
                                                    use_kernel):
    """The oracle hands the node update its gradients as the backward pass
    made them (bf16 for the bf16 parameters): every state leaf keeps its
    dtype across two steps, and the state is bit-equal to the same steps
    with every gradient cast to f32 before the update."""
    from repro.methods.substrates import BatchLossOracle
    got = _smoke_run(variant, use_kernel)
    for s in got[1:]:
        assert _dtypes(s) == _dtypes(got[0])
    assert {x.dtype for x in jax.tree_util.tree_leaves(got[0].h_local)} \
        == {jnp.dtype(jnp.float32)}

    own = BatchLossOracle.per_node_grads
    monkeypatch.setattr(
        BatchLossOracle, "per_node_grads",
        lambda self, params, data: jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32), own(self, params, data)))
    want = _smoke_run(variant, use_kernel)
    for a, b in zip(got, want):
        _assert_same_bits(a, b)


@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16"])
def test_state_keeps_its_dtype_from_an_exact_init(state_dtype):
    """h_i and g_i start from the oracle's gradient (bf16 and f32 leaves)
    in the state's dtype, and keep it through the kernel and the dense
    path and SYNC-MVR's dense sync branch, as the parameters keep
    theirs."""
    for use_kernel in (False, True):
        states = _smoke_run("sync_mvr", use_kernel, state_dtype=state_dtype,
                            init_mode="exact")
        for s in states:
            assert _dtypes(s) == _dtypes(states[0])
        assert {x.dtype for x in jax.tree_util.tree_leaves(
            (states[0].h_local, states[0].g_local))} \
            == {jnp.dtype(state_dtype)}


@pytest.mark.parametrize("arch", ["mamba2-780m", "nemotron-3-nano-30b-a3b"])
def test_kernel_grad_dtype_count(arch):
    """The gradients the oracle makes for both configurations' smoke
    trees reach the kernel in their own dtype, all of them; an f16 leaf
    would take a cast pass to f32 first."""
    from repro.compress.treelevel import kernel_grad_dtype_count
    from repro.launch.train import arch_config
    from repro.methods.substrates import BatchLossOracle
    from repro.models import init_params, lm
    cfg = arch_config(arch, False)
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 1, 32), jnp.int32)
    oracle = BatchLossOracle(lambda p, b: lm.loss_fn(cfg, p, b)[0])
    grads = jax.eval_shape(oracle.per_node_grads, params,
                           {"tokens": toks, "labels": toks})
    dtypes = {x.dtype for x in jax.tree_util.tree_leaves(grads)}
    assert jnp.dtype(jnp.bfloat16) in dtypes
    count = kernel_grad_dtype_count(grads)
    assert count.leaves == count.of_leaves
    assert count.elements == count.of_elements
    n = count.of_leaves
    total = count.of_elements
    assert str(count) == (f"own dtype {n}/{n} leaves, {total / 1e6:.1f}M/"
                          f"{total / 1e6:.1f}M elements")
    half = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float16), grads)
    assert tuple(kernel_grad_dtype_count(half)) == (0, n, 0, total)
