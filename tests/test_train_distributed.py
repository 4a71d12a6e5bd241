"""DASHA-as-training-feature (optim.distributed): loss goes down, the Pallas
kernel path is bit-identical to the reference path, PermK aggregation is
exact, and bf16 state stays numerically sane."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compress import bernoulli_compress, permk_compress
from repro.optim.distributed import DashaTrainConfig, make_method

KEY = jax.random.PRNGKey(0)


def _trainer(cfg, loss, params, key):
    """The trainer's zero-initialised state and its jitted step."""
    method = make_method(cfg, loss)
    return method.init(params, key, init_mode="zeros"), jax.jit(method.step)


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


@pytest.mark.parametrize("mode,variant", [("independent", "dasha"),
                                          ("independent", "mvr"),
                                          ("permk", "dasha")])
def test_training_reduces_loss(mode, variant):
    params, loss, make_batch = _mlp_problem()
    cfg = DashaTrainConfig(gamma=0.01, compression=0.25, mode=mode,
                           variant=variant, b=0.2, n_nodes=4,
                           server_opt="adam")
    state, step = _trainer(cfg, loss, params, jax.random.PRNGKey(3))
    key = jax.random.PRNGKey(4)
    batch0 = make_batch(key, 4)
    flat = jax.tree_util.tree_map(
        lambda x: x.reshape((-1,) + x.shape[2:]), batch0)
    l0 = float(loss(params, flat))
    for t in range(300):
        key, kb = jax.random.split(key)
        state = step(state, make_batch(kb, 4))
    l1 = float(loss(state.x, flat))
    assert l1 < 0.5 * l0, (l0, l1)


def test_kernel_path_matches_reference_path():
    """use_kernel=True produces bit-identical trajectories (same RNG)."""
    params, loss, make_batch = _mlp_problem()
    batches = [make_batch(jax.random.PRNGKey(10 + i), 2) for i in range(5)]
    outs = []
    for uk in (False, True):
        cfg = DashaTrainConfig(gamma=0.05, compression=0.5, n_nodes=2,
                               use_kernel=uk)
        state, step = _trainer(cfg, loss, params, jax.random.PRNGKey(5))
        for b in batches:
            state = step(state, b)
        outs.append(state)
    for a, b in zip(jax.tree_util.tree_leaves(outs[0].x),
                    jax.tree_util.tree_leaves(outs[1].x)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_permk_aggregate_exact():
    """permk_compress returns agg == mean_i m_i exactly, with disjoint
    per-node supports tiling every leaf."""
    n = 4
    delta = {"a": jax.random.normal(KEY, (n, 3, 8)),
             "b": jax.random.normal(jax.random.PRNGKey(1), (n, 10))}
    m, agg = permk_compress(jax.random.PRNGKey(2), delta, n)
    for name in delta:
        mean_m = jnp.mean(m[name], 0)
        np.testing.assert_allclose(np.asarray(mean_m), np.asarray(agg[name]),
                                   rtol=1e-5, atol=1e-6)
        supp = np.asarray(m[name] != 0).reshape(n, -1).astype(int)
        assert (supp.sum(0) <= 1).all()


def test_permk_collection_unbiased_when_equal():
    """When all nodes hold the SAME delta, mean_i m_i == delta exactly."""
    n, d = 4, 24
    x = jax.random.normal(KEY, (d,))
    delta = {"x": jnp.tile(x[None], (n, 1))}
    _, agg = permk_compress(jax.random.PRNGKey(3), delta, n)
    np.testing.assert_allclose(np.asarray(agg["x"]), np.asarray(x),
                               rtol=1e-5, atol=1e-6)


def test_bernoulli_compress_unbiased():
    n = 2
    delta = {"w": jax.random.normal(KEY, (n, 50))}
    p = 0.25
    acc = jnp.zeros((n, 50))
    for i in range(512):
        m = bernoulli_compress(jax.random.PRNGKey(i), delta, p)
        acc = acc + m["w"]
    # per-coordinate MC standard error: |x| * sqrt((1-p)/(p*512)) ~ 0.2|x|
    err = np.abs(np.asarray(acc / 512) - np.asarray(delta["w"]))
    bound = 6 * np.abs(np.asarray(delta["w"])) * np.sqrt((1 - p) / (p * 512))
    assert (err <= bound + 0.05).all()


def test_invariant_g_mean_g_local_training():
    params, loss, make_batch = _mlp_problem()
    cfg = DashaTrainConfig(gamma=0.05, compression=0.5, n_nodes=4)
    state, step = _trainer(cfg, loss, params, jax.random.PRNGKey(6))
    key = jax.random.PRNGKey(7)
    for _ in range(5):
        key, kb = jax.random.split(key)
        state = step(state, make_batch(kb, 4))
    for g, gl in zip(jax.tree_util.tree_leaves(state.g),
                     jax.tree_util.tree_leaves(state.g_local)):
        np.testing.assert_allclose(np.asarray(g),
                                   np.asarray(jnp.mean(gl, 0)),
                                   rtol=1e-4, atol=1e-5)


def test_bf16_state_still_learns():
    params, loss, make_batch = _mlp_problem()
    cfg = DashaTrainConfig(gamma=0.01, compression=0.25, n_nodes=4,
                           server_opt="adam", state_dtype="bfloat16")
    state, step = _trainer(cfg, loss, params, jax.random.PRNGKey(8))
    assert state.h_local["w1"].dtype == jnp.bfloat16
    key = jax.random.PRNGKey(9)
    b0 = make_batch(key, 4)
    flat = jax.tree_util.tree_map(
        lambda x: x.reshape((-1,) + x.shape[2:]), b0)
    l0 = float(loss(params, flat))
    for _ in range(300):
        key, kb = jax.random.split(key)
        state = step(state, make_batch(kb, 4))
    l1 = float(loss(state.x, flat))
    assert l1 < 0.6 * l0, (l0, l1)


def test_shared_coords_common_support():
    """shared_coords: all nodes' messages have the SAME support per round."""
    n = 4
    delta = {"w": jax.random.normal(KEY, (n, 40))}
    m = bernoulli_compress(jax.random.PRNGKey(5), delta, 0.25, shared=True)
    supp = np.asarray(m["w"] != 0)
    for i in range(1, n):
        np.testing.assert_array_equal(supp[i], supp[0])


def test_shared_coords_training():
    params, loss, make_batch = _mlp_problem()
    cfg = DashaTrainConfig(gamma=0.01, compression=0.25, n_nodes=4,
                           mode="shared_coords", server_opt="adam")
    state, step = _trainer(cfg, loss, params, jax.random.PRNGKey(3))
    key = jax.random.PRNGKey(4)
    b0 = make_batch(key, 4)
    flat = jax.tree_util.tree_map(
        lambda x: x.reshape((-1,) + x.shape[2:]), b0)
    l0 = float(loss(params, flat))
    for _ in range(300):
        key, kb = jax.random.split(key)
        state = step(state, make_batch(kb, 4))
    assert float(loss(state.x, flat)) < 0.5 * l0
