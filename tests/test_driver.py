"""The compiled run driver (repro.methods.driver, DESIGN.md §10).

Contract families:

* determinism: chunking is invisible (any chunk size produces bit-identical
  states and traces), and Method.run is a thin shim over the driver;
* resume: run 2N rounds in one go == run N -> full-MethodState checkpoint
  -> restore -> run N, bit-identical x/g/bits_sent, for a sync-coin
  variant (sync_mvr) and a plain one (dasha);
* sweeps: the vmapped gamma sweep reproduces per-gamma sequential runs,
  including pytree value axes ({"gamma", "b"});
* in-jit data: data_fn(fold_in(data_key, t), t) inside the scan matches a
  hand-rolled python loop drawing the same batches;
* checkpoint format: versioned save/load roundtrips every MethodState
  field bit-exactly, and a v2 checkpoint carrying the retired
  prev_params field restores into today's MethodState.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.io import load_state, save_state
from repro.compress import make_round_compressor
from repro.core.oracles import FiniteSumProblem, StochasticProblem
from repro.data.pipeline import synthetic_classification
from repro.methods import FlatSubstrate, Hyper, Method, MethodState
from repro.methods import driver as drv
from repro.optim.distributed import DashaTrainConfig, make_method

N_NODES, M, D, K = 4, 16, 24, 6


def _glm_problem(key=0):
    feats, labels = synthetic_classification(jax.random.PRNGKey(key),
                                             N_NODES, M, D)

    def loss(x, a, y):
        return (1.0 / (1.0 + jnp.exp(y * jnp.dot(a, x)))) ** 2

    return FiniteSumProblem(loss=loss, features=feats, labels=labels)


def _stoch_problem(key=0):
    _, k2 = jax.random.split(jax.random.PRNGKey(key))
    A = jnp.diag(jnp.linspace(1.0, 2.0, D))
    b = jax.random.normal(k2, (D,))

    def loss(x, xi, i):
        return 0.5 * x @ A @ x - b @ x + xi @ x

    def sample(k, i, batch):
        return 0.3 * jax.random.normal(k, (batch, D))

    return StochasticProblem(loss=loss, sample=sample, n=N_NODES,
                             true_grad=lambda x: A @ x - b)


def _method(variant, problem, **hyper_kw):
    comp = make_round_compressor("randk", D, N_NODES, k=K)
    hp = Hyper(gamma=0.05, a=0.2, variant=variant, **hyper_kw)
    return Method.build(variant, comp,
                        FlatSubstrate(problem=problem, n=N_NODES, d=D), hp)


def _dasha():
    m = _method("dasha", _glm_problem())
    return m, m.init(jnp.zeros(D), jax.random.PRNGKey(1))


def _sync_mvr():
    m = _method("sync_mvr", _stoch_problem(), p=0.3, batch=4, batch_sync=16)
    return m, m.init(jnp.zeros(D), jax.random.PRNGKey(1),
                     init_mode="stoch")


def _assert_states_equal(a, b):
    for name in ("x", "g", "g_local", "h_local", "key", "t", "bits_sent"):
        np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                      np.asarray(getattr(b, name)),
                                      err_msg=name)


# ---------------------------------------------------------------------------
# determinism: chunking is invisible; Method.run is the driver
# ---------------------------------------------------------------------------

def test_chunking_is_bit_invariant():
    m, st0 = _dasha()
    metric = {"metric": lambda s, d: jnp.sum(jnp.square(s.g))}
    ref_f, ref_t = drv.run(m, st0, 11, metrics=metric, chunk=11)
    for chunk in (1, 2, 3, 5, 11):
        f, t = drv.run(m, st0, 11, metrics=metric, chunk=chunk)
        _assert_states_equal(f, ref_f)
        for k in ref_t:
            np.testing.assert_array_equal(np.asarray(t[k]),
                                          np.asarray(ref_t[k]), err_msg=k)


def test_method_run_is_a_driver_shim():
    m, st0 = _dasha()
    fin, trace, bits = m.run(st0, 9)
    assert trace.shape == (9,) and bits.shape == (9,)
    f2, t2 = drv.run(
        m, st0, 9,
        metrics={"metric": lambda s, d: jnp.sum(
            _glm_problem().grad_f(s.x) ** 2)})
    _assert_states_equal(fin, f2)
    np.testing.assert_array_equal(np.asarray(bits),
                                  np.asarray(t2["bits_sent"]))
    # chunk passthrough changes nothing
    f3, t3, b3 = m.run(st0, 9, chunk=4)
    _assert_states_equal(fin, f3)
    np.testing.assert_array_equal(np.asarray(trace), np.asarray(t3))


def test_donated_input_state_runs_the_same_rounds():
    """With donation on, the driver copies the caller's state unless it is
    handed over (``donate_input=True``); the rounds are the same, and a
    copied input stays readable."""
    m, st0 = _dasha()
    ref, _ = drv.Driver(m, chunk=3, donate=False).run(st0, 7)
    driver = drv.Driver(m, chunk=3, donate=True)
    kept, _ = driver.run(st0, 7)
    _assert_states_equal(kept, ref)
    np.asarray(st0.x)                     # the caller's input is intact
    own = jax.tree_util.tree_map(jnp.copy, st0)
    handed, _ = driver.run(own, 7, donate_input=True)
    _assert_states_equal(handed, ref)


def test_zero_rounds_returns_empty_traces():
    m, st0 = _dasha()
    f, t = drv.run(m, st0, 0,
                   metrics={"m": lambda s, d: jnp.float32(0)})
    assert t["m"].shape == (0,) and t["bits_sent"].shape == (0,)
    _assert_states_equal(f, st0)


# ---------------------------------------------------------------------------
# resume bit-identity (the ISSUE acceptance contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("build", [_dasha, _sync_mvr],
                         ids=["dasha", "sync_mvr"])
def test_checkpoint_resume_is_bit_identical(build, tmp_path):
    m, st0 = build()
    n = 6
    path = str(tmp_path / "ck")
    mets = {"metric": lambda s, d: jnp.sum(jnp.square(s.g))}

    # one uninterrupted 2N-round run
    full, tr_full = drv.run(m, st0, 2 * n, chunk=3, metrics=mets,
                            metric_every=4)

    # N rounds -> checkpoint -> restore -> N rounds
    half, tr_a = drv.run(m, st0, n, chunk=3, metrics=mets, metric_every=4)
    save_state(path, half)
    restored = load_state(path, jax.tree_util.tree_map(
        jnp.zeros_like, half))
    _assert_states_equal(restored, half)
    resumed, tr_b = drv.run(m, restored, n, chunk=3, metrics=mets,
                            metric_every=4)

    _assert_states_equal(resumed, full)
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(tr_a["bits_sent"]),
                        np.asarray(tr_b["bits_sent"])]),
        np.asarray(tr_full["bits_sent"]))
    # metric cadence is keyed on the GLOBAL round counter (state.t): the
    # resumed segment evaluates at the same rounds as the uninterrupted
    # run (t = 8 here); only held-over values between evaluations restart
    glob = np.asarray(tr_full["metric"])
    res = np.asarray(tr_b["metric"])
    for t in range(n, 2 * n):
        if t % 4 == 0:                       # an evaluated point
            np.testing.assert_array_equal(res[t - n], glob[t])


def test_driver_checkpoint_hook_cadence(tmp_path):
    m, st0 = _dasha()
    seen = []
    drv.run(m, st0, 10, chunk=2,
            checkpoint=lambda s, t, tr: seen.append((t, int(s.t))),
            checkpoint_every=2)
    # chunks end at 2,4,6,8,10 -> hook at every 2nd chunk + the final one
    assert [t for t, _ in seen] == [4, 8, 10]
    assert all(t == st for t, st in seen)


# ---------------------------------------------------------------------------
# vmapped sweeps
# ---------------------------------------------------------------------------

def test_sweep_matches_sequential_runs():
    problem = _glm_problem()
    comp = make_round_compressor("randk", D, N_NODES, k=K)

    def method_fn(gamma):
        return Method.build("dasha", comp,
                            FlatSubstrate(problem=problem, n=N_NODES, d=D),
                            Hyper(gamma=gamma, a=0.2, variant="dasha"))

    st0 = method_fn(0.0).init(jnp.zeros(D), jax.random.PRNGKey(1))
    gammas = [0.02, 0.08]
    metric = {"metric": lambda s, d: jnp.sum(problem.grad_f(s.x) ** 2)}
    fin, tr = drv.sweep(method_fn, jnp.array(gammas), st0, 8,
                        metrics=metric, chunk=3)
    assert tr["metric"].shape == (2, 8)
    for j, g in enumerate(gammas):
        fj, tj = drv.run(method_fn(g), st0, 8, metrics=metric, chunk=3)
        np.testing.assert_allclose(np.asarray(tr["metric"][j]),
                                   np.asarray(tj["metric"]),
                                   rtol=1e-6, atol=1e-9)
        np.testing.assert_allclose(np.asarray(fin.x[j]), np.asarray(fj.x),
                                   rtol=1e-6, atol=1e-8)
        np.testing.assert_array_equal(np.asarray(tr["bits_sent"][j]),
                                      np.asarray(tj["bits_sent"]))


def test_sweep_over_pytree_values():
    """fig5's {gamma, b} axis: vmap over a dict of per-lane values."""
    problem = _stoch_problem()
    comp = make_round_compressor("randk", D, N_NODES, k=K)

    def method_fn(v):
        return Method.build("mvr", comp,
                            FlatSubstrate(problem=problem, n=N_NODES, d=D),
                            Hyper(gamma=v["gamma"], a=0.2, variant="mvr",
                                  b=v["b"], batch=2))

    st0 = method_fn({"gamma": 0.0, "b": 0.0}).init(
        jnp.zeros(D), jax.random.PRNGKey(1), init_mode="stoch")
    values = {"gamma": jnp.array([0.01, 0.05]),
              "b": jnp.array([0.1, 0.5])}
    fin, tr = drv.sweep(method_fn, values, st0, 6, chunk=2)
    for j in range(2):
        mj = method_fn({"gamma": float(values["gamma"][j]),
                        "b": float(values["b"][j])})
        fj, tj = drv.run(mj, st0, 6, chunk=2)
        np.testing.assert_allclose(np.asarray(fin.x[j]), np.asarray(fj.x),
                                   rtol=1e-6, atol=1e-8)
        np.testing.assert_array_equal(np.asarray(tr["bits_sent"][j]),
                                      np.asarray(tj["bits_sent"]))


# ---------------------------------------------------------------------------
# in-jit data (the trainer path)
# ---------------------------------------------------------------------------

def _mlp_method(variant="dasha"):
    key = jax.random.PRNGKey(0)
    params = {"w1": jax.random.normal(key, (8, 16)) * 0.3,
              "b1": jnp.zeros((16,)),
              "w2": jax.random.normal(jax.random.PRNGKey(1), (16, 4)) * 0.3}
    target_w = jax.random.normal(jax.random.PRNGKey(2), (8, 4))

    def loss(p, batch):
        x = batch["x"]
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] - batch["y"]) ** 2)

    def data_fn(k, t):
        x = jax.random.normal(k, (2, 4, 8))
        return {"x": x, "y": jnp.einsum("nbi,io->nbo", x, target_w)}

    cfg = DashaTrainConfig(gamma=0.05, compression=0.5, variant=variant,
                           n_nodes=2)
    return make_method(cfg, loss), params, data_fn, cfg


def test_data_fn_in_scan_matches_python_loop():
    method, params, data_fn, _ = _mlp_method()
    st0 = method.init(params, jax.random.PRNGKey(3), init_mode="zeros")
    data_key = jax.random.PRNGKey(4)

    fin, tr = drv.run(method, st0, 7, data_fn=data_fn, data_key=data_key,
                      chunk=3)

    st = st0
    for _ in range(7):
        batch = data_fn(jax.random.fold_in(data_key, st.t), st.t)
        st = method.step(st, batch)
    # same data stream, same steps -> same trajectory (tolerance only for
    # eager-vs-compiled fusion differences, amplified over the 7 steps)
    for name in ("x", "g", "h_local", "g_local"):
        for a, b in zip(jax.tree_util.tree_leaves(getattr(fin, name)),
                        jax.tree_util.tree_leaves(getattr(st, name))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(fin.key), np.asarray(st.key))
    assert int(fin.t) == 7


def test_data_fn_resume_regenerates_same_stream(tmp_path):
    """fold_in(data_key, t) is stateless: a restored run sees the SAME
    batches, so trainer resume is bit-identical too."""
    method, params, data_fn, _ = _mlp_method()
    st0 = method.init(params, jax.random.PRNGKey(3), init_mode="zeros")
    dk = jax.random.PRNGKey(4)
    full, _ = drv.run(method, st0, 6, data_fn=data_fn, data_key=dk,
                      chunk=2)
    half, _ = drv.run(method, st0, 3, data_fn=data_fn, data_key=dk,
                      chunk=2)
    path = str(tmp_path / "ck")
    save_state(path, half)
    restored = load_state(
        path, jax.tree_util.tree_map(jnp.zeros_like, half))
    resumed, _ = drv.run(method, restored, 3, data_fn=data_fn, data_key=dk,
                         chunk=2)
    for a, b in zip(jax.tree_util.tree_leaves(resumed),
                    jax.tree_util.tree_leaves(full)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# the versioned checkpoint format
# ---------------------------------------------------------------------------

def test_method_state_roundtrip_preserves_dtypes(tmp_path):
    m, st0 = _sync_mvr()
    st, _ = drv.run(m, st0, 3)
    path = str(tmp_path / "ck")
    save_state(path, st)
    out = load_state(path, jax.tree_util.tree_map(jnp.zeros_like,
                                                         st))
    _assert_states_equal(out, st)
    assert out.key.dtype == st.key.dtype
    assert out.t.dtype == jnp.int32
    assert out.bits_sent.dtype == jnp.float32


def test_v2_checkpoint_drops_retired_prev_params_field(tmp_path):
    """A checkpoint written with a retired field beside today's fields
    (the seed-era prev_params, a full params copy) restores into
    MethodState through the field-name shim."""
    import collections
    method, params, data_fn, _ = _mlp_method()
    st0 = method.init(params, jax.random.PRNGKey(5), init_mode="zeros")
    new, _ = drv.run(method, st0, 2, data_fn=data_fn,
                     data_key=jax.random.PRNGKey(6))
    OldState = collections.namedtuple(
        "MethodState", ("x", "prev_params") + MethodState._fields[1:])
    old = OldState(prev_params=params, **new._asdict())
    path = str(tmp_path / "ck")
    save_state(path, old, step=7)
    out = load_state(path, jax.tree_util.tree_map(jnp.zeros_like, new))
    assert isinstance(out, MethodState)
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(new), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_missing_field_fails_loudly(tmp_path):
    m, st0 = _dasha()
    path = str(tmp_path / "ck")
    import collections
    Partial = collections.namedtuple("Partial", ["x", "g"])
    save_state(path, Partial(x=st0.x, g=st0.g))
    with pytest.raises(ValueError, match="lacks state fields"):
        load_state(path, st0)
