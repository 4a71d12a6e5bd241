"""The one-method API (repro.methods, DESIGN.md §7).

Contract families:

* substrate parity: the same variant + compressor + key on FlatSubstrate
  and on a single-leaf TreeSubstrate produces BIT-IDENTICAL g / h_i / g_i
  traces, for every registry variant (the substrates differ only in state
  representation, never in math or RNG);
* all five variants (dasha | page | mvr | sync_mvr | marina) run through
  Method.build on both substrates and keep the estimator invariant
  g == mean_i g_i;
* the trainer (make_method over a TreeSubstrate) reaches page and
  sync_mvr, trains, and keeps the invariant; sync_mvr's prob-p dense
  rounds show up in the unified payload accounting (the static
  payload_frac and the per-round growth of bits_sent);
* Hyper.from_theory assembles the Section-6 constants per variant.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compress import make_round_compressor
from repro.core.oracles import FiniteSumProblem, StochasticProblem
from repro.data.pipeline import synthetic_classification
from repro.methods import (VARIANTS, FlatSubstrate, Hyper, LeafProblemOracle,
                           Method, TreeSubstrate, expected_payload_frac,
                           get_rule, round_payload)
from repro.optim.base import SGD
from repro.optim.distributed import (DashaTrainConfig, make_method,
                                     payload_frac)

N_NODES, M, D, K = 4, 16, 24, 6
ALL_VARIANTS = ("dasha", "page", "mvr", "sync_mvr", "marina")


def _glm_problem(key=0):
    feats, labels = synthetic_classification(jax.random.PRNGKey(key),
                                             N_NODES, M, D)

    def loss(x, a, y):
        return (1.0 / (1.0 + jnp.exp(y * jnp.dot(a, x)))) ** 2

    return FiniteSumProblem(loss=loss, features=feats, labels=labels)


def _stoch_problem(key=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(key))
    A = jnp.diag(jnp.linspace(1.0, 2.0, D))
    b = jax.random.normal(k2, (D,))

    def loss(x, xi, i):
        return 0.5 * x @ A @ x - b @ x + xi @ x

    def sample(k, i, batch):
        return 0.3 * jax.random.normal(k, (batch, D))

    return StochasticProblem(loss=loss, sample=sample, n=N_NODES,
                             true_grad=lambda x: A @ x - b)


def _problem_for(variant):
    return _glm_problem() if variant in ("dasha", "page", "marina") \
        else _stoch_problem()


def _hyper_for(variant):
    kw = dict(gamma=0.05, a=0.2, variant=variant)
    if variant == "page":
        kw.update(p=0.25, batch=2)
    elif variant == "mvr":
        kw.update(b=0.3, batch=4)
    elif variant == "sync_mvr":
        kw.update(p=0.3, batch=4, batch_sync=16)
    elif variant == "marina":
        kw.update(p=0.3, batch=0)       # batch=0: exact full-grad diff
    return Hyper(**kw)


def _flat_method(variant, problem, hp):
    comp = make_round_compressor("randk", D, N_NODES, k=K)
    sub = FlatSubstrate(problem=problem, n=N_NODES, d=D)
    return Method.build(variant, comp, sub, hp)


def _tree_method(variant, problem, hp):
    comp = make_round_compressor("randk", D, N_NODES, k=K)
    oracle = LeafProblemOracle.wrapping(problem, {"w": jnp.zeros(D)})
    sub = TreeSubstrate(oracle=oracle, n=N_NODES,
                        server_opt=SGD(lr=hp.gamma))
    return Method.build(variant, comp, sub, hp)


def _init_mode(variant):
    return "exact" if variant in ("dasha", "page", "marina") else "stoch"


# ---------------------------------------------------------------------------
# substrate parity: flat == single-leaf tree, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", ALL_VARIANTS)
def test_flat_vs_tree_substrate_bit_identical(variant):
    problem = _problem_for(variant)
    hp = _hyper_for(variant)
    mf = _flat_method(variant, problem, hp)
    mt = _tree_method(variant, problem, hp)
    key = jax.random.PRNGKey(1)
    sf = mf.init(jnp.zeros(D), key, init_mode=_init_mode(variant))
    st = mt.init({"w": jnp.zeros(D)}, key, init_mode=_init_mode(variant))
    for t in range(4):
        sf = mf.step(sf)
        st = mt.step(st)
        for name, a, b in (("x", sf.x, st.x["w"]),
                           ("g", sf.g, st.g["w"]),
                           ("h_local", sf.h_local, st.h_local["w"]),
                           ("g_local", sf.g_local, st.g_local["w"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{name} @ t={t}")
        np.testing.assert_allclose(float(sf.bits_sent), float(st.bits_sent))


# ---------------------------------------------------------------------------
# every variant x both substrates: estimator invariant g == mean_i g_i
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("substrate", ["flat", "tree"])
@pytest.mark.parametrize("variant", ALL_VARIANTS)
def test_invariant_g_equals_mean_g_local(variant, substrate):
    problem = _problem_for(variant)
    hp = _hyper_for(variant)
    if substrate == "flat":
        m = _flat_method(variant, problem, hp)
        s = m.init(jnp.zeros(D), jax.random.PRNGKey(2),
                   init_mode=_init_mode(variant))
        leaf = lambda s_, f: getattr(s_, f)
    else:
        m = _tree_method(variant, problem, hp)
        s = m.init({"w": jnp.zeros(D)}, jax.random.PRNGKey(2),
                   init_mode=_init_mode(variant))
        leaf = lambda s_, f: getattr(s_, f)["w"]
    for _ in range(3):
        s = m.step(s)
        np.testing.assert_allclose(
            np.asarray(leaf(s, "g")),
            np.asarray(jnp.mean(leaf(s, "g_local"), 0)),
            rtol=1e-5, atol=1e-6)


def test_unknown_variant_raises():
    with pytest.raises(ValueError):
        get_rule("topk_sgd")
    with pytest.raises(ValueError):
        Method.build("nope", None,
                     FlatSubstrate(problem=None, n=2, d=4),
                     Hyper(gamma=0.1, a=1.0))


# ---------------------------------------------------------------------------
# the trainer reaches page / sync_mvr
# ---------------------------------------------------------------------------

def _trainer(cfg, loss, params, key):
    """The trainer's zero-initialised state and its jitted step."""
    method = make_method(cfg, loss)
    return method.init(params, key, init_mode="zeros"), jax.jit(method.step)


def _mlp_problem():
    key = jax.random.PRNGKey(0)
    params = {"w1": jax.random.normal(key, (8, 16)) * 0.3,
              "b1": jnp.zeros((16,)),
              "w2": jax.random.normal(jax.random.PRNGKey(1), (16, 4)) * 0.3}
    target_w = jax.random.normal(jax.random.PRNGKey(2), (8, 4))

    def loss(p, batch):
        x = batch["x"]
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] - batch["y"]) ** 2)

    def make_batch(k, n_nodes, b=16):
        x = jax.random.normal(k, (n_nodes, b, 8))
        return {"x": x, "y": jnp.einsum("nbi,io->nbo", x, target_w)}

    return params, loss, make_batch


@pytest.mark.parametrize("variant,use_kernel", [
    ("page", False), ("sync_mvr", False), ("sync_mvr", True),
])
def test_trainer_new_variants_learn_and_keep_invariant(variant, use_kernel):
    params, loss, make_batch = _mlp_problem()
    cfg = DashaTrainConfig(gamma=0.01, compression=0.25, variant=variant,
                           p=0.2, b=0.2, n_nodes=4, server_opt="adam",
                           use_kernel=use_kernel)
    state, step = _trainer(cfg, loss, params, jax.random.PRNGKey(3))
    key = jax.random.PRNGKey(4)
    b0 = make_batch(key, 4)
    flat = jax.tree_util.tree_map(
        lambda x: x.reshape((-1,) + x.shape[2:]), b0)
    l0 = float(loss(params, flat))
    for _ in range(200):
        key, kb = jax.random.split(key)
        state = step(state, make_batch(kb, 4))
    assert float(loss(state.x, flat)) < 0.6 * l0
    for g, gl in zip(jax.tree_util.tree_leaves(state.g),
                     jax.tree_util.tree_leaves(state.g_local)):
        np.testing.assert_allclose(np.asarray(g),
                                   np.asarray(jnp.mean(gl, 0)),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# unified payload accounting
# ---------------------------------------------------------------------------

def test_trainer_payload_metrics_bill_sync_rounds():
    """sync_mvr's prob-p dense rounds inflate payload_frac beyond the
    compressed fraction, and each round adds to bits_sent either the
    compressed or the dense coordinate count."""
    params, loss, make_batch = _mlp_problem()
    d_total = sum(x.size for x in jax.tree_util.tree_leaves(params))
    comp_frac, p_sync = 0.25, 0.3
    cfg = DashaTrainConfig(gamma=0.01, compression=comp_frac,
                           variant="sync_mvr", p=p_sync, n_nodes=4)
    state, step = _trainer(cfg, loss, params, jax.random.PRNGKey(5))
    assert payload_frac(cfg) == pytest.approx(
        comp_frac + p_sync * (1 - comp_frac))
    seen = set()
    key = jax.random.PRNGKey(6)
    for _ in range(30):
        key, kb = jax.random.split(key)
        sent = float(state.bits_sent)
        state = step(state, make_batch(kb, 4))
        seen.add(round(float(state.bits_sent) - sent, 3))
    assert seen <= {round(comp_frac * d_total, 3), float(d_total)}
    assert len(seen) == 2        # both branches taken in 30 rounds (p=0.3)

    # plain dasha: no sync rounds, frac is the compressed fraction
    cfg0 = DashaTrainConfig(gamma=0.01, compression=comp_frac, n_nodes=4)
    state0, step0 = _trainer(cfg0, loss, params, jax.random.PRNGKey(7))
    assert float(state0.bits_sent) == 0.0
    state0 = step0(state0, make_batch(key, 4))
    assert payload_frac(cfg0) == pytest.approx(comp_frac)
    assert float(state0.bits_sent) == pytest.approx(comp_frac * d_total)


def test_flat_and_trainer_accounting_agree():
    """One helper serves both layers: the flat loop's bits_sent increments
    equal round_payload(...), and the expectation matches
    expected_payload_frac for every variant."""
    rule = get_rule("sync_mvr")
    hp = _hyper_for("sync_mvr")
    assert expected_payload_frac(rule, hp, K, D) == pytest.approx(
        (K + hp.p * (D - K)) / D)
    assert expected_payload_frac(get_rule("dasha"), _hyper_for("dasha"),
                                 K, D) == pytest.approx(K / D)
    coin = jnp.asarray(True)
    assert float(round_payload(float(K), float(D), coin)) == D
    assert float(round_payload(float(K), float(D), None)) == K

    problem = _stoch_problem()
    m = _flat_method("sync_mvr", problem, hp)
    s = m.init(jnp.zeros(D), jax.random.PRNGKey(8), init_mode="stoch")
    increments = set()
    for _ in range(25):
        prev = float(s.bits_sent)
        s = m.step(s)
        increments.add(round(float(s.bits_sent) - prev, 3))
    assert increments <= {float(K), float(D)}
    assert len(increments) == 2


# ---------------------------------------------------------------------------
# Hyper.from_theory
# ---------------------------------------------------------------------------

def test_from_theory_assembles_constants():
    from repro.core import theory
    omega, n = D / K - 1.0, N_NODES
    hp = Hyper.from_theory("dasha", omega, n, L=2.0, gamma_mult=4.0)
    assert hp.variant == "dasha"
    assert hp.a == pytest.approx(theory.momentum_a(omega))
    assert hp.gamma == pytest.approx(
        4.0 * theory.gamma_dasha(2.0, 2.0, omega, n))

    hp = Hyper.from_theory("page", omega, n, L=2.0, B=2, m=M)
    assert hp.p == pytest.approx(theory.page_p(2, M))
    assert hp.batch == 2

    hp = Hyper.from_theory("mvr", omega, n, L=2.0, B=4, eps=0.05,
                           sigma2=0.09 * D)
    assert 0 < hp.b <= 1.0 and hp.gamma > 0

    hp = Hyper.from_theory("sync_mvr", omega, n, L=2.0, B=4, eps=0.05,
                           sigma2=0.09 * D, zeta=K, d=D)
    assert hp.p == pytest.approx(
        theory.sync_mvr_p(K, D, n, 4, eps=0.05, sigma2=0.09 * D))

    hp = Hyper.from_theory("marina", omega, n, L=2.0, zeta=K, d=D)
    assert hp.p == pytest.approx(K / D)
    assert hp.batch == 0        # plain MARINA: exact full-grad differences
    assert hp.gamma == pytest.approx(
        theory.gamma_marina(2.0, omega, n, K / D))


def test_registry_is_complete():
    assert set(VARIANTS) >= set(ALL_VARIANTS)
    assert get_rule("marina").force_a == 0.0
    assert get_rule("sync_mvr").has_sync and get_rule("marina").has_sync
    assert not get_rule("dasha").has_sync


# ---------------------------------------------------------------------------
# contract regressions
# ---------------------------------------------------------------------------

def test_stoch_init_on_finite_sum_is_a_real_minibatch():
    """init_mode='stoch' must honour batch_init on a FiniteSumProblem (a
    B_init minibatch, Cor. 6.8/6.10) — never silently the exact gradient."""
    problem = _glm_problem()
    m = _flat_method("dasha", problem, _hyper_for("dasha"))
    key = jax.random.PRNGKey(11)
    st = m.init(jnp.zeros(D), key, init_mode="stoch", batch_init=2)
    exact = problem.full_grad(jnp.zeros(D))
    assert not np.allclose(np.asarray(st.h_local), np.asarray(exact))
    assert float(st.bits_sent) == D


def test_marina_variant_oracle_mismatch_raises():
    from repro.core import marina
    glm, stoch = _glm_problem(), _stoch_problem()
    comp = make_round_compressor("randk", D, N_NODES, k=K)
    st = marina.init(jnp.zeros(D), jax.random.PRNGKey(12), glm)
    with pytest.raises(ValueError):
        marina.step(st, marina.MarinaHyper(gamma=0.1, p=0.5,
                                           variant="vr_online"), glm, comp)
    st2 = marina.init(jnp.zeros(D), jax.random.PRNGKey(12), stoch)
    with pytest.raises(ValueError):
        marina.step(st2, marina.MarinaHyper(gamma=0.1, p=0.5,
                                            variant="vr"), stoch, comp)


def test_metric_every_subsamples_and_matches_dense_trace():
    problem = _glm_problem()
    hp = _hyper_for("dasha")
    m = _flat_method("dasha", problem, hp)
    st = m.init(jnp.zeros(D), jax.random.PRNGKey(13))
    _, t1, b1 = m.run(st, 12)
    _, t4, b4 = m.run(st, 12, metric_every=4)
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b4))
    t1, t4 = np.asarray(t1), np.asarray(t4)
    assert t4.shape == t1.shape
    for i in range(12):
        np.testing.assert_allclose(t4[i], t1[4 * (i // 4)], rtol=1e-6)
