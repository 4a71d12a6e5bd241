"""Chunk-resident cohort state store (DESIGN.md §16): the bit-identity
contract of the slab path.

The slab store changes WHERE the persistent (n, d) client state lives —
gathered into a compact (U, d) slab per chunk instead of riding the scan
carry — and nothing else.  The contract pinned here: same RNG chain, same
traces, same wire bytes, same final state as the legacy carry-resident
scatter store, for every sampled-capable variant, barrier and async
(tau in {0, 1, 2}) execution, chunk sizes that do and do not divide the
round count, and exact degeneration at c == n.

Two enabling pieces get unit coverage of their own:

* :func:`repro.methods.substrates.permutation_head` — the selection-based
  replay of ``jax.random.permutation(key, n)[:c]`` that makes the host-
  side cohort schedule O(n) per round.  Its bit-exactness rests on jax's
  stable sort-by-u32-bits shuffle, so it is checked against jax directly
  (including past the u16 ceiling and at collision-stress sizes) and
  against a crafted-collision reference;
* :func:`repro.kernels.ops.slab_writeback` — the per-chunk writeback,
  whose aliased Pallas kernel (interpret mode here) must produce the same
  bytes as the XLA drop-scatter it substitutes for.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import lipschitz_glm, theory_hyper
from repro.compress import make_round_compressor
from repro.core.oracles import FiniteSumProblem
from repro.data.pipeline import synthetic_classification
from repro.fed.sim import FedSim, simulate
from repro.fed.vecsim import VecFedSim
from repro.kernels import ops
from repro.methods import SampledFlatSubstrate
from repro.methods.substrates import (_perm_head_from_bits,
                                      _shuffle_num_rounds, permutation_head,
                                      slab_layout)

D, K = 40, 6


def _problem(n, m=4, d=D):
    feats, labels = synthetic_classification(jax.random.PRNGKey(0), n, m, d)

    def loss(x, a, y):
        return (1.0 - 1.0 / (1.0 + jnp.exp(y * jnp.dot(a, x)))) ** 2

    return FiniteSumProblem(loss=loss, features=feats, labels=labels)


def _sim(cls, variant, n, c, *, tau=None, store="auto", chunk=7,
         fmt="randk", **fkw):
    fkw = fkw or dict(k=K, backend="sparse")
    prob = _problem(n)
    rc = make_round_compressor(fmt, D, n, **fkw)
    sub = SampledFlatSubstrate(prob, n, D, c=c)
    hp = theory_hyper(variant, rc.omega, lipschitz_glm(prob), d=D,
                      k=fkw.get("k", K), n=n, m=4)
    return cls(variant=variant, comp=rc, substrate=sub, hyper=hp,
               seed=3, chunk=chunk, tau=tau, store=store)


def _run(sim, rounds=15):
    st = sim.init(jnp.zeros(D), jax.random.PRNGKey(42))
    return sim.run(st, rounds)


def _assert_bit_identical(a, b, label=""):
    assert set(a.traces) == set(b.traces), label
    for k in a.traces:
        assert np.array_equal(a.traces[k], b.traces[k]), (label, k)
    for x, y in zip(jax.tree_util.tree_leaves(a.state),
                    jax.tree_util.tree_leaves(b.state)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), \
            (label, np.shape(x))


# ---------------------------------------------------------------------------
# permutation head: the host-side cohort schedule's bit-exact replay
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,c", [(8, 3), (37, 9), (1625, 5), (1626, 5),
                                 (2000, 64), (4096, 64)])
def test_permutation_head_matches_jax(n, c):
    """permutation_head(key, n, c) == jax.random.permutation(key, n)[:c]
    bit-for-bit, on both sides of the shuffle's 1->2 round boundary
    (n = 1625 / 1626)."""
    for seed in (0, 1, 7):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), 77)
        got = permutation_head(key, n, c)
        ref = np.asarray(jax.random.permutation(key, n)[:c])
        assert np.array_equal(got, ref), (n, c, seed)


@pytest.mark.slow
def test_permutation_head_matches_jax_at_scale():
    """Past the u16 ceiling and at collision stress: n = 200000 draws
    ~4.7 duplicate u32 sort keys per shuffle round, so this run fails
    loudly if the tie-break (stable order == position-composite key)
    ever diverges from jax's stable sort."""
    for n, c in ((65537, 13), (200_000, 64)):
        key = jax.random.PRNGKey(5)
        got = permutation_head(key, n, c)
        ref = np.asarray(jax.random.permutation(key, n)[:c])
        assert np.array_equal(got, ref), (n, c)


def test_perm_head_crafted_collisions():
    """The selection walk against a crafted-duplicate reference: stable
    argsort of the raw u32 bits is exactly argsort of the (bits << 32) |
    position composite, so ties must resolve by position."""
    bits = np.array([[5, 1, 5, 0, 1, 5, 0]], np.uint64)
    n = bits.shape[1]
    ref = np.argsort(bits[0], kind="stable")         # jax's stable round
    for c in range(1, n + 1):
        got = _perm_head_from_bits(bits, c)
        assert np.array_equal(got, ref[:c]), c
    # two rounds: the second shuffles the first's output
    bits2 = np.array([[5, 1, 5, 0, 1, 5, 0],
                      [2, 2, 0, 7, 2, 0, 1]], np.uint64)
    x = np.arange(n)
    for r in range(2):
        # jax's round: sort_key_val(bits, x) — fresh bits are POSITION-
        # aligned with the current x, so x permutes by argsort(bits)
        x = x[np.argsort(bits2[r], kind="stable")]
    for c in range(1, n + 1):
        assert np.array_equal(_perm_head_from_bits(bits2, c), x[:c]), c


def test_shuffle_round_count_tracks_jax():
    """ceil(3 ln n / ln(2^32 - 1)): 1 round through n = 1625, 2 after —
    the boundary permutation_head's backward walk depends on."""
    assert _shuffle_num_rounds(2) == 1
    assert _shuffle_num_rounds(1625) == 1
    assert _shuffle_num_rounds(1626) == 2
    assert _shuffle_num_rounds(2_600_000) == 2


def test_cohort_schedule_replays_the_engine_key_chain():
    """cohort_schedule(state.key, R) row t == the engine's in-jit draw
    round_cohort(key_t) along the same key chain — the slab path's RNG
    contract."""
    sim = _sim(FedSim, "dasha", 37, 9, store="scatter")
    st = sim.init(jnp.zeros(D), jax.random.PRNGKey(42))
    sub = sim.substrate
    sels = sub.cohort_schedule(st.key, 6)
    key = st.key
    for t in range(6):
        ref = np.asarray(sub.round_cohort(key))
        assert np.array_equal(sels[t], ref), t
        key = jax.random.split(key, 4)[0]


# ---------------------------------------------------------------------------
# slab writeback kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("accumulate", [False, True])
def test_slab_writeback_kernel_matches_scatter(accumulate):
    """The aliased Pallas kernel (interpret mode on this container) and
    the XLA drop-scatter produce identical bytes — set and accumulate,
    including sentinel-padded rows (idx == n drops) and non-block-
    multiple slab lengths (the ops wrapper pads)."""
    rng = np.random.default_rng(0)
    n, d, u = 23, 8, 11
    full = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    idx_np = np.full(u, n, np.int32)
    idx_np[:7] = np.sort(rng.choice(n, 7, replace=False)).astype(np.int32)
    idx = jnp.asarray(idx_np)
    rows = jnp.asarray(rng.standard_normal((u, d)).astype(np.float32))
    got = ops.slab_writeback(full, idx, rows, accumulate=accumulate,
                             use_kernel=True)
    ref = ops.slab_writeback(full, idx, rows, accumulate=accumulate,
                             use_kernel=False)
    assert np.asarray(got).tobytes() == np.asarray(ref).tobytes()
    # untouched rows keep their exact bytes
    untouched = np.setdiff1d(np.arange(n), idx_np[:7])
    assert np.asarray(got)[untouched].tobytes() \
        == np.asarray(full)[untouched].tobytes()


def test_slab_layout_static_shape_and_sentinel():
    """U_pad = min(R*C, n) regardless of the realized union; pad rows
    carry the sentinel n; loc round-trips the schedule."""
    sels = np.array([[3, 1], [3, 5]], np.int32)
    uniq, loc = slab_layout(sels, 10)
    assert uniq.shape == (4,) and loc.shape == (2, 2)
    assert np.array_equal(uniq, [1, 3, 5, 10])       # 1 pad sentinel
    assert np.array_equal(uniq[loc], sels)
    uniq_sat, _ = slab_layout(np.arange(12).reshape(3, 4) % 5, 5)
    assert uniq_sat.shape == (5,)                    # capped at n


# ---------------------------------------------------------------------------
# slab == scatter bit-identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", ["dasha", "page", "mvr"])
@pytest.mark.parametrize("tau", [None, 0, 1])
def test_vec_slab_matches_scatter(variant, tau):
    """VecFedSim: slab store == scatter store bit-for-bit across the
    sampled-capable variants, barrier and async, and chunk sizes 1 / 7 /
    R (15 % 7 != 0 covers the ragged final chunk)."""
    ref = _run(_sim(VecFedSim, variant, 23, 5, tau=tau, store="scatter"))
    for chunk in (1, 7, 15):
        got = _run(_sim(VecFedSim, variant, 23, 5, tau=tau,
                        store="slab", chunk=chunk))
        _assert_bit_identical(ref, got, f"{variant} tau={tau} R={chunk}")


@pytest.mark.parametrize("variant", ["dasha", "page", "mvr"])
def test_heap_slab_matches_scatter(variant):
    """FedSim (the oracle): slab == scatter including the byte-exact
    wire traces (bytes_up / value_bytes are functions of the encoded
    buffers, so equality pins the codec path row for row)."""
    ref = _run(_sim(FedSim, variant, 23, 5, store="scatter"))
    got = _run(_sim(FedSim, variant, 23, 5, store="slab"))
    _assert_bit_identical(ref, got, variant)


@pytest.mark.parametrize("tau", [0, 1, 2])
def test_heap_async_slab_matches_scatter(tau):
    """The async tau path: at tau = 0 the slab rides the barrier's
    chunked scans; at tau >= 1 the heap dispatches per round on the
    legacy store by design — either way store= must not change a bit."""
    ref = _run(_sim(FedSim, "dasha", 23, 5, tau=tau, store="scatter"))
    got = _run(_sim(FedSim, "dasha", 23, 5, tau=tau, store="slab"))
    _assert_bit_identical(ref, got, f"tau={tau}")


@pytest.mark.parametrize("fmt,fkw", [
    ("randk", dict(k=K, backend="sparse")),
    ("permk", dict()),
    ("bernoulli", dict(p=0.2, backend="sparse"))])
def test_vec_equals_heap_on_slab_store(fmt, fkw):
    """Vec == heap on the SLAB store: byte traces bit-exact (integer
    functions of the same engine randomness), per wire format."""
    v = _run(_sim(VecFedSim, "dasha", 23, 5, store="slab",
                  fmt=fmt, **fkw))
    h = _run(_sim(FedSim, "dasha", 23, 5, store="slab", fmt=fmt, **fkw))
    for k in ("bytes_up", "value_bytes", "participants", "sync_round",
              "bits_sent", "metric"):
        assert np.array_equal(v.traces[k], h.traces[k]), (fmt, k)


@pytest.mark.parametrize("variant", ["dasha", "page", "mvr", "sync_mvr",
                                     "marina"])
def test_c_equals_n_degenerates_to_the_dense_path(variant):
    """c == n is the dense path (samples_clients False): store='auto'
    bit-matches store='scatter', and an explicit 'slab' refuses loudly
    instead of pretending there is anything to hoist.  This is also where
    the barrier variants (sync_mvr, marina) meet the store knob — they
    reject sampled substrates outright (engine.py), so the dense
    degeneration IS their whole slab story."""
    for cls in (FedSim, VecFedSim):
        ref = _run(_sim(cls, variant, 12, 12, store="scatter"))
        got = _run(_sim(cls, variant, 12, 12, store="auto"))
        _assert_bit_identical(ref, got, f"{cls.__name__} {variant}")
        with pytest.raises(ValueError, match="slab"):
            _sim(cls, variant, 12, 12, store="slab")
        with pytest.raises(ValueError, match="store"):
            _sim(cls, variant, 12, 12, store="bogus")


def test_simulate_threads_the_store_knob():
    """The one-shot convenience API exposes store= for both engines."""
    prob = _problem(23)
    rc = make_round_compressor("randk", D, 23, k=K, backend="sparse")
    sub = SampledFlatSubstrate(prob, 23, D, c=5)
    hp = theory_hyper("dasha", rc.omega, lipschitz_glm(prob), d=D, k=K,
                      n=23, m=4)
    kw = dict(rounds=8, seed=3, key=jax.random.PRNGKey(42))
    a = simulate("dasha", rc, sub, hp, jnp.zeros(D), kw.pop("key"),
                 rounds=8, seed=3, engine="vec", store="scatter")
    b = simulate("dasha", rc, sub, hp, jnp.zeros(D), jax.random.PRNGKey(42),
                 rounds=8, seed=3, engine="vec", store="slab")
    for k in a.traces:
        assert np.array_equal(a.traces[k], b.traces[k]), k


@pytest.mark.parametrize("cls,prefix", [(VecFedSim, "vec"), (FedSim, "fed")])
def test_slab_spans_on_the_host_track(cls, prefix):
    """Each chunk's gather and writeback are HOST-track spans of a live
    timeline (through :func:`repro.obs.span`), with the slab's rows."""
    from repro.obs import Obs
    from repro.obs.timeline import HOST
    sim = _sim(cls, "dasha", 37, 9, store="slab")
    obs = Obs.full()
    sim.run(sim.init(jnp.zeros(D), jax.random.PRNGKey(42)), 15, obs=obs)
    host = [e for e in obs.timeline.events if e.track == HOST]
    chunks = -(-15 // 7)
    for name in ("slab_gather", "slab_writeback"):
        spans = [e for e in host if e.name == f"{prefix}.{name}"]
        assert len(spans) == chunks
        assert all(e.args["rows"] > 0 and e.t1 >= e.t0 for e in spans)
    if cls is VecFedSim:
        assert [e.args for e in host if e.name == "vec.chunk"] == [
            {"start_round": r, "rounds": min(7, 15 - r)} for r in (0, 7, 14)]
    assert obs.timeline.validate() == []
