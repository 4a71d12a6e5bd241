"""Fault injection in the federated simulators (DESIGN.md §18): seeded
fault campaigns, graceful degradation vs sync retries, heap==vec
bit-exactness of the faulted byte traces, wire-integrity under real
corruption, and the kill-and-restore drill against PR-3 checkpoints."""
import numpy as np
import pytest

import jax

from benchmarks.common import glm_problem, lipschitz_glm, theory_hyper
from repro.checkpoint import io as ckpt_io
from repro.compress import make_round_compressor
from repro.fed import wire
from repro.fed.faults import FaultModel, corrupt_bytes
from repro.fed.net import LinkModel, Lognormal, round_barrier
from repro.fed.sim import FAULT_TRACES, FedSim
from repro.fed.vecsim import VecFedSim
from repro.methods import FlatSubstrate

D, K, N = 40, 6, 5

#: traces that are integer functions of the engine + fault randomness —
#: bit-exact across simulators, chunkings, and kill/restore
INT_TRACES = ("bytes_up", "value_bytes", "bytes_down", "sync_round",
              "participants") + FAULT_TRACES


def _setup(variant, p_participate=1.0):
    prob = glm_problem(d=D, m=32)
    sub = FlatSubstrate(prob, N, D)
    rc = make_round_compressor("randk", D, N, k=K, backend="sparse",
                               p_participate=p_participate)
    hp = theory_hyper(variant, rc.omega, lipschitz_glm(prob),
                      d=D, k=K, n=N, m=32)
    return sub, rc, hp


def _run(cls, variant, p=1.0, faults=None, rounds=40, seed=3, chunk=128,
         **kw):
    sub, rc, hp = _setup(variant, p)
    sim = cls(variant=variant, comp=rc, substrate=sub, hyper=hp,
              faults=faults, seed=seed, chunk=chunk)
    st = sim.init(np.zeros(D, np.float32), jax.random.PRNGKey(0))
    return sim.run(st, rounds, **kw)


FM_MIXED = FaultModel(p_crash=0.08, crash_rounds=2, p_drop_up=0.1,
                      p_drop_down=0.05, p_corrupt=0.05,
                      deadline_mult=3.0, rejoin="reset", seed=7)
FM_SYNC = FaultModel(p_crash=0.08, crash_rounds=2, p_drop_up=0.1,
                     p_corrupt=0.05, deadline_mult=3.0, seed=7)


# ---------------------------------------------------------------------------
# FaultModel / FaultCampaign unit behavior
# ---------------------------------------------------------------------------

def test_fault_model_validation():
    with pytest.raises(ValueError, match="p_crash"):
        FaultModel(p_crash=1.0)
    with pytest.raises(ValueError, match="p_drop_up"):
        FaultModel(p_drop_up=-0.1)
    with pytest.raises(ValueError, match="crash_rounds"):
        FaultModel(crash_rounds=0)
    with pytest.raises(ValueError, match="rejoin"):
        FaultModel(rejoin="reboot")
    with pytest.raises(ValueError, match="deadline_mult"):
        FaultModel(deadline_mult=1.0)
    with pytest.raises(ValueError, match="max_retries"):
        FaultModel(max_retries=0)
    with pytest.raises(ValueError, match="backoff"):
        FaultModel(backoff0_s=0.0)
    FaultModel(deadline_mult=None)     # deadline disabled is legal


def test_campaign_crash_windows_and_rejoins():
    fm = FaultModel(p_crash=0.2, crash_rounds=3, seed=1)
    fc = fm.draw_campaign(60, 8)
    # every crash start opens exactly a k-round outage window
    for t, i in zip(*np.nonzero(fc.crash_start)):
        assert fc.crashed[t: t + 3, i].all()
    # a rejoin is the first up-round after an outage
    assert (fc.rejoin[1:] == (~fc.crashed[1:] & fc.crashed[:-1])).all()
    assert not fc.rejoin[0].any()
    # crash_left counts remaining outage rounds, 0 when up
    assert (fc.crash_left > 0).sum() == fc.crashed.sum()


def test_campaign_crn_monotone_in_drop_rate():
    """Common random numbers: raising a probability knob realizes a
    SUPERSET of the same fault events, never a reshuffle."""
    lo = FaultModel(p_drop_up=0.05, p_crash=0.02, seed=3) \
        .draw_campaign(50, 6)
    hi = FaultModel(p_drop_up=0.3, p_crash=0.1, seed=3) \
        .draw_campaign(50, 6)
    assert (hi.drop_up | lo.drop_up == hi.drop_up).all()
    assert (hi.crash_start | lo.crash_start == hi.crash_start).all()


def test_campaign_retry_draws_do_not_perturb_fault_draws():
    """The fixed in-round draw order makes the retry matrix an APPENDED
    draw: graceful rules (retries=False) and sync rules (retries=True)
    face identical crash/drop/corrupt realizations under one seed."""
    fm = FaultModel(p_crash=0.1, p_drop_up=0.2, p_corrupt=0.1, seed=5)
    a = fm.draw_campaign(40, 6, retries=False)
    b = fm.draw_campaign(40, 6, retries=True)
    for f in ("crash_start", "crashed", "drop_down", "drop_up",
              "corrupt"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert b.first_success is not None and a.first_success is None
    # a retry only lands once the client is back up
    assert (b.first_success >= np.maximum(b.crash_left, 1)).all()


def test_corrupt_bytes_caught_by_wire_verify():
    """The corruption realization is REAL: a flipped byte in an encoded
    record must trip the header checksum."""
    rc = make_round_compressor("randk", D, N, k=K, backend="sparse")
    vals = np.arange(N * K, dtype=np.float32).reshape(N, K)
    idxs = np.tile(np.arange(K, dtype=np.int64), (N, 1))

    class Msgs:
        values, indices = vals, idxs

    bufs = wire.encode_round(rc, None, Msgs, 4, coin=False,
                             sync_values=None, present=None, slots=None)
    for i, buf in enumerate(bufs):
        wire.verify(buf)                      # pristine passes
        with pytest.raises(wire.WireCorruptionError):
            wire.verify(corrupt_bytes(buf, 4, i))


# ---------------------------------------------------------------------------
# scope guards
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cls", [FedSim, VecFedSim])
def test_faults_reject_async_and_sampled(cls):
    sub, rc, hp = _setup("dasha")
    with pytest.raises(ValueError, match="tau"):
        cls(variant="dasha", comp=rc, substrate=sub, hyper=hp,
            faults=FaultModel(), tau=2)
    prob = glm_problem(d=D, m=32)
    from repro.methods import SampledFlatSubstrate
    ssub = SampledFlatSubstrate(prob, N, D, c=3)
    src = make_round_compressor("randk", D, N, k=K, backend="sparse")
    with pytest.raises(ValueError, match="sampled"):
        cls(variant="dasha", comp=src, substrate=ssub, hyper=hp,
            faults=FaultModel())


def test_engine_rejects_faults_for_sync_rules():
    """MARINA/SYNC-MVR recover missing messages via simulator retries;
    the ENGINE must refuse a fault mask for them (their math never
    degrades)."""
    from repro.methods.engine import FaultStep, Method
    import jax.numpy as jnp
    sub, rc, hp = _setup("marina")
    m = Method.build("marina", rc, sub, hp)
    st = m.init(np.zeros(D, np.float32), jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="sync_requires_all"):
        m.step_full(st, None,
                    faults=FaultStep(drop=jnp.zeros((N,), bool)))


def test_run_validates_resume_args():
    sub, rc, hp = _setup("dasha")
    sim = FedSim(variant="dasha", comp=rc, substrate=sub, hyper=hp)
    st = sim.init(np.zeros(D, np.float32), jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="start_round"):
        sim.run(st, 10, start_round=11)


# ---------------------------------------------------------------------------
# zero-fault anchor: an all-zero FaultModel changes nothing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant,p", [("dasha", 1.0), ("dasha", 0.6),
                                       ("marina", 1.0)])
def test_zero_fault_heap_bit_identical(variant, p):
    base = _run(FedSim, variant, p)
    zf = _run(FedSim, variant, p, faults=FaultModel(deadline_mult=4.0))
    for k in base.traces:
        np.testing.assert_array_equal(base.traces[k], zf.traces[k],
                                      err_msg=k)
    np.testing.assert_array_equal(np.asarray(base.state.x),
                                  np.asarray(zf.state.x))


def test_zero_fault_vec_traces_match():
    """The faulted scan body is a different jaxpr, so floats may move an
    ulp (DESIGN.md §10); the integer traces and the masks cannot."""
    base = _run(VecFedSim, "dasha")
    zf = _run(VecFedSim, "dasha", faults=FaultModel(deadline_mult=4.0))
    for k in ("bytes_up", "value_bytes", "bytes_down", "participants",
              "sync_round"):
        np.testing.assert_array_equal(base.traces[k], zf.traces[k],
                                      err_msg=k)
    np.testing.assert_allclose(base.traces["sim_wall_clock"],
                               zf.traces["sim_wall_clock"], rtol=2e-6)
    np.testing.assert_allclose(base.traces["metric"],
                               zf.traces["metric"], rtol=1e-4)


# ---------------------------------------------------------------------------
# heap == vec under faults (the bit-exactness tentpole)
# ---------------------------------------------------------------------------

FAULT_MATRIX = [
    ("dasha", 1.0, FM_MIXED),
    ("dasha", 0.6, FaultModel(p_crash=0.1, p_drop_up=0.15,
                              deadline_mult=3.0, seed=11)),
    ("dasha", 1.0, FaultModel(p_crash=0.1, crash_rounds=3,
                              deadline_mult=None, seed=5)),
    ("page", 1.0, FM_MIXED),
    ("mvr", 1.0, FaultModel(p_crash=0.12, crash_rounds=2,
                            p_drop_up=0.2, rejoin="stale",
                            deadline_mult=3.0, seed=13)),
    ("marina", 1.0, FM_SYNC),
    ("sync_mvr", 1.0, FaultModel(p_crash=0.05, p_drop_up=0.1,
                                 deadline_mult=4.0, seed=9)),
]


@pytest.mark.parametrize("variant,p,fm", FAULT_MATRIX,
                         ids=[f"{v}-p{p}-s{fm.seed}"
                              for v, p, fm in FAULT_MATRIX])
def test_faulted_heap_vs_vec_bit_exact(variant, p, fm):
    hres = _run(FedSim, variant, p, faults=fm)
    vres = _run(VecFedSim, variant, p, faults=fm)
    assert hres.traces["dropped"].sum() > 0     # faults actually fired
    for k in INT_TRACES:
        np.testing.assert_array_equal(hres.traces[k], vres.traces[k],
                                      err_msg=f"{variant} trace {k}")
    np.testing.assert_allclose(hres.traces["sim_wall_clock"],
                               vres.traces["sim_wall_clock"], rtol=2e-6)
    np.testing.assert_allclose(hres.traces["metric"],
                               vres.traces["metric"], rtol=1e-4)


def test_faulted_traces_chunk_invariant():
    """Fault streams are keyed by absolute round: re-chunking the
    campaign cannot move a single fault or byte."""
    a = _run(FedSim, "dasha", faults=FM_MIXED, chunk=128)
    b = _run(FedSim, "dasha", faults=FM_MIXED, chunk=7)
    for k in INT_TRACES:
        np.testing.assert_array_equal(a.traces[k], b.traces[k],
                                      err_msg=k)
    np.testing.assert_array_equal(a.traces["sim_wall_clock"],
                                  b.traces["sim_wall_clock"])


# ---------------------------------------------------------------------------
# semantics: graceful degradation vs sync retries
# ---------------------------------------------------------------------------

def test_graceful_drop_preserves_server_invariant():
    """g == mean_i(g_local_i) must survive drops AND reset rejoins (the
    reset correction models a reliable out-of-band reboot notice)."""
    res = _run(FedSim, "dasha", faults=FM_MIXED)
    assert res.traces["dropped"].sum() > 0
    assert res.traces["rejoins"].sum() > 0
    np.testing.assert_allclose(
        np.asarray(res.state.g),
        np.asarray(res.state.g_local).mean(0), rtol=2e-5, atol=1e-6)


def test_sync_rules_math_invariant_but_bytes_inflate():
    """MARINA's barrier under faults: identical iterates (retries recover
    every message), strictly more bytes and wall-clock."""
    for variant in ("marina", "sync_mvr"):
        base = _run(FedSim, variant)
        f = _run(FedSim, variant, faults=FM_SYNC)
        np.testing.assert_array_equal(base.traces["metric"],
                                      f.traces["metric"])
        np.testing.assert_array_equal(base.traces["bits_sent"],
                                      f.traces["bits_sent"])
        np.testing.assert_array_equal(np.asarray(base.state.x),
                                      np.asarray(f.state.x))
        assert f.traces["retries"].sum() > 0
        assert f.traces["retry_bytes_up"].sum() > 0
        assert f.summary["bytes_up"] > base.summary["bytes_up"]
        assert f.summary["wall_clock_s"] > base.summary["wall_clock_s"]


def test_deadline_cuts_stragglers():
    """A heavy uplink tail + a tight deadline: late clients are cut, and
    every short-handed round costs exactly the static deadline."""
    sub, rc, hp = _setup("dasha")
    fm = FaultModel(deadline_mult=1.5, seed=0)
    up = LinkModel(straggler=Lognormal(2.0))
    sim = FedSim(variant="dasha", comp=rc, substrate=sub, hyper=hp,
                 uplink=up, faults=fm, seed=3)
    st = sim.init(np.zeros(D, np.float32), jax.random.PRNGKey(0))
    res = sim.run(st, 40)
    assert res.traces["late"].sum() > 0
    dl = float(fm.deadline_s(sim.downlink, up, sim.compute_s, D))
    span = res.traces["sim_wall_clock"] - res.traces["bcast_clock"]
    cut = res.traces["dropped"] > 0
    np.testing.assert_allclose(span[cut], dl, rtol=1e-7)
    # and the vec engine realizes the identical late set
    vsim = VecFedSim(variant="dasha", comp=rc, substrate=sub, hyper=hp,
                     uplink=up, faults=fm, seed=3)
    vst = vsim.init(np.zeros(D, np.float32), jax.random.PRNGKey(0))
    vres = vsim.run(vst, 40)
    np.testing.assert_array_equal(res.traces["late"],
                                  vres.traces["late"])


def test_mass_crash_rounds_stay_finite():
    """Degenerate rounds — everyone offline — must cost a finite
    constant, never NaN/-inf, in both engines."""
    fm = FaultModel(p_crash=0.9, crash_rounds=4, deadline_mult=2.0,
                    seed=2)
    for cls in (FedSim, VecFedSim):
        res = _run(cls, "dasha", faults=fm, rounds=30)
        assert np.isfinite(res.traces["sim_wall_clock"]).all()
        assert np.isfinite(res.traces["metric"]).all()
        assert (np.diff(res.traces["sim_wall_clock"]) > 0).all()
        assert (res.traces["participants"] == 0).any()


def test_corruption_is_counted_as_lost():
    fm = FaultModel(p_corrupt=0.2, deadline_mult=4.0, seed=4)
    res = _run(FedSim, "dasha", faults=fm)
    fc = fm.draw_campaign(40, N)
    assert res.traces["lost"].sum() > 0
    # with only corruption active, lost == the delivered-corrupt set
    assert res.traces["lost"].sum() == fc.corrupt.sum()


# ---------------------------------------------------------------------------
# degenerate-network guards (satellite: net.py)
# ---------------------------------------------------------------------------

def test_link_model_rejects_degenerate_links():
    with pytest.raises(ValueError, match="bandwidth"):
        LinkModel(bandwidth_Bps=0.0)
    with pytest.raises(ValueError, match="bandwidth"):
        LinkModel(bandwidth_Bps=-1.0)
    with pytest.raises(ValueError, match="bandwidth"):
        LinkModel(bandwidth_Bps=float("nan"))
    with pytest.raises(ValueError, match="bandwidth"):
        LinkModel(bandwidth_Bps=float("inf"))
    with pytest.raises(ValueError, match="latency"):
        LinkModel(latency_s=-0.1)
    with pytest.raises(ValueError, match="latency"):
        LinkModel(latency_s=float("nan"))


def test_round_barrier_empty_cohort():
    delays = np.array([1.0, 2.0, 3.0])
    assert round_barrier(delays, np.zeros(3, bool)) == 0.0
    assert round_barrier(delays, np.zeros(3, bool), empty=0.5) == 0.5
    assert round_barrier(delays, np.array([True, False, True])) == 3.0
    assert np.isfinite(round_barrier(np.array([]), np.array([], bool)))


# ---------------------------------------------------------------------------
# the kill-and-restore drill (tentpole acceptance)
# ---------------------------------------------------------------------------

class _Killed(RuntimeError):
    """Simulated process death mid-campaign."""


def _drill(cls, variant, fm, kill_chunk, tmp_path, rounds=40, chunk=8):
    """Run a faulted campaign, kill it after ``kill_chunk`` chunks (the
    checkpoint callback saves the full MethodState + round/clock meta and
    raises), restore FROM DISK, and finish.  The continued traces must be
    bit-identical to an uninterrupted run's tail — same fault stream,
    same bytes, same clocks."""
    sub, rc, hp = _setup(variant)
    path = str(tmp_path / f"ck_{cls.__name__}_{variant}_{kill_chunk}")

    def build():
        sim = cls(variant=variant, comp=rc, substrate=sub, hyper=hp,
                  faults=fm, seed=3, chunk=chunk)
        return sim, sim.init(np.zeros(D, np.float32),
                             jax.random.PRNGKey(0))

    sim, st = build()
    full = sim.run(st, rounds)

    calls = {"n": 0}

    def cp(state, next_round, now):
        ckpt_io.save_state(path, state, step=next_round,
                           extra={"wall_clock": now})
        calls["n"] += 1
        if calls["n"] == kill_chunk + 1:
            raise _Killed

    sim, st = build()
    with pytest.raises(_Killed):
        sim.run(st, rounds, checkpoint=cp)

    # "new process": fresh sim, state restored from disk only
    sim2, like = build()
    meta = ckpt_io.checkpoint_meta(path)
    st2 = ckpt_io.load_state(path, like)
    res = sim2.run(st2, rounds, start_round=int(meta["step"]),
                   clock0=float(meta["extra"]["wall_clock"]))
    cut = int(meta["step"])
    assert 0 < cut < rounds
    for k in full.traces:
        np.testing.assert_array_equal(full.traces[k][cut:],
                                      res.traces[k], err_msg=k)


@pytest.mark.parametrize("cls", [FedSim, VecFedSim])
@pytest.mark.parametrize("kill_chunk", [0, 1, 3])
def test_kill_restore_bit_identical_dasha(cls, kill_chunk, tmp_path):
    _drill(cls, "dasha", FM_MIXED, kill_chunk, tmp_path)


@pytest.mark.parametrize("kill_chunk", [0, 3])
def test_kill_restore_bit_identical_sync_mvr(kill_chunk, tmp_path):
    _drill(FedSim, "sync_mvr", FM_SYNC, kill_chunk, tmp_path)


def test_kill_restore_unfaulted_barrier(tmp_path):
    """The resume machinery is fault-independent: a fault-free barrier
    campaign restores bit-identically too (both engines)."""
    for cls in (FedSim, VecFedSim):
        _drill(cls, "dasha", None, 1, tmp_path)
