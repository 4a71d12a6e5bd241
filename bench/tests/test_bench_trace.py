"""The reduction from a profiler trace to busy time, idle share and
kernel time: on hand-made events with known answers, and on a small
trace recorded on a TPU v5e chip (one fused ``dasha_update`` kernel call
and one small matmul program, twice)."""
from types import SimpleNamespace as NS

import pytest

from bench import trace
from bench.tests.harness import ROOT

AR = "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %p), to_apply=%add"
FUS = "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
KER = ("%dasha_update.140 = (f32[16,128]{1,0:T(8,128)}) custom-call("
       "f32[16,128]{1,0:T(8,128)} %a), custom_call_target=\"tpu_custom_call\"")
LOOP = "%while.3 = (s32[]{:T(128)}, f32[8]{0}) while((s32[], f32[8]) %t)"


def _ev(name, s, e):
    return NS(name=name, start_ns=s, end_ns=e)


def _profile():
    """Two chips and a host; a ``while`` spans everything on chip 0 and is
    no work."""
    chip0 = [_ev(LOOP, 0, 200), _ev(AR, 0, 100), _ev(FUS, 50, 80)]
    chip1 = [_ev(AR, 10, 30), _ev(KER, 0, 40)]
    host = [_ev("bench.window", 0, 200), _ev("bench.chunk", 100, 190),
            _ev("bench.log_hook", 120, 180), _ev("PjitFunction(f)", 1, 2)]

    def dev(i, ops):
        return NS(name=f"/device:TPU:{i}",
                  lines=[NS(name="XLA Ops", events=ops)])
    return NS(planes=[dev(0, chip0), dev(1, chip1),
                      NS(name="/host:CPU",
                         lines=[NS(name="python", events=host)])])


def test_interval_algebra():
    assert trace.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [(0, 3), (5, 10)]
    assert trace.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22)]) == \
        [(0, 2), (4, 8), (22, 30)]
    assert trace.clip([(0, 5), (8, 12), (20, 30)], 3, 10) == [(3, 5), (8, 10)]


def test_opcode_and_names():
    assert trace.opcode(AR) == "all-reduce"
    assert trace.opcode(KER) == "custom-call"
    assert trace.short_name(KER) == "dasha_update"
    assert trace.is_container(LOOP) and not trace.is_container(FUS)
    assert trace.short_name(AR) == "all-reduce"


def test_busy_idle_and_exposed_collectives_by_hand():
    tr = trace.from_profile(_profile())
    assert tr.window == (0, 200) and tr.chips == [0, 1]
    # chip 0 busy [0, 100], chip 1 busy [0, 40]: mean 70 ns of 200
    assert tr.busy_s() == pytest.approx(70e-9)
    assert tr.idle_share() == pytest.approx(1 - 70 / 200)
    assert tr.op_seconds(lambda n: trace.short_name(n) == "dasha_update") \
        == pytest.approx(40e-9)


def test_idle_gaps_are_named_by_the_innermost_span():
    # chip 0 is idle over [100, 200] ns; its midpoint lies in the chunk
    # span and, innermost, in the log hook's
    gaps = trace.from_profile(_profile()).idle_gaps(3)
    assert len(gaps) == 1
    assert gaps[0][0] == "bench.log_hook @0.000ms"
    assert gaps[0][1] == pytest.approx(100e-9)


def test_recorded_v5e_trace():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(ROOT / "bench" / "tests" / "data"
                                   / "small.xplane.pb"))
    tr = trace.from_profile(pd)
    assert tr.chips == [0]
    # no window span in this recording: the ops' own extent, in ns
    assert tr.window == (44540655, 52017860)
    # two kernel runs of 11276 and 11315 ns
    kernel = tr.op_seconds(lambda n: trace.short_name(n) == "dasha_update")
    assert kernel == pytest.approx((11276 + 11315) * 1e-9)
    # kernels, prefetch copies and two reduce fusions: 40775 ns busy
    assert tr.busy_s() == pytest.approx(40775e-9)
    assert tr.top_ops(1)[0][0] == "dasha_update.1 custom-call"
    assert {n for n, _, _ in tr.spans} == {"bench.chunk", "bench.log_hook"}
