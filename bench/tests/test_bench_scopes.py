"""Device time per program scope (``bench/scopes.py``) and the per-layer
metrics that read it: the xplane's event metadata read by the wire format,
the scope of an op found by its instruction's head, hand-computed splits
on a synthetic trace, and nothing read from a program without scopes."""
from types import SimpleNamespace as NS

import pytest

from bench import run as runner
from bench import scopes, trace
from bench.tests.harness import ROOT

DATA = ROOT / "bench" / "tests" / "data" / "small.xplane.pb"
KER = ("%dasha_update.140 = (f32[16,128]{1,0:T(8,128)}) custom-call("
       "f32[16,128]{1,0:T(8,128)} %a), custom_call_target=\"tpu_custom_call\"")
FWD = "%fusion.7 = bf16[8,64]{1,0} fusion(bf16[8,64]{1,0} %p), kind=kLoop"
MASK = "%compare_convert_fusion.3 = f32[4,16]{1,0} fusion(u32[4,16]{1,0} %r)"
LANES = "%copy.9 = f32[16,128]{1,0:T(8,128)} copy(f32[4,512]{1,0} %g)"
AGG = "%reduce.2 = f32[512]{0} reduce(f32[4,512]{1,0} %m, f32[] %z)"
SGD = "%fusion.8 = bf16[512]{0} fusion(bf16[512]{0} %x, f32[512]{0} %g)"
EVAL = "%fusion.9 = f32[] fusion(bf16[8,64]{1,0} %q), kind=kLoop"
LOOP = "%while.3 = (s32[]{:T(128)}, f32[8]{0}) while((s32[], f32[8]) %t)"
#: the instructions' op_name paths, as a compiled module lists them
LISTING = "\n".join([
    'ENTRY %main {',
    '  ' + FWD + ', metadata={op_name="jit(run_chunk)/while/body/'
    'dasha.oracle/transpose(jvp(checkpoint))/dot_general"}',
    '  ' + MASK + ', metadata={op_name="jit(run_chunk)/while/body/'
    'dasha.node_update/dasha.compress/convert_element_type"}',
    '  ' + LANES + ', metadata={op_name="jit(run_chunk)/while/body/'
    'dasha.node_update/jit(dasha_update)/reshape"}',
    '  ROOT ' + KER + ', metadata={op_name="jit(run_chunk)/while/body/'
    'dasha.node_update/jit(dasha_update)/pallas_call"}',
    '  ' + AGG + ', metadata={op_name="jit(run_chunk)/while/body/'
    'dasha.node_update/dasha.aggregate/reduce_sum"}',
    '  ' + SGD + ', metadata={op_name="jit(run_chunk)/while/body/'
    'dasha.server/sub"}',
    '  ' + EVAL + ', metadata={op_name="jit(<lambda>)/reduce_sum"}',
    '}'])


def _ev(name, s, e):
    return NS(name=name, start_ns=s, end_ns=e)


def _profile():
    """One chip, two steps in a 1000 ns window: each step 200 ns of
    oracle, 40 of mask, 60 of lane copies and 100 of kernel, 20 of
    aggregate and 30 of server, then 25 idle; 50 ns of a held-out loss
    under no scope ends the window."""
    ops, t = [_ev(LOOP, 0, 1000)], 0
    for _ in range(2):
        for name, dur in ((FWD, 200), (MASK, 40), (LANES, 60), (KER, 100),
                          (AGG, 20), (SGD, 30)):
            ops.append(_ev(name, t, t + dur))
            t += dur
        t += 25
    ops.append(_ev(EVAL, 950, 1000))
    host = [_ev("bench.window", 0, 1000)]
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)]),
        NS(name="/host:CPU", lines=[NS(name="python", events=host)])])


def _metric(name):
    return runner._load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                               "test_metric_" + name.replace(".", "_"))


NEW = ("oracle_ms.train", "compress_ms.train", "node_update_ms.train",
       "aggregate_server_ms.train", "unscoped_share.train")


def test_xplane_metadata_by_the_wire_format():
    planes = scopes.xplane_metadata(str(DATA))
    assert list(planes) == ["/device:TPU:0"]
    (kernel,) = [v for k, v in planes["/device:TPU:0"].items()
                 if k.startswith("%dasha_update.1 = ")]
    assert kernel["tf_op"] == "jit(dasha_update)/pallas_call:"
    assert kernel["hlo_category"] == "custom-call"
    # a recording of a program without scopes: every op is unscoped
    names = scopes.xplane_op_names(str(DATA))
    assert set(names.values()) == {scopes.UNSCOPED}
    tr = trace.from_profile(_recorded())
    assert {scopes.head(n) for n, _, _ in tr.ops[0]} == set(names)


def _recorded():
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(DATA))


def test_innermost_scope_and_heads():
    assert scopes.innermost("jit(f)/while/body/dasha.node_update/"
                            "dasha.compress/convert") == "dasha.compress"
    assert scopes.innermost("jit(f)/transpose(jvp(dasha.oracle))/dot") \
        == "dasha.oracle"
    assert scopes.innermost("jit(f)/dasha.oracles/dot") == scopes.UNSCOPED
    assert scopes.innermost("") == scopes.UNSCOPED
    assert scopes.head(KER) == ("%dasha_update.140 = (f32[16,128]"
                                "{1,0:T(8,128)}) custom-call")
    assert scopes.head("  ROOT " + FWD) == "%fusion.7 = bf16[8,64]{1,0} fusion"


def test_split_and_metrics_by_hand(monkeypatch):
    tr = trace.from_profile(_profile())
    names = scopes.hlo_op_names([LISTING])
    split = scopes.scope_split(tr, names)
    assert split == pytest.approx({
        "dasha.oracle": 400e-9, "dasha.compress": 80e-9,
        "dasha.node_update": 320e-9, "dasha.aggregate": 40e-9,
        "dasha.server": 60e-9, scopes.UNSCOPED: 50e-9})
    assert tr.idle_share() == pytest.approx(50 / 1000)
    # the scopes, the unscoped time and the idle time make up the window
    assert sum(split.values()) + tr.idle_share() * tr.window_s \
        == pytest.approx(tr.window_s)

    monkeypatch.setattr(scopes, "live_op_names", lambda: names)
    ctx = {"trace": tr, "units": 2}
    got = {m: _metric(m).read(ctx) for m in NEW}
    assert got == pytest.approx({
        "oracle_ms.train": 200e-6, "compress_ms.train": 40e-6,
        "node_update_ms.train": 160e-6,
        "aggregate_server_ms.train": 50e-6,
        "unscoped_share.train": 100 * 50 / 950})


def test_instructions_without_metadata_take_what_they_serve():
    """An instruction the compiler made carries no op_name of its own: a
    fusion takes the one scope its body's instructions share, a copy or a
    buffer fill the one scope its users share."""
    listing = "\n".join([
        '%fused_computation.5 (p: f32[8]) -> f32[8] {',
        '  %neg.1 = f32[8]{0} negate(f32[8]{0} %p), metadata={op_name='
        '"jit(f)/dasha.oracle/vmap(transpose(jvp()))/neg"}',
        '  ROOT %bitcast.2 = f32[8]{0} bitcast(f32[8]{0} %neg.1)',
        '}',
        '%fused_computation.6 (p: f32[8]) -> f32[8] {',
        '  %neg.3 = f32[8]{0} negate(f32[8]{0} %p), metadata={op_name='
        '"jit(f)/dasha.oracle/neg"}',
        '  ROOT %abs.4 = f32[8]{0} abs(f32[8]{0} %neg.3), metadata={op_name='
        '"jit(f)/dasha.server/abs"}',
        '}',
        'ENTRY %main (p: f32[8]) -> f32[8] {',
        '  %broadcast.9 = f32[8]{0} broadcast(f32[] %z), dimensions={}',
        '  %copy-start.10 = (f32[8]{0}, f32[8]{0}) copy-start(%broadcast.9)',
        '  %copy-done.10 = f32[8]{0} copy-done(%copy-start.10)',
        '  %fusion.11 = f32[8]{0} fusion(%p, %copy-done.10), kind=kLoop, '
        'calls=%fused_computation.5',
        '  %fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
        'calls=%fused_computation.6',
        '  ROOT %copy.13 = f32[8]{0} copy(f32[8]{0} %fusion.12)',
        '}'])
    names = scopes.hlo_op_names([listing])
    assert names["%fusion.11 = f32[8]{0} fusion"] == "dasha.oracle"
    for key in ("%broadcast.9 = f32[8]{0} broadcast",
                "%copy-start.10 = (f32[8]{0}, f32[8]{0}) copy-start",
                "%copy-done.10 = f32[8]{0} copy-done"):
        assert names[key] == "dasha.oracle", key
    # a body of two scopes decides nothing, nor does a copy nothing uses
    assert names["%fusion.12 = f32[8]{0} fusion"] == scopes.UNSCOPED
    assert names["%copy.13 = f32[8]{0} copy"] == scopes.UNSCOPED


def test_ambiguous_heads_count_as_unscoped():
    other = LISTING.replace("dasha.oracle", "dasha.server")
    names = scopes.hlo_op_names([LISTING, other])
    assert names[scopes.head(FWD)] is scopes.AMBIGUOUS
    split = scopes.scope_split(trace.from_profile(_profile()), names)
    assert "dasha.oracle" not in split
    assert split[scopes.UNSCOPED] == pytest.approx(450e-9)


def test_metrics_read_nothing_without_scopes(monkeypatch):
    """A program without named scopes (the listing's paths stripped), and
    a trace with no device ops (the CPU), read nothing and raise
    nothing."""
    bare = "\n".join(line.split(", metadata=")[0]
                     for line in LISTING.splitlines())
    monkeypatch.setattr(scopes, "live_op_names",
                        lambda: scopes.hlo_op_names([bare]))
    for tr in (trace.from_profile(_profile()),
               trace.Trace({}, [("bench.window", 0, 10)])):
        ctx = {"trace": tr, "units": 2}
        assert {m: _metric(m).read(ctx) for m in NEW} == dict.fromkeys(NEW)


def test_live_op_names_reads_this_process_executables():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("dasha.server"):
            y = jnp.sin(x) * 3.0
        with jax.named_scope("driver.metrics"):
            return jnp.sum(y * y)

    f(jnp.ones((64,))).block_until_ready()
    got = set(scopes.live_op_names().values())
    assert {"dasha.server", "driver.metrics"} <= got
