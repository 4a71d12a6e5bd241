"""Named scopes in the compiled DASHA step and the run loops' host spans.

The scopes (``dasha.*``, ``driver.*``) edit op metadata only, so a device
trace can split the step by what the program was doing; the host spans
(:func:`repro.obs.span`) reach a running profiler as ``repro.*``
annotations and a live timeline's HOST track, and add no compile."""
import collections
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import recompile
from repro.launch.train import arch_config
from repro.methods.driver import Driver, Sweeper
from repro.models import init_params, lm
from repro.obs import Obs
from repro.obs.timeline import HOST
from repro.optim.distributed import DashaTrainConfig, make_method

SCOPES = ("dasha.server", "dasha.oracle", "dasha.compress",
          "dasha.node_update", "dasha.aggregate", "driver.data",
          "driver.metrics")
_OP_NAME = re.compile(r'^\s*(?:ROOT )?%\S+ = .+? ([a-z][a-z0-9_\-]*)\(.*'
                      r'metadata=\{[^}]*?op_name="(jit\([^"]*)"')
#: instructions that move no data: the tuple plumbing of loops and calls
_PLUMBING = {"constant", "parameter", "get-tuple-element", "tuple",
             "bitcast", "while", "call", "conditional"}
NODES, SEQ = 2, 32


def _innermost(op_name):
    found = [s for s in re.findall(r"(?:dasha|driver)\.[a-z_]+", op_name)
             if s in SCOPES]
    return found[-1] if found else ""


def _lm_driver(variant="dasha"):
    """The trainer's shape at test size: mamba2 smoke widths, 2 nodes, the
    fused node update (interpreted off a TPU), data drawn in the scan."""
    cfg = arch_config("mamba2-780m", False, 1, "bfloat16")
    dasha = DashaTrainConfig(gamma=0.01, compression=0.25, n_nodes=NODES,
                             variant=variant, use_kernel=True)
    method = make_method(dasha, lambda p, b: lm.loss_fn(cfg, p, b)[0])
    state = method.init(init_params(cfg, jax.random.PRNGKey(0)),
                        jax.random.PRNGKey(1), init_mode="zeros")

    def data_fn(k, t):
        toks = jax.random.randint(k, (NODES, 1, SEQ + 1), 0, cfg.vocab_size)
        return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}

    drv = Driver(method, data_fn=data_fn, chunk=2, metrics={
        "g_sq": lambda s, b: sum(jnp.sum(jnp.square(x)) for x in
                                 jax.tree_util.tree_leaves(s.g))})
    return drv, state


def test_compiled_chunk_names_every_scope():
    """Every program scope reaches the compiled chunk's op metadata, and
    under 5% of the instructions that compute are in no program scope."""
    drv, state = _lm_driver()
    carry = (state, jnp.zeros((), jnp.int32),
             {"g_sq": jnp.zeros((), jnp.float32)})
    hlo = drv._chunk_fn(2).lower(carry, jax.random.PRNGKey(2)) \
        .compile().as_text()
    count = collections.Counter()
    for line in hlo.splitlines():
        m = _OP_NAME.match(line)
        if m and m.group(1) not in _PLUMBING:
            count[_innermost(m.group(2))] += 1
    assert set(SCOPES) <= set(count), count
    # the interpreted kernel's body runs under its pallas_call name= (the
    # keyed wrapper: compression 0.25 draws its masks in the kernel)
    assert re.search(r'op_name="[^"]*dasha\.node_update/'
                     r'jit\(dasha_update_keyed\)/dasha_update/while/body/',
                     hlo)
    assert count[""] < 0.05 * sum(count.values()), count


def _tiny_driver():
    def step(s, d):
        return {"x": s["x"] * 0.5 + d}

    return Driver(step, data_fn=lambda k, t: jax.random.normal(k, (4,)),
                  metrics={"x": lambda s, d: jnp.sum(s["x"])}, chunk=2,
                  host_traces=True)


def _host_span_names(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    return collections.Counter(
        e.name for p in pd.planes if p.name.startswith("/host:")
        for line in p.lines for e in line.events
        if e.name.startswith("repro."))


def test_driver_spans_reach_the_profiler_and_the_timeline(tmp_path):
    drv = _tiny_driver()
    state = {"x": jnp.zeros((4,))}
    key = jax.random.PRNGKey(0)
    drv.run(state, 4, data_key=key)                # warm
    hooks = []
    obs = Obs.full()
    jax.profiler.start_trace(str(tmp_path))
    try:
        drv.run(state, 6, data_key=key, obs=obs,
                checkpoint=lambda s, t, tr: hooks.append(t))
    finally:
        jax.profiler.stop_trace()
    assert hooks == [2, 4, 6]
    spans = _host_span_names(tmp_path)
    assert spans["repro.driver.prepare"] == 1
    assert spans["repro.driver.dispatch"] == 3
    assert spans["repro.driver.fetch"] == 3
    assert spans["repro.driver.checkpoint"] == 3
    host = [e for e in obs.timeline.events if e.track == HOST]
    assert [e.name for e in host] == ["driver.prepare"] + [
        "driver.dispatch", "driver.fetch", "driver.checkpoint"] * 3
    assert [e.args for e in host if e.name == "driver.dispatch"] == [
        {"start_round": r, "rounds": 2} for r in (0, 2, 4)]
    assert all(e.t1 >= e.t0 for e in host)
    assert obs.timeline.validate() == []


def test_sweeper_spans_on_the_timeline():
    sw = Sweeper(lambda g: (lambda s, d: {"x": s["x"] * g + d}),
                 data=jnp.ones((3,)), metrics={"x": lambda s, d: s["x"][0]},
                 chunk=2, host_traces=True)
    obs = Obs.full()
    _, tr = sw.run(jnp.array([0.5, 0.25]), {"x": jnp.zeros((3,))}, 4,
                   obs=obs)
    assert tr["x"].shape == (2, 4)
    host = [(e.name, e.args) for e in obs.timeline.events
            if e.track == HOST]
    assert host == [("driver.prepare", None),
                    ("driver.dispatch",
                     {"start_round": 0, "rounds": 2, "lanes": 2}),
                    ("driver.fetch", None),
                    ("driver.dispatch",
                     {"start_round": 2, "rounds": 2, "lanes": 2}),
                    ("driver.fetch", None)]


@pytest.mark.parametrize("profiled", [False, True])
def test_warmed_driver_with_obs_and_annotations_compiles_nothing(
        tmp_path, profiled):
    drv = _tiny_driver()
    state = {"x": jnp.zeros((4,))}
    key = jax.random.PRNGKey(0)
    drv.run(state, 4, data_key=key)
    if profiled:
        jax.profiler.start_trace(str(tmp_path))
    try:
        with recompile.watch("driver_obs") as region:
            drv.run(state, 6, data_key=key, obs=Obs.full(),
                    checkpoint=lambda s, t, tr: None)
    finally:
        if profiled:
            jax.profiler.stop_trace()
    assert region.count == 0
