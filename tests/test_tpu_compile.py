"""Compile rehearsal: the main path's Pallas kernels, at real widths, for a
TPU v5e chip that is described but not attached.

Nothing runs, so this says nothing about results or times; it catches what
the interpret-mode tests cannot — blocks the chip's tiling refuses, more
VMEM than a kernel may use, an (n, d) store that would have to fit in VMEM.
The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dasha_update import (LANE, dasha_mvr_update_keyed_pallas,
                                        dasha_mvr_update_pallas,
                                        dasha_update_keyed_pallas,
                                        dasha_update_pallas, quantize_pallas)
from repro.kernels.slab_writeback import slab_writeback_pallas

#: the fused node update at real width: 2M f32 elements, 16 default blocks
ROWS = 16384
#: the sampled federated campaign's store (n clients x d) and one
#: 200-round chunk of a C=64 cohort (U = 200 * 64 touched rows)
STORE_N, STORE_D, SLAB_U = 100_000, 64, 12_800


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache here: keep them out of it
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, shapes, sharding, donate=()):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_dasha_update_compiles_for_v5e(one_chip):
    f32 = jnp.float32
    _compile(lambda g, h, gl, m: dasha_update_pallas(
        g, h, gl, m, 0.2, 32.0, interpret=False),
        [((ROWS, LANE), f32)] * 4, one_chip)


def test_dasha_mvr_update_compiles_for_v5e(one_chip):
    f32 = jnp.float32
    _compile(lambda gn, go, h, gl, m: dasha_mvr_update_pallas(
        gn, go, h, gl, m, 0.2, 0.1, 32.0, interpret=False),
        [((ROWS, LANE), f32)] * 5, one_chip)


def test_dasha_update_keyed_compiles_for_v5e(one_chip):
    """The keyed kernel: the key's words in SMEM, the mask hashed strip by
    strip in the kernel."""
    f32 = jnp.float32
    _compile(lambda g, h, gl, k: dasha_update_keyed_pallas(
        g, h, gl, k, 0.2, 32.0, 8, interpret=False),
        [((ROWS, LANE), f32)] * 3 + [((2,), jnp.uint32)], one_chip)


def test_dasha_mvr_update_keyed_compiles_for_v5e(one_chip):
    f32 = jnp.float32
    _compile(lambda gn, go, h, gl, k: dasha_mvr_update_keyed_pallas(
        gn, go, h, gl, k, 0.2, 0.1, 32.0, 8, interpret=False),
        [((ROWS, LANE), f32)] * 4 + [((2,), jnp.uint32)], one_chip)


@pytest.mark.parametrize("shape", [(4, 50432, 1536), (4, 4, 1536, 48, 64),
                                   (9, 16500)], ids=["embed", "w_z", "lanes"])
@pytest.mark.parametrize("variant", ["dasha", "mvr"])
def test_keyed_update_reads_bf16_gradients_with_no_convert(
        one_chip, monkeypatch, variant, shape):
    """Keyed DASHA and MVR through the ops layer, for a v5e, with bf16
    gradients beside the f32 state: mamba2's embed (a (201728, 1536)
    view), ``w_z`` (a 64-wide view) and a leaf on the lane path.  Each
    gradient reaches the kernel as a bf16 operand, and no convert runs
    outside it: no f32 copy of a gradient is written to HBM."""
    import re

    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    bf16, f32 = jnp.bfloat16, jnp.float32
    if variant == "mvr":
        def update(gn, go, h, gl, k):
            return ops.dasha_mvr_update_keyed(gn, go, h, gl, k, 0.2, 0.1,
                                              32.0, 8)
        n_grads = 2
    else:
        def update(g, h, gl, k):
            return ops.dasha_update_keyed(g, h, gl, k, 0.2, 32.0, 8)
        n_grads = 1
    hlo = _compile(update, [(shape, bf16)] * n_grads + [(shape, f32)] * 2
                   + [((2,), jnp.uint32)], one_chip).as_text()
    assert not re.search(r"\bconvert\(", hlo)
    calls = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1
    operands = calls[0].split("operand_layout_constraints={")[1]
    operands = operands[:operands.index("}}") + 1]
    assert operands.count("bf16[") == n_grads, operands


def test_quantize_compiles_for_v5e(one_chip):
    f32 = jnp.float32
    _compile(lambda x, u: quantize_pallas(x, u, 15, interpret=False),
             [((ROWS, LANE), f32)] * 2, one_chip)


@pytest.mark.parametrize("accumulate", [False, True])
def test_slab_writeback_compiles_for_v5e(one_chip, accumulate):
    """The (n, d) store stays where it is in HBM: the output is the donated
    store itself, the program's temporaries hold no store-sized buffer, and
    no copy relayouts the store or moves it into VMEM (``S(1)``)."""
    compiled = _compile(
        lambda full, idx, rows: slab_writeback_pallas(
            full, idx, rows, accumulate=accumulate, interpret=False),
        [((STORE_N, STORE_D), jnp.float32), ((SLAB_U,), jnp.int32),
         ((SLAB_U, STORE_D), jnp.float32)], one_chip, donate=(0,))
    mem = compiled.memory_analysis()
    store_bytes = STORE_N * STORE_D * 4
    assert mem.alias_size_in_bytes >= store_bytes
    assert mem.temp_size_in_bytes < store_bytes
    hlo = compiled.as_text()
    assert " copy(" not in hlo and "S(1)" not in hlo


@pytest.mark.parametrize("variant,kernel", [("dasha", "dasha_update"),
                                            ("mvr", "dasha_mvr_update")])
def test_node_update_kernel_is_named_in_its_scope(one_chip, monkeypatch,
                                                  variant, kernel):
    """The compiled DASHA step calls the fused kernel under its pallas_call
    name, inside the engine's ``dasha.node_update`` scope (the device
    trace's per-layer split reads both)."""
    import re

    from repro.kernels import ops
    from repro.optim.distributed import DashaTrainConfig, make_method
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    f32 = jnp.float32

    def loss(p, b):
        return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)

    cfg = DashaTrainConfig(gamma=0.01, compression=0.25, n_nodes=2,
                           variant=variant, use_kernel=True)
    method = make_method(cfg, loss)
    params = {"w": jax.ShapeDtypeStruct((256, LANE), f32)}
    state = jax.eval_shape(lambda p, k: method.init(p, k, init_mode="zeros"),
                           params, jax.ShapeDtypeStruct((2,), jnp.uint32))
    batch = {"x": jax.ShapeDtypeStruct((2, 8, 256), f32),
             "y": jax.ShapeDtypeStruct((2, 8, LANE), f32)}

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    hlo = jax.jit(method.step).lower(placed(state), placed(batch)) \
        .compile().as_text()
    calls = [line for line in hlo.splitlines()
             if "tpu_custom_call" in line]
    assert calls
    for line in calls:
        assert re.match(rf"\s*(ROOT )?%{kernel}(\.\d+)? = ", line), line[:80]
        assert re.search(r'op_name="[^"]*/dasha\.node_update/', line)


def test_kernel_head_matches_the_recorded_v5e_trace(one_chip, monkeypatch):
    """The recorded v5e trace's kernel event and the kernel's instruction
    in a v5e compile of the same call have one head: the key by which the
    benchmark's trace reader (``bench/scopes.py``) finds a traced op's
    scope in the compiled listing."""
    from jax.profiler import ProfileData

    from bench import scopes, trace
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    args = [jax.ShapeDtypeStruct((2048, LANE), jnp.float32,
                                 sharding=one_chip)] * 4
    listing = jax.jit(lambda g, h, gl, m: ops.dasha_update(
        g, h, gl, m, 0.2, 32.0)).lower(*args).compile().as_text()
    names = scopes.hlo_op_names([listing])
    recorded = os.path.join(os.path.dirname(__file__), os.pardir, "bench",
                            "tests", "data", "small.xplane.pb")
    tr = trace.from_profile(ProfileData.from_file(recorded))
    kernel = {scopes.head(n) for n, _, _ in tr.ops[0]
              if trace.short_name(n) == "dasha_update"}
    assert len(kernel) == 1 and kernel <= set(names)


def test_nemotron_chunk_compiles_for_v5e_and_fits(one_chip, monkeypatch):
    """The `train.nemotron3.dasha.s8k` cell's compiled 5-step chunk (one
    node, 8192 tokens a step, the keyed fused update) at the chip share's
    real sizes: it compiles for v5e, and the compiler's peak for it (state,
    gradients and activations) stays under 15 GiB of the chip's 16."""
    from repro.kernels import ops
    from repro.launch.train import arch_config
    from repro.methods.driver import Driver, _metric_zeros
    from repro.models import init_params, lm
    from repro.optim.distributed import DashaTrainConfig, make_method
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = arch_config("nemotron-3-nano-30b-a3b", True, 7, None, 8, 16384)
    method = make_method(
        DashaTrainConfig(gamma=0.003, compression=1 / 32, n_nodes=1,
                         use_kernel=True),
        lambda p, b: lm.loss_fn(cfg, p, b)[0])
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    state = jax.eval_shape(
        lambda k: method.init(init_params(cfg, k), k, init_mode="zeros"),
        key)

    def data_fn(k, t):
        toks = jax.random.randint(k, (1, 1, 8193), 1, cfg.vocab_size)
        return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}

    metrics = {"g_sq": lambda s, b: sum(
        jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(s.g))}
    drv = Driver(method, data_fn=data_fn, metrics=metrics, chunk=5,
                 donate=True)
    carry = (state, jax.ShapeDtypeStruct((), jnp.int32), jax.eval_shape(
        lambda: _metric_zeros(metrics, state,
                              jax.eval_shape(data_fn, key, 0))))
    placed = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        (carry, key))
    compiled = drv._chunk_fn(5).lower(*placed).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().peak_memory_in_bytes < 15 * 2 ** 30


#: instructions that move no data: the kernels' operands and results may
#: pass through these alone
_FREE_OPS = {"parameter", "bitcast", "custom-call", "tuple",
             "get-tuple-element", "constant"}


@pytest.mark.parametrize("variant,mode", [("dasha", "independent"),
                                          ("mvr", "permk")])
def test_fused_tree_update_streams_mamba2_leaves_in_own_layout(
        one_chip, monkeypatch, variant, mode):
    """mamba2-780m's leaves at published widths (4 layers, 4 nodes) through
    ``fused_tree_update`` for a v5e, keyed DASHA (6 streams) and
    explicit-mask MVR (8): every leaf's blocks fit the kernel's VMEM, and
    each leaf whose width is a multiple of 128 and whose second-to-last
    dim is a multiple of 8 (embed, w_xbc, w_out: 99.9% of the elements
    that are 128 wide) reaches its kernel and comes back by bitcasts
    alone — no copy, reshape, transpose or fusion in its shape, its
    (rows, cols) view or (R, 128) lane rows, outside the mask draw.  The
    small leaves with 4 rows a node keep (4, 128) tiles in HBM, which
    the kernel's (8, 128) blocks relayout."""
    import math
    import re

    from repro.compress.treelevel import fused_tree_update
    from repro.kernels import ops
    from repro.launch.train import arch_config
    from repro.models import init_params
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = arch_config("mamba2-780m", True, 4)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    tree = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((4,) + s.shape, jnp.float32,
                                       sharding=one_chip), shapes)
    n_in = 4 if variant == "mvr" else 3
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)

    def update(key, *trees):
        kw = dict(grads_old=trees[1]) if variant == "mvr" else {}
        m, h_new, g_new = fused_tree_update(
            key, trees[0], *trees[-2:], mode=mode, a=0.2, p=1 / 32, n=4,
            variant=variant, b=0.1, **kw)
        return h_new, g_new, m

    # h and g die with the update, as a step's state does: the kernels
    # write h_new and g_new over them
    hlo = jax.jit(update, donate_argnums=(n_in - 1, n_in)).lower(
        key, *[tree] * n_in).compile().as_text()
    wide = [x.shape for x in jax.tree_util.tree_leaves(tree)
            if x.shape[-1] % 128 == 0 and x.shape[-2] % 8 == 0]
    assert len(wide) == 3
    ours = set()
    for shape in wide:
        size = math.prod(shape)
        ours |= {shape, ops.node_update_view(shape), (-(-size // 128), 128),
                 (size,)}
    entry = hlo[hlo.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    moved = []
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(",
                     line)
        if m is None or m.group(2) in _FREE_OPS \
                or "dasha.compress" in line:
            continue
        dims = tuple(int(d) for d in m.group(1).split(",") if d)
        if dims in ours:
            moved.append(line.strip()[:120])
    assert not moved, moved
    assert hlo.count("tpu_custom_call") >= 13
