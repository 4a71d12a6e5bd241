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

from repro.kernels.dasha_update import (LANE, dasha_mvr_update_pallas,
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
