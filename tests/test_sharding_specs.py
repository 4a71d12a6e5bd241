"""Sharding policy: every PartitionSpec divides its dim, for every arch on
a 16x16 and a 2x16x16 mesh (validated with AbstractMesh — no devices needed)."""
import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import all_arch_ids, get_config
from repro.models import init_params, lm
from repro.models.sharding import cache_specs, dp_axes, dp_size, param_specs

MESHES = {
    "single_pod": AbstractMesh((16, 16), ("data", "model")),
    "multi_pod": AbstractMesh((2, 16, 16), ("pod", "data", "model")),
}


def _axis_size(mesh, axes):
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    return int(np.prod([mesh.shape[a] for a in axes]))


def _check_divisible(tree, specs, mesh, where):
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    spec_leaves = jax.tree_util.tree_leaves_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == len(spec_leaves)
    for (path, leaf), (_, spec) in zip(leaves, spec_leaves):
        assert len(spec) <= leaf.ndim, (where, path, spec, leaf.shape)
        for dim, axes in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            size = _axis_size(mesh, axes)
            assert dim % size == 0, (where, path, leaf.shape, spec)


@pytest.mark.parametrize("mesh_name", list(MESHES))
@pytest.mark.parametrize("arch", all_arch_ids())
def test_param_specs_divisible(arch, mesh_name):
    cfg = get_config(arch)
    mesh = MESHES[mesh_name]
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    specs = param_specs(cfg, params, mesh)
    _check_divisible(params, specs, mesh, f"{arch}/{mesh_name}")


@pytest.mark.parametrize("arch", all_arch_ids())
def test_cache_specs_divisible(arch):
    cfg = get_config(arch)
    mesh = MESHES["single_pod"]
    for batch, seq in [(128, 32768), (1, 524288)]:
        def mk():
            image_kv = enc_kv = None
            if cfg.arch_type == "vlm":
                G, hd = cfg.num_kv_heads, cfg.head_dim
                n_cross = cfg.num_layers // cfg.cross_attn_every
                import jax.numpy as jnp
                z = jnp.zeros((n_cross, batch, cfg.num_image_tokens, G, hd),
                              cfg.jax_dtype)
                image_kv = {"k": z, "v": z}
            if cfg.arch_type == "audio":
                import jax.numpy as jnp
                G, hd = cfg.num_kv_heads, cfg.head_dim
                z = jnp.zeros((cfg.num_layers, batch, cfg.num_audio_frames,
                               G, hd), cfg.jax_dtype)
                enc_kv = {"k": z, "v": z}
            return lm.init_cache(cfg, batch, seq, image_kv=image_kv,
                                 enc_kv=enc_kv)

        cache = jax.eval_shape(mk)
        specs = cache_specs(cfg, cache, mesh, batch)
        _check_divisible(cache, specs, mesh, f"{arch}/b{batch}")


def test_big_matrices_not_replicated():
    """On the 16x16 mesh, every >=32 MB (bf16) parameter matrix must carry at
    least one sharded dim — replication there means an OOM-scale waste."""
    mesh = MESHES["single_pod"]
    for arch in all_arch_ids():
        cfg = get_config(arch)
        params = jax.eval_shape(
            lambda c=cfg: init_params(c, jax.random.PRNGKey(0)))
        specs = param_specs(cfg, params, mesh)
        flat_p = jax.tree_util.tree_leaves_with_path(params)
        flat_s = jax.tree_util.tree_leaves_with_path(
            specs, is_leaf=lambda x: isinstance(x, P))
        for (path, leaf), (_, spec) in zip(flat_p, flat_s):
            if leaf.size * 2 < 32e6:
                continue
            assert any(a is not None for a in spec), \
                (arch, path, leaf.shape, "replicated big matrix")


def test_dp_axes_and_sizes():
    assert dp_axes(MESHES["single_pod"]) == ("data",)
    assert dp_axes(MESHES["multi_pod"]) == ("pod", "data")
    assert dp_size(MESHES["single_pod"]) == 16
    assert dp_size(MESHES["multi_pod"]) == 32
