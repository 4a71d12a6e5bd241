"""The training launcher (`repro.launch.train`): the depth cut, the node
axis spread over several devices against the same nodes vmapped on one,
and the sharding rule of the trainer state on that mesh.

The multi-device run needs a process whose CPU backend has four devices
(``--xla_force_host_platform_device_count``), set before JAX starts, so it
runs in a subprocess.
"""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.configs import all_arch_ids, get_config, get_smoke_config
from repro.launch import train

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: both runs in one child process: params come back as JSON lists
_MESH_VS_ONE = """
import json, sys
import jax, numpy as np
from repro.launch import train
argv = sys.argv[1:]
mesh_run = train.main(argv)
one_run = train.main(argv + ["--devices", "1"])
out = {}
for name, run in (("mesh", mesh_run), ("one", one_run)):
    out[name] = {
        "devices": 1 if run.mesh is None else int(run.mesh.devices.size),
        "log": run.log,
        "x": [np.asarray(x, np.float32).ravel().tolist()
              for x in jax.tree_util.tree_leaves(run.state.x)],
        "h_sharding": sorted({str(x.sharding.spec) for x in
                              jax.tree_util.tree_leaves(run.state.h_local)
                              if hasattr(x.sharding, "spec")}),
    }
print("RESULT " + json.dumps(out))
"""


def test_layers_replaces_only_num_layers():
    args = train.parse_args(["--arch", "mamba2-780m", "--full",
                             "--layers", "8"])
    cut = train.arch_config(args.arch, args.full, args.layers)
    full = get_config("mamba2-780m")
    assert cut.num_layers == 8 and full.num_layers == 48
    assert dataclasses.replace(cut, num_layers=full.num_layers) == full
    # without --layers (and on the smoke config) nothing is replaced
    assert train.arch_config("mamba2-780m", True) == full
    assert train.arch_config("mamba2-780m", False) \
        == get_smoke_config("mamba2-780m")


@pytest.mark.parametrize("extra", [["--variant", "dasha"],
                                   ["--variant", "mvr", "--use-kernel"]],
                         ids=["dasha", "mvr-kernel"])
def test_node_mesh_matches_one_device_vmap(extra):
    """4 nodes on a ("data", "model") = (4, 1) mesh of 4 CPU devices give
    the same losses and params as the 4 nodes vmapped on one device, with
    the fused kernel too (it runs on each device's shard)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    argv = ["--arch", "mamba2-780m", "--nodes", "4", "--batch", "1",
            "--seq", "32", "--steps", "4", "--log-every", "2",
            "--server-opt", "sgd", "--dtype", "float32", *extra]
    proc = subprocess.run([sys.executable, "-c", _MESH_VS_ONE, *argv],
                          env=env, cwd=REPO, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    res = json.loads(line[len("RESULT "):])
    mesh, one = res["mesh"], res["one"]
    assert mesh["devices"] == 4 and one["devices"] == 1
    # each node's h_i lives on its own device: node axis on "data"
    assert mesh["h_sharding"] and all(s.startswith("PartitionSpec(('data',)")
                                      or s.startswith("PartitionSpec('data'")
                                      for s in mesh["h_sharding"])
    assert [r["step"] for r in mesh["log"]] == [2, 4]
    for a, b in zip(mesh["log"], one["log"], strict=True):
        assert np.isfinite(a["loss"])
        np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-5)
        np.testing.assert_allclose(a["g_norm_sq"], b["g_norm_sq"],
                                   rtol=1e-4)
    for a, b in zip(mesh["x"], one["x"], strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("server_opt", ["sgd", "adam"])
@pytest.mark.parametrize("arch", all_arch_ids() + ["nemotron-3-nano-30b-a3b"])
def test_node_state_specs_cover_the_state(arch, server_opt):
    """The launcher's sharding rule on a (4, 1) ("data", "model") mesh
    gives every leaf of the published config's trainer state a spec: the
    spec tree is the state's tree, each node's h_i / g_i puts its node
    axis on the data axis, and no spec names more axes than its leaf has."""
    import jax
    from jax.sharding import AbstractMesh, PartitionSpec as P

    from repro.models import init_params
    from repro.optim.distributed import DashaTrainConfig, make_method

    cfg = get_config(arch)
    mesh = AbstractMesh((4, 1), ("data", "model"))
    dasha = DashaTrainConfig(gamma=0.01, n_nodes=4, server_opt=server_opt,
                             spmd_axes=("data",))
    method = make_method(dasha, lambda p, b: 0.0)
    params_s = jax.eval_shape(lambda k: init_params(cfg, k),
                              jax.random.PRNGKey(0))
    state_s = jax.eval_shape(
        lambda p, k: method.init(p, k, init_mode="zeros"), params_s,
        jax.random.PRNGKey(1))
    specs = train.node_state_specs(cfg, mesh, dasha, state_s)

    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    assert jax.tree_util.tree_structure(specs, is_leaf=is_spec) \
        == jax.tree_util.tree_structure(state_s)
    for field in ("h_local", "g_local"):
        node_specs = jax.tree_util.tree_leaves(getattr(specs, field),
                                               is_leaf=is_spec)
        assert node_specs and all(s[0] in ("data", ("data",))
                                  for s in node_specs)
    for spec, leaf in zip(
            jax.tree_util.tree_leaves(specs, is_leaf=is_spec),
            jax.tree_util.tree_leaves(state_s), strict=True):
        assert len(spec) <= leaf.ndim, (spec, leaf.shape)
