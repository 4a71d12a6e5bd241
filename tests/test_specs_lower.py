"""Integration: the dry-run path end-to-end in a subprocess (it needs its
own process: 512 placeholder devices are locked in at jax init), plus spec
construction sanity on abstract meshes."""
import os
import subprocess
import sys

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.launch.specs import input_specs, shape_supported
from repro.optim.distributed import DashaTrainConfig

MESH = AbstractMesh((16, 16), ("data", "model"))
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("arch,shape", [
    ("starcoder2-3b", "train_4k"),
    ("mamba2-780m", "long_500k"),
    ("deepseek-v2-lite-16b", "decode_32k"),
    ("gemma3-12b", "prefill_32k"),
    ("whisper-tiny", "decode_32k"),
])
def test_spec_construction(arch, shape):
    """Specs build: abstract args, sharding trees match arg trees."""
    cfg = get_config(arch)
    spec = input_specs(cfg, shape, MESH,
                       dasha=DashaTrainConfig(gamma=0.01, seq_shard=True))
    args_paths = jax.tree_util.tree_structure(spec.args)
    shard_leaves = jax.tree_util.tree_leaves(
        spec.in_shardings, is_leaf=lambda x: isinstance(x, P))
    arg_leaves = jax.tree_util.tree_leaves(spec.args)
    assert len(shard_leaves) == len(arg_leaves)
    for a, s in zip(arg_leaves, shard_leaves):
        assert len(s) <= a.ndim


def test_unsupported_pair_raises():
    cfg = get_config("qwen1.5-110b")
    with pytest.raises(ValueError):
        input_specs(cfg, "long_500k", MESH)


def test_skip_rules():
    skips = {a for a in ("deepseek-v2-lite-16b", "phi3.5-moe-42b-a6.6b",
                         "minitron-8b", "llama-3.2-vision-11b",
                         "qwen1.5-110b", "whisper-tiny")}
    for arch in skips:
        ok, why = shape_supported(get_config(arch), "long_500k")
        assert not ok and why
    for arch in ("mamba2-780m", "zamba2-1.2b", "gemma3-12b",
                 "starcoder2-3b"):
        ok, _ = shape_supported(get_config(arch), "long_500k")
        assert ok


@pytest.mark.slow
def test_dryrun_subprocess_end_to_end():
    """Full lower+compile of one small pair on the 256-dev mesh."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "mamba2-780m", "--shape", "decode_32k"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")),
        cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "1 ok / 0 skip / 0 FAIL" in out.stdout
