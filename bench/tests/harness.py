"""Runs the benchmark harness on the CPU at test sizes.

A test copies ``bench/`` into a scratch root, writes small configurations,
traffic mixes and a ``BENCHMARK.json`` there, and calls ``run.run`` with
the chip look replaced by the CPU's devices.  Everything else is the run
as the chip sees it: set-up, window, reference and comparison.
"""
from __future__ import annotations

import copy
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: mamba2 at the program's smoke sizes (2 layers, d_model 128)
TRAIN_CONFIG = {
    "kind": "train", "arch": "mamba2-780m", "published": False,
    "d_model": 128, "n_layer": 2, "vocab_size": 512, "d_state": 16,
    "headdim": 32, "expand": 2, "d_conv": 4, "chunk_size": 32,
    "ngroups": 1, "tie_embeddings": True, "rms_norm": True,
    "norm_eps": 1e-5, "dtype": "bfloat16", "nodes": 4, "chips": 1,
    "server_opt": "sgd", "gamma": 0.003, "use_kernel": True,
    "state_dtype": "float32", "reference_matmul": "float32",
    "control_matmul": "float8_e4m3fn"}

TRAIN_TRAFFIC = {"variant": "dasha", "mode": "independent",
                 "compression": 0.03125, "batch_per_node": 1, "seq": 64,
                 "chunk": 3, "copy_period": 16, "noise": 0.1}

#: the chip cell whose limits the test cells are held to
CHIP_CELL = "train.mamba2.dasha"


def make_root(tmp: Path, cells, extra_per_layer=()) -> Path:
    """A scratch checkout holding a copy of ``bench/`` and a
    ``BENCHMARK.json`` with the given ``(name, config, traffic)`` cells,
    each held to the limits of :data:`CHIP_CELL`."""
    root = tmp / "root"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (root / "bench" / "peaks.json").write_text(json.dumps(peaks))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"], bench["workloads"] = [], []
    for name, config, traffic in cells:
        chip_cell = CHIP_CELL
        cfg_file = f"bench/configs/{name}.json"
        (root / cfg_file).write_text(json.dumps(config))
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(traffic))
        limits = root / "bench" / "limits"
        shutil.copy(limits / f"{chip_cell}.json", limits / f"{name}.json")
        bench["configs"].append({"name": name, "source": "test",
                                 "file": cfg_file, "reduced": [],
                                 "why": "test"})
        bench["workloads"].append({"name": name, "config": name,
                                   "traffic": name, "chips": 1,
                                   "why": "test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "workloads" in m and chip_cell in m["workloads"]:
                m["workloads"].append(name)
    bench["per_layer"] += list(extra_per_layer)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def cpu_devices(chips):
    import jax
    return jax.devices("cpu")[:chips]


def run_cell(root: Path, name: str, seed: int = 2 ** 33 + 5,
             trace: int = 0) -> dict:
    sys.path[:0] = []
    from bench import run as runner
    return runner.run(["--workload", name, "--seed", str(seed),
                       "--seconds", "0.01", "--trace", str(trace)],
                      root=root, devices_fn=cpu_devices,
                      log=lambda s: None)


def config() -> tuple:
    """The test-size configuration and traffic mix."""
    return copy.deepcopy(TRAIN_CONFIG), copy.deepcopy(TRAIN_TRAFFIC)
