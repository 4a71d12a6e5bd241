"""The harness end to end on the CPU: it refuses to run off a TPU, finds a
new cell, configuration and per-layer metric from new files alone, and
holds the control -- the reference in the precision below the
configuration's, in the program's place -- to the chip cells' limits, which
it fails."""
import hashlib
import json

import numpy as np
import pytest

from bench import check, run as runner
from bench.tests import harness


def test_no_tpu_means_no_result(capsys):
    code = runner.main(["--workload", "train.mamba2.dasha", "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert code == 2
    assert out.out == ""
    assert "no TPU" in out.err


def test_device_kind_missing_from_peaks_is_refused():
    with pytest.raises(runner.NoChip, match="not in bench/peaks.json"):
        runner.peaks_of(harness.ROOT, "TPU v9 imaginary")


def _digest(root):
    return {p.relative_to(root).as_posix(): hashlib.sha256(
        p.read_bytes()).hexdigest()
        for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def test_new_cell_config_and_metric_from_new_files_alone(tmp_path):
    """A later PR adds a configuration file, a traffic file and a metric
    reader, and entries in BENCHMARK.json; the harness runs the new cell
    and reports the new metric with no file of it edited."""
    metric = {"name": "probe.tokens_per_step", "unit": "tokens",
              "better": "higher", "source": "program_counter",
              "layer": "device", "moves": "tokens_per_s",
              "workloads": ["new.train"]}
    cfg, traffic = harness.config()
    root = harness.make_root(tmp_path, [("new.train", cfg, traffic)],
                             extra_per_layer=[metric])
    before = _digest(root)
    (root / "bench" / "metrics" / "probe.tokens_per_step.py").write_text(
        "def read(ctx):\n"
        "    t = ctx['traffic']\n"
        "    return float(ctx['config']['nodes'] * t['seq'])\n")
    res = harness.run_cell(root, "new.train", trace=1)
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert res["metrics"]["probe.tokens_per_step"] == {
        "value": float(cfg["nodes"] * traffic["seq"]), "unit": "tokens"}
    assert res["attempted"] > 0 and isinstance(res["correct"], bool)
    assert list(res)[-1] == "checks"


def _control_and_sound(seed=123):
    from bench.kinds import train
    cfg, traffic = harness.config()
    cell = train.Cell(cfg, traffic, seed, 1)
    cell.warm()
    prog = cell.readings
    cell.release()
    refs = {}
    ref = cell.reference(cache=refs)
    ctl = cell.reference(cache=refs, precision=cfg["control_matmul"])
    return cell.compare(ctl, ref), cell.compare(prog, ref)


def test_training_control_fails_the_limits():
    """At test size, the control (fp8 matmuls) reads ``correct: false``
    against the chip cell's limits, and the program does not."""
    ctl, sound = _control_and_sound()
    limits = check.load_limits(harness.ROOT, harness.CHIP_CELL)
    correct, rows = check.verdict(ctl, limits)
    assert not correct, rows
    assert any(r["value"] > r["limit"] for r in rows)
    assert check.verdict(sound, limits)[0], (sound, limits)


def test_training_control_reads_above_the_program():
    """The control reads a wider gap of directions than the bf16 program on
    the same seed."""
    ctl, sound = _control_and_sound()
    assert ctl["g_rms"] > 3 * sound["g_rms"], (ctl, sound)


def test_limits_sit_between_the_readings():
    """Each limit lies above the largest sound reading and below the
    smallest control or fault reading it is held against."""
    for path in (harness.ROOT / "bench" / "limits").glob("*.json"):
        doc = json.loads(path.read_text())
        for name, limit in doc["limits"].items():
            lo = doc["readings"][name]["lower"]
            hi = doc["readings"][name].get("upper")
            assert lo < limit, (path.name, name)
            if hi is not None:
                assert limit < hi, (path.name, name)
            assert np.isfinite(limit)
