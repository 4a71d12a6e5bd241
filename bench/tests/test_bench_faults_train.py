"""A training run with its timed path broken underneath reads
``correct: false`` (CPU, test size, the chip cell's limits)."""
import pytest

from bench.tests import faults, harness


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_broken_training_is_not_correct(tmp_path, monkeypatch, fault):
    cfg, traffic = harness.config()
    root = harness.make_root(tmp_path, [("t.train", cfg, traffic)])
    faults.plant_train(monkeypatch, fault)
    res = harness.run_cell(root, "t.train")
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
