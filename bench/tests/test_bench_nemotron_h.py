"""The Nemotron-H cell and the MVR cell in the harness, on the CPU: both are
found by name from their files, the Nemotron-H kind's weights, counts and
reference agree with the program at a tiny size, its sub-scopes are split
out of ``dasha.oracle``, and a run of the kind goes end to end."""
import copy
import json

import jax
import numpy as np
import pytest

from bench import counts_nemotron_h, gen_nemotron_h, run as runner
from bench import scopes, subscopes
from bench.tests import harness

CELL = "train.nemotron3.dasha.s8k"
CONFIG = json.loads((harness.ROOT / "bench" / "configs"
                     / "nemotron3-nano.l7.e8.n1.chip1.json").read_text())

#: the program's smoke share (configs/nemotron3_nano_30b.SMOKE) in the
#: configuration's keys
TINY = dict(CONFIG, published=False, hidden_size=128, mamba_num_heads=8,
            mamba_head_dim=32, n_groups=4, ssm_state_size=16, chunk_size=16,
            published_n_routed_experts=16, n_routed_experts=4,
            num_experts_per_tok=3, moe_intermediate_size=64,
            moe_shared_expert_intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, vocab_size=512,
            hybrid_override_pattern="MEM*E", num_hidden_layers=5)


def _tiny_traffic():
    traffic = json.loads((harness.ROOT / "bench" / "traffic"
                          / "dasha.b1.s8192.json").read_text())
    return dict(traffic, seq=64, chunk=2)


@pytest.mark.parametrize("cell,traffic,kind", [
    ("train.mamba2.mvr", "mvr.b1.s2048", "train"),
    (CELL, "dasha.b1.s8192", "train_nemotron_h")])
def test_new_cells_are_found_by_name(cell, traffic, kind):
    bench, found, _, config, _ = runner.find_cell(harness.ROOT, cell)
    assert found["traffic"] == traffic and found["chips"] == 1
    assert config["kind"] == kind
    assert (harness.ROOT / "bench" / "kinds" / f"{kind}.py").exists()
    e2e = {m["name"] for m in runner.end_to_end_for(bench, found)}
    assert {"tokens_per_s", "setup_s"} <= e2e
    layer = {m["name"] for m in runner.per_layer_for(bench, found, e2e)}
    assert {"mfu", "node_update_roofline", "oracle_ms.train",
            "hbm_peak_gib.train"} <= layer
    assert (harness.ROOT / "bench" / "limits" / f"{cell}.json").exists()


def test_mvr_mix_states_its_momentum():
    """The kind defaults DASHA-MVR's b to 0.1 and the reference to 0: the
    mix has to say which, or the check compares two methods."""
    _, _, _, _, mix = runner.find_cell(harness.ROOT, "train.mamba2.mvr")
    assert mix["variant"] == "mvr" and mix["mvr_b"] == 0.1


def test_configuration_keeps_the_published_numbers():
    """Every published number is there under its own key; the changed
    keys are the ones ``reduced`` lists, each beside its published
    count."""
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in bench["configs"]}[
        "nemotron3-nano.l7.e8.n1.chip1"]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size", "nodes"]
    for key in entry["reduced"][:3]:
        assert CONFIG[f"published_{key}"] > CONFIG[key]
    assert (CONFIG["hidden_size"], CONFIG["mamba_num_heads"],
            CONFIG["n_groups"], CONFIG["num_experts_per_tok"],
            CONFIG["moe_intermediate_size"],
            CONFIG["moe_shared_expert_intermediate_size"]) == \
        (2688, 64, 8, 6, 1856, 3712)
    assert gen_nemotron_h.pattern(CONFIG) == "MEMEM*E"


def test_counts_agree_with_the_program():
    """The yardstick's parameter count is the program's, at the cell's
    size (528M) and the tiny one."""
    from repro.launch.train import arch_config
    from repro.models import init_params
    for cfg_json, published in ((CONFIG, True), (TINY, False)):
        cfg = arch_config("nemotron-3-nano-30b-a3b", published,
                          cfg_json["num_hidden_layers"], None,
                          cfg_json["n_routed_experts"],
                          cfg_json["vocab_size"])
        shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                                jax.random.PRNGKey(0))
        assert counts_nemotron_h.params(cfg_json) == sum(
            int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert round(counts_nemotron_h.params(CONFIG) / 1e6) == 528
    flops = counts_nemotron_h.forward_flops_per_token(CONFIG, 8192)
    assert 1.7e9 < 3 * flops < 1.8e9


_LISTING = """\
HloModule m

%body (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %r = f32[8]{0} exponential(f32[8]{0} %p), metadata={op_name="jit(f)/dasha.oracle/moe.route/exp"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %dot.1 = f32[8]{0} multiply(f32[8]{0} %a, f32[8]{0} %a), metadata={op_name="jit(f)/transpose(jvp(dasha.oracle))/ssd.scan/mul"}
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %dot.1), kind=kLoop, calls=%body
  ROOT %add.3 = f32[8]{0} add(f32[8]{0} %fusion.2, f32[8]{0} %a), metadata={op_name="jit(f)/dasha.oracle/add"}
}
"""


def test_subscopes_split_the_oracle():
    """An op under a model scope inside ``dasha.oracle`` is that sub-scope's
    here and the oracle's in ``bench/scopes.py``."""
    sub = subscopes.hlo_op_names([_LISTING])
    top = scopes.hlo_op_names([_LISTING])
    heads = {k.split(" = ")[0]: k for k in sub}
    assert sub[heads["%dot.1"]] == "ssd.scan"
    assert sub[heads["%fusion.2"]] == "moe.route"
    assert sub[heads["%add.3"]] == "dasha.oracle"
    assert {top[heads[h]] for h in ("%dot.1", "%fusion.2", "%add.3")} == \
        {"dasha.oracle"}
    assert subscopes.innermost("a/b") == scopes.UNSCOPED


def test_kind_runs_end_to_end_at_a_tiny_size(tmp_path):
    """The harness runs the kind on the CPU: set-up, the window through the
    driver, the reference and the check, every number of the comparison
    read and finite.  (At this size bf16 rounding is a far larger share of
    each small leaf than at the cell's, so the chip's limits are not held
    here; the planted faults below are held against this size's own sound
    reading.)"""
    root = harness.make_root(tmp_path, [("n.train", copy.deepcopy(TINY),
                                         _tiny_traffic())])
    limits = root / "bench" / "limits"
    limits.joinpath("n.train.json").write_text(
        limits.joinpath(f"{CELL}.json").read_text())
    res = harness.run_cell(root, "n.train")
    assert res["attempted"] > 0 and isinstance(res["correct"], bool)
    assert set(res["checks"]) == {"grad", "step", "g_proj", "g_rms",
                                  "grad_piece", "g_rms_piece"}
    assert all(np.isfinite(c["value"]) for c in res["checks"].values())


@pytest.fixture(scope="module")
def tiny_readings():
    """The program's first chunk and the reference's at the tiny size."""
    from bench.kinds import train_nemotron_h
    cell = train_nemotron_h.Cell(copy.deepcopy(TINY), _tiny_traffic(),
                                 2 ** 33 + 5, 1)
    cell.warm()
    prog = cell.readings
    cell.release()
    refs = {}
    return cell, prog, refs, cell.reference(cache=refs)


@pytest.mark.parametrize("fault", ["unchanged", "capacity", "softmax",
                                   "one_group"])
def test_planted_faults_read_not_correct(tiny_readings, fault):
    """Each fault the kind's reference can plant, in the program's place,
    reads at least 3x the program's own gap on some number of the
    comparison, and fails the chip cell's limits."""
    from bench import check
    cell, prog, refs, ref = tiny_readings
    sound = cell.compare(prog, ref)
    bad = cell.compare(cell.reference(cache=refs, fault=fault), ref)
    assert any(bad[k] > 3 * sound[k] for k in sound), (sound, bad)
    correct, rows = check.verdict(bad, check.load_limits(harness.ROOT, CELL))
    assert not correct, rows


def test_piece_norms_sum_each_piece_in_place():
    """The timed steps' per-piece squared norms equal those of the
    flattened pieces the check reads, piece for piece."""
    from bench.kinds import train_nemotron_h
    from repro.launch.train import arch_config
    from repro.models import init_params
    cfg = arch_config("nemotron-3-nano-30b-a3b", False,
                      TINY["num_hidden_layers"], None,
                      TINY["n_routed_experts"], TINY["vocab_size"])
    tree = init_params(cfg, jax.random.PRNGKey(3))
    want = [float(np.sum(np.square(np.asarray(x, np.float64))))
            for x in train_nemotron_h.slices(tree)]
    got = np.asarray(train_nemotron_h.piece_sq(tree), np.float64)
    assert got.shape == (len(want),)
    np.testing.assert_allclose(got, want, rtol=1e-5)


class _Trace:
    """Device seconds by instruction: the part of a trace the roofline
    reads."""

    def __init__(self, ops):
        self.ops = ops

    def op_seconds(self, match):
        return sum(s for name, s in self.ops.items() if match(name))


def test_gmm_roofline_reads_the_grouped_kernels():
    """The grouped kernels' share of the peak at the expected load sums
    the events of megablox's forward and backward kernels, by their
    instructions' names, and no other op."""
    reader = runner._load_module(harness.ROOT / "bench" / "metrics"
                                 / "gmm_expected_load_share.py",
                                 "gmm_expected_load_share")
    ops = {"%gmm.3 = bf16[128,8]{1,0} custom-call()": 0.25,
           "%transpose_jvp_jit_gmm___.1 = bf16[128,8]{1,0} custom-call()":
               0.25,
           "%transpose_jvp_jit_tgmm___ = bf16[8,8,8]{2,1,0} custom-call()":
               0.5,
           "%fusion.7 = f32[8]{0} fusion()": 9.0,
           "%dasha_update.2 = f32[8,128]{1,0} custom-call()": 9.0}
    ctx = {"trace": _Trace(ops), "units": 4,
           "counts": {"gmm_flops_per_unit": 1e12},
           "peaks": {"bf16_flops_per_s": 1e13}}
    assert reader.read(ctx) == pytest.approx(40.0)
    assert reader.read(dict(ctx, trace=_Trace({}))) is None
    assert reader.read(dict(ctx, counts={})) is None


def test_held_expert_flops_count_the_expected_share():
    """Per step: 3 MoE layers x (2 forward + 4 backward products) x
    2 x rows x d x f, rows = 8192 tokens x 6 of 128 experts x 8 held."""
    rows = 8192 * 6 * 8 / 128
    want = 3 * 6 * 2 * rows * 2688 * 1856
    assert counts_nemotron_h.held_expert_flops(CONFIG, "dasha", 8192) == \
        int(want)
    assert counts_nemotron_h.held_expert_flops(CONFIG, "mvr", 8192) == \
        2 * int(want)
