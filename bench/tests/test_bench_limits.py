"""The rule that sets a limit from its readings, and the gap arithmetic."""
import numpy as np
import pytest

from bench import calibrate, check


def _runs(sound, control, faults):
    return [{"sound": [{"gaps": {"x": v}} for v in sound],
             "control": [{"gaps": {"x": v}} for v in control],
             "faults": [{"fault": f, "gaps": {"x": v}} for f, v in faults]}]


def test_limit_lies_two_thirds_up_in_log_scale():
    row = calibrate.limits_from(_runs([0.01, 0.02], [1.0, 0.8], []))["x"]
    assert row["lower"] == 0.02 and row["upper"] == 0.8
    assert row["upper_from"] == "control"
    # 0.02^(1/3) * 0.8^(2/3) = 0.2340..., rounded down to two digits
    assert row["limit"] == 0.23


def test_faults_set_the_upper_only_when_far_enough():
    runs = _runs([0.1], [0.35], [("half_batch", 0.9), ("half_batch", 2.0),
                                 ("unchanged", 0.5), ("altered", 1.2)])
    row = calibrate.limits_from(runs)["x"]
    # control 0.35 >= 3 x 0.1; unchanged 0.5 >= 3 x 0.1; half_batch's
    # smallest (0.9) and altered (1.2) are under 10 x 0.1 and do not count
    assert row["upper"] == 0.35 and row["upper_from"] == "control"


def test_no_upper_means_no_limit():
    row = calibrate.limits_from(_runs([0.1], [0.2], [("altered", 0.5)]))["x"]
    assert "limit" not in row and "upper" not in row


def test_worst_leaf_gap_uses_the_median_floor():
    ref = np.array([1.0, 4.0, 9.0])          # norms 1, 2, 3; median 2
    prog = np.array([1.21, 4.0, 9.0])        # leaf 0 off by 0.1
    keep = np.ones(3, bool)
    assert check.worst_leaf_gap(prog, ref, keep) == pytest.approx(0.05)
    # a leaf above the median is measured against its own norm
    assert check.worst_leaf_gap(np.array([1.0, 4.0, 10.24]), ref, keep) == \
        pytest.approx(0.2 / 3)


def test_leaves_that_do_not_move_are_left_out():
    ref_grad_sq = np.array([1.0, 1.0, 1e-8, 4.0])   # norm 1e-4 < 1e-3 x 1
    keep = check.moving_leaves(ref_grad_sq)
    assert keep.tolist() == [True, True, False, True]


def test_ulp_sq_by_hand():
    import jax.numpy as jnp
    tree = {"a": jnp.array([1.0, 0.75, 0.0], jnp.bfloat16),
            "b": jnp.array([2.0], jnp.float32)}
    # bf16 keeps 7 fraction bits: ulp(1) = 2^-7, ulp(0.75) = 2^-8, and 0
    # counts nothing; f32 keeps 23: ulp(2) = 2^-22
    got = np.asarray(check.ulp_sq(tree), np.float64)
    assert got[0] == pytest.approx(2.0 ** -14 + 2.0 ** -16)
    assert got[1] == pytest.approx(2.0 ** -44)


def test_leaves_that_move_by_rounding_are_left_out_of_the_step():
    ulp = np.array([4.0, 4.0, 0.0])              # rms ulp x sqrt(n): 2, 2
    dx = np.array([1.0, 0.99, 0.0])              # 1 = half of 2 is kept
    assert check.stepping_leaves(dx, ulp).tolist() == [True, False, True]


def test_gaps_of_directions_by_hand():
    ref = np.array([[1.0, -1.0], [2.0, 2.0]])    # two leaves, two directions
    prog = np.array([[1.3, -0.6], [2.0, 2.0]])
    ref_sq = np.array([1.0, 4.0])
    # leaf 0: rms of (0.3, 0.4) = sqrt(0.125), over its norm 1; leaf 1: 0
    g = check.direction_gaps(prog, ref, ref_sq)
    assert g.tolist() == pytest.approx([0.125 ** 0.5, 0.0])
    keep = np.ones(2, bool)
    assert check.worst_projection_gap(prog, ref, ref_sq, keep) == \
        pytest.approx(0.125 ** 0.5)
    assert check.rms_projection_gap(prog, ref, ref_sq, keep) == \
        pytest.approx(0.25)


def test_projections_tell_a_sign_from_a_norm():
    import jax
    import jax.numpy as jnp
    x = {"w": jnp.arange(1.0, 65.0).reshape(8, 8)}
    key = jax.random.PRNGKey(3)
    p, q = check.project(x, key), check.project(
        jax.tree_util.tree_map(jnp.negative, x), key)
    assert p.shape == (1, check.DIRS)
    sq = np.array([float(jnp.sum(x["w"] ** 2))])
    # the same norm, the opposite direction: twice the norm apart
    assert check.direction_gaps(q, p, sq)[0] == pytest.approx(2.0, rel=0.6)


def test_verdict_needs_every_limit():
    ok, rows = check.verdict({"a": 0.1, "b": 0.2}, {"a": 0.5})
    assert not ok and rows[1]["limit"] is None
    ok, _ = check.verdict({"a": 0.1}, {"a": 0.5})
    assert ok
    ok, _ = check.verdict({"a": float("nan")}, {"a": 0.5})
    assert not ok
