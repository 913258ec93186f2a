"""The comparison's numbers on hand-made readings: a step of the same norm
that points elsewhere reads in ``grad_dir`` and not in ``grad_gap``; the
worst-leaf numbers leave one-element leaves out; a leaf the reference
moves by round-off alone is not counted."""
import numpy as np
import pytest

from bench import harness


def _readings(p0, p1, losses=(1.0,)):
    norm = {k: float(np.linalg.norm(p1[k] - p0[k])) for k in p1}
    return harness.Readings(list(losses), norm, norm, p1)


def _tree(w, clip, still):
    return {"w": np.asarray(w, np.float32), "w_qa": np.float32([clip]),
            "still": np.float32([still])}


P0 = _tree([1.0, 1.0, 1.0, 1.0], 2.0, 3.0)
REF = _tree([1.1, 0.9, 1.1, 0.9], 2.1, 3.0)


def test_a_step_pointing_elsewhere_reads_in_grad_dir_only():
    got = _tree([0.9, 1.1, 0.9, 1.1], 2.1, 3.0)
    ref = _readings(P0, REF)
    nums = harness.compare(_readings(P0, got), ref, 1)
    assert nums["grad_gap"] == pytest.approx(0.0, abs=1e-6)
    assert nums["grad_dir"] == pytest.approx(2.0, rel=1e-5)
    assert nums["_leaves"]["grad_dir"] == "w"
    same = harness.compare(_readings(P0, REF), ref, 1)
    assert same["grad_dir"] == 0.0 and same["loss_gap"] == 0.0


def test_one_element_leaves_are_left_out_of_the_worst_leaf():
    got = _tree([1.1, 0.9, 1.1, 0.9], 1.9, 3.0)   # the clip stepped back
    nums = harness.compare(_readings(P0, got),
                           _readings(P0, REF), 1)
    # 0.2 over the median counted leaf's reference step, (0.2 + 0.1) / 2
    assert nums["_per_leaf"]["grad_dir"]["w_qa"] == pytest.approx(4 / 3,
                                                                 rel=1e-4)
    assert nums["grad_dir"] == 0.0 and nums["grad_gap"] == 0.0
    assert "still" in nums["_excluded"]


def test_a_non_finite_side_reads_infinite():
    got = _tree([np.nan, 1.0, 1.0, 1.0], 2.1, 3.0)
    nums = harness.compare(_readings(P0, got, (np.nan,)),
                           _readings(P0, REF), 1)
    assert all(nums[k] == np.inf for k in
               ("loss_gap", "grad_gap", "grad_dir"))
