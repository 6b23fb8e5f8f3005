"""The control of ``correct``: the program with its walk budget cut
``control.WALK_CUT``-fold (the configuration's guarantee broken) reads
``correct = false``, on ``z_rms`` as well as on the bound it reports,
where the same run at the stated budget reads true."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import control  # noqa: E402
import harness  # noqa: E402
import tiny  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("bench"), rate=30.0)


def _run(root, budget_walks=None):
    return harness.run("wikivote-churn", 3_000_000_019, 3.0, False, root=root,
                       require_chip=False, cache=False,
                       budget_walks=budget_walks)


def test_sound_run_is_correct(root):
    r = _run(root)
    assert r["correct"] is True, r["checks"]


def test_control_is_not_correct(root):
    walks = harness.load_cell("wikivote-churn", root).config["guarantee"][
        "walks_per_query"]
    r = _run(root, walks // control.WALK_CUT)
    assert r["correct"] is False
    failed = {k for k, c in r["checks"].items() if c["value"] > c["limit"]}
    # the program reports its looser bound, and a number compared with the
    # reference fails too, so a cut that kept the stated bound is caught
    assert {"bound_over_eps", "z_rms"} <= failed, r["checks"]
