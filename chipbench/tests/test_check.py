"""What decides ``correct``, driven through a whole run at test sizes on
the CPU: a sound run passes, and the control (the program's lower
precision preset) and each planted fault make ``correct`` false."""

import pytest

from conftest import run_cell

from chipbench import run


@pytest.mark.parametrize("workload", ["tiny-dit.open", "tiny-dit.closed",
                                      "tiny-plan.closed"])
def test_sound_run_is_correct(tiny_root, workload):
    rc, res, err = run_cell(tiny_root, workload)
    assert rc == 0, err
    assert res["correct"], err
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert {run.quantity(n) for n in res["metrics"]} == {
        "samples_per_s", "latency_p50_ms", "latency_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())


@pytest.mark.parametrize("workload", ["tiny-dit.open", "tiny-plan.closed"])
def test_control_fails_the_check(tiny_root, workload):
    rc, res, err = run_cell(tiny_root, workload, "--control")
    assert rc == 0, err
    assert not res["correct"]
    gap = res["checks"]["sample_gap_median"]
    assert gap["value"] > gap["limit"], err


@pytest.mark.parametrize("workload,fault", [
    ("tiny-dit.open", "state_unchanged"),
    ("tiny-dit.closed", "half_batch"),
    ("tiny-dit.open", "answer_altered"),
    ("tiny-plan.closed", "state_unchanged"),
    ("tiny-plan.closed", "half_batch"),
    ("tiny-plan.closed", "answer_altered"),
])
def test_planted_fault_fails_the_check(tiny_root, workload, fault):
    rc, res, err = run_cell(tiny_root, workload, "--fault", fault)
    assert rc == 0, err
    assert not res["correct"], err
