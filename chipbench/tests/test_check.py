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


#: a whole run of a four-chip test cell on four virtual CPU devices, in a
#: process of its own (the device count is fixed when JAX starts); prints
#: one JSON line: each case's exit code and ``correct``
MESH_RUNS = """
import json, pathlib, sys, tempfile
sys.path[:0] = [{tests!r}]
from conftest import make_root, run_cell
root = make_root(pathlib.Path(tempfile.mkdtemp()),
                 cells=[("tiny-dit.mesh", "tiny-dit", "tiny-closed")])
bench = json.loads((root / "BENCHMARK.json").read_text())
bench["workloads"][0]["chips"] = 4
(root / "BENCHMARK.json").write_text(json.dumps(bench))
mix = root / "chipbench" / "traffic" / "tiny-closed.json"
mix.write_text(json.dumps(dict(json.loads(mix.read_text()), slots=8,
                               clients=16)))
out = {{}}
for case in {cases!r}:
    rc, res, err = run_cell(root, "tiny-dit.mesh",
                            *(["--fault", case] if case else []))
    out[case or "sound"] = [rc, res and res["correct"], res and res["checks"]]
print(json.dumps(out))
"""
MESH_CASES = [None, "exchange_left_out", "half_batch", "answer_altered"]


@pytest.fixture(scope="module")
def mesh_runs():
    import json
    import os
    import pathlib
    import subprocess
    import sys

    tests = str(pathlib.Path(__file__).resolve().parent)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", MESH_RUNS.format(tests=tests, cases=MESH_CASES)],
        capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", [c or "sound" for c in MESH_CASES])
def test_four_chip_cell_catches_its_faults(mesh_runs, case):
    """On a mesh of four, with every slot kept full (a fault in slots
    that hold no request shows nothing), a sound run is correct, and a
    delivery that never crosses chips, half the batch and an altered
    answer are not."""
    rc, correct, checks = mesh_runs[case]
    assert rc == 0, checks
    assert correct == (case == "sound"), checks
