"""chip_smoke.py's phases at toy width on the CPU, and its refusal to
run anywhere but on a TPU."""

import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(smoke):
    return smoke.serve_phase(net="small", slots=4, requests=6)


def test_serve_phase_checks_pass_at_toy_width(served):
    rec, _ = served
    assert rec["delivered"] and rec["nfe_identity"], rec
    assert rec["tiers_ordered"], rec["mean_nfe_per_tier"]
    assert rec["alone_match"], rec["alone"]
    assert rec["ok"]
    assert [a["served_nfe"] for a in rec["alone"]] == \
        [a["alone_nfe"] for a in rec["alone"]]


def test_reference_phase_at_toy_width(smoke, served):
    _, server = served
    rec = smoke.reference_phase(server.params, net="small", batch=2)
    assert rec["ok"], rec
    assert set(rec["rel_err"]) == {"0.01", "0.1", "0.5", "1.0"}
    # the served weights are live: the score is not identically zero
    assert rec["max_abs_score"] > 1.0


def test_kernel_phase_at_toy_shapes(smoke):
    cases = smoke.kernel_cases(batch=2, image=8, tokens=16, heads=2,
                               head_dim=32, gn_shapes=((8, 16),), groups=4)
    rec = smoke.kernel_phase(cases, interpret=True)
    assert [r["kernel"] for r in rec["kernels"]] == [
        "error_step", "error_step_vec", "flash_attention", "groupnorm_silu"]
    assert rec["ok"], rec


def test_sharded_phase_on_one_device(smoke):
    rec = smoke.sharded_phase(net="small", devices=1, slots=4, requests=6)
    assert rec["ok"], rec
    assert rec["max_rel_err"] == 0.0


def _run(cmd, cwd, env):
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _run([sys.executable, str(ROOT / "chip_smoke.py")], ROOT, env)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_exits_nonzero_alone_in_a_directory(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = _run([sys.executable, "chip_smoke.py"], tmp_path, env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
