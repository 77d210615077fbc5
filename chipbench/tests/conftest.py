"""The harness's tests run on the CPU at test sizes:

  JAX_PLATFORMS=cpu python -m pytest chipbench/tests
"""

import json
import os
import pathlib
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

DATA = pathlib.Path(__file__).resolve().parent / "data"

#: test cells: (name, config, traffic)
CELLS = [("tiny-dit.open", "tiny-dit", "tiny-open"),
         ("tiny-dit.closed", "tiny-dit", "tiny-closed"),
         ("tiny-plan.closed", "tiny-plan", "tiny-plan-closed")]


def make_root(dest: pathlib.Path, cells=CELLS) -> pathlib.Path:
    """A checkout-shaped directory holding test cells: BENCHMARK.json
    with the real metrics, the tiny configurations and traffic mixes."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (dest / "chipbench" / "traffic").mkdir(parents=True)
    (dest / "chipbench" / "configs").mkdir(parents=True)
    shutil.copytree(ROOT / "chipbench" / "metrics",
                    dest / "chipbench" / "metrics")
    configs = {}
    for name, config, traffic in cells:
        shutil.copy(DATA / f"{traffic}.json",
                    dest / "chipbench" / "traffic" / f"{traffic}.json")
        shutil.copy(DATA / f"{config}.json",
                    dest / "chipbench" / "configs" / f"{config}.json")
        configs[config] = {"name": config, "source": "test",
                           "file": f"chipbench/configs/{config}.json",
                           "reduced": [], "why": "test"}
    # a metric of some cells goes to the test cells of the same family
    family = {c["name"]: json.loads((ROOT / c["file"]).read_text())["family"]
              for c in bench["configs"]}
    family.update({c: json.loads((DATA / f"{c}.json").read_text())["family"]
                   for c in configs})
    of_cell = {w["name"]: family[w["config"]] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            fams = {of_cell[w] for w in m["workloads"]}
            m["workloads"] = [n for n, c, _ in cells if family[c] in fams]
    bench["configs"] = list(configs.values())
    bench["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                           "why": "test"} for n, c, t in cells]
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("checkout"))


def run_cell(root, workload, *extra, seed=2_147_483_999, seconds=1.5):
    """run.main on the CPU; returns (exit code, result dict or None,
    standard error)."""
    import contextlib
    import io

    from chipbench import run

    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", "0", *extra],
                      root=root, require_chip=False)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
