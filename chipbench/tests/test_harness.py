"""The harness is driven by data: cells, configurations, traffic mixes and
per-layer metrics are files found by name. The traffic is a function of
the seed. Outside a TPU, or without the program, a run gives no result."""

import json
import shutil
import subprocess
import sys

import numpy as np
import pytest

from conftest import ROOT, make_root

from chipbench import run, stats
from chipbench.traffic import Traffic, sample_indices

OPEN = {"loop": "open", "rate_per_s": 7.0, "preroll_s": 2,
        "tiers": {"draft": 0.5, "standard": 0.35, "high_fidelity": 0.15}}
CLOSED = {"loop": "closed", "clients": 8, "obs_std": 1.0}


def test_dropped_in_workload_is_found(tmp_path):
    root = make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny-dit.new", "config": "tiny-dit",
                               "traffic": "brand-new", "chips": 1,
                               "why": "a cell added by data alone"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    mix = dict(OPEN, slots=4, max_wait_s=5)
    (root / "chipbench" / "traffic" / "brand-new.json").write_text(
        json.dumps(mix))
    _, cell, cfg, got = run.load_cell(root, "tiny-dit.new")
    assert cell["traffic"] == "brand-new" and got == mix
    assert cfg["family"] == "dit"


def test_dropped_in_metric_reader_is_found(tmp_path):
    root = make_root(tmp_path)
    (root / "chipbench" / "metrics" / "queue_x.serve.py").write_text(
        "def read(ctx):\n    return ctx.value * 2\n")

    class Ctx:
        value = 21

    assert run.read_metric(root, "queue_x.serve", Ctx()) == 42


def test_every_named_metric_has_a_reader():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert run.reader_path(ROOT, m["name"]).exists()
    for m in bench["end_to_end"]:
        assert run.quantity(m["name"]) in {
            "samples_per_s", "latency_p50_ms", "latency_p95_ms", "setup_s"}
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert (ROOT / "chipbench" / "families" / f"{cfg['family']}.py").exists()
    for w in bench["workloads"]:
        assert (ROOT / "chipbench" / "traffic" / f"{w['traffic']}.json").exists()


@pytest.mark.parametrize("params", [OPEN, CLOSED])
def test_traffic_is_a_function_of_the_seed(params):
    seed = 2**31 + 12345
    a = Traffic(params, seed, window_s=5, obs_dim=17, returns_bins=10)
    b = Traffic(params, seed, window_s=5, obs_dim=17, returns_bins=10)
    for i in range(60):
        sa, sb = a.spec(i), b.spec(i)
        assert sa["seed"] == sb["seed"] and sa["tier"] == sb["tier"]
        assert sa["label"] == sb["label"]
        np.testing.assert_array_equal(sa["obs"], sb["obs"])
        if params["loop"] == "open":
            assert a.due(i) == b.due(i)
    c = Traffic(params, seed + 1, window_s=5, obs_dim=17, returns_bins=10)
    assert [a.spec(i)["seed"] for i in range(20)] != \
        [c.spec(i)["seed"] for i in range(20)]


def test_every_seed_gets_the_same_work_in_another_order():
    window = 10.0
    runs = [Traffic(OPEN, s, window_s=window) for s in (1, 2**31 + 7, 10**12)]
    n = round(OPEN["rate_per_s"] * window)
    first = round(OPEN["rate_per_s"] * OPEN["preroll_s"])
    spans = []
    for t in runs:
        due = [t.due(i) for i in range(first + 2 * n + 1)]
        assert due == sorted(due)
        t0 = t.window_start
        # the window holds a fixed number of requests, due at any time in it
        inside = [d for d in due if t0 <= d < t0 + window]
        assert len(inside) == n and due[first] == inside[0]
        assert due[first + n] >= t0 + window
        spans.append(sorted(t.spec(i)["tier"]
                            for i in range(first, first + n)))
    assert spans[0] == spans[1] == spans[2]
    assert spans[0].count("draft") == round(0.5 * n)
    assert [runs[0].due(i) for i in range(first, first + 5)] != \
        [runs[1].due(i) for i in range(first, first + 5)]
    assert [runs[0].spec(i)["tier"] for i in range(first, first + n)] != \
        [runs[1].spec(i)["tier"] for i in range(first, first + n)]


def test_window_arrivals_are_poisson_given_their_count():
    """Gaps inside a window are exponential at the rate (Kolmogorov-
    Smirnov distance of the pooled gaps), not a fixed set per block."""
    rate, window = 24.0, 15.0
    gaps = []
    for s in range(40):
        t = Traffic(dict(OPEN, rate_per_s=rate, preroll_s=0), 2**31 + s,
                    window_s=window)
        gaps += list(np.diff([t.due(i) for i in range(int(rate * window))]))
    gaps = np.sort(gaps)
    ks = np.max(np.abs(np.arange(1, len(gaps) + 1) / len(gaps)
                       - (1 - np.exp(-rate * gaps))))
    assert ks < 0.02
    counts = [sum(1 for d in (Traffic(dict(OPEN, rate_per_s=rate, preroll_s=0),
                                      2**31 + s, window_s=window).due(i)
                              for i in range(int(rate * window)))
                  if d < 1.0) for s in range(200)]
    # arrivals in one second of the window: binomial(360, 1/15), close to
    # Poisson(24): mean 24, variance 23
    assert abs(np.mean(counts) - rate) < 1.0
    assert 15 < np.var(counts) < 32


def test_check_sample_holds_the_longest():
    for seed in range(5):
        picks = sample_indices(seed, 100, 12, must=57)
        assert 57 in picks and len(set(picks)) == 12
    assert sample_indices(3, 5, 12, must=1) == [0, 1, 2, 3, 4]


def test_percentiles_and_spread_follow_the_statistics_module():
    import statistics

    xs = list(np.random.default_rng(0).exponential(size=301))
    assert stats.percentile(xs, 95) == pytest.approx(
        statistics.quantiles(xs, n=100, method="inclusive")[94])
    assert stats.percentile(xs, 50) == pytest.approx(statistics.median(xs))
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / statistics.median(xs))


def test_no_chip_no_result(tiny_root):
    import contextlib
    import io

    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", "tiny-dit.open", "--seed", "1",
                       "--seconds", "1", "--trace", "0"], root=tiny_root)
    assert rc != 0 and out.getvalue() == ""
    assert "TPU" in err.getvalue()


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = bench["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": str(tmp_path)})
    assert p.returncode != 0 and p.stdout.strip() == ""
