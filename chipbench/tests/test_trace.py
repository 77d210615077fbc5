"""The trace reduction: exact on hand-made intervals, and on a trace
recorded on a v5e chip it reproduces the busy time and program time that
the profiler's own Chrome-format export of the same session gives."""

import gzip
import json
import pathlib

import pytest

from conftest import DATA

from chipbench import trace as tr

FIXTURE = DATA / "trace-dit-open-mixed"


def test_union_gaps_and_self_times():
    ops = [(0, 10, "%while.1 = (f32[2]{0}, s32[]{:T(128)}) while()"),
           (2, 4, "%a = f32[2]{0}"),
           (5, 9, "%b = bf16[3]{0}"), (20, 25, "%c = f32[1]{0}"),
           (24, 30, "%d = f32[1]{0}")]
    chip = tr.Chip(ops, [(0, 10, "jit_driver(1)"), (20, 30, "jit_event(2)")])
    assert chip.busy == [(0, 10), (20, 30)]
    assert chip.busy_s(0, 40) == pytest.approx(20e-9)
    assert chip.gaps(0, 40) == [(10, 20), (30, 40)]
    assert chip.program_s("jit_driver", 0, 40) == pytest.approx(10e-9)
    selfs = {n: d for _, d, n in tr.self_times(ops)}
    assert selfs[ops[0][2]] == 10 - 2 - 4
    spans = [(0, 40, "bench/trace"), (8, 22, "bench/step"),
             (12, 18, "serve/admission")]
    assert tr.labels([15, 35], spans) == ["serve/admission", "bench/trace"]
    r = tr.reduce({0: chip}, spans, 0, 40, programs=["jit_driver"])
    assert r["busy_s"] == pytest.approx(20e-9)
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"serve/admission": 10e-9, "bench/trace": 10e-9})
    assert r["device_ops"][0][0] == "d f32[1]"
    assert dict(r["device_ops"])["while.1 f32[2]"] == pytest.approx(4e-9)


def _chrome_witness(path):
    """Busy and jit_driver seconds inside the bench/trace span, read from
    the Chrome-format export (microseconds) without the xplane reader."""
    ev = json.load(gzip.open(path))["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in ev
             if e.get("ph") == "M" and e["name"] == "process_name"}
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in ev
               if e.get("ph") == "M" and e["name"] == "thread_name"}
    dev = [e for e in ev if e.get("ph") == "X"
           and procs.get(e["pid"]) == "/device:TPU:0"]
    win = [e for e in ev if e.get("ph") == "X" and e["name"] == "bench/trace"]
    t0, t1 = win[0]["ts"], win[0]["ts"] + win[0]["dur"]
    ops = sorted((e["ts"], e["ts"] + e["dur"]) for e in dev
                 if threads[(e["pid"], e["tid"])] == "XLA Ops")
    busy, cur_s, cur_e = 0.0, None, None
    for s, e in ops:
        if cur_e is not None and s <= cur_e:
            cur_e = max(cur_e, e)
            continue
        if cur_e is not None:
            busy += max(0.0, min(cur_e, t1) - max(cur_s, t0))
        cur_s, cur_e = s, e
    busy += max(0.0, min(cur_e, t1) - max(cur_s, t0))
    driver = sum(e["dur"] for e in dev
                 if threads[(e["pid"], e["tid"])] == "XLA Modules"
                 and e["name"].startswith("jit_driver(") and t0 <= e["ts"] < t1)
    return busy / 1e6, driver / 1e6, (t1 - t0) / 1e6


def test_recorded_trace_matches_its_chrome_export(tmp_path):
    xplane = tmp_path / "t.xplane.pb"
    xplane.write_bytes(gzip.decompress(
        pathlib.Path(f"{FIXTURE}.xplane.pb.gz").read_bytes()))
    chips, host = tr.load(str(xplane))
    (t0, t1, _), = [s for s in host if s[2] == "bench/trace"]
    r = tr.reduce(chips, host, t0, t1, programs=["jit_driver"])
    busy, driver, window = _chrome_witness(f"{FIXTURE}.trace.json.gz")
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(window, abs=2e-6)
    assert r["busy_s"] == pytest.approx(busy, rel=1e-3, abs=5e-6)
    assert r["program_s"]["jit_driver"] == pytest.approx(driver, rel=1e-3)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["program_runs"]["jit_driver"] >= 2
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
