"""What a per-layer reader gets (``run.layer_context``): every counter,
span, request and named program the run saw, the configuration and the
chip's peaks; the readers read the same numbers from the stored v5e trace
as before, and shares of a peak count every chip of a mesh."""

import gzip
import json
import types

import pytest

from conftest import DATA, ROOT

from chipbench import loop, run, trace as tr
from chipbench.families import dit
from chipbench.peaks import PEAKS

FIXTURE = DATA / "trace-dit-open-mixed.xplane.pb.gz"
CFG = json.loads((ROOT / "chipbench" / "configs" / "dit-b16-px256.json")
                 .read_text())
MIX = json.loads((ROOT / "chipbench" / "traffic" / "open-mixed.json")
                 .read_text())

#: the readers' values on the stored trace with the run of ``_run`` below,
#: as the layer context before this one (which took ``program_s`` of the
#: step program alone and appended a clock-shifted copy of the stage
#: tracer's spans to the host spans) gave them
PINNED = {
    "compile_s": 1.4541,
    "host_transfers_per_sample": 1.7333333333333334,
    "passenger_nfe_pct": 4.761904761904767,
    "nfe_per_sample": 28.88,
    "iteration_ms": 16.83779708333333,
    "step_mfu_pct": 33.764935274882504,
    "device_idle_pct": 5.038904605498129,
}


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    return str(path)


def _server():
    return types.SimpleNamespace(
        step_program="jit_driver", flop_per_eval=dit.flop_per_eval(CFG),
        programs={"step": "jit_driver", "event": dit.EVENT_PROGRAM})


def _run():
    """(outcome, slice) of a made-up run: 300 requests delivered in the
    window, the legacy counters and two registry series."""
    recs = [loop.Record(spec={}, client=None, due=0.0, nfe=14 + 3 * (i % 11),
                        request=types.SimpleNamespace(
                            queue_wait_s=0.001 * (i % 50)))
            for i in range(300)]
    counters = {"iterations": 1000, "useful_nfe": 30000,
                "resident_nfe": 31500, "transfers": 520,
                "serve_host_uploads_total": 600,
                "serve_event_updates_total": 200,
                'serve_delivered_total{tier="draft"}': 150}
    outcome = types.SimpleNamespace(delivered_in_window=recs,
                                    counters=counters)
    sl = types.SimpleNamespace(iterations=60, useful_nfe=1100,
                               counters={"iterations": 60,
                                         "serve_event_updates_total": 12})
    return outcome, sl


def _context(xplane, server=None):
    outcome, sl = _run()
    return run.layer_context(
        xplane, server=server or _server(), family=dit, cfg=CFG, mix=MIX,
        outcome=outcome, sl=sl, peaks=PEAKS["TPU v5 lite"], compile_s=1.4541,
        device={"memory_peak_bytes": 705_000_000})


@pytest.mark.parametrize("name", sorted(PINNED))
def test_readers_read_as_before_on_the_stored_trace(xplane, name):
    assert run.read_metric(ROOT, name, _context(xplane)) == PINNED[name]


def _chip(offset):
    """One chip's slice: two step programs of 6 ns and an event program
    of 2 ns, ops under each."""
    ops = [(offset + s, offset + e, f"%op.{s} = f32[1]{{0}}")
           for s, e in ((0, 6), (10, 16), (20, 22))]
    mods = [(offset + 0, offset + 6, "jit_driver(1)"),
            (offset + 10, offset + 16, "jit_driver(1)"),
            (offset + 20, offset + 22, "jit_event_update(2)")]
    return tr.Chip(ops, mods)


@pytest.mark.parametrize("name", ["step_mfu_pct", "iteration_ms",
                                  "device_idle_pct"])
def test_a_mesh_reads_what_one_chip_reads(name):
    """Four chips, each doing one chip's work on its quarter of the
    slots, with four times the useful NFE and the same iterations, read
    what one chip reads."""
    def ctx(n):
        c = types.SimpleNamespace(
            trace=tr.reduce({i: _chip(0) for i in range(n)}, [], 0, 40,
                            programs=["jit_driver"]),
            slice={"iterations": 10, "useful_nfe": 2 * n},
            step_program="jit_driver", flop_per_eval=1e6, peak_flops=197e12)
        return run.read_metric(ROOT, name, c)

    assert ctx(4) == pytest.approx(ctx(1), rel=1e-12)
    if name == "step_mfu_pct":
        # 2 evaluations of 1 MFLOP in 12 ns of a 197 TFLOP/s chip
        assert ctx(1) == pytest.approx(100 * 2e6 / (12e-9 * 197e12))


def test_counters_hold_the_legacy_four_and_every_registry_series(tiny_root):
    fam_cfg = json.loads((DATA / "tiny-dit.json").read_text())
    mix = json.loads((DATA / "tiny-open.json").read_text())
    server = dit.build(fam_cfg, mix, fam_cfg["weights_key"], precision="fp32",
                       tracer=None, devices=1)
    b = server.batcher
    before = loop.counters(b)
    for uid, tier in enumerate(("draft", "standard", "draft")):
        b.submit(server.request(uid, {"seed": uid, "tier": tier}))
    b.run_to_completion()
    now = loop.counters(b)
    registry = b.metrics.to_json()["counters"]
    assert {k: now[k] for k in loop.LEGACY} == {
        "iterations": b.total_iterations, "useful_nfe": b.useful_nfe,
        "resident_nfe": b.resident_nfe, "transfers": b.host_transfers}
    assert {k: v for k, v in now.items() if k not in loop.LEGACY} == registry
    assert now["serve_host_uploads_total"] > 0
    assert now["serve_event_updates_total"] > 0
    d = loop.deltas(before, now)
    assert d["serve_iterations_total"] == d["iterations"] > 0
    # a labelled series that first appeared in between started at 0
    assert all(d[k] == v for k, v in registry.items() if k not in before)


def test_load_keeps_every_stage_of_host_spans(tmp_path):
    """A profile recorded here: the host plane keeps every span named
    ``<stage>/...``, whatever the stage, and no other event."""
    import jax

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench/trace"):
            for name in ("prefill/admit", "serve/event", "Other/thing",
                         "no_stage"):
                with jax.profiler.TraceAnnotation(name):
                    jax.block_until_ready(jax.numpy.ones(3) + 1)
    path = str(sorted(tmp_path.rglob("*.xplane.pb"))[-1])
    names = {n for _, _, n in tr.load(path)[1]}
    assert {"bench/trace", "serve/event", "prefill/admit"} <= names
    assert not names & {"Other/thing", "no_stage"}
    assert all(tr.STAGE.match(n) for n in names)


def test_a_new_stage_reaches_ctx_spans(xplane, monkeypatch):
    """The spans of a stage no earlier program had reach ``ctx.spans``
    with no declaration, cut to the slice."""
    load = tr.load

    def with_prefill(path):
        chips, host = load(path)
        (t0, t1, _), = [s for s in host if s[2] == "bench/trace"]
        return chips, host + [(t0 - 5, t0 + 1000, "prefill/admit"),
                              (t1 + 1, t1 + 9, "prefill/admit")]

    monkeypatch.setattr(tr, "load", with_prefill)
    ctx = _context(xplane)
    (t0, t1), = [(s, e) for s, e, n in ctx.spans if n == "bench/trace"]
    assert [(s - t0, e - t0) for s, e, n in ctx.spans
            if n == "prefill/admit"] == [(0, 1000)]
    assert all(t0 <= s <= e <= t1 for s, e, _ in ctx.spans)


def test_a_new_reader_reads_every_field_without_an_edit(tmp_path, xplane):
    """A reader dropped into a checkout reads each of the context's
    fields; ``run.py`` is not touched."""
    metrics = tmp_path / "chipbench" / "metrics"
    metrics.mkdir(parents=True)
    (metrics / "everything.py").write_text(
        "def read(ctx):\n"
        "    return {\n"
        "        'uploads': ctx.window['counters']['serve_host_uploads_total'],\n"
        "        'events_in_slice':"
        " ctx.slice['counters']['serve_event_updates_total'],\n"
        "        'spans': len(ctx.spans),\n"
        "        'wait': max(r.queue_wait_s for r in ctx.requests),\n"
        "        'event_runs': ctx.trace['program_runs'][ctx.programs['event']],\n"
        "        'event_s': ctx.trace['program_s'][ctx.programs['event']],\n"
        "        'chips': ctx.trace['chips'],\n"
        "        'hidden': ctx.cfg['hidden_size'],\n"
        "        'slots': ctx.mix['slots'],\n"
        "        'flop': ctx.family.flop_per_eval(ctx.cfg),\n"
        "        'hbm': ctx.peaks.hbm_bytes_s,\n"
        "        'peak': ctx.device['memory_peak_bytes'],\n"
        "    }\n")
    got = run.read_metric(tmp_path, "everything", _context(xplane))
    assert got["uploads"] == 600 and got["events_in_slice"] == 12
    assert got["spans"] >= 5 and got["wait"] == pytest.approx(0.049)
    # the stored slice holds two calls of the event program
    assert got["event_runs"] == 2 and 0 < got["event_s"] < 0.1
    assert got["chips"] == 1
    assert (got["hidden"], got["slots"]) == (768, 16)
    assert got["flop"] == dit.flop_per_eval(CFG)
    assert got["hbm"] == 819e9 and got["peak"] == 705_000_000


def _spans_ctx(spans, window_s=1.0):
    return types.SimpleNamespace(spans=spans, trace={"window_s": window_s})


def test_event_pct_sums_the_host_visits():
    ctx = _spans_ctx([(0, 100_000_000, "serve/event"),
                      (200_000_000, 250_000_000, "serve/sync"),
                      (300_000_000, 900_000_000, "serve/solve")])
    assert run.read_metric(ROOT, "event_pct", ctx) == pytest.approx(15.0)
    assert run.read_metric(ROOT, "event_pct.plan", ctx) == pytest.approx(15.0)
    assert run.read_metric(ROOT, "event_pct",
                           _spans_ctx([(0, 5, "bench/step")])) is None


def test_queue_wait_and_uploads_readers():
    def rec(late, wait):
        return loop.Record(spec={}, client=None, due=10.0, submit=10.0 + late,
                           request=types.SimpleNamespace(queue_wait_s=wait))

    # closed loop: due at submit, so the program's wait alone
    recs = [rec(0.0, None)] + [rec(0.0, 0.01 * i) for i in range(101)]
    ctx = types.SimpleNamespace(
        window={"delivered": recs,
                "counters": {"serve_host_uploads_total": 90,
                             "serve_event_updates_total": 30}})
    assert run.read_metric(ROOT, "queue_wait_p95_ms", ctx) == \
        pytest.approx(950.0)
    assert run.read_metric(ROOT, "uploads_per_event", ctx) == 3.0
    # open loop: the wait from due to submit counts too
    late = types.SimpleNamespace(window={"delivered": [
        rec(0.01 * i, 0.001) for i in range(101)]})
    assert run.read_metric(ROOT, "queue_wait_p95_ms", late) == \
        pytest.approx(951.0)
    empty = types.SimpleNamespace(window={"delivered": [], "counters": {}})
    assert run.read_metric(ROOT, "queue_wait_p95_ms.plan", empty) is None
    assert run.read_metric(ROOT, "uploads_per_event", empty) is None
