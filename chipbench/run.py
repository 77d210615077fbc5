"""Chip benchmark of the served diffusion path: one cell, one run.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` names the cell; the cell names its configuration
(``chipbench/configs/<config>.json``, with the family module
``chipbench/families/<family>.py`` that builds the program's server and
holds the plain reference) and its traffic mix
(``chipbench/traffic/<traffic>.json``). Per-layer metrics are read by
``chipbench/metrics/<metric>.py``; a metric ``<quantity>.<group>`` (the
quantity under a name and bound of its own for the cells it lists) is
read by its quantity's reader. Nothing here names a cell.

A run builds the weights from the configuration's fixed key and the
traffic from ``--seed``, warms up every program the traffic will use
(set-up), serves the traffic for ``--seconds``, and then checks a sample
of what it delivered against the plain reference. With
``--trace 0`` it prints the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics from a profiled slice of the window. The last line
of standard output is one JSON object; the numbers compared for
``correct`` are the last lines of standard error.

It needs a TPU with as many chips as the cell asks for, and exits 2
without a result elsewhere. ``--control`` runs the program in its lower
precision preset in place of the configuration's (the control of the
check); ``--fault`` plants one of ``chipbench/faults.py``'s faults.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]

#: seconds of the window that a traced run profiles (its last ones)
TRACE_SECONDS = 8.0


def keep_every_program():
    """Keep the serve loop's small host-side programs in the persistent
    cache too, so that only a cell's first run in a checkout compiles."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="serve in the program's lower precision preset")
    ap.add_argument("--fault", default=None,
                    help="plant a fault under the timed path")
    ap.add_argument("--keep-trace", default=None,
                    help="with --trace 1: copy the profile and its Chrome "
                         "export into this directory")
    return ap.parse_args(argv)


def load_cell(root: pathlib.Path, name: str):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((root / entry["file"]).read_text())
    mix = json.loads(
        (root / "chipbench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, cfg, mix


def metrics_of(bench, cell, kind):
    """The cell's metrics of ``kind`` (end_to_end or per_layer)."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def quantity(name: str) -> str:
    """What a metric measures: ``<quantity>.<group>`` is the quantity
    under a name (and bound) of its own for the group of cells it lists,
    as ``latency_p50_ms.plan`` for the planner's cells."""
    return name.split(".", 1)[0]


def reader_path(root: pathlib.Path, name: str) -> pathlib.Path:
    """The metric's reader: ``metrics/<name>.py``, or the reader of its
    quantity where the metric has no reader of its own."""
    own = root / "chipbench" / "metrics" / f"{name}.py"
    return own if own.exists() else (
        root / "chipbench" / "metrics" / f"{quantity(name)}.py")


def read_metric(root: pathlib.Path, name: str, ctx):
    path = reader_path(root, name)
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


@contextlib.contextmanager
def watch_compiles():
    """Inside: the names of the programs JAX traces and compiles, from its
    compile log, which is kept off standard error (after set-up nothing
    should compile)."""
    import logging

    import jax

    names = []

    class Names(logging.Handler):
        def emit(self, record):
            msg = record.getMessage()
            if msg.startswith("Compiling "):
                names.append(msg.split(" ", 2)[1])

    logger = logging.getLogger("jax")
    saved = logger.handlers[:], logger.propagate
    logger.handlers, logger.propagate = [Names()], False
    jax.config.update("jax_log_compiles", True)
    try:
        yield names
    finally:
        jax.config.update("jax_log_compiles", False)
        logger.handlers, logger.propagate = saved


class CompileClock:
    """Seconds XLA spent compiling, summed from JAX's monitoring events
    (tracing and lowering not included), and how many compiles."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.total, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.total += duration
            self.count += 1


def end_to_end(outcome, setup_s, seconds):
    """The end-to-end numbers of a run, by the host clock: a request that
    was never delivered counts with the wait until the run gave up."""
    from chipbench.stats import percentile

    lat = [r.latency if r.latency is not None else outcome.t_end - r.due
           for r in outcome.population] or [0.0]
    return {
        "samples_per_s": len(outcome.delivered_in_window) / seconds,
        "latency_p50_ms": 1e3 * percentile(lat, 50),
        "latency_p95_ms": 1e3 * percentile(lat, 95),
        "setup_s": setup_s,
    }


def check(server, outcome, seed, limits, log):
    """The numbers that decide ``correct``, each beside the cell's limit."""
    from chipbench.server import Check, Delivered, relative_gap
    from chipbench.traffic import sample_indices

    done = [Delivered(r.spec, r.result, r.nfe) for r in outcome.population
            if r.delivered is not None]
    checks = [Check("undelivered", float(outcome.undelivered),
                    limits["undelivered"])]
    if done:
        longest = max(range(len(done)), key=lambda i: done[i].nfe)
        picks = sample_indices(seed, len(done), limits["sample_requests"],
                               longest)
        t = time.perf_counter()
        ref = server.reference_solve([done[i].spec for i in picks])
        gaps = [relative_gap(done[i].result, ref[j])
                for j, i in enumerate(picks)]
        log(f"reference: {len(picks)} requests, up to {done[longest].nfe} "
            f"NFE, {time.perf_counter() - t:.1f} s; gaps "
            + " ".join(f"{g:.2e}" for g in gaps))
        # the median holds the precision of every request; the count of
        # far-off answers catches the few that came back wrong (another
        # request's answer, a slot left out), which rounding never does
        checks.append(Check("sample_gap_median", statistics.median(gaps),
                            limits["sample_gap_median"]))
        if "sample_far_off" in limits:
            far = sum(g > limits["far_off_gap"] for g in gaps)
            checks.append(Check("sample_far_off", float(far),
                                limits["sample_far_off"]))
        if "sample_gap_max" in limits:
            checks.append(Check("sample_gap_max", max(gaps),
                                limits["sample_gap_max"]))
    checks += server.extra_checks(done, limits)
    return checks


def main(argv=None, *, root: pathlib.Path = ROOT,
         require_chip: bool = True) -> int:
    args = parse(argv)
    log = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    bench, cell, cfg, mix = load_cell(root, args.workload)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"run.py: the program is not in this checkout ({ROOT / 'src'})")
        return 2
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    import jax

    keep_every_program()

    devs = jax.devices()
    # set-up by phase: interpreter, imports and the chip's runtime; the
    # weights and the server; the warm-up
    phases = {"start": time.perf_counter() - T_PROCESS}
    if require_chip and (devs[0].platform != "tpu"
                         or len(devs) < cell["chips"]):
        log(f"run.py: cell {cell['name']} needs {cell['chips']} TPU chip(s), "
            f"JAX has {len(devs)} {devs[0].platform} device(s)")
        return 2
    from chipbench import faults, loop
    from chipbench.peaks import peaks_for
    from chipbench.traffic import Traffic

    peaks = peaks_for(devs[0].device_kind) if require_chip else None
    clock = CompileClock()
    tracer = None
    if args.trace:
        from repro.observability.tracing import StageTracer

        tracer = StageTracer(clock=time.perf_counter)
    family = importlib.import_module(f"chipbench.families.{cfg['family']}")
    server = family.build(
        cfg, mix, cfg["weights_key"],
        precision=cfg["control_precision"] if args.control
        else cfg["precision"],
        tracer=tracer, devices=cell["chips"],
        wrap_step=faults.wrap_step(args.fault))
    faults.plant_delivery(args.fault, server.batcher)
    phases["build"] = time.perf_counter() - T_PROCESS - sum(phases.values())
    if not loop.warm_up(server, float(mix["max_wait_s"])):
        log("set-up: the warm-up requests were not all delivered")
    compile_s, compiles = clock.total, clock.count
    traffic = Traffic(mix, args.seed, window_s=args.seconds,
                      obs_dim=cfg.get("observation_dim", 0),
                      returns_bins=cfg.get("returns_bins", 0))
    setup_s = time.perf_counter() - T_PROCESS
    phases["warm_up"] = setup_s - sum(phases.values())
    log(f"set-up {setup_s:.2f} s, backend compile {compile_s:.2f} s "
        f"({compiles} programs); " + ", ".join(
            f"{k} {v:.2f} s" for k, v in phases.items()))

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if args.trace else None
    sl = (loop.TraceSlice(server, trace_dir, min(TRACE_SECONDS, args.seconds),
                          tracer, chrome=bool(args.keep_trace))
          if args.trace else None)
    with watch_compiles() as late:
        outcome = loop.serve(server, traffic, args.seconds,
                             max_wait_s=float(mix["max_wait_s"]), trace=sl)
    if outcome.lateness:
        from chipbench.stats import percentile

        log(f"generator lateness: mean {statistics.mean(outcome.lateness) * 1e3:.3f}"
            f" ms, p95 {percentile(outcome.lateness, 95) * 1e3:.3f} ms, max "
            f"{max(outcome.lateness) * 1e3:.3f} ms over "
            f"{len(outcome.lateness)} requests")
    log(f"window: {len(outcome.population)} requests due, "
        f"{len(outcome.delivered_in_window)} delivered in it, "
        f"{len(late)} programs compiled after set-up {late[:8]}, counters "
        f"{json.dumps(outcome.counters)}")
    mem = [d.memory_stats() or {} for d in devs[: cell["chips"]]]
    memory_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": jax.device_count(), "memory_peak_bytes": memory_peak}
    result = {"correct": False, "attempted": len(outcome.population),
              "failed": outcome.undelivered}
    metrics = {}
    breakdown = None
    if args.trace:
        from chipbench import trace as tr

        ctx = layer_context(server, outcome, sl, trace_dir, tracer, peaks,
                            compile_s)
        if args.keep_trace:
            keep = pathlib.Path(args.keep_trace)
            keep.mkdir(parents=True, exist_ok=True)
            for f in pathlib.Path(trace_dir).rglob("*.*.*"):
                shutil.copy(f, keep / f.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        for m in metrics_of(bench, cell, "per_layer"):
            v = read_metric(root, m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": ctx.trace["device_ops"],
                     "idle_gaps": ctx.trace["idle_gaps"]}
        log("trace: " + json.dumps({k: ctx.trace[k] for k in
                                    ("busy_s", "window_s", "program_s",
                                     "program_runs")})
            + f" iterations {sl.iterations} useful_nfe {sl.useful_nfe}")
    else:
        e2e = end_to_end(outcome, setup_s, args.seconds)
        for m in metrics_of(bench, cell, "end_to_end"):
            metrics[m["name"]] = {"value": e2e[quantity(m["name"])],
                                  "unit": m["unit"]}

    # the program's state goes before the reference runs on the chip
    server.release()
    gc.collect()
    checks = check(server, outcome, args.seed, mix["check"], log)
    for c in checks:
        log(f"check {c.name} = {c.value!r} (limit {c.limit!r})"
            f" {'ok' if c.ok else 'FAILED'}")
    result.update(correct=all(c.ok for c in checks), metrics=metrics,
                  device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    print(json.dumps(result), flush=True)
    return 0


class Context:
    """What a per-layer metric reader gets."""


def layer_context(server, outcome, sl, trace_dir, tracer, peaks, compile_s):
    from chipbench import trace as tr

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    chips, host = tr.load(str(files[-1]))
    window = [s for s in host if s[2] == "bench/trace"]
    if not window:
        raise RuntimeError("the trace holds no bench/trace span")
    t0, t1 = window[0][0], window[0][1]
    # the serve loop's own stage spans, moved onto the trace's clock
    host = host + [(t0 + int((s["start"] - sl.mark) * 1e9),
                    t0 + int((s["end"] - sl.mark) * 1e9), s["name"])
                   for s in tracer.spans
                   if s["name"] != "serve/solve" and s["start"] >= sl.mark]
    ctx = Context()
    ctx.trace = tr.reduce(chips, host, t0, t1,
                          programs=[server.step_program])
    ctx.slice = {"iterations": sl.iterations, "useful_nfe": sl.useful_nfe}
    ctx.window = {"delivered": outcome.delivered_in_window,
                  **outcome.counters}
    ctx.step_program = server.step_program
    ctx.flop_per_eval = server.flop_per_eval
    ctx.peak_flops = peaks.flops_bf16
    ctx.compile_s = compile_s
    return ctx


if __name__ == "__main__":
    sys.exit(main())
