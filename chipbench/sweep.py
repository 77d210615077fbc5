"""Sweep of offered rates for an open-loop cell, to find its knee once.

  python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \
      --rates 4,6,8

One process builds the cell's server and warms it up as a run does, then
serves the cell's mix at each rate for a window of ``--seconds``,
draining between rates. A line per rate: offered and delivered rate, the
median and 95th-percentile latency, and how many of the window's
requests were still queued or in a slot when it closed. The knee is the
highest rate whose backlog does not grow; the cell's traffic file
records it and the rate chosen below it.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    bench, cell, cfg, mix = run.load_cell(run.ROOT, args.workload)
    for p in (str(run.ROOT / "src"), str(run.ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    run.keep_every_program()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep.py: needs a TPU", file=sys.stderr)
        return 2
    import importlib

    from chipbench import loop
    from chipbench.stats import percentile
    from chipbench.traffic import Traffic

    family = importlib.import_module(f"chipbench.families.{cfg['family']}")
    server = family.build(cfg, mix, cfg["weights_key"],
                          precision=cfg["precision"], tracer=None,
                          devices=cell["chips"])
    loop.warm_up(server, float(mix["max_wait_s"]))
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = Traffic(dict(mix, rate_per_s=rate), args.seed,
                          window_s=args.seconds,
                          obs_dim=cfg.get("observation_dim", 0),
                          returns_bins=cfg.get("returns_bins", 0))
        t = time.perf_counter()
        out = loop.serve(server, traffic, args.seconds,
                         max_wait_s=float(mix["max_wait_s"]),
                         uid_base=(k + 1) << 32)
        late = [r for r in out.population
                if r.delivered is None or r.delivered >= out.t1]
        lat = [r.latency for r in out.population if r.latency is not None]
        print(json.dumps({
            "rate_per_s": rate,
            "delivered_per_s": len(out.delivered_in_window) / args.seconds,
            "due": len(out.population),
            "open_at_close": len(late),
            "undelivered": sum(1 for r in out.population
                               if r.delivered is None),
            "p50_ms": 1e3 * percentile(lat, 50) if lat else None,
            "p95_ms": 1e3 * percentile(lat, 95) if lat else None,
            "wall_s": time.perf_counter() - t}), flush=True)
        server.batcher.run_to_completion()
    return 0


if __name__ == "__main__":
    sys.exit(main())
