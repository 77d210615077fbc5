"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh).

Proves the distribution config is coherent without hardware: sharding
mismatches, unsupported collectives, and compile-time OOM all surface
here. Records memory_analysis / cost_analysis / collective bytes to
experiments/dryrun/<arch>_<shape>_<mesh>.json for the roofline pass.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--remat dots]
"""

# The 512 placeholder devices MUST be requested before any other import
# triggers jax initialization (device count locks on first init).
import os  # noqa: E402
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    # Dry-run code is never executed — skip CPU codegen effort (validated:
    # identical flops + collective bytes, ~2.4× faster compile).
    "--xla_backend_optimization_level=0 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse
import json
import time
import traceback

import jax

from repro.analysis.hlo import collective_bytes_from_text, summarize_cost
from repro.configs import ARCH_IDS, get_config, get_shape
from repro.configs.shapes import SHAPES
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_dryrun

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _compile_spec(cfg, shape, mesh, remat, unroll):
    spec = build_dryrun(cfg, shape, mesh, remat=remat, unroll=unroll)
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            spec.fn,
            in_shardings=spec.in_shardings,
            out_shardings=spec.out_shardings,
            donate_argnums=spec.donate_argnums,
        )
        lowered = jitted.lower(*spec.args)
        compiled = lowered.compile()
    return lowered, compiled


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            remat: str = "none", save: bool = True, verbose: bool = True) -> dict:
    """One (arch × shape × mesh) dry-run.

    Two-phase accounting (see EXPERIMENTS.md §Dry-run methodology):
      1. compile the PRODUCTION program (scan over layer super-blocks) —
         this is the pass/fail gate and the source of memory_analysis;
      2. compile 1-repeat and 2-repeat unrolled variants and extrapolate
         cost linearly in depth: total(R) = c1 + (R−1)·(c2−c1). Exact
         because every per-layer cost here is depth-linear, and it
         sidesteps XLA's cost_analysis counting loop bodies once.
    """
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2pod" if multi_pod else "1pod"
    t0 = time.time()

    # phase 1: production (scanned) program
    lowered, compiled = _compile_spec(cfg, shape, mesh, remat, unroll=False)
    t_full = time.time() - t0
    mem = compiled.memory_analysis()

    # phase 2: depth-extrapolated exact costs
    period = len(cfg.mixer_pattern)
    R = cfg.num_repeats
    t1 = time.time()
    costs, colls, traffics = [], [], []
    for reps in (1, 2):
        c_small = cfg.replace(num_layers=reps * period)
        _, comp = _compile_spec(c_small, shape, mesh, remat, unroll=True)
        costs.append(summarize_cost(comp.cost_analysis()))
        colls.append(collective_bytes_from_text(comp.as_text()))
        m = comp.memory_analysis()
        # HBM traffic estimate: every argument/output crosses HBM once,
        # every temp buffer is written + read ≥ once. (XLA's per-module
        # cost_analysis drops 'bytes accessed' for multi-computation
        # modules, so this memory_analysis-based estimate stands in.)
        traffics.append(
            (getattr(m, "argument_size_in_bytes", 0) or 0)
            + (getattr(m, "output_size_in_bytes", 0) or 0)
            + 2 * (getattr(m, "temp_size_in_bytes", 0) or 0)
        )
    t_extra = time.time() - t1
    est_traffic = traffics[0] + (R - 1) * max(traffics[1] - traffics[0], 0)

    def _extrapolate(key_fn):
        # per-layer increment clamped at >= 0: tiny decode layers fall
        # below XLA's const-folding noise floor and can make c2 < c1.
        c1, c2 = key_fn(costs[0], colls[0]), key_fn(costs[1], colls[1])
        return c1 + (R - 1) * max(c2 - c1, 0.0)

    cost = {
        k: costs[0].get(k, 0.0)
        + (R - 1) * max(costs[1].get(k, 0.0) - costs[0].get(k, 0.0), 0.0)
        for k in set(costs[0]) | set(costs[1])
    }
    cost["est_hbm_traffic_bytes"] = float(max(est_traffic, 0))
    coll_total = _extrapolate(lambda c, x: x["total_bytes"])
    coll = {
        "total_bytes": int(max(coll_total, 0)),
        "bytes_by_kind": {
            k: int(max(
                colls[0]["bytes_by_kind"].get(k, 0)
                + (R - 1) * (colls[1]["bytes_by_kind"].get(k, 0)
                             - colls[0]["bytes_by_kind"].get(k, 0)),
                0,
            ))
            for k in set(colls[0]["bytes_by_kind"]) | set(colls[1]["bytes_by_kind"])
        },
        "counts_r2": colls[1]["counts"],
        "method": "depth-extrapolated (R1/R2 unrolled)",
    }
    t_lower, t_compile = 0.0, t_full  # phase-1 timings dominate

    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "kind": shape.kind,
        "remat": remat,
        "devices": int(len(mesh.devices.flat)),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "extrapolation_s": round(t_extra, 1),
        "num_repeats": R,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        "cost": cost,  # already summarized per R1/R2 piece
        "collectives": coll,
    }
    if verbose:
        gb = 1024 ** 3
        pk = record["memory"]["peak_bytes"]
        print(
            f"[{arch} × {shape_name} × {mesh_name}] OK  "
            f"compile {t_compile:.0f}s (+{t_extra:.0f}s extrap)  "
            f"flops/dev {record['cost'].get('flops', 0):.3e}  "
            f"peak/dev {pk / gb if pk else float('nan'):.2f} GiB  "
            f"coll {coll['total_bytes'] / gb:.2f} GiB",
            flush=True,
        )
    if save:
        os.makedirs(OUT_DIR, exist_ok=True)
        fname = f"{arch}_{shape_name}_{mesh_name}.json".replace("/", "-")
        with open(os.path.join(OUT_DIR, fname), "w") as f:
            json.dump(record, f, indent=1)
    return record


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--remat", default="none", choices=["none", "full", "dots"])
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    combos = (
        [(a, s) for a in ARCH_IDS for s in SHAPES]
        if args.all
        else [(args.arch, args.shape)]
    )
    failures = []
    for arch, shape in combos:
        mesh_name = "2pod" if args.multi_pod else "1pod"
        fname = os.path.join(OUT_DIR, f"{arch}_{shape}_{mesh_name}.json")
        if args.skip_existing and os.path.exists(fname):
            print(f"[{arch} × {shape} × {mesh_name}] cached, skipping")
            continue
        try:
            run_one(arch, shape, multi_pod=args.multi_pod, remat=args.remat)
        except Exception as e:  # noqa: BLE001 — report every combo
            failures.append((arch, shape, repr(e)))
            print(f"[{arch} × {shape}] FAILED: {e}")
            traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nALL DRY-RUNS PASSED")


if __name__ == "__main__":
    main()
