"""Perf-iteration harness (§Perf): run named variants of an
(arch × shape) dry-run and append the roofline deltas to
experiments/perf/<arch>_<shape>.jsonl.

Each variant is a knob set (remat / moe_dispatch / fsdp / group size…).
The hypothesis → change → before/after → verdict narrative lives in
EXPERIMENTS.md; this harness produces the numbers.

  PYTHONPATH=src python -m repro.launch.perf --arch granite-moe-3b-a800m \
      --shape prefill_32k --variant moe-gather
"""

# Must precede any jax-initializing import (see dryrun.py).
import os  # noqa: E402
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    "--xla_backend_optimization_level=0 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse
import json
import time

import jax

from repro.analysis.hlo import collective_bytes_from_text, summarize_cost
from repro.analysis.roofline import DRYRUN_DEVICE_KIND, peaks_for
from repro.configs import ARCH_IDS, get_config, get_shape
from repro.configs.shapes import SHAPES
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_dryrun

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "perf")

# Named variants: kwargs forwarded to build_dryrun.
VARIANTS = {
    "baseline": {"last_logits_only": False},
    "last-logits": {},  # prefill head only on final position (now default)
    "seq-shard-attn": {"cfg_overrides": {"attn_q_seq_shard": "model"}},
    "seq-parallel": {"cfg_overrides": {"attn_q_seq_shard": "model",
                                       "residual_seq_shard": "model"}},
    "moe-pad48": {"moe_padded_experts": 48},
    "seq-shard+moe-pad48": {"moe_padded_experts": 48,
                            "cfg_overrides": {"attn_q_seq_shard": "model"}},
    "moe-gather": {"cfg_overrides": {"moe_dispatch": "gather"}},
    "remat-full": {"remat": "full"},
    "remat-dots": {"remat": "dots"},
    "fsdp": {"fsdp": True},
    "fsdp+remat": {"fsdp": True, "remat": "full"},
    "fsdp+moe-gather": {"fsdp": True,
                        "cfg_overrides": {"moe_dispatch": "gather"}},
    "zero1": {"zero1": True},
    "zero1+remat": {"zero1": True, "remat": "full"},
    "zero1+seqpar": {"zero1": True,
                     "cfg_overrides": {"residual_seq_shard": "model"}},
    "flash-decode": {"cfg_overrides": {"decode_flash_shard": "model"}},
    "flash-decode-2d": {"cfg_overrides": {"decode_flash_shard": "data,model"}},
}


def run_variant(arch: str, shape_name: str, variant: str,
                *, multi_pod: bool = False) -> dict:
    import dataclasses as _dc

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    kw = dict(VARIANTS[variant])
    pad = kw.pop("moe_padded_experts", None)
    if pad:
        ov = dict(kw.get("cfg_overrides", {}))
        ov["moe"] = _dc.replace(cfg.moe, padded_experts=pad)
        kw["cfg_overrides"] = ov

    def compile_one(c, unroll):
        spec = build_dryrun(
            c, shape, mesh, unroll=unroll,
            **{k: v for k, v in kw.items()},
        )
        with jax.set_mesh(mesh):
            jitted = jax.jit(spec.fn, in_shardings=spec.in_shardings,
                             out_shardings=spec.out_shardings,
                             donate_argnums=spec.donate_argnums)
            return jitted.lower(*spec.args).compile()

    t0 = time.time()
    full = compile_one(cfg, unroll=False)
    mem = full.memory_analysis()

    period = len(cfg.mixer_pattern)
    R = cfg.num_repeats
    pieces = []
    for reps in (1, 2):
        comp = compile_one(cfg.replace(num_layers=reps * period), unroll=True)
        m = comp.memory_analysis()
        pieces.append({
            "cost": summarize_cost(comp.cost_analysis()),
            "coll": collective_bytes_from_text(comp.as_text()),
            "traffic": (getattr(m, "argument_size_in_bytes", 0) or 0)
            + (getattr(m, "output_size_in_bytes", 0) or 0)
            + 2 * (getattr(m, "temp_size_in_bytes", 0) or 0),
        })

    def ext(f):
        return f(pieces[0]) + (R - 1) * max(f(pieces[1]) - f(pieces[0]), 0.0)

    rec = {
        "arch": arch, "shape": shape_name, "variant": variant,
        "mesh": "2pod" if multi_pod else "1pod",
        "flops": ext(lambda p: p["cost"].get("flops", 0.0)),
        "est_hbm_traffic_bytes": ext(lambda p: p["traffic"]),
        "collective_bytes": ext(lambda p: p["coll"]["total_bytes"]),
        "coll_by_kind": {
            k: int(max(ext(lambda p: p["coll"]["bytes_by_kind"].get(k, 0)), 0))
            for k in set().union(*(p["coll"]["bytes_by_kind"] for p in pieces))
        },
        "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "wall_s": round(time.time() - t0, 1),
    }
    # roofline terms for the chip the dry-run compiles for
    peaks = peaks_for(DRYRUN_DEVICE_KIND)
    rec["t_compute_s"] = rec["flops"] / peaks.flops
    rec["t_memory_s"] = rec["est_hbm_traffic_bytes"] / peaks.hbm_bw
    rec["t_collective_s"] = rec["collective_bytes"] / peaks.ici_bw
    terms = {k: rec[f"t_{k}_s"] for k in ("compute", "memory", "collective")}
    rec["dominant"] = max(terms, key=terms.get)

    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{arch}_{shape_name}.jsonl")
    with open(path, "a") as f:
        f.write(json.dumps(rec) + "\n")
    gb = 1024 ** 3
    print(f"[{arch} × {shape_name} × {variant}] "
          f"compute {rec['t_compute_s']:.3f}s  "
          f"memory {rec['t_memory_s']:.3f}s  "
          f"coll {rec['t_collective_s']:.3f}s  "
          f"dominant={rec['dominant']}  "
          f"peak {(rec['peak_bytes'] or 0) / gb:.1f} GiB  "
          f"args {(rec['argument_bytes'] or 0) / gb:.1f} GiB", flush=True)
    return rec


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--shape", choices=sorted(SHAPES), required=True)
    ap.add_argument("--variant", choices=sorted(VARIANTS), default="baseline")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    run_variant(args.arch, args.shape, args.variant, multi_pod=args.multi_pod)


if __name__ == "__main__":
    main()
