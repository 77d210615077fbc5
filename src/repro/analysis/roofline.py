"""Roofline analysis from dry-run artifacts (deliverable g).

Per (arch × shape × mesh):
    compute    = HLO_FLOPs / (chips × peak_FLOP/s)
    memory     = HLO_bytes / (chips × HBM_bw)
    collective = collective_bytes / (chips × link_bw)

All numerators are per-device (the dry-run records per-device HLO costs),
so the formulas divide by per-chip peaks only. The peaks come from one
table keyed by ``device_kind`` (``PEAKS``); a device that is not in it
is an error, never a default. The dry-runs compile for the production
TPU v5e mesh (on placeholder CPU devices), so their terms are bounds for
that target, not measurements.

Also derives MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE) per device
and the usefulness ratio MODEL_FLOPS / HLO_FLOPs (catches remat and
redundant compute).

Usage:
  PYTHONPATH=src python -m repro.analysis.roofline [--mesh 1pod] [--md]
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import math
import os
from typing import Dict, Optional

from repro.configs import get_config, get_shape
from repro.configs.shapes import apply_shape_policy


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks."""

    flops: float   # bf16 FLOP/s
    hbm_bw: float  # HBM bytes/s
    ici_bw: float  # chip-to-chip interconnect bytes/s, all links of a chip
    source: str


#: per-chip peaks keyed by ``jax.Device.device_kind``
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s interchip interconnect",
    ),
}

#: what the dry-runs (experiments/dryrun/) compile for
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks_for(device_kind) -> ChipPeaks:
    """The ``PEAKS`` row of ``device_kind``; raises for any other device."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(PEAKS)}")
    return PEAKS[device_kind]


DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                          "experiments", "dryrun")
SCORE_EVAL_ARTIFACT = os.path.join(
    os.path.dirname(__file__), "..", "..", "..",
    "experiments", "score_eval", "BENCH_score_eval.json")


def _param_counts(cfg) -> Dict[str, float]:
    """(total, active) parameter counts excluding the embedding table
    (embeddings do lookup, not matmul; the LM head IS a matmul and is
    counted)."""
    import jax

    from repro.launch.specs import abstract_params

    shapes = abstract_params(cfg)
    total = active = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        n = math.prod(leaf.shape)
        if name == "embed":
            continue
        total += n
        if cfg.moe and "/mlp/w_" in name and "shared" not in name:
            # routed experts: only top_k of num_experts active per token
            active += n * cfg.moe.top_k / cfg.moe.num_experts
        else:
            active += n
    return {"total": total, "active": active}


def model_flops_per_device(arch: str, shape_name: str, devices: int) -> Dict:
    """6·N_active·D for train, 2·N_active·D forward-only shapes."""
    cfg = apply_shape_policy(get_config(arch), get_shape(shape_name))
    shape = get_shape(shape_name)
    counts = _param_counts(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        factor = 6.0
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        factor = 2.0
    else:  # decode: ONE token per sequence
        tokens = shape.global_batch
        factor = 2.0
    return {
        "model_flops_total": factor * counts["active"] * tokens,
        "model_flops_per_device": factor * counts["active"] * tokens / devices,
        "params_total": counts["total"],
        "params_active": counts["active"],
    }


def analyze_record(rec: dict) -> dict:
    flops = rec["cost"].get("flops", 0.0)
    bytes_acc = rec["cost"].get(
        "bytes_accessed", rec["cost"].get("est_hbm_traffic_bytes", 0.0)
    )
    coll = rec["collectives"]["total_bytes"]
    peaks = peaks_for(DRYRUN_DEVICE_KIND)
    t_compute = flops / peaks.flops
    t_memory = bytes_acc / peaks.hbm_bw
    t_coll = coll / peaks.ici_bw
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops_per_device(rec["arch"], rec["shape"], rec["devices"])
    ratio = mf["model_flops_per_device"] / flops if flops else float("nan")
    bound_time = max(terms.values())
    mfu_bound = (
        mf["model_flops_per_device"] / peaks.flops / bound_time
        if bound_time else float("nan")
    )
    return {
        **{f"t_{k}_s": v for k, v in terms.items()},
        "dominant": dominant,
        "model_flops_per_device": mf["model_flops_per_device"],
        "useful_ratio": ratio,
        "mfu_upper_bound": mfu_bound,
        "peak_gib": (rec["memory"]["peak_bytes"] or 0) / 2**30,
    }


def load_all(mesh: str = "1pod") -> Dict[str, dict]:
    from repro.configs import ARCH_IDS

    out = {}
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, f"*_{mesh}.json"))):
        rec = json.load(open(path))
        if rec["arch"] not in ARCH_IDS:
            continue  # extras (e.g. the dit-sampler dry-run) have their own report
        out[f"{rec['arch']}:{rec['shape']}"] = rec
    return out


def score_eval_markdown(artifact: Optional[dict] = None) -> str:
    """Roofline join for the score-eval bench (DESIGN.md §13).

    Each row of ``experiments/score_eval/BENCH_score_eval.json`` carries
    the per-NFE model FLOPs/bytes (baseline-path AOT cost analysis) and
    the measured per-NFE wall time; this join divides by the peaks of
    the device that made the record (its ``device_kind``) to classify
    each score eval as compute- or memory-bound and report achieved
    FLOP/s as a fraction of peak. A record from a device without a
    ``PEAKS`` row (the CPU among them) raises.
    """
    if artifact is None:
        with open(SCORE_EVAL_ARTIFACT) as f:
            artifact = json.load(f)
    peaks = peaks_for(artifact.get("device_kind"))
    header = ("workload", "preset", "variant", "us/NFE", "GFLOP/NFE",
              "t_compute_s", "t_memory_s", "bound", "achieved_GFLOP/s",
              "frac_peak")
    lines = ["| " + " | ".join(header) + " |", "|" + "---|" * len(header)]
    for r in artifact["rows"]:
        flops = float(r.get("flops_per_nfe") or 0.0)
        byts = float(r.get("bytes_per_nfe") or 0.0)
        t_c = flops / peaks.flops
        t_m = byts / peaks.hbm_bw
        bound = "compute" if t_c >= t_m else "memory"
        us = float(r["us_per_call"])
        achieved = flops / (us * 1e-6) if us else 0.0
        lines.append("| " + " | ".join((
            r["workload"], r["preset"], r["variant"], f"{us:.1f}",
            f"{flops / 1e9:.2f}", f"{t_c:.3e}", f"{t_m:.3e}", bound,
            f"{achieved / 1e9:.2f}", f"{achieved / peaks.flops:.2e}",
        )) + " |")
    lines.append("")
    lines.append(
        f"_device: {artifact['device_kind']}; peaks: "
        f"{peaks.flops / 1e12:.0f} TFLOP/s bf16, "
        f"{peaks.hbm_bw / 1e9:.0f} GB/s HBM ({peaks.source})._")
    return "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="1pod", choices=["1pod", "2pod"])
    ap.add_argument("--md", action="store_true", help="markdown table")
    ap.add_argument("--score-eval", action="store_true",
                    help="print the score-eval per-NFE roofline join "
                         "(reads experiments/score_eval/)")
    args = ap.parse_args()

    if args.score_eval:
        print(score_eval_markdown())
        return

    recs = load_all(args.mesh)
    if not recs:
        raise SystemExit(f"no dry-run records for mesh {args.mesh}")

    header = ("arch", "shape", "compute_s", "memory_s", "coll_s",
              "dominant", "useful", "mfu_ub", "peak_GiB")
    if args.md:
        print("| " + " | ".join(header) + " |")
        print("|" + "---|" * len(header))
    else:
        print(",".join(header))
    for key, rec in sorted(recs.items()):
        a = analyze_record(rec)
        row = (
            rec["arch"], rec["shape"],
            f"{a['t_compute_s']:.3e}", f"{a['t_memory_s']:.3e}",
            f"{a['t_collective_s']:.3e}", a["dominant"],
            f"{a['useful_ratio']:.2f}", f"{a['mfu_upper_bound']:.2f}",
            f"{a['peak_gib']:.1f}",
        )
        if args.md:
            print("| " + " | ".join(row) + " |")
        else:
            print(",".join(row))


if __name__ == "__main__":
    main()
