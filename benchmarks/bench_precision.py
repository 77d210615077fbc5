"""Precision-policy benchmark: fp32 vs bf16 vs bf16_full (DESIGN.md §8).

Two workloads, each run under every preset:

  * **analytic OU conformance** — the exact-Gaussian setting of
    ``tests/test_solver_conformance.py``: x0 ~ N(MU, S0²) under VP, so
    the marginal mean/std at t_eps are known in closed form and the
    marginal-moment error of each preset is measured against an exact
    reference, not against another sampler;
  * **small DiT end-to-end** — a randomly-initialized DiT score net
    sampled with the adaptive solver, timing the full solve so the
    bf16 casts sit exactly where they would in production (the CPU CI
    host has no bf16 matmul units, so wall-clock parity — not speedup —
    is the expectation here; the artifact records the numbers that
    matter everywhere: NFE, iterations, moment drift).

Every row reports mean NFE, wall-clock, and the marginal-moment error;
the gate the conformance suite enforces (bf16 moment error ≤ 2× fp32,
mean NFE ≤ 1.25× fp32) is recomputed here and written to the artifact
``experiments/precision/bench_precision.json``.

CSV: ``precision_<workload>_<preset>,us_per_call,nfe=..|w2=..|...``
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp

from repro.core import VPSDE, AdaptiveConfig, sample
from repro.core.analytic import (
    gaussian_marginal_moments, gaussian_score, gaussian_w2,
)
from repro.core.precision import PRESETS, resolve_policy
from repro.models.dit import DiTConfig, init_dit, liven_dit, make_score_fn

from .common import emit, timed

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join(ROOT, "experiments", "precision")

MU, S0 = 0.3, 0.5
OU_SHAPE = (512, 8)
DIT_SHAPE = (16, 16, 16, 3)


def _moments(x) -> tuple:
    # fp32 upcast first: a bf16 state dtype must not leak reduction
    # error into the measurement
    xf = jnp.asarray(x, jnp.float32)
    return float(jnp.mean(xf)), float(jnp.std(xf))


def bench_ou(preset: str) -> dict:
    sde = VPSDE()
    score = gaussian_score(sde, MU, S0)
    cfg = AdaptiveConfig(eps_rel=0.05, precision=preset)
    fn = jax.jit(lambda k: sample(sde, score, OU_SHAPE, k,
                                  method="adaptive", config=cfg))
    us, res = timed(fn, jax.random.PRNGKey(0), repeats=3)
    mu_a, s_a = gaussian_marginal_moments(sde, MU, S0)
    mu, s = _moments(res.x)
    return {
        "workload": "ou", "preset": preset, "us_per_call": us,
        "mean_nfe": float(res.mean_nfe), "iterations": int(res.iterations),
        "mean_err": abs(mu - mu_a), "std_err": abs(s - s_a),
        "w2": gaussian_w2(mu, s, mu_a, s_a),
    }


def bench_dit(preset: str) -> dict:
    net = DiTConfig(image_size=16, patch=4, d_model=64, num_layers=2,
                    num_heads=4, d_ff=128)
    sde = VPSDE()
    policy = resolve_policy(preset)
    params = liven_dit(init_dit(net, jax.random.PRNGKey(0)),
                       jax.random.PRNGKey(2))
    score = make_score_fn(params, net, sde, policy=policy)
    cfg = AdaptiveConfig(eps_rel=0.05, precision=preset)
    fn = jax.jit(lambda k: sample(sde, score, DIT_SHAPE, k,
                                  method="adaptive", config=cfg))
    us, res = timed(fn, jax.random.PRNGKey(1), repeats=3)
    mu, s = _moments(res.x)
    return {
        "workload": "dit", "preset": preset, "us_per_call": us,
        "mean_nfe": float(res.mean_nfe), "iterations": int(res.iterations),
        "sample_mean": mu, "sample_std": s,
    }


def main() -> None:
    rows = []
    for preset in sorted(PRESETS):
        for bench in (bench_ou, bench_dit):
            r = bench(preset)
            rows.append(r)
            derived = "|".join(
                f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
                for k, v in r.items()
                if k not in ("workload", "preset", "us_per_call")
            )
            emit(f"precision_{r['workload']}_{preset}", r["us_per_call"], derived)

    by = {(r["workload"], r["preset"]): r for r in rows}
    ref = by[("ou", "fp32")]
    dit_ref = by[("dit", "fp32")]
    gates = {}
    for preset in ("bf16", "bf16_full"):
        r = by[("ou", preset)]
        d = by[("dit", preset)]
        gates[preset] = {
            # the conformance suite's gate, recomputed on the bench run
            "w2_vs_fp32": r["w2"] / max(ref["w2"], 1e-9),
            "moment_error_le_2x_fp32": bool(r["w2"] <= 2.0 * ref["w2"] + 1e-3),
            "nfe_vs_fp32": r["mean_nfe"] / ref["mean_nfe"],
            "nfe_le_1p25x_fp32": bool(r["mean_nfe"] <= 1.25 * ref["mean_nfe"]),
            "dit_moment_drift": abs(d["sample_std"] - dit_ref["sample_std"]),
        }
        emit(
            f"precision_gate_{preset}", 0.0,
            f"w2x={gates[preset]['w2_vs_fp32']:.3f}"
            f"|nfex={gates[preset]['nfe_vs_fp32']:.3f}"
            f"|pass={gates[preset]['moment_error_le_2x_fp32'] and gates[preset]['nfe_le_1p25x_fp32']}",
        )

    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "bench_precision.json"), "w") as f:
        json.dump({"rows": rows, "gates": gates}, f, indent=1, sort_keys=True)


if __name__ == "__main__":
    main()
