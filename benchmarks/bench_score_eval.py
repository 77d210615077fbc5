"""Score-eval roofline bench (DESIGN.md §13): per-NFE forward cost.

The adaptive solver's wall-clock is NFE × score-eval time — every speed
lever in this repo either cuts NFE (the paper's contribution) or cuts
the per-NFE forward cost (the hot-path kernels). This bench measures
the second factor directly: one jitted score-network forward per row,
so ``us_per_call`` IS the per-NFE wall time at that batch.

Workloads × variants:

  * ``dit_cifar`` — the CIFAR-scale DiT (``configs.diffusion.CIFAR_DIT``,
    64 tokens, d_model 256); baseline = reference attention,
    fast = ``use_flash=True`` through the public attention owner.
  * ``unet_traj16x6`` / ``unet_traj32x8`` — the temporal UNet at the two
    trajectory shapes the serving benches use (horizon 16 × transition 6
    and 32 × 8), with the bottleneck attention block enabled; baseline =
    jnp attention + unfused GroupNorm→SiLU, fast = ``use_flash=True`` +
    ``use_fused_norm=True``.

Both variants of a workload share ONE param tree (the zero-init leaves —
``conv2``/``conv_out``/attention ``wo`` — are perturbed first, otherwise
the parity numbers compare kernels on activations that never reach
them), so the fast-vs-baseline parity in the derived column is a real
numerics check, per precision preset.

FLOPs/bytes per NFE come from the baseline variant's AOT
``compiled.cost_analysis()`` (via ``repro.analysis.hlo.summarize_cost``)
— the model cost, not the kernel implementation's, so "achieved FLOP/s"
is speed-of-light-normalized for both variants. The roofline join
(``repro.analysis.roofline.score_eval_markdown``) turns the artifact
into the compute-vs-memory-bound table, for a device in its peak table.

On CPU the Pallas kernels run in interpreter mode: wall-times validate
plumbing only and the speedup column is suppressed (parity is the
payload, per the kernel-bench convention). On an accelerator the same
artifact reports measured fast-vs-baseline speedup and achieved
fraction-of-peak.

CSV: ``score_eval_<workload>_<preset>_<variant>,us_per_call,derived``.
Artifact: ``experiments/score_eval/BENCH_score_eval.json`` (+
``ROOFLINE.md``, the rendered join, on a device with published peaks).
"""

from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.hlo import summarize_cost
from repro.observability.quality import proxy_fid
from repro.analysis.roofline import PEAKS, score_eval_markdown
from repro.configs.diffusion import CIFAR_DIT
from repro.core.precision import resolve_policy
from repro.models.dit import dit_forward, init_dit, liven_dit
from repro.models.temporal_unet import (
    TemporalUNetConfig, init_temporal_unet, temporal_unet_forward,
)

from .common import emit, timed

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join(ROOT, "experiments", "score_eval")

PRESETS = ("fp32", "bf16")
#: fast-vs-baseline max|Δ| / max|baseline| bound per preset; fp32 flash
#: and the fused norm are near-exact, bf16 adds one-vs-two rounding of
#: the GroupNorm→SiLU chain plus bf16 attention accumulate differences
PARITY_RTOL = {"fp32": 1e-3, "bf16": 8e-2}

DIT_BATCH = 8
UNET_BATCH = 16

# the two trajectory shapes the serving/planning benches exercise
TRAJ16 = TemporalUNetConfig(horizon=16, transition_dim=6, base=32,
                            mults=(1, 2), t_dim=32, groups=8,
                            attention=True, attn_heads=4)
TRAJ32 = TemporalUNetConfig(horizon=32, transition_dim=8, base=32,
                            mults=(1, 2, 4), t_dim=64, groups=8,
                            attention=True, attn_heads=4)


def _liven_unet(params, key):
    """Perturb the zero-init leaves so every branch carries signal.

    A fresh temporal UNet has zero-init ``conv2``/``conv_out``/attention
    ``wo`` (the bitwise-neutrality guardrails); benchmarking a net whose
    forward is identically zero would make every parity check pass
    vacuously.
    """
    ks = iter(jax.random.split(key, 64))
    bump = lambda w: 0.02 * jax.random.normal(next(ks), w.shape, w.dtype)
    blocks = ([d["res"] for d in params["downs"]]
              + [params["mid1"], params["mid2"]]
              + [u["res"] for u in params["ups"]])
    for blk in blocks:
        blk["conv2"] = bump(blk["conv2"])
    params["conv_out"] = bump(params["conv_out"])
    params["attn"]["wo"] = bump(params["attn"]["wo"])
    return params


def _dit_workload():
    cfg0 = CIFAR_DIT
    cfg1 = dataclasses.replace(cfg0, use_flash=True)
    params = liven_dit(init_dit(cfg0, jax.random.PRNGKey(0)),
                       jax.random.PRNGKey(2))
    x = jax.random.normal(
        jax.random.PRNGKey(1),
        (DIT_BATCH, cfg0.image_size, cfg0.image_size, cfg0.channels))
    t = jnp.linspace(0.1, 1.0, DIT_BATCH)

    def make(cfg, policy):
        p = policy.cast_params(params)
        return jax.jit(lambda x, t: dit_forward(p, x, t, cfg, policy=policy))

    return "dit_cifar", make, (cfg0, cfg1), (x, t), DIT_BATCH


def _unet_workload(name, cfg1):
    cfg0 = dataclasses.replace(cfg1, use_flash=False, use_fused_norm=False)
    fast = dataclasses.replace(cfg1, use_flash=True, use_fused_norm=True)
    params = _liven_unet(init_temporal_unet(cfg1, jax.random.PRNGKey(0)),
                         jax.random.PRNGKey(2))
    x = jax.random.normal(
        jax.random.PRNGKey(1),
        (UNET_BATCH, cfg1.horizon, cfg1.transition_dim))
    t = jnp.linspace(0.1, 1.0, UNET_BATCH)

    def make(cfg, policy):
        p = policy.cast_params(params)
        return jax.jit(
            lambda x, t: temporal_unet_forward(p, x, t, cfg, policy=policy))

    return name, make, (cfg0, fast), (x, t), UNET_BATCH


def main() -> None:
    on_cpu = jax.default_backend() == "cpu"
    workloads = [
        _dit_workload(),
        _unet_workload("unet_traj16x6", TRAJ16),
        _unet_workload("unet_traj32x8", TRAJ32),
    ]

    rows = []
    for wname, make, (cfg0, cfg1), args, batch in workloads:
        for preset in PRESETS:
            policy = resolve_policy(preset)
            base = make(cfg0, policy)
            fast = make(cfg1, policy)
            us_b, out_b = timed(base, *args, repeats=2)
            us_f, out_f = timed(fast, *args, repeats=2)

            # model cost per NFE from the baseline path's AOT analysis
            cost = summarize_cost(base.lower(*args).compile().cost_analysis())
            flops = cost.get("flops", 0.0)
            byts = cost.get("bytes_accessed", 0.0)

            a = jnp.asarray(out_b, jnp.float32)
            b = jnp.asarray(out_f, jnp.float32)
            scale = float(jnp.max(jnp.abs(a)))
            diff = float(jnp.max(jnp.abs(a - b)))
            ok = diff <= PARITY_RTOL[preset] * max(scale, 1e-3)
            # quality-proxy gauge (DESIGN.md §15): distributional drift
            # between the two variants' outputs under the fixed
            # random-projection extractor — a max|Δ| parity can stay
            # inside rtol while the output *distribution* shifts; this
            # catches that failure mode. dim=8 keeps the fitted moments
            # sane at these small bench batches.
            pfid = proxy_fid(np.asarray(a), np.asarray(b), dim=8, seed=0)

            common = {
                "workload": wname, "preset": preset, "batch": batch,
                "backend": jax.default_backend(),
                "flops_per_nfe": flops, "bytes_per_nfe": byts,
            }
            rows.append({**common, "variant": "baseline",
                         "us_per_call": us_b})
            fast_row = {**common, "variant": "fast", "us_per_call": us_f,
                        "parity_max_abs": diff, "parity_scale": scale,
                        "parity_pass": bool(ok), "proxy_fid": pfid}
            if not on_cpu:
                fast_row["speedup"] = us_b / us_f
            rows.append(fast_row)

            derived = (f"gflops_nfe={flops / 1e9:.2f}"
                       f"|parity={diff:.2e}|pass={ok}"
                       f"|proxy_fid={pfid:.2e}")
            if not on_cpu:
                derived += f"|speedup={us_b / us_f:.2f}x"
            emit(f"score_eval_{wname}_{preset}_baseline", us_b,
                 f"gflops_nfe={flops / 1e9:.2f}")
            emit(f"score_eval_{wname}_{preset}_fast", us_f, derived)

    device_kind = jax.devices()[0].device_kind
    artifact = {
        "backend": jax.default_backend(),
        "device_kind": device_kind,
        "interpret_mode": on_cpu,
        "note": ("CPU wall-times validate plumbing only (Pallas runs in "
                 "interpreter mode); parity is the payload. Accelerator "
                 "runs add measured speedup + achieved fraction-of-peak."),
        "rows": rows,
    }
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "BENCH_score_eval.json"), "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
    if device_kind in PEAKS:
        with open(os.path.join(OUT_DIR, "ROOFLINE.md"), "w") as f:
            f.write(score_eval_markdown(artifact) + "\n")


if __name__ == "__main__":
    main()
