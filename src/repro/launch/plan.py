"""Receding-horizon planner launcher (DESIGN.md §10).

Runs the trajectory-diffusion planning closed loop on an analytic
environment: every control round each environment submits a plan
request (current state pinned via horizon-axis inpainting, optional
returns-bin CFG label) into the continuous-batching
``DiffusionBatcher``, executes the first action of its delivered plan,
and re-admits the re-conditioned request — the live form of the §7
retire/compact/admit lifecycle with §9 condition payloads aboard.

By default the score is the analytic returns-binned Gaussian
(``class_gaussian_noise_pred`` — exact, train-free, so the loop is
meaningful without a checkpoint); ``--unet`` swaps in a train-free
``temporal_unet`` to exercise the real network path (zero-init output
⇒ prior plans). ``--compare-em`` additionally prints the single-shot
adaptive-vs-EM NFE comparison on the trajectory shape — the paper's
headline economy on the third workload.

  PYTHONPATH=src python -m repro.launch.plan [--env ou|pointmass]
      [--envs 6] [--steps 4] [--slots 4] [--sync-horizon 4]
      [--horizon 8] [--cfg-scale 1.5] [--precision fp32] [--unet]
      [--unet-attention] [--fused-norm] [--compare-em 200]
      [--no-compaction]

``launch/serve --plan`` exposes the same loop through the serving CLI.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.core import AdaptiveConfig, VPSDE, sample
from repro.core.analytic import class_gaussian_noise_pred, gaussian_score
from repro.core.precision import PRESETS, resolve_policy
from repro.launch.cache import use_compile_cache
from repro.planning import (
    PlannerConfig, RecedingHorizonPlanner, get_env,
)

MU, S0 = 0.3, 0.5
RETURNS_BINS = 5


def _make_forward(pcfg: PlannerConfig, unet: bool, precision: str,
                  attention: bool = False, fused_norm: bool = False):
    """Noise-prediction ``forward_fn(params, x, t, y=None)`` + params:
    analytic returns-binned Gaussian (default) or a train-free
    ``temporal_unet`` (DESIGN.md §10). ``attention`` adds the
    bottleneck flash-attention block and ``fused_norm`` the fused
    GroupNorm→SiLU kernel — the §13 hot-path levers, flags so the
    serving loop can A/B them in place."""
    sde = VPSDE()
    policy = resolve_policy(precision)
    if not unet:
        fwd = class_gaussian_noise_pred(
            sde, MU + 0.5 * jnp.linspace(-1.0, 1.0, RETURNS_BINS), S0, MU)
        return sde, fwd, None
    from repro.models.temporal_unet import (
        TemporalUNetConfig, init_temporal_unet, temporal_unet_forward,
    )

    ucfg = TemporalUNetConfig(
        horizon=pcfg.horizon, transition_dim=pcfg.transition_dim,
        base=16, mults=(1, 2), t_dim=32, groups=4,
        returns_bins=RETURNS_BINS if pcfg.guidance_scale else 0,
        attention=attention, use_flash=attention,
        use_fused_norm=fused_norm,
    )
    params = policy.cast_params(
        init_temporal_unet(ucfg, jax.random.PRNGKey(0)))

    def fwd(p, x, t, y=None):
        return temporal_unet_forward(p, x, t, ucfg, policy=policy, y=y)

    return sde, fwd, params


def serve_planning(
    *, env_name: str = "ou", envs: int = 6, steps: int = 4,
    slots: int = 4, sync_horizon: int = 4, compaction: bool = True,
    horizon: int = 8, cfg_scale: float = 0.0, precision: str = "fp32",
    unet: bool = False, unet_attention: bool = False,
    fused_norm: bool = False,
) -> dict:
    """Closed-loop planning as a service (DESIGN.md §10): drain
    ``envs × steps`` plan requests through the batcher, executing each
    plan's first action between rounds. Prints plans/s, per-plan NFE,
    reward, and the §7 waste accounting."""
    env = get_env(env_name)
    pcfg = PlannerConfig(horizon=horizon, obs_dim=env.obs_dim,
                         act_dim=env.act_dim, guidance_scale=cfg_scale)
    sde, fwd, params = _make_forward(pcfg, unet, precision,
                                     attention=unet_attention,
                                     fused_norm=fused_norm)
    rh = RecedingHorizonPlanner(
        sde, fwd, params, pcfg, env,
        cfg=AdaptiveConfig(eps_rel=0.05, precision=precision),
        slots=slots, sync_horizon=sync_horizon, compaction=compaction,
    )
    returns_label = RETURNS_BINS - 1 if cfg_scale else None
    t0 = time.time()
    out = rh.rollout(jax.random.PRNGKey(1), n_envs=envs, n_steps=steps,
                     returns_label=returns_label)
    dt = time.time() - t0
    n_plans = envs * steps
    rec = {
        "env": env_name,
        "envs": envs,
        "steps": steps,
        "slots": slots,
        "sync_horizon": sync_horizon,
        "compaction": compaction,
        "score": "temporal_unet" if unet else "analytic",
        "cfg_scale": cfg_scale,
        "plans": n_plans,
        "plans_per_sec": n_plans / dt,
        "mean_nfe": float(out["nfe"].mean()),
        "mean_reward": float(out["rewards"].mean()),
        "final_round_reward": float(out["rewards"][-1].mean()),
        "wasted_nfe_fraction": out["wasted_nfe_fraction"],
        "passenger_nfe_fraction": out["passenger_nfe_fraction"],
        "refills_per_device": out["refills_per_device"],
    }
    print(f"plan serve[{env_name}, {rec['score']}, "
          f"cfg={cfg_scale}]: {n_plans} plans in {dt:.1f}s "
          f"({rec['plans_per_sec']:.2f} plans/s), "
          f"{envs} envs × {steps} rounds on {slots} slots "
          f"(horizon {sync_horizon}), mean NFE {rec['mean_nfe']:.0f}, "
          f"mean reward {rec['mean_reward']:.3f} "
          f"(final round {rec['final_round_reward']:.3f}), "
          f"wasted NFE {rec['wasted_nfe_fraction']:.1%}, "
          f"refills/device {rec['refills_per_device']}")
    return rec


def compare_em(horizon: int = 8, dim: int = 4, batch: int = 64,
               em_steps: int = 200) -> dict:
    """Single-shot adaptive-vs-EM NFE on the trajectory shape — the
    paper's headline on the third workload, same default tolerances as
    images (DESIGN.md §10)."""
    sde = VPSDE()
    score = gaussian_score(sde, MU, S0)
    shape = (batch, horizon, dim)
    key = jax.random.PRNGKey(0)
    res_ad = jax.jit(lambda k: sample(
        sde, score, shape, k, method="adaptive", eps_rel=0.05))(key)
    res_em = jax.jit(lambda k: sample(
        sde, score, shape, k, method="em", n_steps=em_steps))(key)
    rec = {
        "shape": shape,
        "adaptive_nfe": float(res_ad.mean_nfe),
        "em_nfe": float(res_em.mean_nfe),
        "nfe_ratio": float(res_ad.mean_nfe) / float(res_em.mean_nfe),
    }
    print(f"trajectory ({horizon}×{dim}): adaptive NFE "
          f"{rec['adaptive_nfe']:.0f} vs EM-{em_steps} NFE "
          f"{rec['em_nfe']:.0f} ({rec['nfe_ratio']:.2f}×)")
    return rec


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--env", default="ou", choices=["ou", "pointmass"])
    ap.add_argument("--envs", type=int, default=6)
    ap.add_argument("--steps", type=int, default=4,
                    help="control rounds per environment")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--sync-horizon", type=int, default=4)
    ap.add_argument("--no-compaction", action="store_true")
    ap.add_argument("--horizon", type=int, default=8,
                    help="plan horizon H (trajectory rows)")
    ap.add_argument("--cfg-scale", type=float, default=0.0,
                    help="returns-CFG guidance scale (DESIGN.md §10)")
    ap.add_argument("--precision", choices=sorted(PRESETS), default="fp32")
    ap.add_argument("--unet", action="store_true",
                    help="train-free temporal UNet instead of the "
                         "analytic score")
    ap.add_argument("--unet-attention", action="store_true",
                    help="with --unet: bottleneck self-attention block "
                         "routed through the flash kernel (DESIGN.md "
                         "§13; fresh block is the identity)")
    ap.add_argument("--fused-norm", action="store_true",
                    help="with --unet: fused GroupNorm→SiLU Pallas "
                         "kernel in every residual block (DESIGN.md §13)")
    ap.add_argument("--compare-em", type=int, default=None, metavar="N",
                    help="also print adaptive vs EM-N NFE on the "
                         "trajectory shape")
    args = ap.parse_args()
    serve_planning(
        env_name=args.env, envs=args.envs, steps=args.steps,
        slots=args.slots, sync_horizon=args.sync_horizon,
        compaction=not args.no_compaction, horizon=args.horizon,
        cfg_scale=args.cfg_scale, precision=args.precision, unet=args.unet,
        unet_attention=args.unet_attention, fused_norm=args.fused_norm,
    )
    if args.compare_em is not None:
        compare_em(horizon=args.horizon, em_steps=args.compare_em)


if __name__ == "__main__":
    main()
