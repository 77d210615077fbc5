"""Serving launchers: batched LM decode, mesh-sharded diffusion, and
receding-horizon planning.

LM mode prefills a batch of prompts through ``forward`` (building the KV
caches by replaying tokens through ``serve_step`` — exact,
cache-consistent), then decodes greedily. On CPU this demonstrates the
full serving path with reduced configs; the production mesh lowers the
same ``serve_step``.

``--diffusion`` runs the continuous-batching diffusion server
(DESIGN.md §4) instead, optionally sharded over ``--fake-devices N``
placeholder devices so the per-device slot-refill path is exercised on a
CPU-only host exactly as it would run on a real data-parallel mesh.

``--plan`` runs the receding-horizon trajectory planner as a service
(DESIGN.md §10): closed-loop plan requests (state pinned via
horizon-axis inpainting, optional ``--cfg-scale`` returns guidance)
draining through the same ``DiffusionBatcher`` —
``repro.launch.plan`` is the underlying launcher.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
      --batch 4 --prompt-len 32 --gen-len 32
  PYTHONPATH=src python -m repro.launch.serve --diffusion --fake-devices 4 \
      --slots 8 --requests 32
  PYTHONPATH=src python -m repro.launch.serve --plan --envs 6 --plan-steps 4
"""

from __future__ import annotations

# Placeholder devices MUST be requested before jax first initializes.
import os  # noqa: E402

from repro.launch._argv import argv_value  # noqa: E402

_n = argv_value("--fake-devices")
if _n and _n.isdigit() and int(_n) > 0:
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_n} "
        + os.environ.get("XLA_FLAGS", "")
    )

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.configs.diffusion import DIT_NETS
from repro.core.precision import PRESETS
from repro.launch.cache import use_compile_cache
from repro.launch.steps import make_serve_step
from repro.models import init_decode_state, init_model
from repro.models.config import ModelConfig


def serve_batch(
    cfg: ModelConfig,
    params,
    prompts,  # (B, P[, K]) int32
    *,
    gen_len: int = 32,
    cache_len: int | None = None,
    cross_embeds=None,
):
    B = prompts.shape[0]
    P = prompts.shape[1]
    cache_len = cache_len or (P + gen_len)
    state = init_decode_state(cfg, B, cache_len)
    step = jax.jit(make_serve_step(cfg))

    # prefill by replay (exact; a fused prefill is a perf lever, §Perf)
    next_tok = None
    for i in range(P):
        b = {"tokens": prompts[:, i : i + 1]}
        if cross_embeds is not None:
            b["cross_embeds"] = cross_embeds
        next_tok, state = step(params, b, state)

    out = [next_tok]
    for _ in range(gen_len - 1):
        b = {"tokens": out[-1]}
        if cross_embeds is not None:
            b["cross_embeds"] = cross_embeds
        nt, state = step(params, b, state)
        out.append(nt)
    return jnp.concatenate(out, axis=1)


def serve_diffusion(*, slots: int, requests: int, net: str = "small",
                    devices: int | None = None,
                    sync_horizon: int = 4, compaction: bool = True,
                    precision: str = "fp32", inpaint: bool = False,
                    cfg_scale: float | None = None,
                    device_resident: bool = False,
                    tier: str | None = None,
                    deadline_ms: float | None = None,
                    telemetry: int = 0,
                    metrics_out: str | None = None,
                    trace_out: str | None = None) -> dict:
    """Continuous-batching diffusion serving on the ambient device set.

    Builds a data-parallel mesh over the first ``devices`` devices (all
    by default), shards the slot batch across it, and drains
    ``requests`` prior-seeded requests through the DiT ``net`` names in
    ``configs.diffusion.DIT_NETS`` (random weights from seed 0, with the
    zero-init leaves filled by ``liven_dit`` so the score is not
    identically 0) with the horizon-chunked solver:
    ``sync_horizon`` Algorithm-1 iterations per host round-trip, with
    converged slots retired and refilled at every sync (DESIGN.md §7).
    Returns (and prints) throughput, the wasted-NFE fraction, and the
    per-device refill counts that evidence shard-local compaction; the
    record's ``batcher`` entry is the drained ``DiffusionBatcher``
    (delivered requests in ``batcher.finished``).

    ``device_resident=True`` (DESIGN.md §12) runs the on-device serve
    loop instead: retirement polling, compaction, and admission execute
    in donated jitted programs, and the host is consulted only when a
    delivery or admission actually occurs — the printed record then
    also carries host-transfer counts.

    Per-request conditioning (DESIGN.md §9): ``inpaint=True`` attaches
    a checkerboard mask (phase alternating per request) to every
    request; ``cfg_scale`` switches to a class-conditional DiT with
    classifier-free guidance, labels cycling per request uid. The
    conditioner is per-server (one compiled program); the payload is
    per-request and travels with its slot through compaction.

    Tolerance tiers (DESIGN.md §14): ``tier`` names a quality class
    every request rides (``draft``/``standard``/``high_fidelity``), or
    ``"mixed"`` to cycle the presets across requests — the tiered
    server then runs EDF-within-priority-band admission and the record
    carries per-class NFE + deadline stats. ``deadline_ms`` sets each
    request's latency budget; late deliveries count as misses.

    Observability (DESIGN.md §15): ``telemetry=N`` attaches an N-deep
    per-slot step-telemetry ring to the carry (0 = off, bit-identical
    serve loop); ``metrics_out`` writes the metrics registry as JSON
    plus a sibling ``.prom`` Prometheus text file after the drain;
    ``trace_out`` turns on the stage tracer and writes the full
    ``trace_record()`` (requests, metrics, spans, step history) as JSON
    — the input of ``repro.analysis.telemetry``'s markdown report.
    """
    import dataclasses

    from repro.core import AdaptiveConfig, VPSDE
    from repro.core.guidance import ClassifierFree, Inpaint
    from repro.core.precision import resolve_policy
    from repro.launch.mesh import make_data_mesh
    from repro.launch.sample import make_sample_step
    from repro.models.dit import init_dit, liven_dit
    from repro.observability.tracing import StageTracer
    from repro.serving.diffusion_server import DiffusionBatcher, ImageRequest
    from repro.serving.scheduler import EdfPriorityAdmission

    if inpaint and cfg_scale is not None:
        raise ValueError("pick one conditioner per server: "
                         "--inpaint or --cfg-scale")
    mesh = make_data_mesh(devices)
    ndev = mesh.size
    num_classes = 10 if cfg_scale is not None else 0
    net_name = net
    net = dataclasses.replace(DIT_NETS[net_name], num_classes=num_classes)
    image_size = net.image_size
    sde = VPSDE()
    policy = resolve_policy(precision)
    conditioner = None
    if inpaint:
        conditioner = Inpaint()
    elif cfg_scale is not None:
        conditioner = ClassifierFree(scale=float(cfg_scale))
    cfg = AdaptiveConfig(eps_rel=0.05, precision=precision,
                         conditioner=conditioner)
    # weights stored at the policy's param dtype; the per-device weight
    # HBM and weight-broadcast bytes halve under bf16_full
    k_init, k_live = jax.random.split(jax.random.PRNGKey(0))
    # one program: an eager init compiles every op of it on its own
    params = jax.jit(lambda: policy.cast_params(
        liven_dit(init_dit(net, k_init), k_live)))()
    step = make_sample_step(net, sde, cfg)
    shape = (image_size, image_size, net.channels)
    tiered = tier is not None
    if tiered and tier != "mixed":
        from repro.configs.diffusion import resolve_tier
        resolve_tier(tier)  # fail fast on a bad preset name
    tracer = StageTracer() if trace_out else None
    b = DiffusionBatcher(sde, step, params, shape,
                         slots=slots, cfg=cfg, mesh=mesh,
                         sync_horizon=sync_horizon, compaction=compaction,
                         device_resident=device_resident,
                         tolerance_classes=tiered or None,
                         admission=(EdfPriorityAdmission(aging_s=5.0)
                                    if tiered else None),
                         telemetry=telemetry, tracer=tracer)
    mixed_cycle = ("draft", "standard", "high_fidelity")

    def request_tier(uid: int):
        if not tiered:
            return None
        return mixed_cycle[uid % len(mixed_cycle)] if tier == "mixed" else tier

    def request_cond(uid: int):
        if inpaint:
            yy, xx = jnp.mgrid[:image_size, :image_size]
            mask = (((yy // 2 + xx // 2) + uid) % 2 == 0)
            mask = jnp.broadcast_to(mask[:, :, None], shape)
            observed = jnp.broadcast_to(
                jnp.linspace(-0.5, 0.5, image_size)[:, None, None], shape)
            return {"mask": mask.astype(jnp.float32),
                    "observed": jnp.asarray(observed, jnp.float32)}
        if cfg_scale is not None:
            return {"label": uid % num_classes}
        return None

    for uid in range(requests):
        b.submit(ImageRequest(uid=uid, seed=uid, cond=request_cond(uid),
                              tier=request_tier(uid),
                              deadline_ms=deadline_ms))
    t0 = time.time()
    done = b.run_to_completion()
    dt = time.time() - t0
    nfes = [done[u].nfe for u in sorted(done)]
    rec = {
        "net": net_name,
        "devices": ndev,
        "slots": slots,
        "slots_per_device": b.slots_per_device,
        "sync_horizon": sync_horizon,
        "compaction": compaction,
        "precision": policy.as_dict(),
        "conditioner": ("inpaint" if inpaint
                        else f"cfg:{cfg_scale}" if cfg_scale is not None
                        else "none"),
        "completed": len(done),
        "samples_per_sec": len(done) / dt,
        "mean_nfe": sum(nfes) / len(nfes),
        "total_iterations": b.total_iterations,
        "wasted_nfe_fraction": b.wasted_nfe_fraction,
        "refills_per_device": list(b.refills_per_device),
        "device_resident": device_resident,
        "host_transfers": b.host_transfers,
        "host_transfers_per_request": b.host_transfers / max(len(done), 1),
        "tier": tier,
        "deadline_ms": deadline_ms,
        "class_stats": b.class_stats if tiered else None,
        "telemetry": telemetry,
        "metrics_out": metrics_out,
        "trace_out": trace_out,
        "batcher": b,
    }
    if metrics_out:
        import json
        import pathlib

        reg = b.metrics_snapshot()
        path = pathlib.Path(metrics_out)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(reg.to_json(), indent=2) + "\n")
        # Prometheus text exposition rides next to the JSON, same stem
        path.with_suffix(".prom").write_text(reg.to_prometheus())
        print(f"metrics -> {path} (+ {path.with_suffix('.prom').name})")
    if trace_out:
        import json
        import pathlib

        path = pathlib.Path(trace_out)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(b.trace_record(), indent=2) + "\n")
        print(f"trace -> {path}")
    print(f"diffusion serve[{net_name}, {policy.name}, {rec['conditioner']}"
          f"{', device-resident' if device_resident else ''}]: "
          f"{rec['completed']}/{requests} requests in {dt:.1f}s "
          f"({rec['samples_per_sec']:.2f} samples/s) on {ndev} device(s), "
          f"{b.slots_per_device} slots/device, horizon {sync_horizon}, "
          f"mean NFE {rec['mean_nfe']:.0f}, "
          f"wasted NFE {rec['wasted_nfe_fraction']:.1%}, "
          f"host transfers/request {rec['host_transfers_per_request']:.1f}, "
          f"refills/device {rec['refills_per_device']}")
    if tiered:
        for name in sorted(rec["class_stats"]):
            s = rec["class_stats"][name]
            print(f"  tier {name:>13}: {s['delivered']} delivered, "
                  f"mean NFE {s['mean_nfe']:.0f}, "
                  f"deadline misses {s['deadline_misses']}, "
                  f"mean wait {s['mean_wait_s'] * 1e3:.0f}ms")
    return rec


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--diffusion", action="store_true",
                    help="run the mesh-sharded diffusion server instead")
    ap.add_argument("--plan", action="store_true",
                    help="run the receding-horizon planner service "
                         "(DESIGN.md §10)")
    ap.add_argument("--plan-env", default="ou", choices=["ou", "pointmass"],
                    help="analytic environment for --plan")
    ap.add_argument("--envs", type=int, default=6,
                    help="closed-loop environments for --plan")
    ap.add_argument("--plan-steps", type=int, default=4,
                    help="control rounds per environment for --plan")
    ap.add_argument("--plan-horizon", type=int, default=8,
                    help="plan horizon H for --plan")
    ap.add_argument("--unet", action="store_true",
                    help="--plan with a train-free temporal UNet score "
                         "instead of the analytic one")
    ap.add_argument("--fake-devices", type=int, default=None,
                    help="force N placeholder host devices (set pre-init)")
    ap.add_argument("--net", default="small", choices=sorted(DIT_NETS),
                    help="DiT score net to serve (diffusion mode; "
                         "configs.diffusion.DIT_NETS)")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--sync-horizon", type=int, default=4,
                    help="device iterations per host sync (diffusion mode)")
    ap.add_argument("--no-compaction", action="store_true",
                    help="monolithic-wave baseline: no mid-flight slot refill")
    ap.add_argument("--device-resident", action="store_true",
                    help="on-device serve loop (DESIGN.md §12): donated "
                         "carry, event-driven host syncs (diffusion mode)")
    ap.add_argument("--precision", default="fp32", choices=sorted(PRESETS),
                    help="precision policy for the diffusion server "
                         "(DESIGN.md §8); error control always stays fp32")
    ap.add_argument("--inpaint", action="store_true",
                    help="per-request checkerboard-mask inpainting "
                         "(diffusion mode, DESIGN.md §9)")
    ap.add_argument("--cfg-scale", type=float, default=None,
                    help="per-request classifier-free guidance at this "
                         "scale (diffusion mode, DESIGN.md §9)")
    ap.add_argument("--tier", default=None,
                    help="tolerance class for diffusion requests — a "
                         "preset (draft/standard/high_fidelity) or "
                         "'mixed' to cycle presets across requests "
                         "(DESIGN.md §14)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request latency budget; late deliveries "
                         "count as deadline misses in the per-class "
                         "stats (diffusion mode, DESIGN.md §14)")
    ap.add_argument("--telemetry", type=int, default=0,
                    help="per-slot step-telemetry ring capacity; 0 = off "
                         "(bit-identical serve loop, DESIGN.md §15)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the metrics registry as JSON here plus a "
                         "sibling .prom Prometheus text file "
                         "(diffusion mode, DESIGN.md §15)")
    ap.add_argument("--trace-out", default=None,
                    help="enable stage tracing and write the JSON trace "
                         "record here — feed it to "
                         "'python -m repro.analysis.telemetry' for the "
                         "markdown report (diffusion mode, DESIGN.md §15)")
    args = ap.parse_args()

    if args.plan:
        from repro.launch.plan import serve_planning

        serve_planning(env_name=args.plan_env, envs=args.envs,
                       steps=args.plan_steps, slots=args.slots,
                       sync_horizon=args.sync_horizon,
                       compaction=not args.no_compaction,
                       horizon=args.plan_horizon,
                       cfg_scale=args.cfg_scale or 0.0,
                       precision=args.precision, unet=args.unet)
        return
    if args.diffusion:
        serve_diffusion(slots=args.slots, requests=args.requests,
                        net=args.net,
                        sync_horizon=args.sync_horizon,
                        compaction=not args.no_compaction,
                        precision=args.precision,
                        inpaint=args.inpaint, cfg_scale=args.cfg_scale,
                        device_resident=args.device_resident,
                        tier=args.tier, deadline_ms=args.deadline_ms,
                        telemetry=args.telemetry,
                        metrics_out=args.metrics_out,
                        trace_out=args.trace_out)
        return
    if args.arch is None:
        ap.error("--arch is required unless --diffusion is given")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.scaled_down()
    key = jax.random.PRNGKey(0)
    params = init_model(cfg, key)
    shape = (args.batch, args.prompt_len)
    if cfg.num_codebooks > 1:
        shape += (cfg.num_codebooks,)
    prompts = jax.random.randint(key, shape, 0, cfg.vocab_size)
    cross = (
        jax.random.normal(key, (args.batch, cfg.num_patches, cfg.vision_dim),
                          jnp.dtype(cfg.dtype))
        if cfg.vision_dim else None
    )
    t0 = time.time()
    toks = serve_batch(cfg, params, prompts, gen_len=args.gen_len,
                       cross_embeds=cross)
    dt = time.time() - t0
    n_new = toks.shape[1] * args.batch
    print(f"generated {toks.shape} in {dt:.1f}s ({n_new / dt:.1f} tok/s)")
    print("sample:", jax.device_get(toks[0, :16]).tolist())


if __name__ == "__main__":
    main()
