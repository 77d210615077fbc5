#!/usr/bin/env python3
"""Smoke test of the served sampling path at full width on TPU.

One process, one chip by default:

  serve      ``serve_diffusion`` with ``HIGHRES_DIT`` (256²×3, patch 16,
             width 768, 12 layers, fp32, seeded random weights with the
             zero-init leaves filled), device-resident, 8 slots, 16
             requests, sync horizon 4, mixed tolerance tiers. Checks that
             every request is delivered finite, that nfe == 2·(accepted +
             rejected) per request, that mean NFE orders draft < standard
             < high_fidelity, and that two delivered requests re-solved
             alone with ``adaptive()`` match them.
  reference  the served score forward at default matmul precision
             against the same forward at ``highest`` precision, on
             noised inputs at several t.
  kernels    the Pallas kernels with ``interpret=False`` at the main
             model's shapes, each against its ``ref.py``; the compiled
             program must hold a ``tpu_custom_call``.

``--chips 4`` runs only the sharded phase: the same requests served
over a 4-device data mesh (16 slots, 4 per device) and on one device.

It exits non-zero, printing no result, unless JAX's first device is a
TPU. The last line of its output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

  python chip_smoke.py
  python chip_smoke.py --chips 4
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

#: served sample vs the same request re-solved alone (or on another
#: mesh), max|Δ|/max|ref|. The two programs are compiled for different
#: batches (1 vs the slot batch), and at default precision the TPU rounds
#: fp32 matmul operands to bf16 wherever its fusions put the conversion,
#: so each score can differ by up to a default-precision forward's error
#: (FORWARD_RTOL). A wrong key, tolerance or slot mix-up gives O(1).
SAMPLE_RTOL = 1e-2

#: default-precision vs ``highest`` score forward, max|Δ|/max|ref|. At
#: default precision the TPU rounds fp32 matmul operands to bf16 (2^-9
#: relative); through 12 residual layers at width 768 that compounds to
#: under 1e-2. (A CPU forward of this net with all weights and
#: activations in bf16, a coarser rounding, is 9.8e-3 off fp32.)
FORWARD_RTOL = 2e-2

#: kernel vs ``ref.py`` (the reference at ``highest`` precision),
#: max|Δ|/max|ref|: the solver step is elementwise fp32 plus one row
#: reduction; flash attention and GroupNorm reduce through the MXU,
#: which may round fp32 operands to bf16 like a default-precision dot.
KERNEL_RTOL = {"error_step": 1e-5, "error_step_vec": 1e-5,
               "flash_attention": 2e-2, "groupnorm_silu": 2e-2}

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds XLA spent compiling, summed from JAX's monitoring events
    (tracing and lowering not included); ``lap()`` returns the seconds
    since the last lap."""

    def __init__(self):
        import jax

        self.total = 0.0
        self._mark = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.total += duration

    def lap(self) -> float:
        s, self._mark = self.total - self._mark, self.total
        return s


def rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def resolve_alone(batcher, net, req):
    """Re-solve one delivered request by itself with ``adaptive()``: its
    own prior draw and noise key, its tier's tolerance, batch 1."""
    import jax

    from repro.core.solvers.adaptive import adaptive
    from repro.models.dit import make_score_fn

    sde = batcher.sde
    atol, rtol, h0 = batcher._request_tol(req)
    k_prior, k_noise = jax.random.split(jax.random.PRNGKey(req.seed))
    x0 = sde.prior_sample(k_prior, batcher.shape)[None]
    res = jax.jit(lambda p, x, k: adaptive(
        sde, make_score_fn(p, net, sde), x, k, config=batcher.cfg,
        denoise=False, atol=atol, rtol=rtol, h0=h0,
    ))(batcher.params, x0, k_noise[None])
    return int(res.nfe[0]), jax.device_get(res.x[0])


def serve_phase(net: str = "highres", slots: int = 8, requests: int = 16,
                sync_horizon: int = 4):
    """Serve ``requests`` mixed-tier requests through the device-resident
    server and check what it delivered. Returns (record, batcher)."""
    import numpy as np

    from repro.configs.diffusion import DIT_NETS
    from repro.launch.serve import serve_diffusion

    t0 = time.perf_counter()
    rec = serve_diffusion(slots=slots, requests=requests, net=net,
                          sync_horizon=sync_horizon, device_resident=True,
                          tier="mixed")
    wall = time.perf_counter() - t0
    b = rec["batcher"]
    done = b.finished
    stats = rec["class_stats"]
    tiers = ("draft", "standard", "high_fidelity")
    mean_nfe = {k: stats[k]["mean_nfe"] for k in tiers if k in stats}
    out = {
        "wall_s": wall,
        "samples_per_s": rec["samples_per_sec"],
        "mean_nfe_per_tier": mean_nfe,
        "delivered": len(done) == requests and all(
            np.isfinite(r.result).all() for r in done.values()),
        "nfe_identity": all(r.nfe == 2 * (r.accepted + r.rejected)
                            for r in done.values()),
        "tiers_ordered": len(mean_nfe) == 3 and (
            mean_nfe["draft"] < mean_nfe["standard"]
            < mean_nfe["high_fidelity"]),
        "alone": [],
    }
    # one draft and one standard request: the cheap tiers
    picks = [min(u for u in done if done[u].tier == k)
             for k in ("draft", "standard")]
    for uid in picks:
        nfe, x = resolve_alone(b, DIT_NETS[net], done[uid])
        out["alone"].append({"uid": uid, "served_nfe": done[uid].nfe,
                             "alone_nfe": nfe,
                             "rel_err": rel_err(done[uid].result, x)})
    out["alone_match"] = all(a["served_nfe"] == a["alone_nfe"]
                             and a["rel_err"] <= SAMPLE_RTOL
                             for a in out["alone"])
    out["ok"] = all(out[k] for k in ("delivered", "nfe_identity",
                                     "tiers_ordered", "alone_match"))
    return out, b


def reference_phase(params, net: str = "highres", batch: int = 4,
                    ts=(0.01, 0.1, 0.5, 1.0)) -> dict:
    """The served score forward at default matmul precision against the
    same forward at ``highest`` precision, on noised inputs at ``ts``."""
    import jax
    import jax.numpy as jnp

    from repro.configs.diffusion import DIT_NETS
    from repro.core import VPSDE
    from repro.models.dit import make_score_fn

    cfg = DIT_NETS[net]
    sde = VPSDE()
    shape = (batch, cfg.image_size, cfg.image_size, cfg.channels)
    kx, kz = jax.random.split(jax.random.PRNGKey(7))
    x0 = 0.5 * jax.random.normal(kx, shape)
    z = jax.random.normal(kz, shape)
    served = jax.jit(lambda p, x, t: make_score_fn(p, cfg, sde)(x, t))
    highest = jax.jit(lambda p, x, t: make_score_fn(p, cfg, sde)(x, t))
    errs, scale = {}, 0.0
    for tv in ts:
        t = jnp.full((batch,), tv, jnp.float32)
        mean, std = sde.marginal(t)
        x = mean[:, None, None, None] * x0 + std[:, None, None, None] * z
        got = served(params, x, t)
        with jax.default_matmul_precision("highest"):
            want = highest(params, x, t)
        errs[str(tv)] = rel_err(got, want)
        scale = max(scale, float(jnp.max(jnp.abs(want))))
    worst = max(errs.values())
    # a zero forward (adaLN-Zero weights left at init) would pass vacuously
    return {"rel_err": errs, "max_rel_err": worst, "bound": FORWARD_RTOL,
            "max_abs_score": scale, "ok": worst <= FORWARD_RTOL and scale > 0}


def kernel_cases(batch: int = 8, image: int = 256, tokens: int = 256,
                 heads: int = 12, head_dim: int = 64,
                 gn_shapes=((32, 32), (16, 64), (8, 128)), groups: int = 8):
    """(name, kernel fn, reference fn, args) at the main model's shapes:
    the HIGHRES_DIT slot carry for the solver step, its attention for
    flash attention, and the trajectory UNet's GroupNorm→SiLU levels
    (``TRAJ_UNET``: horizon 32, base 32, mults 1-2-4; the DiT has no
    GroupNorm)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
    from repro.kernels.groupnorm_silu import ops as gn_ops, ref as gn_ref
    from repro.kernels.solver_step import ops as st_ops, ref as st_ref

    ks = iter(jax.random.split(jax.random.PRNGKey(11), 32))
    normal = lambda shape: jax.random.normal(next(ks), shape, jnp.float32)
    state = (batch, image, image, 3)
    x, xp, s2, z, xv = (normal(state) for _ in range(5))
    e0, d1, d2 = (0.01 * normal((batch,)) for _ in range(3))
    flat = lambda a: a.reshape(batch, -1)
    cases = []
    for name, ea, er in (
            ("error_step", 1e-2, 0.05),
            ("error_step_vec", jnp.full((batch,), 1e-2),
             jnp.linspace(0.01, 0.5, batch))):
        def kern(x, xp, s2, z, xv, e0, d1, d2, ea=ea, er=er, *, interpret):
            return st_ops.error_step(x, xp, s2, z, xv, e0, d1, d2,
                                     eps_abs=ea, eps_rel=er,
                                     interpret=interpret)

        def ref(x, xp, s2, z, xv, e0, d1, d2, ea=ea, er=er):
            xh, e2 = st_ref.error_step(flat(x), flat(xp), flat(s2), flat(z),
                                       flat(xv), e0, d1, d2,
                                       eps_abs=ea, eps_rel=er)
            return xh.reshape(x.shape), e2

        cases.append((name, kern, ref, (x, xp, s2, z, xv, e0, d1, d2)))
    q, k, v = (normal((batch, heads, tokens, head_dim)) for _ in range(3))
    cases.append((
        "flash_attention",
        lambda q, k, v, *, interpret: fa_ops.attention(
            q, k, v, causal=False, interpret=interpret),
        lambda q, k, v: fa_ref.attention(q, k, v, causal=False),
        (q, k, v),
    ))
    for h, c in gn_shapes:
        xs = normal((batch, h, c))
        scale, bias = 1.0 + 0.1 * normal((c,)), 0.1 * normal((c,))
        cases.append((
            "groupnorm_silu",
            lambda x, s, b, *, interpret: gn_ops.groupnorm_silu(
                x, s, b, groups=groups, interpret=interpret),
            lambda x, s, b: gn_ref.groupnorm_silu(x, s, b, groups=groups),
            (xs, scale, bias),
        ))
    return cases


def kernel_phase(cases, interpret: bool = False) -> dict:
    """Compile and run each kernel case, check it against its reference
    and (compiled for the chip) that the program holds the kernel."""
    import jax

    results = []
    for name, kern, ref, args in cases:
        compiled = jax.jit(
            lambda *a, kern=kern: kern(*a, interpret=interpret)
        ).lower(*args).compile()
        got = compiled(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref)(*args)
        got, want = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
        err = max(rel_err(g, w) for g, w in zip(got, want))
        custom = "tpu_custom_call" in compiled.as_text()
        results.append({"kernel": name,
                        "shape": list(args[0].shape),
                        "rel_err": err,
                        "tpu_custom_call": custom,
                        "ok": err <= KERNEL_RTOL[name]
                        and (custom or interpret)})
    return {"kernels": results, "ok": all(r["ok"] for r in results)}


def sharded_phase(net: str = "highres", devices: int = 4, slots: int = 16,
                  requests: int = 16, sync_horizon: int = 4) -> dict:
    """The same requests over a ``devices``-device data mesh and on one
    device: per request equal NFE and samples within ``SAMPLE_RTOL``,
    every device refilled, and the carry sharded over distinct devices."""
    from repro.launch.serve import serve_diffusion

    kw = dict(slots=slots, requests=requests, net=net,
              sync_horizon=sync_horizon, device_resident=True, tier="mixed")
    t0 = time.perf_counter()
    many = serve_diffusion(devices=devices, **kw)
    wall_many = time.perf_counter() - t0
    t0 = time.perf_counter()
    one = serve_diffusion(devices=1, **kw)
    wall_one = time.perf_counter() - t0
    bm, b1 = many["batcher"], one["batcher"]
    per_request = {
        u: (bm.finished[u].nfe == b1.finished[u].nfe,
            rel_err(bm.finished[u].result, b1.finished[u].result))
        for u in b1.finished if u in bm.finished
    }
    carry_devices = {s.device.id for s in bm._carry.x.addressable_shards}
    out = {
        "wall_s": {"mesh": wall_many, "one_device": wall_one},
        "samples_per_s": {"mesh": many["samples_per_sec"],
                          "one_device": one["samples_per_sec"]},
        "mean_nfe_per_tier": {k: v["mean_nfe"]
                              for k, v in many["class_stats"].items()},
        "delivered": len(per_request) == requests,
        "nfe_equal": all(eq for eq, _ in per_request.values()),
        "max_rel_err": max((e for _, e in per_request.values()), default=0.0),
        "refills_per_device": list(bm.refills_per_device),
        "carry_devices": len(carry_devices),
    }
    out["ok"] = (out["delivered"] and out["nfe_equal"]
                 and out["max_rel_err"] <= SAMPLE_RTOL
                 and len(out["refills_per_device"]) == devices
                 and all(r >= 1 for r in out["refills_per_device"])
                 and out["carry_devices"] == devices)
    return out


def _print_phase(name: str, rec: dict, compile_s: float) -> None:
    print(f"[{name}] compile_s={compile_s:.1f} " + json.dumps(rec),
          flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded serve phase on 4 chips")
    args = ap.parse_args()

    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's first device is "
              f"{dev.platform} ({dev.device_kind})", file=sys.stderr)
        return 2
    if jax.device_count() < args.chips:
        print(f"chip_smoke: --chips {args.chips} but only "
              f"{jax.device_count()} device(s)", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{jax.device_count()}",
          flush=True)
    clock = CompileClock()
    t_start = time.perf_counter()
    phases = {}
    if args.chips == 4:
        phases["sharded"] = sharded_phase()
        _print_phase("sharded", phases["sharded"], clock.lap())
    else:
        phases["serve"], server = serve_phase()
        _print_phase("serve", phases["serve"], clock.lap())
        phases["reference"] = reference_phase(server.params)
        _print_phase("reference", phases["reference"], clock.lap())
        phases["kernels"] = kernel_phase(kernel_cases())
        _print_phase("kernels", phases["kernels"], clock.lap())
    failed = [k for k, v in phases.items() if not v["ok"]]
    print(f"wall_s={time.perf_counter() - t_start:.1f} "
          f"compile_s={clock.total:.1f} failed={failed}", flush=True)
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
