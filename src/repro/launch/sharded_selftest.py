"""CPU self-test of the mesh-sharded sampling & serving path (DESIGN.md §3).

Forces fake host devices (the same ``xla_force_host_platform_device_count``
trick the production dry-run and tests/test_sharding_rules.py's sibling
integration test use), then executes — not just lowers — the multi-device
path end-to-end:

  1. ``sample(..., mesh=...)`` is bit-identical to the unsharded run for
     a fixed key, with both the jnp step math and the shard_map'd fused
     Pallas kernel;
  2. the fused ``sharded_error_step`` matches the single-device kernel,
     batch-sharded (bitwise) and batch+feature-sharded (the cross-device
     ``scaled_error_l2_psum`` combine, exact up to fp summation order);
  3. the mesh-sharded ``DiffusionBatcher`` completes every request and
     refills finished slots independently on every device.

Prints one JSON line with the results; exits non-zero on any failure.

  PYTHONPATH=src python -m repro.launch.sharded_selftest
  SELFTEST_DEVICES=8 PYTHONPATH=src python -m repro.launch.sharded_selftest
"""

# Fake devices MUST be requested before jax initializes.
import os  # noqa: E402

_DEVICES = int(os.environ.get("SELFTEST_DEVICES", "4"))
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={_DEVICES} "
    + os.environ.get("XLA_FLAGS", "")
)

import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import AdaptiveConfig, VPSDE, sample
from repro.core.analytic import gaussian_noise_pred, gaussian_score
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_data_mesh, make_mesh


def check_sample_equivalence(mesh, *, fused: bool) -> dict:
    """sample() sharded vs unsharded: same key ⇒ bit-identical output."""
    sde = VPSDE()
    score = gaussian_score(sde)
    shape = (2 * jax.device_count(), 64)
    cfg = AdaptiveConfig(eps_rel=0.05, use_fused_kernel=fused)
    key = jax.random.PRNGKey(0)
    ref = jax.jit(lambda k: sample(sde, score, shape, k, config=cfg))(key)
    sh = jax.jit(lambda k: sample(sde, score, shape, k, config=cfg, mesh=mesh))(key)
    n_shards = len(sh.x.sharding.device_set)
    return {
        "bitwise_equal": bool(
            np.array_equal(np.asarray(ref.x), np.asarray(sh.x))
            and np.array_equal(np.asarray(ref.nfe), np.asarray(sh.nfe))
        ),
        "max_abs_diff": float(jnp.max(jnp.abs(ref.x - sh.x))),
        "mean_nfe": float(ref.mean_nfe),
        "n_shards": n_shards,
        "sharded_over_devices": n_shards == jax.device_count(),
    }


def check_fused_kernel(mesh2d) -> dict:
    """sharded_error_step vs error_step, batch- and batch+feature-sharded."""
    from repro.kernels.solver_step import ops

    ks = jax.random.split(jax.random.PRNGKey(1), 8)
    B, shape = 8, (8, 10, 10, 3)  # D=300: exercises lane padding too
    x, xp, s2, z, xv = (jax.random.normal(k, shape) for k in ks[:5])
    e0, d1, d2 = (0.01 * jax.random.normal(k, (B,)) for k in ks[5:])
    kw = dict(eps_abs=1e-2, eps_rel=0.01)
    ref_x, ref_e = ops.error_step(x, xp, s2, z, xv, e0, d1, d2, **kw)
    b_x, b_e = ops.sharded_error_step(
        x, xp, s2, z, xv, e0, d1, d2, mesh=mesh2d, batch_axes=("data",), **kw
    )
    f_x, f_e = ops.sharded_error_step(
        x, xp, s2, z, xv, e0, d1, d2,
        mesh=mesh2d, batch_axes=("data",), feature_axis="model", **kw
    )
    return {
        "batch_sharded_bitwise": bool(
            np.array_equal(np.asarray(ref_x), np.asarray(b_x))
            and np.array_equal(np.asarray(ref_e), np.asarray(b_e))
        ),
        "feature_sharded_close": bool(
            np.array_equal(np.asarray(ref_x), np.asarray(f_x))
            and np.allclose(np.asarray(ref_e), np.asarray(f_e), rtol=1e-5)
        ),
    }


def check_batcher(mesh) -> dict:
    """Sharded DiffusionBatcher: completion + per-device slot refill."""
    from repro.launch.sample import make_sample_step
    from repro.models.dit import DiTConfig
    from repro.serving.diffusion_server import DiffusionBatcher, ImageRequest

    sde = VPSDE()
    cfg = AdaptiveConfig(eps_rel=0.05)
    forward_fn = gaussian_noise_pred(sde)

    net = DiTConfig(image_size=4, patch=4, d_model=8, num_layers=1,
                    num_heads=1, d_ff=8)  # signature holder; forward_fn wins
    step = make_sample_step(net, sde, cfg, forward_fn=forward_fn)
    ndev = jax.device_count()
    slots = 2 * ndev
    b = DiffusionBatcher(sde, step, params=None, sample_shape=(32,),
                         slots=slots, cfg=cfg, mesh=mesh, sync_horizon=4)
    n_req = 6 * ndev
    for uid in range(n_req):
        b.submit(ImageRequest(uid=uid, seed=uid))
    done = b.run_to_completion()
    xs = np.stack([done[u].result for u in range(n_req)]) \
        if len(done) == n_req else np.zeros((1, 1))

    # shard-locality + scheduling invariance: an unsharded batcher with a
    # different horizon must deliver bit-identical per-request samples —
    # per-slot keys make trajectories independent of slot placement,
    # compaction permutations, and device count
    b_ref = DiffusionBatcher(sde, step, params=None, sample_shape=(32,),
                             slots=slots, cfg=cfg, sync_horizon=1)
    for uid in range(n_req):
        b_ref.submit(ImageRequest(uid=uid, seed=uid))
    done_ref = b_ref.run_to_completion()
    invariant = len(done_ref) == n_req and len(done) == n_req and all(
        np.array_equal(done[u].result, done_ref[u].result)
        for u in range(n_req)
    )
    return {
        "all_completed": len(done) == n_req,
        "finite": bool(np.isfinite(xs).all()),
        "slots_per_device": b.slots_per_device,
        "refills_per_device": list(b.refills_per_device),
        # every device refilled beyond its initial fill ⇒ refill is
        # per-device, never gated on the global batch finishing
        "per_device_refill": all(
            r > b.slots_per_device for r in b.refills_per_device
        ),
        "total_assignments_match": sum(b.refills_per_device) == n_req,
        "wasted_nfe_fraction": b.wasted_nfe_fraction,
        "scheduling_invariant": bool(invariant),
    }


def check_device_resident(mesh) -> dict:
    """Device-resident serving on a real mesh (DESIGN.md §12): the
    donated multi-horizon driver + on-device event program must deliver
    bit-identical samples to the host-driven sharded loop, with fewer
    device→host transfers."""
    from repro.launch.sample import make_sample_step
    from repro.models.dit import DiTConfig
    from repro.serving.diffusion_server import DiffusionBatcher, ImageRequest

    sde = VPSDE()
    cfg = AdaptiveConfig(eps_rel=0.05)
    net = DiTConfig(image_size=4, patch=4, d_model=8, num_layers=1,
                    num_heads=1, d_ff=8)
    step = make_sample_step(net, sde, cfg,
                            forward_fn=gaussian_noise_pred(sde))
    ndev = jax.device_count()
    slots, n_req = 2 * ndev, 6 * ndev

    def run(device_resident):
        b = DiffusionBatcher(sde, step, params=None, sample_shape=(32,),
                             slots=slots, cfg=cfg, mesh=mesh,
                             sync_horizon=4,
                             device_resident=device_resident)
        for uid in range(n_req):
            b.submit(ImageRequest(uid=uid, seed=uid))
        done = b.run_to_completion()
        return b, done

    b_host, done_host = run(False)
    b_res, done_res = run(True)
    completed = len(done_host) == n_req and len(done_res) == n_req
    return {
        "all_completed": completed,
        "bitwise_equal": completed and all(
            np.array_equal(done_host[u].result, done_res[u].result)
            for u in range(n_req)
        ),
        "iterations_equal": b_host.total_iterations == b_res.total_iterations,
        "host_transfers": b_host.host_transfers,
        "resident_transfers": b_res.host_transfers,
        "transfers_reduced": b_res.host_transfers < b_host.host_transfers,
    }


def main() -> int:
    use_compile_cache()
    ndev = jax.device_count()
    mesh = make_data_mesh()
    mesh2d = make_mesh((ndev // 2, 2), ("data", "model"))
    results = {
        "devices": ndev,
        "sample_jnp": check_sample_equivalence(mesh, fused=False),
        "sample_fused": check_sample_equivalence(mesh, fused=True),
        "fused_kernel": check_fused_kernel(mesh2d),
        "batcher": check_batcher(mesh),
        "device_resident": check_device_resident(mesh),
    }
    ok = (
        ndev >= 2
        and results["sample_jnp"]["bitwise_equal"]
        and results["sample_jnp"]["sharded_over_devices"]
        and results["sample_fused"]["bitwise_equal"]
        and results["fused_kernel"]["batch_sharded_bitwise"]
        and results["fused_kernel"]["feature_sharded_close"]
        and results["batcher"]["all_completed"]
        and results["batcher"]["finite"]
        and results["batcher"]["per_device_refill"]
        and results["batcher"]["total_assignments_match"]
        and results["batcher"]["scheduling_invariant"]
        and results["device_resident"]["bitwise_equal"]
        and results["device_resident"]["iterations_equal"]
        and results["device_resident"]["transfers_reduced"]
    )
    results["ok"] = ok
    print(json.dumps(results))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
