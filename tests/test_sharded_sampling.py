"""Mesh-sharded sampling & serving (DESIGN.md §3).

Two layers of coverage:

  * in-process, on the single real CPU device: a degenerate 1-device
    mesh must be a bit-exact no-op for ``sample(..., mesh=...)``, the
    shard_map'd fused kernel, and the sharded ``DiffusionBatcher`` —
    cheap guards that run on every test invocation;
  * subprocess, with ≥2 fake host devices forced via
    ``xla_force_host_platform_device_count`` (the same trick the
    production dry-run uses): ``repro.launch.sharded_selftest`` executes
    the genuinely multi-device path and asserts (a) bit-identical
    samples sharded vs unsharded for a fixed seed, and (b) per-device
    slot refill in the batcher.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AdaptiveConfig, VPSDE, sample
from repro.core.analytic import gaussian_noise_pred, gaussian_score
from repro.launch.mesh import make_data_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MU, S0 = 0.3, 0.5


def _score(sde):
    return gaussian_score(sde, MU, S0)


# ---------------------------------------------------------------------------
# in-process: 1-device mesh is an exact no-op
# ---------------------------------------------------------------------------


def test_sample_mesh_1device_bitwise_noop():
    sde = VPSDE()
    mesh = make_data_mesh(1)
    key = jax.random.PRNGKey(0)
    cfg = AdaptiveConfig(eps_rel=0.05)
    ref = jax.jit(lambda k: sample(sde, _score(sde), (4, 32), k, config=cfg))(key)
    sh = jax.jit(
        lambda k: sample(sde, _score(sde), (4, 32), k, config=cfg, mesh=mesh)
    )(key)
    np.testing.assert_array_equal(np.asarray(ref.x), np.asarray(sh.x))
    np.testing.assert_array_equal(np.asarray(ref.nfe), np.asarray(sh.nfe))


def test_sample_mesh_indivisible_batch_replicates():
    # batch 3 on a 1-device mesh: batch_sharding falls back to replication
    # and sampling still works (the guard for batch % devices != 0).
    sde = VPSDE()
    mesh = make_data_mesh(1)
    res = sample(sde, _score(sde), (3, 16), jax.random.PRNGKey(1),
                 config=AdaptiveConfig(eps_rel=0.1), mesh=mesh)
    assert bool(jnp.all(jnp.isfinite(res.x)))


def test_adaptive_accepts_replicated_sharding():
    # P() has no leading entry — must be treated as "no batch axes",
    # not crash (regression: IndexError on sharding.spec[0])
    from repro.parallel.sharding import replicated

    mesh = make_data_mesh(1)
    sde = VPSDE()
    res = sample(sde, _score(sde), (2, 16), jax.random.PRNGKey(0),
                 config=AdaptiveConfig(eps_rel=0.1, use_fused_kernel=True),
                 sharding=replicated(mesh))
    assert bool(jnp.all(jnp.isfinite(res.x)))


def test_sharded_error_step_1device_matches():
    from repro.kernels.solver_step import ops

    mesh = make_data_mesh(1)
    ks = jax.random.split(jax.random.PRNGKey(2), 8)
    B, shape = 4, (4, 6, 5)  # D=30: exercises lane padding
    x, xp, s2, z, xv = (jax.random.normal(k, shape) for k in ks[:5])
    e0, d1, d2 = (0.01 * jax.random.normal(k, (B,)) for k in ks[5:])
    kw = dict(eps_abs=1e-2, eps_rel=0.01)
    ref_x, ref_e = ops.error_step(x, xp, s2, z, xv, e0, d1, d2, **kw)
    sh_x, sh_e = ops.sharded_error_step(
        x, xp, s2, z, xv, e0, d1, d2, mesh=mesh, batch_axes=("data",), **kw
    )
    np.testing.assert_array_equal(np.asarray(ref_x), np.asarray(sh_x))
    np.testing.assert_array_equal(np.asarray(ref_e), np.asarray(sh_e))


def test_batcher_mesh_1device():
    from repro.launch.sample import make_sample_step
    from repro.models.dit import DiTConfig
    from repro.serving.diffusion_server import DiffusionBatcher, ImageRequest

    sde = VPSDE()
    cfg = AdaptiveConfig(eps_rel=0.05)
    net = DiTConfig(image_size=4, patch=4, d_model=8, num_layers=1,
                    num_heads=1, d_ff=8)
    step = make_sample_step(net, sde, cfg,
                            forward_fn=gaussian_noise_pred(sde, MU, S0))
    mesh = make_data_mesh(1)
    b = DiffusionBatcher(sde, step, params=None, sample_shape=(16,),
                         slots=4, cfg=cfg, mesh=mesh)
    for uid in range(8):
        b.submit(ImageRequest(uid=uid, seed=uid))
    done = b.run_to_completion()
    assert len(done) == 8
    assert b.refills_per_device == [8]
    assert all(np.isfinite(done[u].result).all() for u in range(8))


def test_batcher_slots_must_divide_devices():
    from repro.serving.diffusion_server import DiffusionBatcher

    class TwoDeviceMesh:  # duck-type: pretend 2 data devices
        shape = {"data": 2}
        axis_names = ("data",)

    with pytest.raises(ValueError, match="divide"):
        DiffusionBatcher(VPSDE(), lambda p, s: s, None, (8,), slots=3,
                        mesh=TwoDeviceMesh())


# ---------------------------------------------------------------------------
# subprocess: real multi-device path on ≥2 forced fake devices
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def selftest_results():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               SELFTEST_DEVICES="4")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.sharded_selftest"],
        env=env, capture_output=True, text=True, timeout=560, cwd=ROOT,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_selftest_sample_bitwise_equivalence(selftest_results):
    res = selftest_results
    assert res["devices"] >= 2
    for kind in ("sample_jnp", "sample_fused"):
        assert res[kind]["bitwise_equal"], res
        assert res[kind]["max_abs_diff"] == 0.0, res
        assert res[kind]["sharded_over_devices"], res


@pytest.mark.slow
def test_selftest_fused_kernel_sharding(selftest_results):
    assert selftest_results["fused_kernel"]["batch_sharded_bitwise"]
    assert selftest_results["fused_kernel"]["feature_sharded_close"]


@pytest.mark.slow
def test_selftest_batcher_per_device_refill(selftest_results):
    b = selftest_results["batcher"]
    assert b["all_completed"] and b["finite"]
    # every device refilled its slots beyond the initial fill, and every
    # request was assigned exactly once — refill is per-device
    assert b["per_device_refill"], b
    assert b["total_assignments_match"], b
    assert len(b["refills_per_device"]) == selftest_results["devices"]
    # per-slot keys: identical per-request samples for sharded horizon-4
    # vs unsharded horizon-1 serving (shard-local compaction is invisible)
    assert b["scheduling_invariant"], b


@pytest.mark.slow
def test_selftest_device_resident_serving(selftest_results):
    """Device-resident serving on a real multi-device mesh (DESIGN.md
    §12): bit-identical deliveries and accounting vs the host-driven
    sharded loop, with strictly less device→host traffic."""
    dr = selftest_results["device_resident"]
    assert dr["all_completed"] and dr["bitwise_equal"], dr
    assert dr["iterations_equal"], dr
    assert dr["transfers_reduced"], dr
    assert dr["resident_transfers"] < dr["host_transfers"]
