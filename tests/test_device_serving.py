"""Device-resident serving hot path (DESIGN.md §12).

The device-resident ``DiffusionBatcher`` folds retirement, shard-local
compaction, and queue admission into on-device programs with donated
carries; the host is consulted only when the scalar events flag fires.
Three properties pin it:

  * **bit-identity** — per-request samples, iteration totals, and waste
    accounting exactly match the host-driven ``_sync`` loop (compaction
    on and off, unconditioned and with per-request condition payloads):
    per-slot PRNG keys make every trajectory independent of where
    retirement/admission decisions are computed;
  * **O(events) host traffic** — device→host transfers (counted by a
    shim around ``jax.device_get``, independently of the batcher's own
    counter) scale with deliveries, not sync horizons: ≥5× fewer than
    the host-driven loop at sync_horizon ≤ 8, and near-constant as the
    horizon shrinks while the host-driven count blows up;
  * **donation** — the driver actually consumes its input carry, so the
    hot loop is not double-buffering state;
  * **fixed-size admission** — an event visit reaches the device through
    a fixed number of host uploads (counted by a shim around
    ``jax.device_put``) and runs no eager op on host values, however
    many requests it seats: the event program derives every admitted
    request's keys from its seed word, bit-identical to the eager
    ``split(PRNGKey)``.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import AdaptiveConfig, VPSDE
from repro.core.analytic import gaussian_noise_pred
from repro.core.guidance import Inpaint
from repro.launch.sample import make_sample_step
from repro.models.dit import DiTConfig
from repro.serving.diffusion_server import DiffusionBatcher, ImageRequest

MU, S0 = 0.3, 0.5
D = 32
SLOTS = 4
N_REQ = 12


def _make_step(sde, cfg):
    net = DiTConfig(image_size=4, patch=4, d_model=8, num_layers=1,
                    num_heads=1, d_ff=8)  # signature holder; forward_fn wins
    return make_sample_step(net, sde, cfg,
                            forward_fn=gaussian_noise_pred(sde, MU, S0))


@pytest.fixture(scope="module")
def server_parts():
    sde = VPSDE()
    cfg = AdaptiveConfig(eps_rel=0.05)
    return sde, cfg, _make_step(sde, cfg)


#: seeds whose eager ``PRNGKey`` goes past int32: negative, ≥ 2³¹, ≥ 2³²
WIDE_SEEDS = (0, 7, 2**31 - 1, 2**31 + 5, 2**32 - 1, -3, 2**40 + 7)
TIERS = ("draft", "standard", "high_fidelity")


def _drain(b, n_req, cond_for=None, seed_for=None, tier_for=None):
    for uid in range(n_req):
        b.submit(ImageRequest(uid=uid,
                              seed=seed_for(uid) if seed_for else uid,
                              cond=cond_for(uid) if cond_for else None,
                              tier=tier_for(uid) if tier_for else None))
    done = b.run_to_completion()
    assert len(done) == n_req
    return done


def _run(sde, cfg, step, *, n_req=N_REQ, cond_for=None, seed_for=None,
         tier_for=None, **kw):
    b = DiffusionBatcher(sde, step, params=None, sample_shape=(D,),
                         slots=SLOTS, cfg=cfg, **kw)
    done = _drain(b, n_req, cond_for, seed_for, tier_for)
    return b, np.stack([done[u].result for u in range(n_req)]), done


def _wide_seed(uid):
    """A seed past int32 for most requests, each with its own low word."""
    return WIDE_SEEDS[uid % len(WIDE_SEEDS)] + (uid // len(WIDE_SEEDS)) * 101


# ---------------------------------------------------------------------------
# bit-identity vs the host-driven loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("compaction,wide,tiered", [
    pytest.param(True, False, False, id="compaction"),
    pytest.param(False, False, False, id="monolithic"),
    pytest.param(True, True, False, id="compaction-wide-seeds"),
    pytest.param(False, True, False, id="monolithic-wide-seeds"),
    pytest.param(True, True, True, id="compaction-wide-seeds-tiered"),
    pytest.param(False, True, True, id="monolithic-wide-seeds-tiered"),
])
def test_device_resident_bitwise_matches_host_driven(server_parts,
                                                     compaction, wide,
                                                     tiered):
    """Same keys + same request wave ⇒ the device-resident loop delivers
    bit-identical samples AND identical accounting (iterations, per-
    request NFE, waste fraction) to the host-driven ``_sync`` loop —
    retirement/compaction/admission decisions moved devices, the math
    did not. Holds for both turnover disciplines, for seeds past int32
    (the event program derives keys from seed words, the host-driven
    loop splits eagerly), and for mixed tolerance tiers."""
    sde, cfg, step = server_parts
    kw = dict(sync_horizon=4, compaction=compaction)
    if wide:
        kw["seed_for"] = _wide_seed
    if tiered:
        kw.update(tolerance_classes=True,
                  tier_for=lambda uid: TIERS[uid % len(TIERS)])
    b_host, x_host, done_h = _run(sde, cfg, step, **kw)
    b_dev, x_dev, done_d = _run(sde, cfg, step, device_resident=True, **kw)
    np.testing.assert_array_equal(x_host, x_dev)
    assert b_host.total_iterations == b_dev.total_iterations
    assert [done_h[u].nfe for u in range(N_REQ)] == \
        [done_d[u].nfe for u in range(N_REQ)]
    assert b_host.wasted_nfe_fraction == \
        pytest.approx(b_dev.wasted_nfe_fraction)


def test_device_resident_conditioned_bitwise(server_parts):
    """Per-request condition payloads survive on-device compaction and
    admission: payload *indices* (perm/admit masks) are applied on
    device while the ragged payload rows are scattered host-side — each
    delivery must still honor its OWN observation exactly."""
    sde, _, _ = server_parts
    ccfg = AdaptiveConfig(eps_rel=0.05, conditioner=Inpaint())
    step = _make_step(sde, ccfg)

    def cond_for(uid):
        mask = (np.arange(D) % 2 == uid % 2).astype(np.float32)
        return {"mask": mask,
                "observed": np.full(D, 0.1 + 0.05 * uid, np.float32)}

    _, x_host, _ = _run(sde, ccfg, step, cond_for=cond_for, sync_horizon=4)
    _, x_dev, _ = _run(sde, ccfg, step, cond_for=cond_for, sync_horizon=4,
                       device_resident=True)
    np.testing.assert_array_equal(x_host, x_dev)
    for uid in range(N_REQ):
        c = cond_for(uid)
        obs = c["mask"] == 1.0
        np.testing.assert_array_equal(x_dev[uid][obs], c["observed"][obs])


# ---------------------------------------------------------------------------
# host-sync traffic: O(events), not O(horizons)
# ---------------------------------------------------------------------------


class _GetCounter:
    """Counting shim around ``jax.device_get`` — an *independent* witness
    of device→host traffic, not the batcher's own ``host_transfers``."""

    def __init__(self, monkeypatch):
        self.calls = 0
        real = jax.device_get

        def counting(tree):
            self.calls += 1
            return real(tree)

        monkeypatch.setattr(jax, "device_get", counting)


def _transfers(server_parts, monkeypatch, **kw):
    sde, cfg, step = server_parts
    counter = _GetCounter(monkeypatch)
    b, _, _ = _run(sde, cfg, step, **kw)
    monkeypatch.undo()
    return counter.calls, b


def test_host_transfer_reduction_at_small_horizons(server_parts,
                                                   monkeypatch):
    """The acceptance gate: ≥5× fewer device→host transfers per request
    at sync_horizon ≤ 8, counted by the shim. The shim also cross-checks
    the batcher's own ``host_transfers`` counter (every serve-loop pull
    goes through ``_d2h``; the shim may see a handful of extra calls
    from delivery-side numpy conversions outside it)."""
    for horizon in (2, 8):
        n_host, b_host = _transfers(server_parts, monkeypatch,
                                    sync_horizon=horizon)
        n_dev, b_dev = _transfers(server_parts, monkeypatch,
                                  sync_horizon=horizon,
                                  device_resident=True)
        assert n_host >= b_host.host_transfers
        assert n_dev >= b_dev.host_transfers
        if horizon == 2:
            assert n_host >= 5 * n_dev, (horizon, n_host, n_dev)
        else:
            assert n_host > n_dev, (horizon, n_host, n_dev)


def test_device_resident_transfers_scale_with_events_not_horizons(
        server_parts, monkeypatch):
    """Shrinking the horizon 8× explodes the host-driven transfer count
    but barely moves the device-resident one: its traffic is pinned to
    delivery/admission *events*, which the workload (not the horizon)
    determines."""
    n_host_1, _ = _transfers(server_parts, monkeypatch, sync_horizon=1)
    n_host_8, _ = _transfers(server_parts, monkeypatch, sync_horizon=8)
    n_dev_1, _ = _transfers(server_parts, monkeypatch, sync_horizon=1,
                            device_resident=True)
    n_dev_8, _ = _transfers(server_parts, monkeypatch, sync_horizon=8,
                            device_resident=True)
    assert n_host_1 >= 3 * n_host_8          # host: O(horizons)
    assert n_dev_1 <= 2 * n_dev_8            # device: ~O(events)


# ---------------------------------------------------------------------------
# donation: the driver consumes its input carry
# ---------------------------------------------------------------------------


def test_driver_donates_carry_buffers(server_parts):
    """After a device step, the pre-step carry's buffers are donated
    (deleted): the hot loop reuses them instead of allocating a second
    resident copy per horizon window."""
    sde, cfg, step = server_parts
    b = DiffusionBatcher(sde, step, params=None, sample_shape=(D,),
                         slots=SLOTS, cfg=cfg, sync_horizon=4,
                         device_resident=True)
    for uid in range(SLOTS):
        b.submit(ImageRequest(uid=uid, seed=uid))
    before = b._carry.x
    assert b.step() >= 0
    assert before.is_deleted()
    b.run_to_completion()
    assert len(b.finished) == SLOTS


# ---------------------------------------------------------------------------
# admission: seed words in, keys derived on device, fixed uploads per visit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", WIDE_SEEDS)
def test_event_program_keys_match_eager_split(server_parts, seed):
    """The event program derives an admitted request's keys from its seed
    word: the slot's noise stream and its prior draw equal those of the
    eager ``jax.random.split(jax.random.PRNGKey(seed))``, for seeds that
    are negative or wider than 32 bits too."""
    sde, cfg, step = server_parts
    b = DiffusionBatcher(sde, step, params=None, sample_shape=(D,),
                         slots=SLOTS, cfg=cfg, device_resident=True)
    b.submit(ImageRequest(uid=0, seed=seed))
    b._process_events(deliver=False)
    k_prior, k_noise = jax.random.split(jax.random.PRNGKey(seed))
    np.testing.assert_array_equal(np.asarray(b._carry.key[0]),
                                  np.asarray(k_noise))
    np.testing.assert_array_equal(np.asarray(b._carry.x[0]),
                                  np.asarray(sde.prior_sample(k_prior, (D,))))


class _PutCounter:
    """Counting shim around ``jax.device_put`` — an independent witness
    of host→device uploads, not the batcher's own
    ``serve_host_uploads_total``."""

    def __init__(self, monkeypatch):
        self.calls = 0
        real = jax.device_put

        def counting(*a, **kw):
            self.calls += 1
            return real(*a, **kw)

        monkeypatch.setattr(jax, "device_put", counting)


def _seat(b, seeds, monkeypatch):
    """Submit one request per seed and run the admission-only event visit
    that seats them all, with implicit host→device transfers refused:
    an eager op on host values (a key split, a slice, ``jnp.zeros``, a
    scatter at host indices) would raise. Returns the uploads counted by
    the batcher and by the shim, and the event program calls."""
    for s in seeds:
        b.submit(ImageRequest(uid=1000 + s, seed=s))
    counters = ("serve_host_uploads_total", "serve_event_updates_total")
    before = [b.metrics.counter(c).value for c in counters]
    puts = _PutCounter(monkeypatch)
    try:
        with jax.transfer_guard_host_to_device("disallow"):
            b._process_events(deliver=False)
    finally:
        monkeypatch.undo()
    uploads, events = (int(b.metrics.counter(c).value - v)
                       for c, v in zip(counters, before))
    return uploads, puts.calls, events


@pytest.mark.parametrize("tiered", [False, True], ids=["untiered", "tiered"])
def test_event_visit_uploads_are_fixed(server_parts, monkeypatch, tiered):
    """One event visit uploads one packed admission array (two when
    tiered: the tolerance rows) and the occupancy mask, whether it seats
    1 request or 16, and makes no implicit transfer: no per-request
    eager op is left in the admission."""
    sde, cfg, step = server_parts
    b = DiffusionBatcher(sde, step, params=None, sample_shape=(D,),
                         slots=16, cfg=cfg, sync_horizon=4,
                         device_resident=True, tolerance_classes=tiered)
    one = _seat(b, [3], monkeypatch)
    b.run_to_completion()
    sixteen = _seat(b, range(100, 116), monkeypatch)
    want = 3 if tiered else 2
    assert one == sixteen == (want, want, 1), (one, sixteen)
    b.run_to_completion()
    assert len(b.finished) == 17


_MESH_PROBE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                               + os.environ.get("XLA_FLAGS", ""))
    import json
    import jax
    from repro.core import AdaptiveConfig, VPSDE
    from repro.core.analytic import gaussian_noise_pred
    from repro.launch.mesh import make_data_mesh
    from repro.launch.sample import make_sample_step
    from repro.models.dit import DiTConfig
    from repro.serving.diffusion_server import DiffusionBatcher, ImageRequest

    sde, cfg = VPSDE(), AdaptiveConfig(eps_rel=0.05)
    net = DiTConfig(image_size=4, patch=4, d_model=8, num_layers=1,
                    num_heads=1, d_ff=8)
    step = make_sample_step(net, sde, cfg, forward_fn=gaussian_noise_pred(sde))
    b = DiffusionBatcher(sde, step, params=None, sample_shape=(32,),
                         slots=16, cfg=cfg, mesh=make_data_mesh(),
                         sync_horizon=4, device_resident=True,
                         tolerance_classes=True)
    real, puts = jax.device_put, []

    def counting(x, device=None, *a, **kw):
        puts.append(device == b._carry.done.sharding)
        return real(x, device, *a, **kw)

    out = {"devices": jax.device_count()}
    for name, seeds in (("one", [3]), ("sixteen", range(100, 116))):
        b.run_to_completion()
        for s in seeds:
            b.submit(ImageRequest(uid=1000 + s, seed=s, tier="standard"))
        x, n0, puts[:] = b._carry.x, b.metrics.counter(
            "serve_host_uploads_total").value, []
        jax.device_put = counting
        with jax.transfer_guard_host_to_device("disallow"):
            b._process_events(deliver=False)
        jax.device_put = real
        out[name] = {
            "uploads": b.metrics.counter("serve_host_uploads_total").value - n0,
            "puts": len(puts), "slot_sharded": all(puts),
            "donated": x.is_deleted(),
        }
    b.run_to_completion()
    out["delivered"] = len(b.finished)
    print(json.dumps(out))
""")


def test_event_visit_uploads_are_fixed_on_a_mesh():
    """The same fixed uploads on a 4-device CPU mesh (fake host devices in
    a subprocess), each placed with the carry's slot sharding, and the
    event program still consumes (donates) the sharded carry."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _MESH_PROBE], env=env,
                       capture_output=True, text=True, timeout=300, cwd=root)
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    for name in ("one", "sixteen"):
        v = out[name]
        assert v["uploads"] == v["puts"] == 3, out
        assert v["slot_sharded"] and v["donated"], out
    assert out["delivered"] == 17
