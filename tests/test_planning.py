"""Trajectory-diffusion planning subsystem (DESIGN.md §10): temporal
score network contract, plan-conditioner guardrails (returns-CFG at
scale 0 and absent state pinning bit-identical to unconditional),
chunked-vs-monolithic bitwise equality with plan payloads aboard, and
the receding-horizon closed loop through the DiffusionBatcher —
re-admission preserves per-request keys and exact NFE accounting."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AdaptiveConfig, VPSDE, available_solvers, sample
from repro.core.analytic import (
    class_gaussian_noise_pred, class_gaussian_score, gaussian_score,
)
from repro.core.sampling import solve_in_chunks
from repro.core.solvers.adaptive import adaptive
from repro.launch.mesh import make_data_mesh
from repro.models.temporal_unet import (
    TemporalUNetConfig, init_temporal_unet, make_score_fn,
    temporal_unet_forward,
)
from repro.planning import (
    OUEnv, PlanConditioner, PlannerConfig, PointMassEnv,
    RecedingHorizonPlanner, first_action, plan, plan_conditioner,
    returns_to_bin, state_pin,
)

MU, S0 = 0.3, 0.5
BINS = 5
BIN_MUS = jnp.linspace(-1.0, 1.0, BINS)

PCFG = PlannerConfig(horizon=8, obs_dim=2, act_dim=2, guidance_scale=1.5)


def _perturbed_unet(cfg, key):
    """Init + perturb every leaf so the forward actually depends on all
    its inputs (the zero-init second convs / output conv of a
    train-free net would otherwise cut the conditioning path)."""
    params = init_temporal_unet(cfg, key)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(key, len(leaves))
    leaves = [l + 0.05 * jax.random.normal(k, l.shape, l.dtype)
              for l, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# temporal score network
# ---------------------------------------------------------------------------


def test_temporal_unet_forward_shapes_and_depths():
    for mults, H in [((1,), 4), ((1, 2), 8), ((1, 2, 4), 16)]:
        cfg = TemporalUNetConfig(horizon=H, transition_dim=5, base=8,
                                 mults=mults, t_dim=16, groups=4)
        p = init_temporal_unet(cfg, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (3, H, 5))
        out = temporal_unet_forward(p, x, jnp.full((3,), 0.4), cfg)
        assert out.shape == x.shape and out.dtype == jnp.float32


def test_temporal_unet_rejects_indivisible_horizon():
    with pytest.raises(ValueError):
        TemporalUNetConfig(horizon=6, transition_dim=4, mults=(1, 2, 4))


def test_temporal_unet_policy_dtypes():
    """PR-3 precision contract (DESIGN.md §8): compute dtype through the
    blocks, fp32 time-embedding math, score delivered in state dtype."""
    from repro.core.precision import resolve_policy

    cfg = TemporalUNetConfig(horizon=4, transition_dim=4, base=8,
                             mults=(1, 2), t_dim=16, groups=4)
    p = _perturbed_unet(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 4))
    t = jnp.full((2,), 0.3)
    pol = resolve_policy("bf16")
    out = temporal_unet_forward(p, x, t, cfg, policy=pol)
    assert out.dtype == jnp.bfloat16
    score = make_score_fn(p, cfg, VPSDE(), policy=pol)
    assert score(x, t).dtype == pol.state
    score_full = make_score_fn(p, cfg, VPSDE(),
                               policy=resolve_policy("bf16_full"))
    assert score_full(x, t).dtype == jnp.bfloat16


def test_temporal_unet_null_row_bitwise_unconditional():
    """The returns table's null row is zero-init, so the null-labeled
    forward is bit-identical to the unconditional (y=None) forward —
    what makes ClassifierFree scale=0 on this net collapse exactly
    (DESIGN.md §10)."""
    cfg = TemporalUNetConfig(horizon=4, transition_dim=4, base=8,
                             mults=(1, 2), t_dim=16, groups=4,
                             returns_bins=BINS)
    p = _perturbed_unet(cfg, jax.random.PRNGKey(0))
    # restore the contract the perturbation broke: the null row is zero
    p["ret_emb"] = p["ret_emb"].at[cfg.returns_bins].set(0.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 4, 4))
    t = jnp.full((3,), 0.5)
    out_u = temporal_unet_forward(p, x, t, cfg)
    out_null = temporal_unet_forward(p, x, t, cfg,
                                     y=jnp.full((3,), -1, jnp.int32))
    np.testing.assert_array_equal(np.asarray(out_u), np.asarray(out_null))
    # and a real bin label actually changes the field
    out_y = temporal_unet_forward(p, x, t, cfg,
                                  y=jnp.zeros((3,), jnp.int32))
    assert bool(jnp.any(out_y != out_u))


def test_every_registered_solver_runs_on_trajectories():
    """The make_score_fn adapter is workload-agnostic: every registered
    solver consumes the temporal score unmodified (DESIGN.md §10)."""
    cfg = TemporalUNetConfig(horizon=4, transition_dim=3, base=8,
                             mults=(1, 2), t_dim=16, groups=4)
    p = _perturbed_unet(cfg, jax.random.PRNGKey(0))
    sde = VPSDE()
    unet_score = make_score_fn(p, cfg, sde)
    base = gaussian_score(sde, MU, S0)

    # the sweep verifies the (B, H, D) adapter signature on every
    # registered solver; the analytic term keeps the field at a sane
    # magnitude (PC's Langevin step ∝ 1/‖score‖² diverges on the
    # zero/garbage field of an untrained net)
    def score(x, t):
        return base(x, t) + 0.1 * unet_score(x, t)

    # PC's ancestral VP predictor needs a non-degenerate grid (it is
    # NaN-unstable below ~tens of steps on any workload)
    kw = {"em": dict(n_steps=5), "pc": dict(n_steps=50),
          "pc_hmc": dict(n_steps=50), "ddim": dict(n_steps=5),
          "adaptive": dict(eps_rel=0.1), "momentum": dict(eps_rel=0.1),
          "heun": dict(eps_rel=0.1), "ode": {}}
    for solver in available_solvers():
        res = sample(sde, score, (2, 4, 3), jax.random.PRNGKey(1),
                     method=solver, **kw[solver])
        assert res.x.shape == (2, 4, 3)
        assert bool(jnp.all(jnp.isfinite(res.x))), solver


# ---------------------------------------------------------------------------
# plan conditioner guardrails
# ---------------------------------------------------------------------------


def test_plan_conditioner_factory_cases():
    obs = jnp.ones((3, 2))
    labels = jnp.arange(3)
    c, p = plan_conditioner(PCFG, state=None, returns=None)
    assert c is None and p is None
    c, p = plan_conditioner(PCFG, state=obs, returns=None)
    assert type(c).__name__ == "Inpaint" and set(p) == {"mask", "observed"}
    c, p = plan_conditioner(PCFG, state=None, returns=labels)
    assert type(c).__name__ == "ClassifierFree" and set(p) == {"label"}
    c, p = plan_conditioner(PCFG, state=obs, returns=labels)
    assert isinstance(c, PlanConditioner)
    assert set(p) == {"label", "mask", "observed"}
    assert c.has_projection


def test_returns_cfg_scale0_bitwise_unconditional():
    """ISSUE-5 guardrail: returns-CFG at scale=0 is bit-identical to
    unconditional trajectory sampling (the null branch computes the
    same arithmetic; no extra noise draws on the CFG-only path)."""
    sde = VPSDE()
    pcfg = dataclasses.replace(PCFG, guidance_scale=0.0)
    score_u = gaussian_score(sde, MU, S0)
    score_y = class_gaussian_score(sde, BIN_MUS, S0, MU)
    key = jax.random.PRNGKey(0)
    shape = (4,) + pcfg.sample_shape
    res_u = sample(sde, score_u, shape, key, method="adaptive", eps_rel=0.05)
    conditioner, cond = plan_conditioner(pcfg, returns=jnp.arange(4) % BINS)
    res_c = sample(sde, score_y, shape, key, method="adaptive", eps_rel=0.05,
                   conditioner=conditioner, cond=cond)
    np.testing.assert_array_equal(np.asarray(res_u.x), np.asarray(res_c.x))
    np.testing.assert_array_equal(np.asarray(res_u.nfe), np.asarray(res_c.nfe))


def test_state_mask_none_bitwise_unconditional():
    """ISSUE-5 guardrail: no state pin and no returns → plan() IS the
    unconditional trajectory solve, bit for bit."""
    sde = VPSDE()
    score = gaussian_score(sde, MU, S0)
    key = jax.random.PRNGKey(0)
    res_u = sample(sde, score, (4,) + PCFG.sample_shape, key,
                   method="adaptive", eps_rel=0.05)
    res_p = plan(sde, score, None, key, pcfg=PCFG, batch=4, eps_rel=0.05)
    np.testing.assert_array_equal(np.asarray(res_u.x), np.asarray(res_p.x))


def test_plan_pins_state_exactly_and_free_region_on_marginal():
    """Delivered plans pin the current state bit-exactly (finalize
    projection) while the free region stays on the data marginal."""
    sde = VPSDE()
    score = class_gaussian_score(sde, BIN_MUS, S0, MU)
    obs = jnp.asarray([[0.1, -0.2], [0.4, 0.0], [-0.3, 0.25],
                       [0.05, 0.6]], jnp.float32)
    res = plan(sde, score, obs, jax.random.PRNGKey(0), pcfg=PCFG,
               returns=jnp.arange(4) % BINS, eps_rel=0.05)
    x = np.asarray(res.x)
    np.testing.assert_array_equal(x[:, 0, :2], np.asarray(obs))
    a = first_action(res.x, PCFG)
    assert a.shape == (4, 2)
    free = x[:, 1:, :]
    assert abs(free.mean()) < 1.0 and np.isfinite(free).all()


def test_first_action_selects_action_columns():
    """first_action must slice the ACTION coordinates of row context−1
    — distinguishable values per column pin the contract (an obs-column
    slice would have the same shape and slip through shape checks)."""
    x = jnp.arange(2 * 8 * 4, dtype=jnp.float32).reshape(2, 8, 4)
    a = first_action(x, PCFG)  # obs_dim=2, act_dim=2, context=1
    np.testing.assert_array_equal(np.asarray(a), np.asarray(x[:, 0, 2:4]))


def test_returns_to_bin_and_state_pin_shapes():
    bins = returns_to_bin(jnp.asarray([-2.0, 0.0, 2.0]), -1.0, 1.0, BINS)
    assert bins.tolist() == [0, 2, BINS - 1]
    pin = state_pin(PCFG, jnp.ones((2, 2)))
    assert pin["mask"].shape == (2,) + PCFG.sample_shape
    assert float(pin["mask"].sum()) == 2 * PCFG.context * PCFG.obs_dim
    with pytest.raises(ValueError):
        state_pin(PCFG, jnp.ones((2, 3)))  # wrong obs_dim


def test_chunked_plan_bitwise_equals_monolithic_with_payload():
    """ISSUE-5 guardrail: horizon-chunked planning solves are bitwise
    equal to the monolithic solve with the full PlanConditioner payload
    (labels + pin mask) aboard (DESIGN.md §7/§9/§10). Compared at equal
    jit granularity (a maximal single chunk vs small chunks through the
    same host chain), the discipline the §7/§9 chunking suites use."""
    sde = VPSDE()
    score = class_gaussian_score(sde, BIN_MUS, S0, MU)
    obs = 0.2 * jnp.ones((3, 2))
    conditioner, cond = plan_conditioner(PCFG, state=obs,
                                         returns=jnp.arange(3) % BINS)
    cfg = AdaptiveConfig(eps_rel=0.05, conditioner=conditioner)
    key = jax.random.PRNGKey(2)
    shape = (3,) + PCFG.sample_shape
    res_mono = solve_in_chunks(sde, score, shape, key,
                               max_sync_iters=10**6, config=cfg, cond=cond)
    res_chunk = solve_in_chunks(sde, score, shape, key, max_sync_iters=7,
                                config=cfg, cond=cond)
    np.testing.assert_array_equal(np.asarray(res_mono.x),
                                  np.asarray(res_chunk.x))
    np.testing.assert_array_equal(np.asarray(res_mono.nfe),
                                  np.asarray(res_chunk.nfe))
    x = np.asarray(res_mono.x)
    np.testing.assert_array_equal(x[:, 0, :2], np.asarray(obs))


# ---------------------------------------------------------------------------
# receding-horizon closed loop through the batcher
# ---------------------------------------------------------------------------


def _forward():
    sde = VPSDE()
    return sde, class_gaussian_noise_pred(sde, BIN_MUS, S0, MU)


def _rollout(slots, sync_horizon, *, compaction=True, n_envs=4, n_steps=2):
    sde, fwd = _forward()
    rh = RecedingHorizonPlanner(sde, fwd, None, PCFG, OUEnv(obs_dim=2),
                                slots=slots, sync_horizon=sync_horizon,
                                compaction=compaction)
    out = rh.rollout(jax.random.PRNGKey(1), n_envs=n_envs, n_steps=n_steps,
                     returns_label=BINS - 1)
    return rh, out


def test_closed_loop_smoke_plans_pin_and_progress():
    """Tier-1 closed-loop smoke on a tiny horizon: every delivered plan
    pins its request's own pinned state exactly, rewards are finite,
    and every plan did real solver work."""
    rh, out = _rollout(slots=4, sync_horizon=4, n_envs=3, n_steps=2)
    assert out["rewards"].shape == (2, 3)
    assert np.isfinite(out["rewards"]).all()
    assert (out["nfe"] > 10).all() and (out["nfe"] % 2 == 0).all()
    for req in out["finished"].values():
        m = np.asarray(req.cond["mask"])
        o = np.asarray(req.cond["observed"])
        np.testing.assert_array_equal(np.asarray(req.result)[m == 1.0],
                                      o[m == 1.0])


def test_closed_loop_readmission_invariant_to_scheduling():
    """ISSUE-5 acceptance: closed-loop re-admission preserves per-request
    keys — delivered plans and per-request NFE are bit-identical across
    sync horizons and with compaction on/off, with n_envs > slots so
    requests genuinely queue and re-admit into freed slots."""
    _, o1 = _rollout(slots=4, sync_horizon=1, n_envs=6)
    _, o2 = _rollout(slots=4, sync_horizon=8, n_envs=6)
    _, o3 = _rollout(slots=4, sync_horizon=8, n_envs=6, compaction=False)
    assert o1["finished"].keys() == o2["finished"].keys() == o3["finished"].keys()
    for uid in o1["finished"]:
        r1, r2, r3 = (o["finished"][uid] for o in (o1, o2, o3))
        np.testing.assert_array_equal(r1.result, r2.result)
        np.testing.assert_array_equal(r2.result, r3.result)
        assert r1.nfe == r2.nfe == r3.nfe


def test_closed_loop_request_reproducible_standalone():
    """ISSUE-5 acceptance: every request delivered by the closed loop is
    bit-identical to a standalone adaptive() solve of the same (seed,
    payload) at matching batch width, with exact NFE accounting — the
    per-slot-key + payload-compaction contract (DESIGN.md §7/§9)."""
    sde, fwd = _forward()
    rh, out = _rollout(slots=1, sync_horizon=4, n_envs=1, n_steps=3)

    def score_fn(x, t, y=None):  # exactly make_sample_step's wrapping
        _, std = sde.marginal(t)
        return -fwd(None, x, t, y).astype(jnp.float32) / std.reshape(
            (-1,) + (1,) * (x.ndim - 1))

    assert len(out["finished"]) == 3
    for uid, req in sorted(out["finished"].items()):
        k_prior, k_noise = jax.random.split(jax.random.PRNGKey(req.seed))
        x0 = sde.prior_sample(k_prior, PCFG.sample_shape)[None]
        cond = {k: jnp.asarray(v)[None] for k, v in req.cond.items()}
        res = adaptive(sde, score_fn, x0, k_noise[None], config=rh.cfg,
                       cond=cond, denoise=False)
        np.testing.assert_array_equal(np.asarray(res.x[0]), req.result)
        assert int(res.nfe[0]) == req.nfe


def test_solver_carry_shardings_cover_plan_payload():
    """The §9 payload-sharding rule extends to the merged plan payload:
    every PlanConditioner leaf (label (B,), mask/observed (B, H, D))
    gets a batch-axis spec of its own ndim (DESIGN.md §10)."""
    from repro.parallel.sharding import solver_carry_shardings

    mesh = make_data_mesh(1)
    c = PlanConditioner(scale=1.5)
    struct = c.cond_struct(4, PCFG.sample_shape)
    sh = solver_carry_shardings(mesh, 4, 3, per_slot_keys=True, cond=struct)
    assert set(sh.cond) == {"label", "mask", "observed"}
    for name, leaf in struct.items():
        assert len(sh.cond[name].spec) == leaf.ndim, name


def test_planner_rejects_mismatched_env_dims():
    sde, fwd = _forward()
    with pytest.raises(ValueError):
        RecedingHorizonPlanner(sde, fwd, None, PCFG, OUEnv(obs_dim=3))


@pytest.mark.slow
def test_closed_loop_e2e_pointmass_improves():
    """Slow closed-loop e2e: a longer receding-horizon rollout on the
    deterministic point-mass env with the train-free temporal UNet —
    the full network path through the batcher — completes every round
    and keeps waste accounting sane; and on the OU analytic loop the
    returns guidance measurably steers realized reward in the predicted
    direction (the zero-mean action bin beats the high-action bin,
    which pays quadratic action cost for anti-goal drift)."""
    env = PointMassEnv()
    pcfg = PlannerConfig(horizon=8, obs_dim=env.obs_dim,
                         act_dim=env.act_dim, guidance_scale=1.0)
    cfg = TemporalUNetConfig(horizon=pcfg.horizon,
                             transition_dim=pcfg.transition_dim,
                             base=8, mults=(1, 2), t_dim=16, groups=4,
                             returns_bins=BINS)
    params = init_temporal_unet(cfg, jax.random.PRNGKey(0))
    sde = VPSDE()

    def fwd(p, x, t, y=None):
        return temporal_unet_forward(p, x, t, cfg, y=y)

    rh = RecedingHorizonPlanner(sde, fwd, params, pcfg, env,
                                slots=4, sync_horizon=4)
    out = rh.rollout(jax.random.PRNGKey(3), n_envs=6, n_steps=3,
                     returns_label=BINS - 1)
    assert out["rewards"].shape == (3, 6)
    assert np.isfinite(out["rewards"]).all()
    assert len(out["finished"]) == 18
    assert 0.0 <= out["wasted_nfe_fraction"] < 1.0
    assert 0.0 <= out["passenger_nfe_fraction"] < 1.0

    # analytic OU loop: the returns-bin label is a real control signal —
    # bin mus are linspace(-1, 1, 5), so bin 2 (μ=0) plans near-zero
    # actions (cheap, no anti-goal drift) while bin 4 (μ=+1) plans
    # large positive ones (quadratic action cost + drift away from 0);
    # realized reward must order accordingly, which also fails if
    # first_action ever returned observation columns (pinned near the
    # stationary state) instead of the guided action columns
    def ou_reward(label):
        sde2, fwd2 = _forward()
        rh2 = RecedingHorizonPlanner(sde2, fwd2, None, PCFG,
                                     OUEnv(obs_dim=2),
                                     slots=4, sync_horizon=4)
        out2 = rh2.rollout(jax.random.PRNGKey(4), n_envs=4, n_steps=4,
                           returns_label=label)
        assert np.isfinite(out2["rewards"]).all()
        return float(out2["rewards"].mean())

    assert ou_reward(2) > ou_reward(4)
