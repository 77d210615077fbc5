"""Precision-policy subsystem (DESIGN.md §8).

Covers the policy object itself, the dtype contract at every seam
(models → score fn → solver carry → kernels), the fp32-preset
bit-identity guarantee, and the bf16 tier-1 smoke (the fast-job gate CI
runs on every push).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AdaptiveConfig,
    PrecisionPolicy,
    VPSDE,
    init_carry,
    resolve_policy,
    sample,
    solve_in_chunks,
)
from repro.core.analytic import gaussian_score
from repro.models.dit import (
    DiTConfig, dit_forward, init_dit, liven_dit, make_score_fn,
)

MU, S0 = 0.3, 0.5


def _score(sde):
    return gaussian_score(sde, MU, S0)


# ---------------------------------------------------------------------------
# the policy object
# ---------------------------------------------------------------------------


def test_presets():
    assert PrecisionPolicy("fp32").compute == jnp.float32
    p = PrecisionPolicy("bf16")
    assert (p.compute, p.param, p.state) == (
        jnp.bfloat16, jnp.float32, jnp.float32)
    pf = PrecisionPolicy("bf16_full")
    assert (pf.compute, pf.param, pf.state) == (
        jnp.bfloat16, jnp.bfloat16, jnp.bfloat16)
    assert pf.name == "bf16_full" and not pf.is_fp32
    with pytest.raises(ValueError):
        PrecisionPolicy("fp8")


def test_control_dtype_is_pinned_fp32():
    """There is no knob that downcasts the control path."""
    for preset in ("fp32", "bf16", "bf16_full"):
        assert PrecisionPolicy(preset).control == jnp.float32
    # per-seam overrides exist, but none for control
    p = PrecisionPolicy("bf16", state_dtype="bfloat16")
    assert p.state == jnp.bfloat16 and p.control == jnp.float32
    import inspect

    assert "control_dtype" not in inspect.signature(
        PrecisionPolicy.__init__
    ).parameters


def test_resolve_policy_forms():
    p = PrecisionPolicy("bf16")
    assert resolve_policy(None).is_fp32
    assert resolve_policy("bf16") == p
    assert resolve_policy(p) is p
    with pytest.raises(TypeError):
        resolve_policy(16)


def test_policy_is_static_pytree_and_hashable():
    p = PrecisionPolicy("bf16_full")
    assert jax.tree_util.tree_leaves(p) == []  # static: no traced leaves
    assert hash(p) == hash(PrecisionPolicy("bf16_full"))
    out = jax.jit(lambda pol, x: pol.to_compute(x))(p, jnp.ones((2,)))
    assert out.dtype == jnp.bfloat16


def test_cast_params_touches_only_floating_leaves():
    p = PrecisionPolicy("bf16_full")
    tree = {"w": jnp.ones((2, 2), jnp.float32),
            "steps": jnp.zeros((3,), jnp.int32)}
    cast = p.cast_params(tree)
    assert cast["w"].dtype == jnp.bfloat16
    assert cast["steps"].dtype == jnp.int32


def test_wrap_score_fn_dtypes():
    p = PrecisionPolicy("bf16")
    seen = {}

    def raw(x, t):
        seen["x_dtype"] = x.dtype
        return x * 2.0

    out = p.wrap_score_fn(raw)(jnp.ones((4, 2), jnp.float32), jnp.ones((4,)))
    assert seen["x_dtype"] == jnp.bfloat16  # entry cast → compute
    assert out.dtype == jnp.float32         # exit cast → state


# ---------------------------------------------------------------------------
# solver seams
# ---------------------------------------------------------------------------


def test_carry_state_dtype_follows_policy_control_stays_fp32(rng):
    sde = VPSDE()
    x0 = sde.prior_sample(rng, (4, 8))
    for preset, sdt in (("fp32", jnp.float32), ("bf16", jnp.float32),
                        ("bf16_full", jnp.bfloat16)):
        c = init_carry(sde, x0, rng, config=AdaptiveConfig(precision=preset))
        assert c.x.dtype == sdt and c.x_prev.dtype == sdt, preset
        # control path never downcasts
        assert c.t.dtype == jnp.float32 and c.h.dtype == jnp.float32, preset


def test_fp32_policy_bit_identical_to_default(rng):
    """Acceptance bar: PrecisionPolicy('fp32') — as a config default, a
    preset string, or an explicit object — is bitwise the unpoliced
    solver, chunked and monolithic alike."""
    sde = VPSDE()
    cfg_forms = [
        AdaptiveConfig(eps_rel=0.05),                        # field default
        AdaptiveConfig(eps_rel=0.05, precision="fp32"),      # preset name
        AdaptiveConfig(eps_rel=0.05,
                       precision=PrecisionPolicy("fp32")),   # object
    ]
    results = [
        jax.jit(lambda k, cfg=cfg: sample(sde, _score(sde), (8, 16), k,
                                          config=cfg))(rng)
        for cfg in cfg_forms
    ]
    for other in results[1:]:
        np.testing.assert_array_equal(np.asarray(results[0].x),
                                      np.asarray(other.x))
        np.testing.assert_array_equal(np.asarray(results[0].nfe),
                                      np.asarray(other.nfe))
    chunked = solve_in_chunks(sde, _score(sde), (8, 16), rng,
                              max_sync_iters=7, config=cfg_forms[2])
    np.testing.assert_array_equal(np.asarray(results[0].x),
                                  np.asarray(chunked.x))


def test_bf16_chunking_still_bitwise_vs_monolithic(rng):
    """Horizon-chunking transparency (PR 2's invariant) survives the
    bf16 state: chunk boundaries introduce no extra rounding."""
    sde = VPSDE()
    cfg = AdaptiveConfig(eps_rel=0.05, precision="bf16_full")
    mono = jax.jit(
        lambda k: sample(sde, _score(sde), (8, 16), k, config=cfg)
    )(rng)
    chunked = solve_in_chunks(sde, _score(sde), (8, 16), rng,
                              max_sync_iters=7, config=cfg)
    for field in ("x", "nfe", "accepted", "rejected"):
        np.testing.assert_array_equal(
            np.asarray(getattr(mono, field), np.float32),
            np.asarray(getattr(chunked, field), np.float32), err_msg=field,
        )


# ---------------------------------------------------------------------------
# model seams + tier-1 bf16 smoke
# ---------------------------------------------------------------------------


def test_bf16_policy_smoke(rng):
    """Fast-job gate: a DiT forward and a full adaptive solve under the
    bf16 policy produce finite outputs at the right dtypes, close to the
    fp32 run (the tier-1 CI job runs this on every push)."""
    net = DiTConfig(image_size=8, patch=4, d_model=32, num_layers=2,
                    num_heads=2, d_ff=64)
    sde = VPSDE()
    params = liven_dit(init_dit(net, rng), rng)
    x = jax.random.normal(rng, (4, 8, 8, 3))
    t = jnp.full((4,), 0.5)

    out32 = dit_forward(params, x, t, net)
    policy = PrecisionPolicy("bf16")
    outbf = dit_forward(policy.cast_params(params), x, t, net, policy=policy)
    assert outbf.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(outbf, np.float32),
                               np.asarray(out32), rtol=0.1, atol=0.05)

    score = make_score_fn(params, net, sde, policy=policy)
    assert score(x, t).dtype == policy.state  # fp32 under "bf16"
    res = jax.jit(lambda k: sample(sde, score, (4, 8, 8, 3), k,
                                   eps_rel=0.05, precision="bf16"))(rng)
    assert bool(jnp.all(jnp.isfinite(res.x)))
    assert res.x.dtype == jnp.float32  # Tweedie delivery is fp32
    assert int(res.iterations) > 0


def test_score_fn_policy_casts_are_idempotent_with_solver_wrap(rng):
    """make_score_fn(policy=...) + the solver's own wrap must compose:
    double-casting x→compute and out→state changes nothing."""
    sde = VPSDE()
    policy = PrecisionPolicy("bf16_full")
    score = policy.wrap_score_fn(_score(sde))
    x = jax.random.normal(rng, (4, 8), jnp.bfloat16)
    t = jnp.full((4,), 0.5)
    once = score(x, t)
    twice = policy.wrap_score_fn(score)(x, t)
    np.testing.assert_array_equal(np.asarray(once, np.float32),
                                  np.asarray(twice, np.float32))
