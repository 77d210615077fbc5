"""Controlled generation: the conditioning seam (DESIGN.md §9).

The contract under test, in three parts:

  * **disabled ⇒ bit-identical** — ``conditioner=None`` (the default),
    ``classifier_free(..., scale=0)``, and ``inpaint(mask=None, ...)``
    all collapse to exactly the unconditional stack: same samples, same
    NFE, same noise stream.
  * **score-field transforms compose** — CFG is one doubled batched
    forward; inpainting projects *after* accept at each slot's own t
    and pins observed data exactly at delivery; colorization is the
    same projection in the rotated channel basis.
  * **payloads ride the carry** — condition pytrees thread through
    ``solve_chunk`` bit-identically to the monolithic solve, and the
    sharding layer gives every payload leaf a batch-axis spec.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AdaptiveConfig,
    ClassifierFree,
    VPSDE,
    class_conditional,
    classifier_free,
    colorize,
    inpaint,
    sample,
    solve_in_chunks,
)
from repro.core.analytic import (
    class_gaussian_score,
    gaussian_marginal_moments,
    gaussian_score,
    gaussian_w2,
)
from repro.core.guidance import cond_batch, gray_basis, to_gray
from repro.launch.mesh import make_data_mesh

MU, S0 = 0.3, 0.5
BATCH, DIM = 64, 8
CLASS_MUS = jnp.linspace(-1.0, 1.0, 10)

KEY = jax.random.PRNGKey(0)


def _uncond(sde, shape=(BATCH, DIM), method="adaptive", **kw):
    return sample(sde, gaussian_score(sde, MU, S0), shape, KEY,
                  method=method, eps_rel=0.05, **kw)


#: every solver that rides the conditioning seam through AdaptiveConfig
#: (DESIGN.md §11) must honor the disabled ⇒ bit-identical contract
CARRY_METHODS = ["adaptive", "momentum", "heun"]


# ---------------------------------------------------------------------------
# disabled ⇒ bit-identical to the unconditional path
# ---------------------------------------------------------------------------


def test_default_config_has_no_conditioner():
    """The new field defaults off, and off means *equal* off — configs
    built before and after the conditioning seam hash/compare the same,
    so nothing downstream (lru caches, jit closures) can fork on it."""
    assert AdaptiveConfig().conditioner is None
    assert AdaptiveConfig() == AdaptiveConfig(conditioner=None)
    assert dataclasses.replace(AdaptiveConfig(), eps_rel=0.05) == \
        AdaptiveConfig(eps_rel=0.05)
    # the zoo fields (DESIGN.md §11) obey the same off-means-equal rule
    assert AdaptiveConfig().momentum == 0.0
    assert AdaptiveConfig().probability_flow is False
    assert AdaptiveConfig() == AdaptiveConfig(momentum=0.0,
                                              probability_flow=False)


@pytest.mark.parametrize("method", CARRY_METHODS)
def test_cfg_scale_zero_bitwise_equals_unconditional(method):
    """CFG at scale=0 evaluates the single null-labeled forward with no
    projection draw — the whole solve (samples, per-sample NFE,
    iteration count) is bit-identical to the unconditional path. Holds
    for every carry family: momentum and Heun reuse the Algorithm-1
    body, so the conditioning seam composes without solver changes."""
    sde = VPSDE()
    res_u = _uncond(sde, method=method)
    conditioner, cond = class_conditional(jnp.arange(BATCH) % 10, 0.0)
    res_c = sample(sde, class_gaussian_score(sde, CLASS_MUS, S0, MU),
                   (BATCH, DIM), KEY, method=method, eps_rel=0.05,
                   conditioner=conditioner, cond=cond)
    np.testing.assert_array_equal(np.asarray(res_u.x), np.asarray(res_c.x))
    np.testing.assert_array_equal(np.asarray(res_u.nfe), np.asarray(res_c.nfe))
    assert int(res_u.iterations) == int(res_c.iterations)


@pytest.mark.parametrize("method", CARRY_METHODS)
def test_inpaint_mask_none_bitwise_equals_unconditional(method):
    """``inpaint(mask=None, ...)`` collapses to (None, None), so feeding
    it straight into ``sample`` must reproduce the unconditional solve
    bit-for-bit — the no-op inpaint cannot perturb the noise stream of
    any carry-family solver."""
    sde = VPSDE()
    conditioner, cond = inpaint(None, None)
    res_u = _uncond(sde, method=method)
    res_c = sample(sde, gaussian_score(sde, MU, S0), (BATCH, DIM), KEY,
                   method=method, eps_rel=0.05,
                   conditioner=conditioner, cond=cond)
    np.testing.assert_array_equal(np.asarray(res_u.x), np.asarray(res_c.x))
    np.testing.assert_array_equal(np.asarray(res_u.nfe), np.asarray(res_c.nfe))


def test_functional_classifier_free_scale_zero_is_identity():
    sde = VPSDE()
    u = gaussian_score(sde, MU, S0)
    c = gaussian_score(sde, MU + 0.2, S0)
    assert classifier_free(c, u, 0.0) is u


def test_inpaint_mask_none_returns_no_conditioner():
    assert inpaint(None, None) == (None, None)
    assert colorize(None) == (None, None)


# ---------------------------------------------------------------------------
# classifier-free guidance
# ---------------------------------------------------------------------------


def test_functional_classifier_free_formula_and_solvers():
    """The functional transform is s_u + w(s_c − s_u) and needs no
    solver support — it runs under the fixed-grid EM baseline too."""
    sde = VPSDE()
    u = gaussian_score(sde, MU, S0)
    c = gaussian_score(sde, MU + 0.4, S0)
    guided = classifier_free(c, u, 2.0)
    x = jax.random.normal(KEY, (8, DIM))
    t = jnp.full((8,), 0.5)
    np.testing.assert_allclose(
        np.asarray(guided(x, t)),
        np.asarray(u(x, t) + 2.0 * (c(x, t) - u(x, t))),
        rtol=1e-6,
    )
    res = sample(sde, guided, (16, DIM), KEY, method="em", n_steps=50)
    assert bool(jnp.all(jnp.isfinite(res.x)))


def test_cfg_single_doubled_forward_layout():
    """The conditioner evaluates the guided field as ONE forward over a
    2B stacked batch — [x; x] with labels [y; null] — never two calls."""
    calls = []

    def counting_score(x, t, y):
        calls.append((x.shape[0], np.asarray(y)))
        return jnp.zeros_like(x)

    cond = {"label": jnp.arange(4, dtype=jnp.int32)}
    guided = ClassifierFree(scale=1.5).wrap_score(counting_score, cond)
    guided(jnp.ones((4, DIM)), jnp.full((4,), 0.5))
    assert len(calls) == 1
    b2, y2 = calls[0]
    assert b2 == 8
    np.testing.assert_array_equal(y2[:4], np.arange(4))
    assert (y2[4:] < 0).all()  # null half


def test_cfg_neutral_cond_is_null_label():
    """The serving loop's idle-slot / no-payload filler must mean
    *unconditional* — the null label, never class 0."""
    neutral = ClassifierFree(scale=1.5).neutral_cond(4, (DIM,))
    assert (np.asarray(neutral["label"]) < 0).all()


def test_cfg_steers_per_class_means():
    """At scale=1 the guided field IS the class-conditional field, so
    each sample's delivered mean tracks its class mean."""
    sde = VPSDE()
    labels = jnp.arange(BATCH) % 10
    conditioner, cond = class_conditional(labels, 1.0)
    res = sample(sde, class_gaussian_score(sde, CLASS_MUS, S0, MU),
                 (BATCH, DIM), KEY, method="adaptive", eps_rel=0.05,
                 conditioner=conditioner, cond=cond)
    x = np.asarray(res.x)
    per_class = np.array([x[np.asarray(labels) == k].mean() for k in range(10)])
    # strong signal: per-class means correlate with the true class means
    assert np.corrcoef(per_class, np.asarray(CLASS_MUS))[0, 1] > 0.95


# ---------------------------------------------------------------------------
# inpainting / colorization projections
# ---------------------------------------------------------------------------


def test_inpaint_exact_observed_and_free_marginals_and_nfe():
    """Observed pixels are pinned exactly at delivery; the free region
    stays on the analytic OU marginal (independent pixels ⇒ the
    conditional equals the marginal); NFE overhead ≤ 1.1×."""
    sde = VPSDE()
    res_u = _uncond(sde, denoise=False)
    observed = MU + S0 * jax.random.normal(jax.random.PRNGKey(7), (BATCH, DIM))
    mask = jnp.zeros((BATCH, DIM)).at[:, : DIM // 2].set(1.0)
    conditioner, cond = inpaint(mask, observed)
    res = sample(sde, gaussian_score(sde, MU, S0), (BATCH, DIM), KEY,
                 method="adaptive", eps_rel=0.05, denoise=False,
                 conditioner=conditioner, cond=cond)
    x = np.asarray(res.x)
    np.testing.assert_array_equal(
        x[:, : DIM // 2], np.asarray(observed)[:, : DIM // 2]
    )
    mu_a, s_a = gaussian_marginal_moments(sde, MU, S0)
    free = x[:, DIM // 2:]
    w2 = gaussian_w2(float(free.mean()), float(free.std()), mu_a, s_a)
    assert w2 < 0.08, w2  # the adaptive solver's conformance gate
    assert float(res.mean_nfe) <= 1.1 * float(res_u.mean_nfe), (
        float(res.mean_nfe), float(res_u.mean_nfe),
    )


def test_colorize_pins_gray_component():
    sde = VPSDE()
    shape = (16, 4, 4, 3)
    ref = MU + S0 * jax.random.normal(jax.random.PRNGKey(3), shape)
    gray = to_gray(ref)
    conditioner, cond = colorize(gray)
    res = sample(sde, gaussian_score(sde, MU, S0), shape, KEY,
                 method="adaptive", eps_rel=0.05,
                 conditioner=conditioner, cond=cond)
    np.testing.assert_allclose(
        np.asarray(to_gray(res.x)), np.asarray(gray), atol=1e-5
    )
    assert bool(jnp.all(jnp.isfinite(res.x)))


def test_gray_basis_is_orthonormal():
    for c in (3, 4):
        m = np.asarray(gray_basis(c))
        np.testing.assert_allclose(m @ m.T, np.eye(c), atol=1e-6)
        np.testing.assert_allclose(m[0], np.full(c, 1 / np.sqrt(c)), atol=1e-6)


# ---------------------------------------------------------------------------
# payload plumbing: carry, chunking, sharding
# ---------------------------------------------------------------------------


def test_cond_batch_mismatch_raises():
    with pytest.raises(ValueError):
        cond_batch({"a": jnp.zeros((4, 2)), "b": jnp.zeros((5, 2))})
    sde = VPSDE()
    conditioner, cond = inpaint(jnp.zeros((4, DIM)), jnp.zeros((4, DIM)))
    with pytest.raises(ValueError):
        sample(sde, gaussian_score(sde, MU, S0), (BATCH, DIM), KEY,
               method="adaptive", conditioner=conditioner, cond=cond)


def test_chunked_solve_bitwise_with_conditioner():
    """The §7 chunk-≡-monolithic invariant extends to conditioning: the
    payload rides the carry, so horizon boundaries cannot perturb a
    conditioned trajectory. Compared at equal jit granularity (a
    maximal single chunk vs small chunks through the same host chain) —
    the same discipline the unconditional chunking suite uses, since
    XLA fusion across a jit boundary is not part of the invariant."""
    sde = VPSDE()
    observed = jnp.full((BATCH, DIM), 0.25)
    mask = jnp.zeros((BATCH, DIM)).at[:, ::2].set(1.0)
    conditioner, cond = inpaint(mask, observed)
    kw = dict(eps_rel=0.05, conditioner=conditioner)
    score = gaussian_score(sde, MU, S0)
    mono = solve_in_chunks(sde, score, (BATCH, DIM), KEY,
                           max_sync_iters=10**6, cond=cond, **kw)
    chunked = solve_in_chunks(sde, score, (BATCH, DIM), KEY,
                              max_sync_iters=7, cond=cond, **kw)
    np.testing.assert_array_equal(np.asarray(mono.x), np.asarray(chunked.x))
    np.testing.assert_array_equal(np.asarray(mono.nfe),
                                  np.asarray(chunked.nfe))
    one = solve_in_chunks(sde, score, (BATCH, DIM), KEY,
                          max_sync_iters=1, cond=cond, **kw)
    np.testing.assert_array_equal(np.asarray(mono.x), np.asarray(one.x))


def test_solver_carry_shardings_cover_cond_leaves():
    from repro.core.guidance import Inpaint
    from repro.parallel.sharding import solver_carry_shardings

    mesh = make_data_mesh()
    struct = Inpaint().cond_struct(8, (DIM,))
    s = solver_carry_shardings(mesh, 8, 2, per_slot_keys=True, cond=struct)
    assert set(s.cond) == {"mask", "observed"}
    # payload leaves shard over the batch axis exactly like the state
    assert s.cond["mask"].spec == s.x.spec
