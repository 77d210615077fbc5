"""The plain references agree with the program's networks at test sizes,
and the FLOP counts agree with what XLA counts for the reference forward
at the cells' own sizes (XLA also counts elementwise work, so it reads a
little higher, never lower)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import DATA, ROOT

from chipbench.families import dit, planner

KEY = jnp.asarray([7, 11], jnp.uint32)


def _cfg(path):
    return json.loads(path.read_text())


def test_dit_reference_matches_the_program():
    from repro.models.dit import DiTConfig, dit_forward

    cfg = _cfg(DATA / "tiny-dit.json")
    s = dit.shapes(cfg)
    net = DiTConfig(image_size=cfg["input_size"], channels=cfg["in_channels"],
                    patch=cfg["patch_size"], d_model=s["d"],
                    num_layers=s["layers"], num_heads=s["heads"], d_ff=s["ff"])
    w = dit.init_weights(cfg, KEY)
    x = jax.random.normal(jax.random.PRNGKey(1), (3,) + dit.sample_shape(cfg))
    t = jnp.asarray([0.01, 0.4, 1.0])
    got = dit_forward(w, x, t, net)
    want = dit.reference_net(cfg, w, x, t)
    assert float(jnp.max(jnp.abs(want))) > 0.1
    np.testing.assert_allclose(got, want, atol=1e-5 * float(jnp.max(jnp.abs(want))))


def test_unet_reference_matches_the_program():
    from repro.models.temporal_unet import (
        TemporalUNetConfig, temporal_unet_forward,
    )

    cfg = _cfg(DATA / "tiny-plan.json")
    ucfg = TemporalUNetConfig(
        horizon=cfg["horizon"], transition_dim=planner.sample_shape(cfg)[1],
        base=cfg["dim"], mults=tuple(cfg["dim_mults"]), t_dim=cfg["time_dim"],
        groups=cfg["n_groups"], kernel=cfg["kernel_size"],
        returns_bins=cfg["returns_bins"])
    w = planner.init_weights(cfg, KEY)
    x = jax.random.normal(jax.random.PRNGKey(2), (4,) + planner.sample_shape(cfg))
    t = jnp.asarray([0.01, 0.3, 0.7, 1.0])
    y = jnp.asarray([0, 3, -1, 1])
    got = temporal_unet_forward(w, x, t, ucfg, y=y)
    want = planner.reference_net(cfg, w, x, t, y)
    assert float(jnp.max(jnp.abs(want))) > 0.1
    np.testing.assert_allclose(got, want, atol=1e-5 * float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("family,config", [
    (dit, "dit-b16-px256"), (planner, "diffuser-cheetah-h32")])
def test_flop_count_matches_xla_at_cell_size(family, config):
    cfg = _cfg(ROOT / "chipbench" / "configs" / f"{config}.json")
    w = jax.eval_shape(lambda: family.init_weights(cfg, KEY))
    x = jax.ShapeDtypeStruct((1,) + family.sample_shape(cfg), jnp.float32)
    t = jax.ShapeDtypeStruct((1,), jnp.float32)
    if family is dit:
        fn = lambda w, x, t: dit.reference_net(cfg, w, x, t)  # noqa: E731
        args = (w, x, t)
        per_eval = 1
    else:
        y = jax.ShapeDtypeStruct((1,), jnp.int32)
        fn = lambda w, x, t, y: planner.reference_net(cfg, w, x, t, y)  # noqa: E731
        args = (w, x, t, y)
        per_eval = 2 if cfg["condition_guidance_w"] else 1
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    xla = per_eval * float(cost["flops"])
    ours = family.flop_per_eval(cfg)
    assert ours <= xla <= 1.1 * ours, (ours, xla)
