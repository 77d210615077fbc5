"""Pipeline parallelism: single-stage degenerate path must equal the
plain scan over the full stack (exact), with any microbatch count."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_host_mesh
from repro.parallel.pipeline import pipeline_forward


def _stacked_mlp(key, R, d):
    k1, k2 = jax.random.split(key)
    return {
        "w1": 0.3 * jax.random.normal(k1, (R, d, d)),
        "w2": 0.3 * jax.random.normal(k2, (R, d, d)),
    }


def _body(stage_params, x):
    """Scan over the stage's local super-blocks."""

    def block(x, p):
        h = jax.nn.gelu(x @ p["w1"])
        return x + h @ p["w2"], None

    x, _ = jax.lax.scan(block, x, stage_params)
    return x


def _reference(params, x):
    return _body(params, x)


@pytest.mark.parametrize("microbatches", [1, 2, 4])
def test_single_stage_equals_scan(microbatches, rng):
    R, d, B = 4, 16, 8
    params = _stacked_mlp(rng, R, d)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (B, d))
    want = _reference(params, x)
    with jax.set_mesh(make_host_mesh()):  # data axis size 1 → one pipeline stage
        got = pipeline_forward(params, x, _body, axis="data",
                               num_microbatches=microbatches)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_is_jittable(rng):
    R, d, B = 2, 8, 4
    params = _stacked_mlp(rng, R, d)
    x = jax.random.normal(rng, (B, d))
    with jax.set_mesh(make_host_mesh()):
        fn = jax.jit(lambda p, x: pipeline_forward(
            p, x, _body, axis="data", num_microbatches=2))
        got = fn(params, x)
    assert bool(jnp.all(jnp.isfinite(got)))
