"""Compile for a described TPU v5e, at the shapes the cells run.

Interpret mode cannot catch what the chip's compiler refuses: slices not
aligned to the tiling, more fast memory than a kernel may use, programs
that do not fit the device. These tests compile the Pallas kernels with
``interpret=False`` and the full-width DiT sample step for one chip of a
described ``v5e:2x2`` topology. Nothing runs; a compile that passes is
not a chip run.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and the
test workers each import every test file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.diffusion import HIGHRES_DIT, TRAJ_UNET

#: one v5e chip holds 16 GiB of HBM
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip, so keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("width", [32 * 32 * 3, 256 * 256 * 3])
@pytest.mark.parametrize("vector_eps", [False, True])
def test_solver_step_compiles(one_chip, width, vector_eps):
    from repro.kernels.solver_step import ops

    B = 8

    def step(x, xp, s2, z, xv, e0, d1, d2, ea, er):
        if not vector_eps:
            ea, er = 1e-2, 0.05
        return ops.error_step(x, xp, s2, z, xv, e0, d1, d2, eps_abs=ea,
                              eps_rel=er, interpret=False)

    args = [_spec(one_chip, (B, width))] * 5 + [_spec(one_chip, (B,))] * 5
    assert "tpu_custom_call" in _compile(step, *args).as_text()


@pytest.mark.parametrize("seq,head_dim", [(64, 32), (256, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_compiles(one_chip, seq, head_dim, dtype):
    from repro.kernels.flash_attention import ops

    q = _spec(one_chip, (8, 12, seq, head_dim), dtype)
    fn = lambda q, k, v: ops.attention(q, k, v, causal=False, interpret=False)
    assert "tpu_custom_call" in _compile(fn, q, q, q).as_text()


def _traj_unet_levels():
    """(horizon, channels) at each level of the trajectory UNet."""
    cfg = TRAJ_UNET
    return [(cfg.horizon // 2**i, cfg.base * m) for i, m in enumerate(cfg.mults)]


@pytest.mark.parametrize("level", range(len(TRAJ_UNET.mults)))
def test_groupnorm_silu_compiles(one_chip, level):
    from repro.kernels.groupnorm_silu import ops

    h, c = _traj_unet_levels()[level]
    fn = lambda x, s, b: ops.groupnorm_silu(x, s, b, groups=TRAJ_UNET.groups,
                                            interpret=False)
    args = (_spec(one_chip, (16, h, c)), _spec(one_chip, (c,)),
            _spec(one_chip, (c,)))
    assert "tpu_custom_call" in _compile(fn, *args).as_text()


def test_highres_dit_sample_step_fits_one_chip(one_chip):
    """One served Algorithm-1 chunk of the full-width DiT, over an
    8-slot tiered carry, fits one chip's HBM."""
    from repro.core import AdaptiveConfig, VPSDE
    from repro.core.solvers.adaptive import SolverCarry
    from repro.launch.sample import make_sample_step
    from repro.models.dit import init_dit

    net, B = HIGHRES_DIT, 8
    params = jax.eval_shape(lambda k: init_dit(net, k), jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda s: _spec(one_chip, s.shape, s.dtype), params)
    shape = (B, net.image_size, net.image_size, net.channels)
    vec = _spec(one_chip, (B,))
    ivec = _spec(one_chip, (B,), jnp.int32)
    carry = SolverCarry(
        x=_spec(one_chip, shape), x_prev=_spec(one_chip, shape), t=vec, h=vec,
        key=_spec(one_chip, (B, 2), jnp.uint32), nfe=ivec, accepted=ivec,
        rejected=ivec, done=_spec(one_chip, (B,), jnp.bool_),
        iterations=_spec(one_chip, (), jnp.int32), atol=vec, rtol=vec,
    )
    step = make_sample_step(net, VPSDE(), AdaptiveConfig(eps_rel=0.05))
    compiled = _compile(lambda p, c: step(p, c, max_sync_iters=4),
                        params, carry)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used
