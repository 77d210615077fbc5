"""Correctness of the §Perf optimization levers: every variant must be
numerically equivalent to the baseline path (they only change layout /
communication, never math)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_host_mesh
from repro.models import (
    ModelConfig, MoEConfig, decode_step, forward, init_decode_state,
    init_model,
)


def _dense_cfg(**kw):
    base = dict(name="t", arch_type="dense", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=97,
                mixer_pattern=("L", "A"), mlp_pattern=("D", "D"),
                sliding_window=4)
    base.update(kw)
    return ModelConfig(**base)


def _teacher_forced(cfg, params, toks, cross=None):
    logits, _ = forward(params, toks, cfg, cross_embeds=cross)
    return logits


@pytest.mark.parametrize("axis", [
    "model",
    pytest.param("data,model", marks=pytest.mark.slow),  # 2-axis variant
])
def test_flash_decode_matches_teacher_forcing(axis, rng):
    cfg = _dense_cfg()
    params = init_model(cfg, rng)
    toks = jax.random.randint(rng, (2, 10), 0, cfg.vocab_size)
    want = _teacher_forced(cfg, params, toks)

    cfg_fd = cfg.replace(decode_flash_shard=axis)
    with jax.set_mesh(make_host_mesh()):
        st = init_decode_state(cfg_fd, 2, cache_len=12)
        step = jax.jit(lambda p, t, s: decode_step(p, t, s, cfg_fd))
        outs = []
        for i in range(10):
            lg, st = step(params, toks[:, i : i + 1], st)
            outs.append(lg[:, 0])
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=5e-4)


def test_flash_decode_ring_wraparound(rng):
    """Sliding-window layer with cache smaller than the sequence: the
    ring buffer wraps and flash-decode must stay exact."""
    cfg = _dense_cfg(mixer_pattern=("L", "L"), sliding_window=3)
    params = init_model(cfg, rng)
    toks = jax.random.randint(rng, (1, 12), 0, cfg.vocab_size)
    want = _teacher_forced(cfg, params, toks)
    cfg_fd = cfg.replace(decode_flash_shard="model")
    with jax.set_mesh(make_host_mesh()):
        st = init_decode_state(cfg_fd, 1, cache_len=4)  # < seq len → wraps
        step = jax.jit(lambda p, t, s: decode_step(p, t, s, cfg_fd))
        outs = []
        for i in range(12):
            lg, st = step(params, toks[:, i : i + 1], st)
            outs.append(lg[:, 0])
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=5e-4)


def test_expert_padding_preserves_outputs(rng):
    """Padded experts must never be routed to: outputs identical to the
    unpadded model given identical real-expert weights."""
    from repro.models.moe import apply_moe, init_moe

    cfg = ModelConfig(
        name="moe", arch_type="moe", num_layers=1, d_model=32, num_heads=4,
        num_kv_heads=4, d_ff=0, vocab_size=16, mlp_pattern=("E",),
        moe=MoEConfig(num_experts=5, top_k=2, expert_ffn=16),
    )
    cfg_pad = cfg.replace(moe=cfg.moe.__class__(
        num_experts=5, top_k=2, expert_ffn=16, padded_experts=8,
    ))
    params_pad = init_moe(rng, cfg_pad)
    # unpadded params = slice of padded params
    params = {
        "router": params_pad["router"][:, :5],
        "w_in": params_pad["w_in"][:5],
        "w_gate": params_pad["w_gate"][:5],
        "w_out": params_pad["w_out"][:5],
    }
    x = jax.random.normal(rng, (2, 64, 32))
    y0, a0 = apply_moe(params, x, cfg)
    y1, a1 = apply_moe(params_pad, x, cfg_pad)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=2e-5, atol=2e-5)
    assert float(a0) == pytest.approx(float(a1), rel=1e-5)


def test_seq_shard_constraint_is_noop_on_host_mesh(rng):
    """attn_q_seq_shard / residual_seq_shard only change layout: on a
    1×1 mesh the outputs are bit-comparable to the unconstrained path."""
    cfg = _dense_cfg()
    cfg_sp = cfg.replace(attn_q_seq_shard="model", residual_seq_shard="model")
    params = init_model(cfg, rng)
    toks = jax.random.randint(rng, (2, 8), 0, cfg.vocab_size)
    with jax.set_mesh(make_host_mesh()):
        l0, _ = jax.jit(lambda p, t: forward(p, t, cfg))(params, toks)
        l1, _ = jax.jit(lambda p, t: forward(p, t, cfg_sp))(params, toks)
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                               rtol=1e-6, atol=1e-6)
