"""Score-network hot-path guardrails (DESIGN.md §13).

Three families:

  * **public attention owner** — ``repro.models.attention.attention`` is
    the single flash/softcap/window dispatch point: ``use_flash=False``
    is bitwise the reference path, the flash path matches to kernel
    tolerance (including the sequence-padding path), and softcap /
    cross-length calls fall back to the reference bitwise.
  * **DiT / temporal-UNet routing** — flash-vs-reference and
    fused-vs-unfused parity per precision preset, and the off-state /
    fresh-block bitwise-neutrality pins: flags default off, a config
    with the flags off produces bit-identical params AND outputs to the
    pre-flag stack, and a freshly-initialized attention block (zero-init
    output projection) is the identity.
  * **_groupnorm fp32-stats regression** — the bf16-preset audit: group
    statistics must be computed in fp32 (a large common offset with
    small spread would lose its variance to bf16 cancellation),
    parametrized over operand dtype.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.precision import resolve_policy
from repro.models.attention import _ref_attention, attention
from repro.models.dit import DiTConfig, dit_forward, init_dit, liven_dit
from repro.models.temporal_unet import (
    TemporalUNetConfig, _groupnorm, _gn_silu, init_temporal_unet,
    temporal_unet_forward,
)

PRESETS = ["fp32", "bf16", "bf16_full"]
# fast-vs-baseline forward tolerance per preset (outputs compared in
# fp32): fp32 differs only by kernel reduction order; the bf16 presets
# add one-vs-two rounding in the norm chain and bf16 matmul inputs
TOLS = {"fp32": dict(rtol=1e-4, atol=1e-4),
        "bf16": dict(rtol=5e-2, atol=5e-2),
        "bf16_full": dict(rtol=5e-2, atol=5e-2)}


def _f32(a):
    return np.asarray(a, np.float32)


def _qkv(rng, B=2, S=37, H=4, D=16):
    kq, kk, kv = jax.random.split(rng, 3)
    # (B, S, H, D) — the model-side layout the owner accepts
    return (jax.random.normal(kq, (B, S, H, D)),
            jax.random.normal(kk, (B, S, H, D)),
            jax.random.normal(kv, (B, S, H, D)))


# --------------------------- attention owner ---------------------------

def test_attention_off_state_bitwise(rng):
    """use_flash=False IS the reference path — bitwise, not allclose."""
    q, k, v = _qkv(rng)
    out = attention(q, k, v, causal=False, window=None, softcap=0.0,
                    use_flash=False)
    want = _ref_attention(q, k, v, causal=False, window=None, softcap=0.0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_attention_flash_padding_path(rng):
    """S=25 with 8-wide blocks pads 7 key/query rows — the masked tail
    must not leak into the softmax."""
    q, k, v = _qkv(rng, S=25)
    out = attention(q, k, v, causal=False, window=None, softcap=0.0,
                    use_flash=True, block_q=8, block_k=8)
    want = _ref_attention(q, k, v, causal=False, window=None, softcap=0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_attention_softcap_falls_back_bitwise(rng):
    """No flash softcap kernel — the owner must take the reference path
    (with the cap applied) even when use_flash=True."""
    q, k, v = _qkv(rng)
    out = attention(q, k, v, causal=False, window=None, softcap=30.0,
                    use_flash=True)
    want = _ref_attention(q, k, v, causal=False, window=None, softcap=30.0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_attention_cross_length_falls_back_bitwise(rng):
    """Sq != Sk (cross-attention) has no flash path — reference, bitwise."""
    q, _, _ = _qkv(rng, S=8)
    _, k, v = _qkv(rng, S=16)
    out = attention(q, k, v, causal=False, window=None, softcap=0.0,
                    use_flash=True)
    want = _ref_attention(q, k, v, causal=False, window=None, softcap=0.0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


# ------------------------------- DiT ----------------------------------

def _small_dit(**kw):
    return DiTConfig(image_size=16, patch=4, d_model=64, num_layers=2,
                     num_heads=4, d_ff=128, **kw)


@pytest.mark.parametrize("preset", PRESETS)
def test_dit_flash_parity(preset, rng):
    cfg0 = _small_dit()
    cfg1 = dataclasses.replace(cfg0, use_flash=True)
    assert cfg0.use_flash is False  # flag defaults off
    policy = resolve_policy(preset)
    params = policy.cast_params(liven_dit(init_dit(cfg0, rng), rng))
    x = jax.random.normal(rng, (2, 16, 16, 3))
    t = jnp.linspace(0.1, 1.0, 2)
    base = dit_forward(params, x, t, cfg0, policy=policy)
    fast = dit_forward(params, x, t, cfg1, policy=policy)
    np.testing.assert_allclose(_f32(base), _f32(fast), **TOLS[preset])


def test_dit_flash_token_padding(rng):
    """image_size=8 / patch=4 → 4 tokens, under the kernel's minimum
    8-wide block: the owner's flash path must survive the pad-and-mask
    route, not just block-aligned token counts."""
    cfg0 = DiTConfig(image_size=8, patch=4, d_model=32, num_layers=1,
                     num_heads=4, d_ff=64)
    cfg1 = dataclasses.replace(cfg0, use_flash=True)
    params = liven_dit(init_dit(cfg0, rng), rng)
    x = jax.random.normal(rng, (2, 8, 8, 3))
    t = jnp.linspace(0.1, 1.0, 2)
    base = dit_forward(params, x, t, cfg0)
    fast = dit_forward(params, x, t, cfg1)
    np.testing.assert_allclose(_f32(base), _f32(fast), rtol=3e-5, atol=3e-5)


# --------------------------- temporal UNet -----------------------------

UCFG = TemporalUNetConfig(horizon=16, transition_dim=6, base=16,
                          mults=(1, 2), t_dim=32, groups=4, attn_heads=4)


def _liven(params, key, wo=False):
    """Perturb the zero-init leaves (conv2/conv_out, optionally the
    attention output projection) so forwards carry signal — a fresh
    net's output is identically zero and every parity check would pass
    vacuously."""
    ks = iter(jax.random.split(key, 64))
    bump = lambda w: 0.02 * jax.random.normal(next(ks), w.shape, w.dtype)
    blocks = ([d["res"] for d in params["downs"]]
              + [params["mid1"], params["mid2"]]
              + [u["res"] for u in params["ups"]])
    for blk in blocks:
        blk["conv2"] = bump(blk["conv2"])
    params["conv_out"] = bump(params["conv_out"])
    if wo:
        params["attn"]["wo"] = bump(params["attn"]["wo"])
    return params


def _traj_inputs(rng, cfg=UCFG, B=3):
    x = jax.random.normal(rng, (B, cfg.horizon, cfg.transition_dim))
    t = jnp.linspace(0.1, 1.0, B)
    return x, t


def test_unet_param_tree_backcompat(rng):
    """attention=True appends params LAST: every pre-existing leaf is
    bit-identical to the attention=False init from the same key."""
    pa = init_temporal_unet(dataclasses.replace(UCFG, attention=True), rng)
    pb = init_temporal_unet(UCFG, rng)
    attn = pa.pop("attn")
    assert set(attn) == {"gn_s", "gn_b", "wq", "wk", "wv", "wo"}
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)), pa, pb)


def test_unet_fresh_attention_block_bitwise_neutral(rng):
    """Zero-init output projection: a freshly-added bottleneck attention
    block is the identity, so attention=True-with-fresh-block and
    attention=False produce bit-identical outputs."""
    cfg_on = dataclasses.replace(UCFG, attention=True)
    params = _liven(init_temporal_unet(cfg_on, rng), rng)  # wo stays zero
    x, t = _traj_inputs(rng)
    on = temporal_unet_forward(params, x, t, cfg_on)
    off = temporal_unet_forward(
        {k: v for k, v in params.items() if k != "attn"}, x, t, UCFG)
    np.testing.assert_array_equal(np.asarray(on), np.asarray(off))


def test_unet_off_state_is_unfused_chain(rng):
    """use_fused_norm=False is literally the historical
    silu(_groupnorm(...)) chain — bitwise."""
    assert UCFG.use_fused_norm is False and UCFG.use_flash is False
    kx, ks, kb = jax.random.split(rng, 3)
    x = jax.random.normal(kx, (3, 16, 32))
    scale = 1.0 + 0.1 * jax.random.normal(ks, (32,))
    bias = 0.1 * jax.random.normal(kb, (32,))
    a = _gn_silu(x, scale, bias, 4, fused=False)
    b = jax.nn.silu(_groupnorm(x, scale, bias, 4))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("preset", PRESETS)
def test_unet_fast_path_parity(preset, rng):
    """use_flash + use_fused_norm vs the jnp baseline, same (livened)
    params, per preset — the full-forward analog of the kernel sweeps."""
    cfg_base = dataclasses.replace(UCFG, attention=True)
    cfg_fast = dataclasses.replace(cfg_base, use_flash=True,
                                   use_fused_norm=True)
    policy = resolve_policy(preset)
    params = policy.cast_params(
        _liven(init_temporal_unet(cfg_base, rng), rng, wo=True))
    x, t = _traj_inputs(rng)
    base = temporal_unet_forward(params, x, t, cfg_base, policy=policy)
    fast = temporal_unet_forward(params, x, t, cfg_fast, policy=policy)
    np.testing.assert_allclose(_f32(base), _f32(fast), **TOLS[preset])


# ----------------------- _groupnorm fp32 stats -------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=lambda d: jnp.dtype(d).name)
def test_groupnorm_fp32_stats_large_offset(dtype, rng):
    """The bf16-preset audit pin: statistics run in fp32 regardless of
    operand dtype. x = 100 + 2·noise has var ≈ 4 while E[x²] ≈ 10⁴;
    bf16 statistics (or the one-pass E[x²]−μ² form near bf16 precision,
    where the spacing at 10⁴ is 64) would lose the variance to
    cancellation and return garbage normalization. The noise scale is
    chosen above bf16's quantization step at 100 (0.5), so the spread
    survives *input* quantization and any failure is the statistics'.
    The output must be ≈ zero-mean / unit-std per (sample, group) slab."""
    B, H, C, g = 4, 16, 32, 8
    noise = 2.0 * jax.random.normal(rng, (B, H, C))
    x = (100.0 + noise).astype(dtype)
    out = _f32(_groupnorm(x, jnp.ones((C,), dtype), jnp.zeros((C,), dtype), g))
    slabs = out.reshape(B, H, g, C // g)
    mu = slabs.mean(axis=(1, 3))
    sd = slabs.std(axis=(1, 3))
    tol = 5e-3 if dtype == jnp.float32 else 6e-2  # bf16 quantizes x itself
    np.testing.assert_allclose(mu, np.zeros_like(mu), atol=tol)
    np.testing.assert_allclose(sd, np.ones_like(sd), atol=2 * tol)
    # and the fp64 elementwise reference from the quantized operands
    xq = _f32(x).astype(np.float64).reshape(B, H, g, C // g)
    want = ((xq - xq.mean(axis=(1, 3), keepdims=True))
            / np.sqrt(xq.var(axis=(1, 3), keepdims=True) + 1e-6)
            ).reshape(B, H, C)
    np.testing.assert_allclose(out, want, rtol=2e-2, atol=2e-2)
