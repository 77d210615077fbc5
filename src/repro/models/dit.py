"""DiT — transformer score network over image patches (adaLN conditioning).

This is how the paper's technique becomes a first-class feature of the
LM framework (DESIGN.md §4): any dense ``ModelConfig`` doubles as the
backbone of a time-conditioned score network. Patchified image tokens
run through the same attention/MLP blocks (non-causal), modulated per
block by adaLN(t). ``score_apply`` exposes the s(x, t) signature every
solver in ``repro.core`` consumes.

Precision (DESIGN.md §8): pass ``policy=`` (a
``repro.core.precision.PrecisionPolicy``) to run activations — and the
weight copies the matmuls consume — in the policy's compute dtype. The
timestep-embedding MLP always computes in fp32 from the stored (master)
weights, and the norms upcast internally (``apply_norm``), so the
conditioning path keeps full precision while the O(L·D²) block math
runs reduced. ``make_score_fn(..., policy=...)`` additionally stores
weights at ``param_dtype`` and returns the score in ``state_dtype``
with the 1/std rescale done in fp32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.attention import attention, init_attention
from repro.models.config import ModelConfig
from repro.models.layers import (
    apply_mlp,
    apply_norm,
    dense_init,
    init_mlp,
    init_norm,
    rope,
    timestep_embedding,
)

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class DiTConfig:
    image_size: int = 32
    channels: int = 3
    patch: int = 4
    d_model: int = 256
    num_layers: int = 6
    num_heads: int = 8
    d_ff: int = 1024
    dtype: str = "float32"
    #: class-conditional mode (DESIGN.md §9): > 0 adds a label-embedding
    #: table with one extra null row (classifier-free training style);
    #: 0 (the default) leaves params and forward bit-identical to the
    #: unconditional net.
    num_classes: int = 0
    #: route the block attention through the Pallas flash kernel
    #: (DESIGN.md §13). ``False`` (the default) is bit-identical to the
    #: reference-attention stack; ``True`` agrees to fp32-accumulation
    #: tolerance per precision preset (gated by
    #: ``tests/test_score_hotpath.py``).
    use_flash: bool = False

    @property
    def tokens(self) -> int:
        return (self.image_size // self.patch) ** 2

    @property
    def patch_dim(self) -> int:
        return self.patch * self.patch * self.channels

    def as_model_config(self) -> ModelConfig:
        return ModelConfig(
            name="dit-backbone",
            arch_type="dense",
            num_layers=self.num_layers,
            d_model=self.d_model,
            num_heads=self.num_heads,
            num_kv_heads=self.num_heads,
            d_ff=self.d_ff,
            vocab_size=8,  # unused
            dtype=self.dtype,
        )


def init_dit(cfg: DiTConfig, key: Array) -> Dict[str, Any]:
    mcfg = cfg.as_model_config()
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    R = cfg.num_layers

    def init_layer(k):
        ka, km, kc = jax.random.split(k, 3)
        return {
            "attn": init_attention(ka, mcfg, "A"),
            "mlp": init_mlp(km, cfg.d_model, cfg.d_ff, True, dtype),
            "norm1": init_norm(kc, cfg.d_model, "layernorm_np", dtype),
            "norm2": init_norm(kc, cfg.d_model, "layernorm_np", dtype),
            # adaLN: 6 modulation vectors from the time embedding
            "ada": jnp.zeros((cfg.d_model, 6 * cfg.d_model), dtype),
            "ada_b": jnp.zeros((6 * cfg.d_model,), dtype),
        }

    layers = jax.vmap(init_layer)(jax.random.split(ks[0], R))
    extra = {}
    if cfg.num_classes > 0:
        # one embedding row per class + a trailing null row (index
        # num_classes) for the unconditional branch of CFG sampling
        extra["label_emb"] = 0.02 * jax.random.normal(
            ks[6], (cfg.num_classes + 1, cfg.d_model), jnp.float32
        ).astype(dtype)
    return {
        **extra,
        "patch_in": dense_init(ks[1], (cfg.patch_dim, cfg.d_model), dtype),
        "pos_emb": 0.02 * jax.random.normal(ks[2], (cfg.tokens, cfg.d_model), jnp.float32).astype(dtype),
        "t_mlp1": dense_init(ks[3], (256, cfg.d_model), dtype),
        "t_mlp2": dense_init(ks[4], (cfg.d_model, cfg.d_model), dtype),
        "layers": layers,
        "final_norm": init_norm(ks[5], cfg.d_model, "layernorm_np", dtype),
        "final_ada": jnp.zeros((cfg.d_model, 2 * cfg.d_model), dtype),
        "final_ada_b": jnp.zeros((2 * cfg.d_model,), dtype),
        "patch_out": jnp.zeros((cfg.d_model, cfg.patch_dim), dtype),
    }


def liven_dit(params: Dict[str, Any], key: Array,
              std: float = 0.02) -> Dict[str, Any]:
    """Fill the zero-init leaves (``ada``, ``final_ada``, ``patch_out``)
    with N(0, std²) noise drawn from ``key``.

    ``init_dit`` zeroes them (adaLN-Zero), so a fresh DiT outputs
    exactly 0 and any comparison of two forwards passes vacuously. Use
    this for random weights that are served or compared.
    """
    k_ada, k_final, k_out = jax.random.split(key, 3)

    def noise(k, w):
        return (std * jax.random.normal(k, w.shape, jnp.float32)).astype(w.dtype)

    return {
        **params,
        "layers": {**params["layers"],
                   "ada": noise(k_ada, params["layers"]["ada"])},
        "final_ada": noise(k_final, params["final_ada"]),
        "patch_out": noise(k_out, params["patch_out"]),
    }


def _patchify(x: Array, cfg: DiTConfig) -> Array:
    B, H, W, C = x.shape
    p = cfg.patch
    x = x.reshape(B, H // p, p, W // p, p, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(B, cfg.tokens, cfg.patch_dim)


def _unpatchify(t: Array, cfg: DiTConfig) -> Array:
    B = t.shape[0]
    p = cfg.patch
    n = cfg.image_size // p
    t = t.reshape(B, n, n, p, p, cfg.channels)
    return t.transpose(0, 1, 3, 2, 4, 5).reshape(
        B, cfg.image_size, cfg.image_size, cfg.channels
    )


def dit_forward(params: Dict[str, Any], x: Array, t: Array, cfg: DiTConfig,
                policy=None, y: Array | None = None) -> Array:
    """x (B, H, W, C), t (B,) → same-shape output (raw network output).

    With ``policy`` the activations (and the weight copies the matmuls
    consume) run in ``policy.compute``; the timestep-embedding math is
    fp32 from the stored weights, and ``apply_norm`` upcasts internally,
    so only the block matmuls/attention run reduced. The output is in
    the compute dtype; ``make_score_fn`` handles the downstream cast.

    ``y`` (DESIGN.md §9): optional int32 (B,) class labels for a
    class-conditional net (``cfg.num_classes > 0``); negative labels
    select the trailing null row (the unconditional branch of CFG).
    The label embedding joins the conditioning path, so like the
    timestep embedding it is added in fp32 from the stored weights.
    """
    mcfg = cfg.as_model_config()
    # fp32 timestep-embedding math from the stored (master) weights,
    # before any compute-dtype cast touches the tree
    f32 = lambda w: w.astype(jnp.float32)
    temb = timestep_embedding(t, 256)  # fp32
    temb = jax.nn.silu(temb @ f32(params["t_mlp1"])) @ f32(params["t_mlp2"])
    if y is not None and cfg.num_classes > 0:
        idx = jnp.where(y < 0, cfg.num_classes, y).astype(jnp.int32)
        temb = temb + f32(params["label_emb"])[idx]

    if policy is not None:
        x = x.astype(policy.compute)
        params = policy.params_for_compute(params)
    h = _patchify(x, cfg) @ params["patch_in"] + params["pos_emb"]
    temb = temb.astype(h.dtype)  # (B, D)

    def layer(h, lp):
        mod = jax.nn.silu(temb) @ lp["ada"] + lp["ada_b"]  # (B, 6D)
        s1, b1, g1, s2, b2, g2 = jnp.split(mod[:, None, :], 6, axis=-1)
        hn = apply_norm(lp["norm1"], h, "layernorm_np") * (1 + s1) + b1
        q = jnp.einsum("bse,ehd->bshd", hn, lp["attn"]["wq"])
        k = jnp.einsum("bse,ehd->bshd", hn, lp["attn"]["wk"])
        v = jnp.einsum("bse,ehd->bshd", hn, lp["attn"]["wv"])
        att = attention(q, k, v, causal=False, window=None, softcap=0.0,
                        use_flash=cfg.use_flash)
        h = h + g1 * jnp.einsum("bshd,hde->bse", att, lp["attn"]["wo"])
        hn = apply_norm(lp["norm2"], h, "layernorm_np") * (1 + s2) + b2
        h = h + g2 * apply_mlp(lp["mlp"], hn, "silu", True)
        return h, None

    h, _ = jax.lax.scan(layer, h, params["layers"])
    mod = jax.nn.silu(temb) @ params["final_ada"] + params["final_ada_b"]
    s, b = jnp.split(mod[:, None, :], 2, axis=-1)
    h = apply_norm(params["final_norm"], h, "layernorm_np") * (1 + s) + b
    return _unpatchify(h @ params["patch_out"], cfg)


def make_score_fn(params, cfg: DiTConfig, sde, policy=None,
                  conditioner=None, cond=None):
    """Wrap the raw net into s(x,t) = net(x,t)/std(t) (noise-pred param.).

    With ``policy``: weights are stored at ``param_dtype``, x casts to
    ``compute_dtype`` on entry, the 1/std rescale runs in fp32 (std can
    be O(1e-2) for VE — dividing in bf16 would waste the score's
    mantissa), and the returned score is in ``state_dtype``.

    When ``cfg.num_classes > 0`` the returned score is label-aware —
    ``s(x, t, y)`` with ``y`` optional — which is the signature a
    ``ClassifierFree`` conditioner consumes (DESIGN.md §9).

    ``conditioner``/``cond`` (DESIGN.md §9) bake a *static* payload
    into the returned field (standalone/whole-batch use: fixed labels,
    one mask for the run). The solver/serving path instead threads the
    payload through ``SolverCarry.cond`` and wraps per-chunk — do not
    pass a conditioner here *and* in ``AdaptiveConfig``, that would
    apply the transform twice.
    """
    if policy is not None:
        params = policy.cast_params(params)

    def score(x: Array, t: Array, y: Array | None = None) -> Array:
        _, std = sde.marginal(t)
        if policy is not None:
            x = policy.to_compute(x)
        out = dit_forward(params, x, t, cfg, policy=policy, y=y)
        s = -out.astype(jnp.float32) / std.reshape((-1,) + (1,) * (x.ndim - 1))
        return s if policy is None else policy.to_state(s)

    if conditioner is not None:
        return conditioner.wrap_score(score, cond)
    return score
