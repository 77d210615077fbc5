"""DiT image server: configuration, weights, FLOP count and the plain
reference of the score network.

The system under test is ``repro.serving.diffusion_server.DiffusionBatcher``
run device-resident over ``repro.models.dit``, as ``launch/serve`` serves
it: tolerance tiers with EDF admission (``EdfPriorityAdmission(aging_s=
5.0)``), the solver step from ``repro.launch.sample.make_sample_step``.
The benchmark makes the weights itself from the seed and hands the same
ones to the program and to the reference below.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference
from chipbench.server import Server

#: how far the benchmark's sinusoidal time embedding is wide
T_EMB = 256


def shapes(cfg: Dict[str, Any]) -> Dict[str, int]:
    d, p = cfg["hidden_size"], cfg["patch_size"]
    n = cfg["input_size"] // p
    return {"d": d, "layers": cfg["depth"], "heads": cfg["num_heads"],
            "dh": d // cfg["num_heads"], "ff": int(cfg["mlp_ratio"] * d),
            "tokens": n * n, "pdim": p * p * cfg["in_channels"]}


def sample_shape(cfg):
    return (cfg["input_size"], cfg["input_size"], cfg["in_channels"])


def flop_per_eval(cfg) -> float:
    """Multiply-add FLOPs (2 per MAC) of one score evaluation of one
    sample: the matrix products of the network. Elementwise work (norms,
    softmax, activations, the solver) is left out."""
    s = shapes(cfg)
    d, ff, n, L = s["d"], s["ff"], s["tokens"], s["layers"]
    per_token_layer = (2 * 4 * d * d            # q, k, v, o projections
                       + 2 * 3 * d * ff          # gated MLP: in, gate, out
                       + 2 * 2 * n * d)          # q k^T and p v
    per_sample = (L * 2 * d * 6 * d             # adaLN modulation
                  + 2 * d * 2 * d               # final adaLN
                  + 2 * T_EMB * d + 2 * d * d)  # time-embedding MLP
    return float(L * n * per_token_layer + 2 * n * 2 * s["pdim"] * d
                 + per_sample)


def init_weights(cfg, key):
    """The served weights, fp32, drawn from ``key`` in one program:
    N(0, 1/fan_in) for the projections, N(0, 0.02^2) for positions and
    the adaLN and output layers (random, so the score is not zero), zero
    adaLN biases. Laid out as ``repro.models.dit`` reads them."""
    s = shapes(cfg)
    d, L, H, dh, ff = s["d"], s["layers"], s["heads"], s["dh"], s["ff"]
    ks = iter(jax.random.split(key, 16))

    def w(shape, fan_in=None, std=None):
        std = std if std is not None else (fan_in or shape[-2]) ** -0.5
        return std * jax.random.normal(next(ks), shape, jnp.float32)

    return {
        "patch_in": w((s["pdim"], d)),
        "pos_emb": w((s["tokens"], d), std=0.02),
        "t_mlp1": w((T_EMB, d)),
        "t_mlp2": w((d, d)),
        "layers": {
            "attn": {"wq": w((L, d, H, dh), fan_in=d),
                     "wk": w((L, d, H, dh), fan_in=d),
                     "wv": w((L, d, H, dh), fan_in=d),
                     "wo": w((L, H, dh, d), fan_in=H * dh)},
            "mlp": {"w_in": w((L, d, ff)), "w_gate": w((L, d, ff)),
                    "w_out": w((L, ff, d))},
            "norm1": {}, "norm2": {},
            "ada": w((L, d, 6 * d), std=0.02),
            "ada_b": jnp.zeros((L, 6 * d), jnp.float32),
        },
        "final_norm": {},
        "final_ada": w((d, 2 * d), std=0.02),
        "final_ada_b": jnp.zeros((2 * d,), jnp.float32),
        "patch_out": w((d, s["pdim"]), std=0.02),
    }


# -- plain reference ---------------------------------------------------------
def _layernorm(h):
    mu = jnp.mean(h, -1, keepdims=True)
    var = jnp.mean((h - mu) ** 2, -1, keepdims=True)
    return (h - mu) / jnp.sqrt(var + 1e-6)


def reference_net(cfg, w, x, t):
    """The DiT forward of the configuration, layer by layer: noise
    prediction for x (B, H, W, C) at times t (B,)."""
    s = shapes(cfg)
    p, n, d = cfg["patch_size"], cfg["input_size"] // cfg["patch_size"], s["d"]
    B, C = x.shape[0], cfg["in_channels"]
    silu = jax.nn.silu
    temb = silu(reference.time_embedding(t, T_EMB) @ w["t_mlp1"]) @ w["t_mlp2"]
    tok = (x.reshape(B, n, p, n, p, C).transpose(0, 1, 3, 2, 4, 5)
           .reshape(B, n * n, p * p * C))
    h = tok @ w["patch_in"] + w["pos_emb"]
    lw = w["layers"]
    for i in range(s["layers"]):
        mod = silu(temb) @ lw["ada"][i] + lw["ada_b"][i]
        s1, b1, g1, s2, b2, g2 = (m[:, None, :] for m in jnp.split(mod, 6, -1))
        hn = _layernorm(h) * (1 + s1) + b1
        q, k, v = (jnp.einsum("bsd,dhe->bhse", hn, lw["attn"][m][i])
                   for m in ("wq", "wk", "wv"))
        a = jax.nn.softmax(jnp.einsum("bhse,bhte->bhst", q, k)
                           / np.sqrt(s["dh"]), -1)
        o = jnp.einsum("bhst,bhte->bshe", a, v)
        h = h + g1 * jnp.einsum("bshe,hed->bsd", o, lw["attn"]["wo"][i])
        hn = _layernorm(h) * (1 + s2) + b2
        mlp = lw["mlp"]
        gate = silu(hn @ mlp["w_gate"][i]) * (hn @ mlp["w_in"][i])
        h = h + g2 * (gate @ mlp["w_out"][i])
    mod = silu(temb) @ w["final_ada"] + w["final_ada_b"]
    sc, sh = (m[:, None, :] for m in jnp.split(mod, 2, -1))
    out = (_layernorm(h) * (1 + sc) + sh) @ w["patch_out"]
    return (out.reshape(B, n, n, p, p, C).transpose(0, 1, 3, 2, 4, 5)
            .reshape(B, n * p, n * p, C))


# -- the server under test ---------------------------------------------------
class DitServer(Server):
    device_resident = True

    def __init__(self, cfg, batcher, key_words, request_cls):
        super().__init__(cfg)
        self.batcher = batcher
        self.key_words = key_words
        self.request_cls = request_cls
        self.step_program = cfg["serving"]["step_program"]
        self.flop_per_eval = flop_per_eval(cfg)
        self.sample_shape = sample_shape(cfg)

    def request(self, uid, spec):
        return self.request_cls(uid=uid, seed=spec["seed"], tier=spec["tier"])

    def warm_request(self, uid):
        return self.request_cls(uid=uid, seed=0,
                                tier=self.cfg["serving"]["warm_tier"])

    def warm_tail(self, uid):
        """A long and a short request: the short one is delivered alone
        with nothing queued, after which the server runs its next solve
        on a carry whose iteration counter it reset outside its event
        program, which the driver compiles for once."""
        return [self.request_cls(uid=uid, seed=0, tier=t)
                for uid, t in ((uid, "high_fidelity"), (uid + 1, "draft"))]

    def reference_aux(self, specs):
        return jax.jit(lambda k: init_weights(self.cfg, k))(self.key_words)

    def reference_score(self, w, x, t):
        _, std = reference.marginal(t)
        return -reference_net(self.cfg, w, x, t) / std[:, None, None, None]


def build(cfg, mix, key_words, *, precision: str, tracer, devices: int,
          wrap_step=None) -> DitServer:
    """The program's device-resident tiered server over the benchmark's
    weights, as ``launch/serve`` builds it."""
    from repro.core import AdaptiveConfig, VPSDE
    from repro.core.precision import resolve_policy
    from repro.launch.mesh import make_data_mesh
    from repro.launch.sample import make_sample_step
    from repro.models.dit import DiTConfig
    from repro.serving.diffusion_server import DiffusionBatcher, ImageRequest
    from repro.serving.scheduler import EdfPriorityAdmission

    s, serving, solver = shapes(cfg), cfg["serving"], cfg["solver"]
    net = DiTConfig(image_size=cfg["input_size"], channels=cfg["in_channels"],
                    patch=cfg["patch_size"], d_model=s["d"],
                    num_layers=s["layers"], num_heads=s["heads"], d_ff=s["ff"])
    policy = resolve_policy(precision)
    words = jnp.asarray(key_words, jnp.uint32)
    params = jax.jit(lambda k: policy.cast_params(init_weights(cfg, k)))(words)
    sde = VPSDE()
    acfg = AdaptiveConfig(eps_rel=solver["tiers"]["default"]["eps_rel"],
                          h_init=solver["h_init"], precision=precision)
    step = make_sample_step(net, sde, acfg)
    batcher = DiffusionBatcher(
        sde, step if wrap_step is None else wrap_step(step), params,
        sample_shape(cfg), slots=mix["slots"], cfg=acfg,
        mesh=make_data_mesh(devices), sync_horizon=serving["sync_horizon"],
        max_horizons=serving["max_horizons"], device_resident=True,
        tolerance_classes=True,
        admission=EdfPriorityAdmission(aging_s=serving["edf_aging_s"]),
        tracer=tracer)
    return DitServer(cfg, batcher, words, ImageRequest)
