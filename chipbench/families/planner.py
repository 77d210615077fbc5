"""Diffuser-style trajectory planner: configuration, weights, FLOP count
and the plain reference of the temporal UNet.

The system under test is ``repro.planning.RecedingHorizonPlanner``'s
host-driven ``DiffusionBatcher`` over ``repro.models.temporal_unet``,
with returns classifier-free guidance and the current state pinned by
``PlanConditioner``. The benchmark makes the weights itself from the
seed and hands the same ones to the program and to the reference below.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference
from chipbench.server import Check, Server


def widths(cfg) -> List[int]:
    return [cfg["dim"] * m for m in cfg["dim_mults"]]


def sample_shape(cfg):
    return (cfg["horizon"], cfg["observation_dim"] + cfg["action_dim"])


def _conv_flops(h_out, k, cin, cout):
    return 2 * h_out * k * cin * cout


def flop_per_eval(cfg) -> float:
    """Multiply-add FLOPs (2 per MAC) of one score evaluation of one
    plan: the convolutions and linear layers of the UNet, twice under
    classifier-free guidance (the conditional and the null row).
    GroupNorm, activations and the solver are left out."""
    k, td, D = cfg["kernel_size"], cfg["time_dim"], sum(sample_shape(cfg)[1:])
    H = cfg["horizon"]

    def res(h, cin, cout):
        return (_conv_flops(h, k, cin, cout) + _conv_flops(h, k, cout, cout)
                + 2 * td * cout + (2 * h * cin * cout if cin != cout else 0))

    ws = widths(cfg)
    f = 2 * 2 * td * td + _conv_flops(H, k, D, ws[0])
    cin, h = ws[0], H
    for i, w in enumerate(ws):
        f += res(h, cin, w)
        if i < len(ws) - 1:
            h //= 2
            f += _conv_flops(h, k, w, w)
        cin = w
    f += 2 * res(h, cin, cin)
    for i, w in enumerate(reversed(ws)):
        if i:
            h *= 2
            f += _conv_flops(h, k, cin, w) + res(h, 2 * w, w)
        else:
            f += res(h, cin, w)
        cin = w
    f += _conv_flops(H, k, cin, D)
    return float(f * (2 if cfg["condition_guidance_w"] else 1))


def init_weights(cfg, key):
    """The served weights, fp32, drawn from ``key`` in one program:
    N(0, 1/fan_in) convolutions and linear layers, unit GroupNorm scales,
    zero biases, N(0, 0.5^2) returns embeddings with a zero null row (the
    unconditional branch). Laid out as ``repro.models.temporal_unet``
    reads them."""
    k, td, D = cfg["kernel_size"], cfg["time_dim"], sum(sample_shape(cfg)[1:])
    ks = iter(jax.random.split(key, 128))

    def conv(kk, cin, cout):
        return (kk * cin) ** -0.5 * jax.random.normal(
            next(ks), (kk, cin, cout), jnp.float32)

    def res(cin, cout):
        p = {"gn1_s": jnp.ones((cin,)), "gn1_b": jnp.zeros((cin,)),
             "conv1": conv(k, cin, cout),
             "temb_w": td ** -0.5 * jax.random.normal(next(ks), (td, cout)),
             "temb_b": jnp.zeros((cout,)),
             "gn2_s": jnp.ones((cout,)), "gn2_b": jnp.zeros((cout,)),
             "conv2": conv(k, cout, cout)}
        if cin != cout:
            p["skip"] = conv(1, cin, cout)
        return p

    ws = widths(cfg)
    bins = cfg["returns_bins"]
    emb = 0.5 * jax.random.normal(next(ks), (bins + 1, td), jnp.float32)
    p: Dict[str, Any] = {
        "t_w1": td ** -0.5 * jax.random.normal(next(ks), (td, td)),
        "t_w2": td ** -0.5 * jax.random.normal(next(ks), (td, td)),
        "conv_in": conv(k, D, ws[0]),
        "ret_emb": emb.at[bins].set(0.0),
    }
    cin, downs = ws[0], []
    for i, w in enumerate(ws):
        downs.append({"res": res(cin, w),
                      **({"down": conv(k, w, w)} if i < len(ws) - 1 else {})})
        cin = w
    p["downs"] = downs
    p["mid1"], p["mid2"] = res(cin, cin), res(cin, cin)
    ups = []
    for i, w in enumerate(reversed(ws)):
        ups.append({**({"up": conv(k, cin, w)} if i else {}),
                    "res": res(2 * w if i else cin, w)})
        cin = w
    p["ups"] = ups
    p["gn_out_s"], p["gn_out_b"] = jnp.ones((cin,)), jnp.zeros((cin,))
    p["conv_out"] = conv(k, cin, D)
    return p


# -- plain reference ---------------------------------------------------------
def conv1d(x, w, stride: int = 1):
    """'SAME' 1-D convolution of x (B, H, Cin) with w (K, Cin, Cout), as a
    sum over the K taps of shifted matrix products."""
    K, H = w.shape[0], x.shape[1]
    h_out = -(-H // stride)
    pad = max((h_out - 1) * stride + K - H, 0)
    xp = jnp.pad(x, ((0, 0), (pad // 2, pad - pad // 2), (0, 0)))
    return sum(xp[:, j: j + (h_out - 1) * stride + 1: stride] @ w[j]
               for j in range(K))


def group_norm(x, scale, bias, groups):
    B, H, C = x.shape
    g = min(groups, C)
    xg = x.reshape(B, H, g, C // g)
    mu = xg.mean(axis=(1, 3), keepdims=True)
    var = ((xg - mu) ** 2).mean(axis=(1, 3), keepdims=True)
    return ((xg - mu) / jnp.sqrt(var + 1e-6)).reshape(B, H, C) * scale + bias


def reference_net(cfg, w, x, t, label):
    """The UNet of the configuration: noise prediction for plans x
    (B, H, D) at times t (B,) with returns labels (B,) (-1 = null)."""
    g, silu = cfg["n_groups"], jax.nn.silu
    bins = cfg["returns_bins"]
    temb = silu(reference.time_embedding(t, cfg["time_dim"]) @ w["t_w1"])
    temb = temb @ w["t_w2"]
    temb = temb + w["ret_emb"][jnp.where(label < 0, bins, label)]

    def res(p, x):
        h = conv1d(silu(group_norm(x, p["gn1_s"], p["gn1_b"], g)), p["conv1"])
        h = h + (silu(temb) @ p["temb_w"] + p["temb_b"])[:, None, :]
        h = conv1d(silu(group_norm(h, p["gn2_s"], p["gn2_b"], g)), p["conv2"])
        return (conv1d(x, p["skip"]) if "skip" in p else x) + h

    h = conv1d(x, w["conv_in"])
    skips = []
    for d in w["downs"]:
        h = res(d["res"], h)
        if "down" in d:
            skips.append(h)
            h = conv1d(h, d["down"], stride=2)
    h = res(w["mid2"], res(w["mid1"], h))
    for u in w["ups"]:
        if "up" in u:
            h = conv1d(jnp.repeat(h, 2, axis=1), u["up"])
            h = jnp.concatenate([h, skips.pop()], -1)
        h = res(u["res"], h)
    h = silu(group_norm(h, w["gn_out_s"], w["gn_out_b"], g))
    return conv1d(h, w["conv_out"])


def pin(cfg, obs: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(mask, observed) that pin the first row's observation coordinates
    of a plan to ``obs``."""
    mask = np.zeros(sample_shape(cfg), np.float32)
    observed = np.zeros(sample_shape(cfg), np.float32)
    mask[0, : cfg["observation_dim"]] = 1.0
    observed[0, : cfg["observation_dim"]] = obs
    return mask, observed


# -- the server under test ---------------------------------------------------
class _Dims:
    """The planner checks its environment's widths; the served traffic
    brings its own observations, so no environment is stepped."""

    def __init__(self, obs_dim, act_dim):
        self.obs_dim, self.act_dim = obs_dim, act_dim


class PlanServer(Server):
    device_resident = False

    def __init__(self, cfg, planner, key_words, request_cls):
        super().__init__(cfg)
        self.planner = planner
        self.batcher = planner.batcher
        self.key_words = key_words
        self.request_cls = request_cls
        self.step_program = cfg["serving"]["step_program"]
        self.flop_per_eval = flop_per_eval(cfg)
        self.sample_shape = sample_shape(cfg)

    def request(self, uid, spec):
        return self.request_cls(
            uid=uid, seed=spec["seed"],
            cond=self.planner.request_cond(spec["obs"], spec["label"]))

    def warm_request(self, uid):
        if not hasattr(self, "_warm_cond"):
            self._warm_cond = self.planner.request_cond(
                np.zeros(self.cfg["observation_dim"], np.float32), 0)
        return self.request_cls(uid=uid, seed=0, cond=self._warm_cond)

    def reference_aux(self, specs):
        mask, observed = self._pins(specs)
        return {"w": jax.jit(lambda k: init_weights(self.cfg, k))(self.key_words),
                "label": jnp.asarray([s["label"] for s in specs], jnp.int32),
                "mask": mask, "observed": observed}

    def reference_score(self, aux, x, t):
        _, std = reference.marginal(t)
        w, scale = aux["w"], self.cfg["condition_guidance_w"]
        out = reference_net(self.cfg, w, x, t, jnp.full_like(aux["label"], -1))
        if scale:
            out = out + scale * (reference_net(self.cfg, w, x, t, aux["label"])
                                 - out)
        return -out / std[:, None, None]

    def _pins(self, specs):
        pins = [pin(self.cfg, s["obs"]) for s in specs]
        return (jnp.asarray(np.stack([m for m, _ in pins])),
                jnp.asarray(np.stack([o for _, o in pins])))

    @staticmethod
    def reference_projection(aux, x, t, z):
        m, std = reference.marginal(t)
        noised = m[:, None, None] * aux["observed"] + std[:, None, None] * z
        return aux["mask"] * noised + (1.0 - aux["mask"]) * x

    def finalize(self, x, specs):
        mask, observed = (np.asarray(a) for a in self._pins(specs))
        return mask * observed + (1.0 - mask) * x

    def extra_checks(self, delivered, limits):
        """The pinned state at delivery, exactly, for every plan."""
        od = self.cfg["observation_dim"]
        gap = max((float(np.max(np.abs(d.result[0, :od] - d.spec["obs"])))
                   for d in delivered), default=0.0)
        return [Check("pin_gap", gap, limits["pin_gap"])]


def build(cfg, mix, key_words, *, precision: str, tracer, devices: int,
          wrap_step=None) -> PlanServer:
    """The program's receding-horizon planner over the benchmark's
    weights: returns guidance and state pinning through its
    ``PlanConditioner``, its host-driven batcher."""
    from repro.core import AdaptiveConfig, VPSDE
    from repro.core.precision import resolve_policy
    from repro.models.temporal_unet import (
        TemporalUNetConfig, temporal_unet_forward,
    )
    from repro.planning import PlannerConfig, RecedingHorizonPlanner
    from repro.planning.planner import PlanRequest

    if devices != 1:
        raise ValueError("the planner serves on one chip")
    ucfg = TemporalUNetConfig(
        horizon=cfg["horizon"], transition_dim=sample_shape(cfg)[1],
        base=cfg["dim"], mults=tuple(cfg["dim_mults"]), t_dim=cfg["time_dim"],
        groups=cfg["n_groups"], kernel=cfg["kernel_size"],
        returns_bins=cfg["returns_bins"])
    pcfg = PlannerConfig(horizon=cfg["horizon"], obs_dim=cfg["observation_dim"],
                         act_dim=cfg["action_dim"],
                         guidance_scale=cfg["condition_guidance_w"])
    policy = resolve_policy(precision)
    words = jnp.asarray(key_words, jnp.uint32)
    params = jax.jit(lambda k: policy.cast_params(init_weights(cfg, k)))(words)

    def forward(p, x, t, y=None):
        return temporal_unet_forward(p, x, t, ucfg, policy=policy, y=y)

    solver = cfg["solver"]
    acfg = AdaptiveConfig(eps_rel=solver["tiers"]["default"]["eps_rel"],
                          h_init=solver["h_init"], precision=precision)
    planner = RecedingHorizonPlanner(
        VPSDE(), forward, params, pcfg,
        _Dims(cfg["observation_dim"], cfg["action_dim"]), cfg=acfg,
        slots=mix["slots"], sync_horizon=cfg["serving"]["sync_horizon"],
        tracer=tracer)
    # the planner builds its step program itself: a planted fault wraps
    # the jitted chunk the host-driven loop calls
    if wrap_step is not None:
        step = planner.batcher.step_fn
        wrapped = wrap_step(lambda p, c, max_sync_iters=None: step(p, c))
        planner.batcher.step_fn = lambda p, c: wrapped(p, c)
    return PlanServer(cfg, planner, words, PlanRequest)
