"""Plain reference of the served sampler: the paper's Algorithm 1 on the
variance-preserving SDE, written from the paper (Jolicoeur-Martineau et
al. 2021, arXiv:2105.14080, Sec. 3 and Algorithm 1) in ``jax.numpy`` and
float32, one straightforward batched step, no kernels and no serving.

It imports nothing of the program under test. Its conventions are the
ones a served request is defined by:

* a request with noise seed ``s`` draws its prior from the first and its
  per-iteration noise from the second half of
  ``jax.random.split(jax.random.PRNGKey(s))``; every iteration splits the
  noise key and draws ``z`` from the second key (a projecting request
  splits once more for the re-noising draw of its observed coordinates);
* VP SDE with beta in [0.1, 20], t from 1 down to t_eps = 1e-3;
* mixed tolerance ``max(eps_abs, eps_rel * max(|x'|, |x'_prev|))``,
  dimension-normalised l2 error, accept when E <= 1, step rule
  ``h <- clip(0.9 * h * E^-0.9, 0, t - t_eps)``, extrapolated state x''.

The caller runs it under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

BETA_MIN, BETA_MAX, T_END, T_EPS = 0.1, 20.0, 1.0, 1e-3
SAFETY, R_EXP = 0.9, 0.9
#: absolute tolerance for data in [-1, 1]: (hi - lo) / 256
EPS_ABS = 2.0 / 256.0


def beta(t):
    return BETA_MIN + t * (BETA_MAX - BETA_MIN)


def marginal(t):
    """(mean scale, std) of x_t given x_0."""
    ib = BETA_MIN * t + 0.5 * t * t * (BETA_MAX - BETA_MIN)
    return jnp.exp(-0.5 * ib), jnp.sqrt(jnp.maximum(1.0 - jnp.exp(-ib), 1e-12))


def time_embedding(t, dim):
    """Sinusoidal embedding (B, dim) of times t (B,) in [0, 1], scaled by
    1000 as the score networks take them."""
    half = dim // 2
    freqs = jnp.exp(-jnp.log(10_000.0) * jnp.arange(half) / half)
    args = 1000.0 * t[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], -1)


def request_keys(seeds):
    """(prior keys, noise keys), each (B, 2) uint32, of noise seeds."""
    pairs = [np.asarray(jax.random.split(jax.random.PRNGKey(int(s))))
             for s in seeds]
    return (jnp.asarray(np.stack([p[0] for p in pairs])),
            jnp.asarray(np.stack([p[1] for p in pairs])))


def _bc(v, x):
    return v.reshape(v.shape + (1,) * (x.ndim - 1))


def _draw(keys, shape):
    pairs = jax.vmap(jax.random.split)(keys)
    z = jax.vmap(lambda k: jax.random.normal(k, shape, jnp.float32))(pairs[:, 1])
    return pairs[:, 0], z


def make_step(score: Callable, projection: Optional[Callable] = None):
    """One Algorithm-1 iteration over a batch of independent requests:
    ``step(aux, state) -> state``.

    ``score(aux, x, t)`` is the score field; ``projection(aux, x, t, z)``
    (optional) re-imposes observed coordinates on accepted states at their
    new time. ``aux`` carries the weights and per-request data as
    arguments, so the compiled step does not depend on them.
    """

    def step(aux, st):
        x, x_prev, t, h, keys, eps_rel, nfe = st
        shape = x.shape[1:]
        active = t > T_EPS + 1e-12
        t1 = jnp.clip(t, T_EPS, T_END)
        h1 = jnp.where(active, h, 0.0)
        t2 = jnp.clip(t1 - h1, T_EPS, T_END)
        keys, z = _draw(keys, shape)
        if projection is not None:
            keys, z_proj = _draw(keys, shape)
        # Euler-Maruyama proposal x' of the reverse SDE
        # dx = [-beta/2 x - beta s] dt + sqrt(beta) dw, stepped backwards
        b1 = beta(t1)
        x1 = (_bc(1.0 + 0.5 * h1 * b1, x) * x
              + _bc(h1 * b1, x) * score(aux, x, t1)
              + _bc(jnp.sqrt(h1 * b1), x) * z)
        # stochastic Improved Euler from x', same noise, then extrapolate
        b2 = beta(t2)
        x_tilde = (x + _bc(0.5 * h1 * b2, x) * x1
                   + _bc(h1 * b2, x) * score(aux, x1, t2)
                   + _bc(jnp.sqrt(h1 * b2), x) * z)
        x2 = 0.5 * (x1 + x_tilde)
        delta = jnp.maximum(EPS_ABS, _bc(eps_rel, x)
                            * jnp.maximum(jnp.abs(x1), jnp.abs(x_prev)))
        err = jnp.sqrt(jnp.mean(((x1 - x2) / delta) ** 2,
                                axis=tuple(range(1, x.ndim))))
        accept = (err <= 1.0) & active
        t_new = jnp.where(accept, t - h, t)
        x_new = jnp.where(_bc(accept, x), x2, x)
        if projection is not None:
            x_new = jnp.where(_bc(accept, x),
                              projection(aux, x_new, t_new, z_proj), x_new)
        x_prev = jnp.where(_bc(accept, x), x1, x_prev)
        h_new = jnp.clip(SAFETY * h * jnp.maximum(err, 1e-8) ** (-R_EXP),
                         0.0, jnp.maximum(t_new - T_EPS, 0.0))
        h = jnp.where(active, h_new, h)
        nfe = nfe + jnp.where(active, 2, 0)
        return x_new, x_prev, t_new, h, keys, eps_rel, nfe

    return step


def solve(step_fn, aux, seeds, shape, eps_rel, h0, *,
          max_iters: int = 10_000):
    """Solve each request to t_eps alone (as a batch of independent rows)
    with the jitted ``step_fn(aux, state)``. Returns the final states
    (B, *shape) and the NFE per request."""
    pk, nk = request_keys(seeds)
    x = jax.vmap(lambda k: jax.random.normal(k, shape, jnp.float32))(pk)
    b = len(seeds)
    st = (x, x, jnp.full((b,), T_END, jnp.float32),
          jnp.full((b,), min(float(h0), T_END - T_EPS), jnp.float32),
          nk, jnp.asarray(eps_rel, jnp.float32), jnp.zeros((b,), jnp.int32))
    for _ in range(max_iters):
        st = step_fn(aux, st)
        if not bool(jnp.any(st[2] > T_EPS + 1e-12)):
            break
    return np.asarray(st[0]), np.asarray(st[6])
