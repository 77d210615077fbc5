"""Receding-horizon trajectory planning on the adaptive solver
(DESIGN.md §10).

Decision-diffuser-style planning is controlled generation over
``(B, H, D)`` trajectories (horizon H, transition width D = obs + act),
and this module is deliberately *thin*: every mechanism it needs
already exists in the conditioning seam (DESIGN.md §9) and the serving
stack (DESIGN.md §7). Song et al. (2021, App. I) reduce conditional
generation to a modified score field; here

  * **current-state conditioning** is inpainting along the horizon
    axis — the first ``context`` rows' observation coordinates are
    observed data, projected after every accepted step and pinned
    exactly at delivery;
  * **returns conditioning** is classifier-free guidance over
    discretized returns-to-go bins — ``ClassifierFree`` consuming the
    label payload of a returns-aware score (``temporal_unet`` with
    ``returns_bins > 0``, or the analytic class score);
  * :class:`PlanConditioner` composes the two (one static conditioner,
    one merged payload), and :func:`plan_conditioner` builds the
    (conditioner, payload) pair from an observation/returns pair —
    returning ``(None, None)`` when there is nothing to condition on,
    the bit-identical unconditional path.

:func:`plan` is the single-shot form (one adaptive solve per call);
:class:`RecedingHorizonPlanner` is the closed loop: plans are requests
in a ``DiffusionBatcher`` (DESIGN.md §7), each env executes the first
action of its delivered plan, and the *re-conditioned* request — same
request machinery, new pinned state — is re-admitted into a freed slot.
Per-slot keys and the carry-payload compaction rule are what make the
loop correct: a plan's trajectory depends only on its (seed, payload),
never on which slot it lands in or which envs share the batch.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import AdaptiveConfig, sample
from repro.core.guidance import ClassifierFree, Inpaint, cond_batch
from repro.core.solvers import SolveResult
from repro.serving.diffusion_server import DiffusionBatcher, ImageRequest

Array = jax.Array

#: planning requests are ordinary batcher requests — same queue, same
#: slots, same compaction (DESIGN.md §10)
PlanRequest = ImageRequest

#: sentinel returns-bin meaning "unconditional" (the null CFG branch)
NULL_RETURN = -1


@dataclasses.dataclass(frozen=True)
class PlannerConfig:
    """Trajectory layout + conditioning knobs (DESIGN.md §10).

    A trajectory row h is ``[s_h, a_h]``: ``transition_dim = obs_dim +
    act_dim``. The first ``context`` rows' observation coordinates are
    the pinned (inpainted) current state; the executed action is row
    ``context - 1``'s action — the action taken *from* the newest
    pinned state.
    """

    horizon: int = 8
    obs_dim: int = 2
    act_dim: int = 2
    context: int = 1
    #: returns-CFG scale (0 = evaluate the null branch — bit-identical
    #: to unconditional for the zero-null-row nets, DESIGN.md §10)
    guidance_scale: float = 0.0
    null_label: int = NULL_RETURN

    @property
    def transition_dim(self) -> int:
        return self.obs_dim + self.act_dim

    @property
    def sample_shape(self) -> Tuple[int, int]:
        return (self.horizon, self.transition_dim)


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class PlanConditioner(ClassifierFree):
    """Returns-CFG × current-state pinning, one conditioner
    (DESIGN.md §10).

    The score-field half is inherited from :class:`ClassifierFree`
    (``wrap_score`` consumes ``cond["label"]``; ``scale == 0`` is the
    single null-labeled forward). The projection half is verbatim
    :class:`Inpaint` — post-accept, at each slot's own new t, fp32
    under every precision preset, exact pin at delivery (DESIGN.md §9's
    project-after-accept rationale applies unchanged: the mask just
    happens to select horizon rows instead of pixels). The payload
    merges both: ``{"label": (B,), "mask"/"observed": (B, H, D)}``.
    """

    has_projection = True

    # the projection half is Inpaint's, bit for bit — these hooks only
    # read cond["mask"] / cond["observed"], which the merged payload has
    project = Inpaint.project
    finalize_project = Inpaint.finalize_project

    def cond_struct(self, batch: int, sample_shape) -> Any:
        shp = (batch,) + tuple(sample_shape)
        sds = jax.ShapeDtypeStruct(shp, jnp.float32)
        return {
            "label": jax.ShapeDtypeStruct((batch,), jnp.int32),
            "mask": sds,
            "observed": sds,
        }

    def neutral_cond(self, batch: int, sample_shape) -> Any:
        """Null label (unconditional branch) + zero mask (identity
        projection) — the idle-slot payload (DESIGN.md §9)."""
        shp = (batch,) + tuple(sample_shape)
        return {
            "label": jnp.full((batch,), self.null_label, jnp.int32),
            "mask": jnp.zeros(shp, jnp.float32),
            "observed": jnp.zeros(shp, jnp.float32),
        }


def state_pin(pcfg: PlannerConfig, state) -> Dict[str, Array]:
    """Inpainting payload pinning the current state along the horizon
    axis (DESIGN.md §10): mask = 1 on the observation coordinates of
    the first ``context`` rows, ``observed`` carrying the state there.

    ``state`` is ``(B, obs_dim)`` (context = 1) or
    ``(B, context, obs_dim)``.
    """
    s = jnp.asarray(state, jnp.float32)
    if s.ndim == 2:
        s = s[:, None, :]
    b, ctx, od = s.shape
    if ctx != pcfg.context or od != pcfg.obs_dim:
        raise ValueError(
            f"state {s.shape[1:]} != (context, obs_dim) "
            f"({pcfg.context}, {pcfg.obs_dim})"
        )
    shp = (b,) + pcfg.sample_shape
    mask = jnp.zeros(shp, jnp.float32).at[:, :ctx, :od].set(1.0)
    observed = jnp.zeros(shp, jnp.float32).at[:, :ctx, :od].set(s)
    return {"mask": mask, "observed": observed}


def plan_conditioner(pcfg: PlannerConfig, *, state=None, returns=None):
    """(conditioner, payload) for a planning solve (DESIGN.md §10).

    ``state`` pins the current observation(s) via inpainting over the
    horizon axis; ``returns`` is an int ``(B,)`` vector of returns-to-go
    bin labels for classifier-free guidance at
    ``pcfg.guidance_scale``. Either may be None:

      * both None → ``(None, None)``: the bit-identical unconditional
        path (no conditioner object at all);
      * state only → plain :class:`Inpaint`;
      * returns only → plain :class:`ClassifierFree`;
      * both → :class:`PlanConditioner` with the merged payload.
    """
    if state is None and returns is None:
        return None, None
    if returns is None:
        return Inpaint(), state_pin(pcfg, state)
    labels = jnp.asarray(returns, jnp.int32)
    if state is None:
        return (
            ClassifierFree(scale=float(pcfg.guidance_scale),
                           null_label=pcfg.null_label),
            {"label": labels},
        )
    return (
        PlanConditioner(scale=float(pcfg.guidance_scale),
                        null_label=pcfg.null_label),
        {"label": labels, **state_pin(pcfg, state)},
    )


def returns_to_bin(returns, lo: float, hi: float, bins: int) -> Array:
    """Discretize returns-to-go into the embedding-table bins of a
    returns-aware score net (``TemporalUNetConfig.returns_bins``)."""
    r = jnp.asarray(returns, jnp.float32)
    idx = jnp.floor((r - lo) / (hi - lo) * bins)
    return jnp.clip(idx, 0, bins - 1).astype(jnp.int32)


def plan(
    sde,
    score_fn,
    obs,
    key: Array,
    *,
    pcfg: PlannerConfig,
    returns=None,
    config: AdaptiveConfig | None = None,
    mesh=None,
    batch: int | None = None,
    **overrides,
) -> SolveResult:
    """One planning solve: sample ``(B, H, D)`` trajectories with the
    adaptive solver, conditioned on the current observation(s) ``obs``
    (``(B, obs_dim)``; None → unconditional prior plans) and optional
    returns-to-go bin labels (DESIGN.md §10).

    The delivered trajectories have the pinned coordinates equal to
    ``obs`` exactly (``finalize_project``); read the executed action
    with :func:`first_action`. The score must be label-aware
    (``s(x, t, y)``) whenever ``returns`` is given.
    """
    conditioner, cond = plan_conditioner(pcfg, state=obs, returns=returns)
    cfg = config or AdaptiveConfig(eps_rel=0.05)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    if conditioner is not None:
        cfg = dataclasses.replace(cfg, conditioner=conditioner)
    if cond is not None:
        payload_batch = cond_batch(cond)
        if batch is not None and batch != payload_batch:
            raise ValueError(
                f"batch={batch} disagrees with the condition payload's "
                f"batch dim {payload_batch}")
        batch = payload_batch
    elif batch is None:
        raise ValueError("unconditional plan() needs an explicit batch=")
    return sample(sde, score_fn, (batch,) + pcfg.sample_shape, key,
                  method="adaptive", config=cfg, cond=cond, mesh=mesh)


def first_action(x, pcfg: PlannerConfig):
    """Executed action of a delivered plan: row ``context − 1``'s action
    coordinates — the action taken from the newest pinned state.
    Accepts ``(H, D)`` or ``(B, H, D)``."""
    row = pcfg.context - 1
    return x[..., row, pcfg.obs_dim: pcfg.obs_dim + pcfg.act_dim]


class RecedingHorizonPlanner:
    """Closed-loop planner serving on the diffusion batcher
    (DESIGN.md §10).

    Each environment's plan is an ordinary :class:`PlanRequest` in a
    :class:`DiffusionBatcher` whose conditioner is a
    :class:`PlanConditioner` (or plain :class:`Inpaint` when returns
    guidance is off). One control round:

      1. every env submits a request whose payload pins its *current*
         observation (and carries its returns bin);
      2. the batcher drains — converged plans retire at sync horizons,
         survivors compact shard-locally, queued requests admit into
         freed slots (envs > slots exercises real queueing);
      3. each env executes :func:`first_action` of its delivered plan
         against the analytic environment and the *re-conditioned*
         request (new pinned state, fresh uid/seed) re-enters the queue
         next round.

    Per-slot keys + the §9 payload-compaction rule make every delivered
    plan bit-identical to a standalone ``adaptive()`` solve of the same
    (seed, payload) — re-admission can never perturb a neighbour —
    which ``tests/test_planning.py`` asserts along with exact
    per-request NFE accounting.
    """

    def __init__(
        self,
        sde,
        forward_fn,
        params,
        pcfg: PlannerConfig,
        env,
        *,
        cfg: AdaptiveConfig | None = None,
        slots: int = 4,
        sync_horizon: int = 4,
        compaction: bool = True,
        mesh=None,
        tracer=None,
    ):
        from repro.launch.sample import make_sample_step

        self.pcfg = pcfg
        self.env = env
        if env.obs_dim != pcfg.obs_dim or env.act_dim != pcfg.act_dim:
            raise ValueError(
                f"env dims ({env.obs_dim}, {env.act_dim}) != planner "
                f"({pcfg.obs_dim}, {pcfg.act_dim})"
            )
        base = cfg or AdaptiveConfig(eps_rel=0.05)
        if base.conditioner is None:
            base = dataclasses.replace(
                base,
                conditioner=PlanConditioner(
                    scale=float(pcfg.guidance_scale),
                    null_label=pcfg.null_label,
                ),
            )
        self.cfg = base
        # the device step is built HERE, from the same final cfg the
        # batcher gets — a step compiled without the conditioner would
        # silently skip the in-loop projection while delivery still
        # pinned, exactly the kind of mismatch one constructor prevents.
        # ``forward_fn(params, x, t, y=None)`` is noise-prediction
        # (score = −out/std), label-aware when returns guidance is on.
        # precision threads the same way: the batcher derives its slot
        # dtype from this cfg's policy, so pass AdaptiveConfig(precision=
        # ...) rather than a separate policy that could diverge
        sample_step = make_sample_step(None, sde, base, forward_fn=forward_fn)
        self.batcher = DiffusionBatcher(
            sde, sample_step, params, pcfg.sample_shape,
            slots=slots, cfg=base, mesh=mesh,
            sync_horizon=sync_horizon, compaction=compaction,
            # one tracer through planner rounds AND the batcher's
            # admission/solve/delivery stages (DESIGN.md §15), so a
            # plan/round span brackets the serve spans it caused
            tracer=tracer,
        )
        self._uid = 0

    def request_cond(self, obs, returns_label: Optional[int] = None):
        """Unbatched per-request payload rows (DESIGN.md §9), shaped by
        the server conditioner's own ``cond_struct``: the pin mask /
        observation for this env's current state and/or its returns bin
        (None → the null label) — so Inpaint-only and CFG-only
        conditioners get exactly the keys they declare. Traced as
        ``plan/request`` on the batcher's tracer (DESIGN.md §15)."""
        with self.batcher.tracer.span("plan/request"):
            struct = self.cfg.conditioner.cond_struct(1, self.pcfg.sample_shape)
            if returns_label is not None and "label" not in struct:
                raise ValueError(
                    f"returns_label={returns_label} given but the server "
                    f"conditioner {type(self.cfg.conditioner).__name__} carries "
                    f"no label payload — the guidance would be silently dropped")
            pin = state_pin(self.pcfg, jnp.asarray(obs)[None])
            label = (self.pcfg.null_label if returns_label is None
                     else int(returns_label))
            rows = {"label": jnp.int32(label), **{k: v[0] for k, v in pin.items()}}
            unknown = set(struct) - set(rows)
            if unknown:
                raise ValueError(
                    f"server conditioner declares payload keys {sorted(unknown)} "
                    f"the planner cannot fill (have {sorted(rows)})")
            return {k: rows[k] for k in struct}

    def rollout(
        self,
        key: Array,
        *,
        n_envs: int,
        n_steps: int,
        returns_label: Optional[int] = None,
        seed0: int = 0,
    ) -> Dict[str, Any]:
        """Run ``n_envs`` environments for ``n_steps`` control rounds
        through the shared batcher; returns rewards, per-request NFE,
        and the batcher's waste accounting (DESIGN.md §10)."""
        keys = jax.random.split(key, n_envs + 1)
        obs = [self.env.reset(keys[i + 1]) for i in range(n_envs)]
        step_key = keys[0]
        rewards = np.zeros((n_steps, n_envs))
        nfes = np.zeros((n_steps, n_envs), np.int64)
        for round_i in range(n_steps):
            with self.batcher.tracer.span(
                "plan/round", round=round_i, envs=n_envs
            ) as sp:
                uids = []
                for i in range(n_envs):
                    uid = seed0 + self._uid
                    self._uid += 1
                    self.batcher.submit(PlanRequest(
                        uid=uid, seed=uid,
                        cond=self.request_cond(obs[i], returns_label),
                    ))
                    uids.append(uid)
                sp["attrs"]["uids"] = list(uids)
                done = self.batcher.run_to_completion()
                for i, uid in enumerate(uids):
                    req = done[uid]
                    a = np.asarray(first_action(req.result, self.pcfg))
                    step_key, k = jax.random.split(step_key)
                    obs[i], r = self.env.step(obs[i], jnp.asarray(a), k)
                    rewards[round_i, i] = r
                    nfes[round_i, i] = req.nfe
        b = self.batcher
        return {
            "rewards": rewards,
            "nfe": nfes,
            "finished": b.finished,
            "total_iterations": b.total_iterations,
            "wasted_nfe_fraction": b.wasted_nfe_fraction,
            "passenger_nfe_fraction": b.passenger_nfe_fraction,
            "refills_per_device": list(b.refills_per_device),
        }
