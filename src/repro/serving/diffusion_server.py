"""Continuous-batching diffusion sampling server.

The paper's per-sample step sizes (Sec. 3.1.5) mean each sample in a
batch finishes its reverse diffusion at its own NFE. In a serving
context that is exactly the continuous-batching opportunity: run a fixed
slot batch of Algorithm-1 state, and whenever a slot's t reaches t_eps,
deliver the image and refill the slot with a fresh prior draw for the
next request — no request ever waits for the batch's slowest sample.

Horizon-chunked solve (DESIGN.md §7): the device step is the solver's
own ``solve_chunk`` over a ``SolverCarry`` with *per-slot* PRNG keys —
``sync_horizon`` Algorithm-1 iterations run device-side per host
round-trip, then the host retires converged slots, compacts survivors,
and admits queued requests into the freed slots (fresh prior draw at
t = T under the request's own key). Because every slot owns its noise
stream, a sample's trajectory is invariant to which slot it occupies
and to what its seatmates do — compaction and admission never perturb
in-flight samples.

Throughput math (DESIGN.md §4): naive batched sampling costs max_i NFE_i
per batch of requests; slot refill costs ~mean_i NFE_i — the gap grows
with the per-sample NFE spread the paper's adaptivity creates. The
``wasted_nfe_fraction`` property measures the residual waste: the share
of issued score-net evaluations that served idle or already-converged
slots.

Mesh scale-out (DESIGN.md §3): pass ``mesh=`` to shard the slot batch
over the mesh's data axes. Each device then owns a contiguous block of
``slots / device_count`` slots and compaction is *shard-local*: slots
are only ever permuted within their device's block, so no sample (or
its PRNG key) ever crosses a shard boundary. ``refills_per_device``
records the per-device admission counts.

Device-resident hot path (DESIGN.md §12): with ``device_resident=True``
the per-horizon polling loop itself moves on-device. A jitted driver
(``solve_horizons``-shaped ``lax.while_loop`` with the slot carry
*donated*) chains sync-horizon chunks until a serving event — a pending
delivery — fires, and the host reads back exactly one scalar
``events_pending`` flag per driver call. Only when the flag is set does
the host pull the (B,) bookkeeping + retired rows, compute the
compaction permutation and admissions, and apply them through a second
jitted, donated event update (gather by permutation, masked admission
scatter, per-request keys derived from seed words and prior draws, all
on device). Host↔device traffic is O(delivered requests), not O(sync
horizons); delivered samples are bit-identical to the host-driven loop
because per-slot keys make trajectories invariant to slot placement and
sync timing.

Device step = repro.launch.sample.make_sample_step (the same
``solve_chunk`` unit the production-mesh dry-run lowers); the host loop
only watches t and swaps slots.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import AdaptiveConfig
from repro.core.precision import resolve_policy
from repro.core.sde import SDE
from repro.core.solvers import solver_nfe_per_iteration
from repro.core.solvers.adaptive import SolverCarry, events_pending
from repro.observability.metrics import MetricsRegistry
from repro.observability.telemetry import (
    StepTelemetry, init_telemetry, telemetry_history,
)
from repro.observability.tracing import NULL_TRACER
from repro.serving.scheduler import (
    AdmissionPolicy, FifoAdmission, TierAccounting, tier_name,
)

Array = jax.Array


@dataclasses.dataclass
class ImageRequest:
    """One sampling request (DESIGN.md §4/§9/§14): a seed, optionally a
    per-request condition payload for the server's conditioner, and —
    on a tiered server — a tolerance class, deadline, and priority."""

    uid: int
    seed: int
    #: per-request condition payload (DESIGN.md §9): the *unbatched*
    #: pytree this request's slot row should carry (e.g. ``{"mask":
    #: (H, W, C), "observed": (H, W, C)}`` or ``{"label": ()}``). None
    #: (with a conditioner configured) means the neutral payload —
    #: zero mask / label 0, i.e. effectively unconditional.
    cond: Any = None
    #: tolerance class (DESIGN.md §14): a preset name from
    #: ``repro.configs.diffusion.TOLERANCE_CLASSES`` (or the server's
    #: own registry) or a ``ToleranceClass``. None = the server's
    #: static-config tolerance (the pre-tier behaviour).
    tier: Any = None
    #: latency budget in milliseconds from submission; None = no
    #: deadline (a tier's own ``deadline_ms`` applies if set)
    deadline_ms: Optional[float] = None
    #: admission band, lower = more urgent; None defers to the tier's
    #: ``priority`` (0 for untiered requests)
    priority: Optional[int] = None
    result: Optional[np.ndarray] = None
    nfe: int = 0
    done: bool = False
    #: set at delivery: did this request outlive its deadline?
    deadline_missed: bool = False
    #: device iterations spent occupying a slot (admission → retirement);
    #: nfe_per_iter·resident_iters − nfe is this request's
    #: frozen-passenger waste
    resident_iters: int = 0
    #: per-request accept/reject counts (DESIGN.md §15), pulled with the
    #: NFE at retirement from the same carry bookkeeping — for the
    #: Algorithm-1 families nfe == nfe_per_iter·(accepted + rejected),
    #: the identity the telemetry reconciliation test pins
    accepted: int = 0
    rejected: int = 0
    #: absolute deadline on the server's clock, stamped at submit()
    deadline_at: Optional[float] = dataclasses.field(default=None, repr=False)
    _admit_iters: int = dataclasses.field(default=0, repr=False)
    _submit_t: float = dataclasses.field(default=0.0, repr=False)
    _seat_t: Optional[float] = dataclasses.field(default=None, repr=False)

    @property
    def queue_wait_s(self) -> Optional[float]:
        """Seconds from ``submit()`` to the admission that seated this
        request, on the batcher's clock; None until it is seated."""
        return None if self._seat_t is None else self._seat_t - self._submit_t


class DiffusionBatcher:
    """Slot-compacting sampler around a pjit-able ``solve_chunk`` step.

    ``sync_horizon`` sets how many Algorithm-1 iterations run device-side
    between host syncs (1 = the classic per-step loop; larger horizons
    amortize host round-trips at the cost of up to horizon-1 iterations
    of retirement latency per converged slot).

    ``compaction=True`` (default) retires converged slots and admits
    queued requests at every sync horizon. ``compaction=False`` is the
    monolithic-wave baseline: the batch only turns over once *every*
    occupied slot has converged — exactly the "wait for all images"
    semantics of the paper's batched loop, kept for A/B measurement
    (benchmarks/bench_compaction.py).

    ``policy`` (DESIGN.md §8) sets the slot carry's state dtype; it
    defaults to ``cfg.precision`` so the carry matches what the
    ``sample_step`` built from the same cfg expects. Retirement,
    compaction, and admission are dtype-agnostic — admitted priors are
    cast to the carry's dtype, and the host only ever reads the fp32
    control fields plus the retired rows.

    Conditioning (DESIGN.md §9): when ``cfg.conditioner`` is set, the
    carry grows a per-slot condition payload (``SolverCarry.cond``).
    Idle slots hold the conditioner's neutral payload; at admission a
    request's own ``ImageRequest.cond`` is written into its slot's
    rows, and compaction moves condition leaves with their samples —
    shard-locally, exactly like the per-slot PRNG keys — so a
    request's conditioning follows it through any slot permutation.

    Tolerance tiers (DESIGN.md §14): ``tolerance_classes`` turns on
    per-request quality tiers — the carry grows per-slot ``atol``/
    ``rtol`` leaves so every seated request solves at its own class's
    tolerance inside one fused device step; ``admission`` picks which
    queued requests take free slots (FIFO default; EDF within priority
    bands via ``scheduler.EdfPriorityAdmission``) and ``delivery``
    accumulates per-class NFE + deadline-miss counters at the ``_d2h``
    accounting seam (``class_stats``). Left off, the carry keeps the
    exact pre-tier pytree structure and the serve loop is bitwise
    identical to the static-config stack.

    ``device_resident=True`` (DESIGN.md §12) replaces the per-horizon
    host round-trip with the on-device multi-horizon driver: up to
    ``max_horizons`` sync-horizon chunks run per host visit, the carry
    buffers are donated to both the driver and the event update, and
    the host reads one scalar event flag per driver call (see module
    docstring). ``host_transfers`` counts every device→host read the
    serve loop issues — the metric bench_device_serving.py reports.

    ``solver``/``solver_kwargs`` name the solver family the
    ``sample_step`` runs so waste accounting can convert loop
    iterations to issued score-net evaluations via the registry's
    ``solver_nfe_per_iteration`` (hardcoding the adaptive family's 2
    made ``wasted_nfe_fraction`` negative for e.g. ``pc_hmc``, which
    issues ``1 + corrector_steps·hmc_leapfrog`` per iteration).
    """

    def __init__(
        self,
        sde: SDE,
        sample_step: Callable,  # (params, carry, max_sync_iters=N) -> carry
        params,
        sample_shape,           # per-sample shape, e.g. (16, 16, 3)
        *,
        slots: int = 8,
        cfg: AdaptiveConfig | None = None,
        mesh=None,
        sync_horizon: int = 1,
        compaction: bool = True,
        policy=None,
        device_resident: bool = False,
        max_horizons: int = 32,
        solver: str = "adaptive",
        solver_kwargs: Optional[dict] = None,
        tolerance_classes=None,
        admission: Optional[AdmissionPolicy] = None,
        delivery=None,
        clock: Optional[Callable[[], float]] = None,
        telemetry: int = 0,
        tracer=None,
        metrics: Optional[MetricsRegistry] = None,
    ):
        self.sde = sde
        self.cfg = cfg or AdaptiveConfig()
        self.policy = resolve_policy(
            policy if policy is not None else self.cfg.precision
        )
        self.params = params
        self.n = slots
        self.shape = tuple(sample_shape)
        self.mesh = mesh
        self.sync_horizon = int(sync_horizon)
        self.compaction = bool(compaction)
        self.device_resident = bool(device_resident)
        self.max_horizons = int(max_horizons)
        self.solver = solver
        #: score-net evaluations one device loop iteration issues over
        #: the full slot batch, from the solver registry (DESIGN.md §7)
        self.nfe_per_iter = solver_nfe_per_iteration(
            solver, **(solver_kwargs or {})
        )
        #: tiered serving (DESIGN.md §14): truthy grows the carry per-slot
        #: ``atol``/``rtol`` leaves so each seated request solves at its
        #: own tolerance; a dict is this server's name → ToleranceClass
        #: registry (default: the ``configs.diffusion`` presets). False
        #: keeps the exact pre-tier carry structure — the static-config
        #: path stays bitwise identical.
        self.tiered = bool(tolerance_classes)
        self.tolerance_classes = (
            tolerance_classes if isinstance(tolerance_classes, dict) else None
        )
        #: admission stage (DESIGN.md §14): which queued requests take
        #: free slots. FIFO = the pre-policy behaviour, exactly.
        self.admission = admission if admission is not None else FifoAdmission()
        #: delivery stage: per-class NFE + deadline accounting at the
        #: ``_d2h`` seam (anything with ``on_deliver(req, now)``)
        self.delivery = delivery if delivery is not None else TierAccounting()
        self._clock = clock if clock is not None else time.monotonic
        #: step-telemetry ring capacity per slot (DESIGN.md §15): > 0
        #: grows the carry a ``StepTelemetry`` ring so the device loop
        #: records every iteration's (t, h, err, accept) per slot; 0
        #: (the default) keeps the exact pre-telemetry carry treedef and
        #: serve loop, bit for bit
        self.telemetry_capacity = int(telemetry)
        #: stage tracer (DESIGN.md §15): spans around the admission /
        #: solve / delivery stages with request-id attrs; the default
        #: NULL_TRACER records nothing and reads no clock
        self.tracer = tracer if tracer is not None else NULL_TRACER
        #: metrics registry (DESIGN.md §15): every serve-loop counter —
        #: iterations, useful/resident NFE, host transfers, accept /
        #: reject totals, the delivery stage's per-tier series — lives
        #: here; the legacy attribute names below read through to it
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._c_iters = self.metrics.counter("serve_iterations_total")
        self._c_useful = self.metrics.counter("serve_nfe_useful_total")
        self._c_resident = self.metrics.counter("serve_nfe_resident_total")
        self._c_transfers = self.metrics.counter("serve_host_transfers_total")
        self._c_uploads = self.metrics.counter("serve_host_uploads_total")
        self._c_events = self.metrics.counter("serve_event_updates_total")
        self._c_accept = self.metrics.counter("serve_accepted_total")
        self._c_reject = self.metrics.counter("serve_rejected_total")
        if hasattr(self.delivery, "bind"):
            # seam unification (DESIGN.md §15): the delivery stage's
            # per-tier books and the fold-and-reset waste books write
            # one shared registry, so they can be asserted consistent
            self.delivery.bind(self.metrics)
        #: the static-config tolerance a tier-less request rides — same
        #: resolution rule as ``solve_chunk`` (sde-calibrated eps_abs
        #: unless the config pins one)
        self._default_atol = float(
            sde.abs_tolerance if self.cfg.eps_abs is None else self.cfg.eps_abs
        )
        self._default_rtol = float(self.cfg.eps_rel)
        self._default_h0 = min(float(self.cfg.h_init), sde.T - sde.t_eps)
        self.conditioner = self.cfg.conditioner
        cond_struct = (
            None if self.conditioner is None
            else self.conditioner.cond_struct(slots, self.shape)
        )
        if mesh is not None:
            from repro.parallel.sharding import (
                data_axes, solver_carry_shardings,
            )

            axes = data_axes(mesh)
            self.n_devices = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
            if slots % self.n_devices != 0:
                raise ValueError(
                    f"slots={slots} must divide across {self.n_devices} devices"
                )
            self._carry_shardings = solver_carry_shardings(
                mesh, slots, 1 + len(self.shape), per_slot_keys=True,
                cond=cond_struct, tolerances=self.tiered,
                telemetry=self.telemetry_capacity > 0,
            )
            self.step_fn = jax.jit(
                lambda p, c: sample_step(p, c, max_sync_iters=self.sync_horizon),
                out_shardings=self._carry_shardings,
            )
        else:
            self.n_devices = 1
            self._carry_shardings = None
            self.step_fn = jax.jit(
                lambda p, c: sample_step(p, c, max_sync_iters=self.sync_horizon)
            )
        self.slots_per_device = slots // self.n_devices
        #: per-device count of queue→slot assignments (includes the
        #: initial fill); shows admission proceeding independently per device
        self.refills_per_device: List[int] = [0] * self.n_devices
        self.queue: Deque[ImageRequest] = deque()
        self.finished: Dict[int, ImageRequest] = {}
        self._slot_req: List[Optional[ImageRequest]] = [None] * slots
        #: driver calls (device-resident) / step() chunks (host-driven)
        self.horizon_windows = 0
        #: host mirror of the carry's device iteration counter, so the
        #: host-driven step() needs one read per chunk, not two
        self._host_iters = 0
        B = slots
        zi = jnp.zeros((B,), jnp.int32)
        self._carry = SolverCarry(
            x=jnp.zeros((B,) + self.shape, self.policy.state),
            x_prev=jnp.zeros((B,) + self.shape, self.policy.state),
            t=jnp.zeros((B,), jnp.float32),    # 0 = idle/converged
            h=jnp.full((B,), self.cfg.h_init, jnp.float32),
            key=jnp.zeros((B, 2), jnp.uint32),  # per-slot noise streams
            nfe=zi, accepted=zi, rejected=zi,
            done=jnp.ones((B,), bool),
            iterations=jnp.asarray(0, jnp.int32),
            # idle slots carry the neutral payload (zero mask / label 0)
            cond=(None if self.conditioner is None
                  else self.conditioner.neutral_cond(B, self.shape)),
            # tiered: idle slots hold the default-class tolerance; the
            # admission scatter overwrites admitted rows (DESIGN.md §14)
            atol=(jnp.full((B,), self._default_atol, jnp.float32)
                  if self.tiered else None),
            rtol=(jnp.full((B,), self._default_rtol, jnp.float32)
                  if self.tiered else None),
            # telemetry ring (DESIGN.md §15): capacity 0 keeps the exact
            # pre-telemetry treedef, so the off path retraces nothing
            telemetry=(init_telemetry(B, self.telemetry_capacity)
                       if self.telemetry_capacity > 0 else None),
        )
        self._carry = self._shard_carry(self._carry)
        self._occupied = None
        self._driver_fn = None
        self._event_fn = None
        if self.device_resident:
            # donation demands distinct buffers per leaf: the fresh carry
            # aliases its zero-init leaves (and jnp.zeros constant-caches),
            # which XLA rejects as donating the same buffer twice
            self._carry = jax.tree_util.tree_map(
                lambda a: jnp.array(a, copy=True), self._carry
            )
            self._build_device_loop(sample_step)
            self._set_occupied()

    # ------------------------------------------------------------------
    def _d2h(self, tree):
        """The serve loop's single device→host seam: every read crosses
        here (counted), so transfer accounting — and the regression test
        pinning the device-resident path to O(events) — sees all of
        them. One call = one logical sync, however many leaves ride in
        the pytree; its ``serve/pull`` span is where the host waits for
        the device."""
        self._c_transfers.inc()
        with self.tracer.span("serve/pull"):
            return jax.device_get(tree)

    def _h2d(self, arr: np.ndarray):
        """The device-resident serve loop's single host→device seam, the
        mirror of ``_d2h``: one numpy array, slot axis first, placed in
        one ``device_put`` with the carry's slot sharding (the default
        device without a mesh) and counted in
        ``serve_host_uploads_total``."""
        self._c_uploads.inc()
        return jax.device_put(
            arr, None if self._carry_shardings is None
            else self._carry_shardings.done
        )

    def _set_occupied(self) -> None:
        """Mirror host slot occupancy into the device-side (B,) mask the
        driver's ``events_pending`` consults (idle slots ride with
        done=True, so the device cannot derive occupancy from the carry)."""
        self._occupied = self._h2d(
            np.array([r is not None for r in self._slot_req])
        )

    def _build_device_loop(self, sample_step: Callable) -> None:
        """Jit the two device-resident stages (DESIGN.md §12).

        The *driver* chains sync-horizon chunks in a ``lax.while_loop``
        until an event is pending (or ``max_horizons`` chunks ran, so a
        straggler-bound wave still returns control), and returns the
        carry plus the scalar event flag — the sole per-call read. The
        *event update* applies one host decision batch entirely
        on-device: gather every carry leaf by the compaction
        permutation, then overwrite admitted rows with fresh prior draws,
        reset their control fields, and install their noise keys. Each
        admitted request's keys are derived in the program from its seed
        word, ``split(PRNGKey(seed))`` vmapped over the slots — bit-
        identical to the host-driven loop's eager split. Both donate the
        carry, so the (B, ...) state buffers are reused in place rather
        than copied per call. The admission inputs are fixed-shape full-B
        host arrays (one, two when tiered) to keep a single trace; only
        the *condition payload* rows are scattered host-side afterwards —
        admission payloads stay per-request (ragged pytrees, not worth a
        trace per admission-count), see DESIGN.md §12.
        """
        wait_all = not self.compaction

        def driver(params, carry, occupied):
            def cond(state):
                c, n = state
                running = jnp.any(
                    jnp.logical_and(occupied, jnp.logical_not(c.done))
                )
                no_event = jnp.logical_not(
                    events_pending(c, occupied, wait_all=wait_all)
                )
                return running & no_event & (n < self.max_horizons)

            def body(state):
                c, n = state
                c = sample_step(params, c, max_sync_iters=self.sync_horizon)
                return c, n + 1

            carry, _ = jax.lax.while_loop(
                cond, body, (carry, jnp.asarray(0, jnp.int32))
            )
            return carry, events_pending(carry, occupied, wait_all=wait_all)

        def event_update(carry, admit, tol=None):
            # admit (B, 3) uint32, per slot: [compaction source slot,
            # admitted?, seed word]; tol (B, 3) fp32 [atol, rtol, h0] is
            # the tiered admission's rows (DESIGN.md §14) — the untiered
            # server never passes it, so its trace is unchanged
            perm = admit[:, 0].astype(jnp.int32)
            admit_mask = admit[:, 1] != 0

            def upd(leaf, new):
                leaf = jnp.take(leaf, perm, axis=0)
                m = admit_mask.reshape((-1,) + (1,) * (leaf.ndim - 1))
                return jnp.where(m, new, leaf)

            # row 0 draws the prior, row 1 is the slot's noise stream
            keys = jax.vmap(
                lambda s: jax.random.split(jax.random.PRNGKey(s))
            )(admit[:, 2])
            priors = jax.vmap(
                lambda k: self.sde.prior_sample(k, self.shape)
            )(keys[:, 0]).astype(carry.x.dtype)
            h0 = min(self.cfg.h_init, self.sde.T - self.sde.t_eps)
            return SolverCarry(
                x=upd(carry.x, priors),
                x_prev=upd(carry.x_prev, priors),
                t=upd(carry.t, jnp.float32(self.sde.T)),
                h=upd(carry.h, jnp.float32(h0) if tol is None else tol[:, 2]),
                key=upd(carry.key, keys[:, 1]),
                nfe=upd(carry.nfe, 0),
                accepted=upd(carry.accepted, 0),
                rejected=upd(carry.rejected, 0),
                done=upd(carry.done, False),
                # fold-and-reset: the host adds the pulled counter to
                # total_iterations at every event, so the device counter
                # restarts (and cfg.max_iters never trips on a
                # long-lived server)
                iterations=jnp.asarray(0, jnp.int32),
                cond=(None if carry.cond is None else
                      jax.tree_util.tree_map(
                          lambda l: jnp.take(l, perm, axis=0), carry.cond
                      )),
                atol=(None if carry.atol is None else upd(carry.atol, tol[:, 0])),
                rtol=(None if carry.rtol is None else upd(carry.rtol, tol[:, 1])),
                # telemetry rows travel with their sample, permute-only
                # (DESIGN.md §15): admission does NOT clear rows —
                # records are globally iteration-stamped and age out by
                # ring wrap, keeping the ring's aggregate accept/reject
                # sums exactly reconcilable with delivered requests
                telemetry=(None if carry.telemetry is None else
                           StepTelemetry(
                               t=jnp.take(carry.telemetry.t, perm, axis=0),
                               h=jnp.take(carry.telemetry.h, perm, axis=0),
                               err=jnp.take(carry.telemetry.err, perm, axis=0),
                               accept=jnp.take(
                                   carry.telemetry.accept, perm, axis=0),
                               head=carry.telemetry.head,
                           )),
            )

        if self._carry_shardings is not None:
            from repro.parallel.sharding import serving_loop_shardings

            cond_struct = (None if self.conditioner is None else
                           self.conditioner.cond_struct(self.n, self.shape))
            carry_s, flag_s = serving_loop_shardings(
                self.mesh, self.n, 1 + len(self.shape),
                per_slot_keys=True, cond=cond_struct,
                tolerances=self.tiered,
                telemetry=self.telemetry_capacity > 0,
            )
            self._driver_fn = jax.jit(
                driver, donate_argnums=(1,),
                out_shardings=(carry_s, flag_s),
            )
            self._event_fn = jax.jit(
                event_update, donate_argnums=(0,),
                out_shardings=carry_s,
            )
        else:
            self._driver_fn = jax.jit(driver, donate_argnums=(1,))
            self._event_fn = jax.jit(event_update, donate_argnums=(0,))

    # ------------------------------------------------------------------
    def _shard_carry(self, carry: SolverCarry) -> SolverCarry:
        if self._carry_shardings is None:
            return jax.tree_util.tree_map(jnp.asarray, carry)
        return jax.tree_util.tree_map(
            lambda a, s: jax.device_put(jnp.asarray(a), s),
            carry, self._carry_shardings,
        )

    def slot_device(self, slot: int) -> int:
        """Mesh data-axis index owning ``slot`` (contiguous block
        layout, DESIGN.md §3)."""
        return slot // self.slots_per_device

    def _request_cond(self, req: ImageRequest):
        """An admitted request's per-sample condition rows: its own
        ``cond`` (leaves shaped like ``cond_struct`` minus the batch
        dim; scalars allowed for (B,) leaves) coerced to the payload
        dtypes, or the conditioner's *neutral* payload (DESIGN.md §9 —
        e.g. the null label for CFG, never class 0)."""
        if req.cond is None:
            return jax.tree_util.tree_map(
                lambda l: l[0], self.conditioner.neutral_cond(1, self.shape)
            )
        struct = self.conditioner.cond_struct(1, self.shape)
        return jax.tree_util.tree_map(
            lambda s, l: jnp.asarray(l, s.dtype).reshape(s.shape[1:]),
            struct, req.cond,
        )

    def _resolve_tier(self, tier):
        """Tier name / ToleranceClass → ToleranceClass, against this
        server's registry (or the ``configs.diffusion`` presets)."""
        from repro.configs.diffusion import ToleranceClass, resolve_tier

        if isinstance(tier, ToleranceClass):
            return tier
        if self.tolerance_classes is not None:
            if tier in self.tolerance_classes:
                return self.tolerance_classes[tier]
            raise KeyError(
                f"unknown tolerance class {tier!r}; this server registers "
                f"{sorted(self.tolerance_classes)}"
            )
        return resolve_tier(tier)

    def _request_tol(self, req: ImageRequest):
        """An admitted request's (atol, rtol, h0) floats (DESIGN.md §14):
        its tolerance class with None fields deferring to the serving
        config / SDE defaults — a tier-less request rides exactly the
        static-config values."""
        if req.tier is None:
            return self._default_atol, self._default_rtol, self._default_h0
        tier = self._resolve_tier(req.tier)
        atol = self._default_atol if tier.eps_abs is None else float(tier.eps_abs)
        h = self.cfg.h_init if tier.h_init is None else tier.h_init
        return atol, float(tier.eps_rel), min(
            float(h), self.sde.T - self.sde.t_eps
        )

    def submit(self, req: ImageRequest) -> None:
        """Queue a request; it enters a slot at the next sync horizon
        with a free slot (DESIGN.md §7). Stamps the submission clock and
        resolves the request's deadline/priority from its tolerance
        class (DESIGN.md §14) so the admission policy orders on settled
        values."""
        if req.tier is not None and not self.tiered:
            raise ValueError(
                f"request {req.uid} carries tier {req.tier!r} but this "
                "server was built without tolerance_classes — its carry "
                "has no per-slot tolerance leaves to honour it"
            )
        now = self._clock()
        req._submit_t = now
        tier = None if req.tier is None else self._resolve_tier(req.tier)
        if req.priority is None:
            req.priority = 0 if tier is None else int(tier.priority)
        deadline_ms = req.deadline_ms
        if deadline_ms is None and tier is not None:
            deadline_ms = tier.deadline_ms
        req.deadline_at = (
            None if deadline_ms is None else now + deadline_ms / 1000.0
        )
        self.queue.append(req)

    # -- serve-loop counters (DESIGN.md §15): the books live in the
    # metrics registry; these legacy names read through to it ----------
    @property
    def total_iterations(self) -> int:
        """Total device loop iterations executed (each costs nfe_per_iter
        score-net forwards over the full slot batch, busy or not)."""
        return int(self._c_iters.value)

    @property
    def useful_nfe(self) -> int:
        """Σ per-request NFE actually delivered — the useful fraction of
        nfe_per_iter · slots · total_iterations issued evaluations."""
        return int(self._c_useful.value)

    @property
    def resident_nfe(self) -> int:
        """Σ nfe_per_iter·resident_iters over delivered requests:
        evaluations issued to *occupied* slots (excludes never-occupied
        idle capacity)."""
        return int(self._c_resident.value)

    @property
    def host_transfers(self) -> int:
        """Device→host reads the serve loop issued (every one goes
        through ``_d2h``); the device-resident path keeps this
        O(delivered requests) instead of O(sync horizons)."""
        return int(self._c_transfers.value)

    @property
    def class_stats(self) -> Dict[str, Any]:
        """Per-tolerance-class delivery counters (DESIGN.md §14) as
        plain dicts — mean NFE, deadline misses, queue wait — from the
        delivery stage's accounting at the ``_d2h`` seam."""
        return {name: s.as_dict() for name, s in self.delivery.stats.items()}

    @property
    def wasted_nfe_fraction(self) -> float:
        """Fraction of issued score-net evaluations spent on idle or
        already-converged slots so far (0 when nothing ran yet) —
        DESIGN.md §7 waste accounting. Issued evaluations are
        ``nfe_per_iter · slots · total_iterations``, with the
        per-iteration factor taken from the solver registry for the
        family this batcher runs (a hardcoded 2 is only right for the
        Algorithm-1 families and e.g. went *negative* for ``pc_hmc``,
        whose iterations each issue ``1 + corrector_steps·L``)."""
        issued = self.nfe_per_iter * self.n * self.total_iterations
        if issued == 0:
            return 0.0
        return 1.0 - min(self.useful_nfe, issued) / issued

    @property
    def passenger_nfe_fraction(self) -> float:
        """Fraction of evaluations issued to *occupied* slots whose sample
        had already converged — the paper's frozen-passenger waste, the
        part of ``wasted_nfe_fraction`` that only compaction (not capacity
        provisioning) can remove (DESIGN.md §7). 0 when nothing was
        delivered yet."""
        if self.resident_nfe == 0:
            return 0.0
        return 1.0 - min(self.useful_nfe, self.resident_nfe) / self.resident_nfe

    # ------------------------------------------------------------------
    def _retire(self, rows, nfe, acc, rej, conv_idx) -> None:
        """Deliver the already-transferred retired rows: fill in each
        request, move it to ``finished``, free its slot, and charge the
        waste accounting (shared by the host-driven and device-resident
        paths)."""
        now = self._clock()
        attrs = {}
        if self.tracer.enabled:
            attrs = dict(uids=[self._slot_req[i].uid for i in conv_idx],
                         slots=list(conv_idx),
                         nfe=[int(nfe[i]) for i in conv_idx])
        with self.tracer.span("serve/delivery", **attrs):
            for row, i in zip(rows, conv_idx):
                req = self._slot_req[i]
                req.result = row
                req.nfe = int(nfe[i])
                req.accepted = int(acc[i])
                req.rejected = int(rej[i])
                req.done = True
                req.resident_iters = self.total_iterations - req._admit_iters
                self.finished[req.uid] = req
                self._c_useful.inc(int(nfe[i]))
                self._c_resident.inc(self.nfe_per_iter * req.resident_iters)
                self._c_accept.inc(int(acc[i]))
                self._c_reject.inc(int(rej[i]))
                self._slot_req[i] = None
                # delivery stage (DESIGN.md §14): per-class NFE + deadline
                # accounting rides the rows already pulled through _d2h
                self.delivery.on_deliver(req, now)

    def _admit_from_queue(self):
        """Seat queued requests in free slots (host bookkeeping only —
        the slot-state writes are the caller's, per path). The admission
        stage picks *which* queued requests go (FIFO by default, EDF-
        within-priority-bands via ``EdfPriorityAdmission``); the chosen
        are seated lowest-free-slot-first. Returns the admitted (slot
        index, request) lists."""
        free = [i for i in range(self.n) if self._slot_req[i] is None]
        if not free or not self.queue:
            return [], []
        now = self._clock()
        with self.tracer.span(
            "serve/admission", free=len(free), queued=len(self.queue)
        ) as sp:
            reqs = self.admission.select(self.queue, len(free), now)
            admit_pos = free[: len(reqs)]
            for i, req in zip(admit_pos, reqs):
                self._slot_req[i] = req
                req._admit_iters = self.total_iterations
                req._seat_t = now
                self.refills_per_device[self.slot_device(i)] += 1
            if self.tracer.enabled:
                # request-id propagation (DESIGN.md §15): the admission
                # span names exactly the uids seated and the slots they took
                sp["attrs"]["uids"] = [r.uid for r in reqs]
                sp["attrs"]["slots"] = list(admit_pos)
        return admit_pos, reqs

    def _compaction_perm(self) -> np.ndarray:
        """Shard-local compaction permutation: within each device's
        contiguous slot block, pack the surviving in-flight samples to
        the front (slots never cross a block = shard boundary). Also
        reorders ``_slot_req`` to match. Identity when compaction is
        off."""
        perm = np.arange(self.n)
        if self.compaction:
            for d in range(self.n_devices):
                lo = d * self.slots_per_device
                hi = lo + self.slots_per_device
                block = list(range(lo, hi))
                live = [i for i in block if self._slot_req[i] is not None]
                free = [i for i in block if self._slot_req[i] is None]
                perm[lo:hi] = live + free
            self._slot_req = [self._slot_req[j] for j in perm]
        return perm

    def _sync(self) -> None:
        """Host sync: retire converged slots, compact, admit from queue.

        Only (B,)-sized bookkeeping and the *retired rows* of x cross the
        device↔host boundary; the compaction permutation and slot
        admissions are applied device-side (gather + row scatters), so
        the big (B, ...) state never round-trips through the host.
        """
        with self.tracer.span("serve/sync"):
            c = self._carry
            # the device's own convergence mask — using anything else (e.g. a
            # host-side t threshold) can disagree with the loop's active mask
            # and make retirement depend on the sync horizon
            done = self._d2h(c.done)
            occupied = [r is not None for r in self._slot_req]
            conv = [occupied[i] and bool(done[i]) for i in range(self.n)]
            if not self.compaction and occupied != conv and any(occupied):
                # monolithic-wave baseline: the batch only turns over once
                # every occupied slot has converged
                return
            if not any(conv) and not (self.queue and not all(occupied)):
                return

            # 1. deliver converged slots: transfer only those rows. Samples
            #    are delivered at the t_eps state, pre-Tweedie-denoise — the
            #    batcher holds only the fused sample_step, not a standalone
            #    score_fn, so the paper's +1-NFE denoise epilogue is the
            #    caller's (cf. sample()/finalize(denoise=True))
            conv_idx = [i for i in range(self.n) if conv[i]]
            if conv_idx:
                # delivery is always fp32 regardless of the state dtype
                rows_j = c.x[jnp.asarray(conv_idx)].astype(jnp.float32)
                if self.conditioner is not None:
                    # exact, noise-free constraint replacement on delivery
                    # (DESIGN.md §9): e.g. inpainting pins observed pixels
                    # to the observation, matching the finalize() contract
                    cond_rows = jax.tree_util.tree_map(
                        lambda l: l[jnp.asarray(conv_idx)], c.cond
                    )
                    rows_j = self.conditioner.finalize_project(rows_j, cond_rows)
                rows, nfe, acc, rej = self._d2h(
                    (rows_j, c.nfe, c.accepted, c.rejected)
                )
                self._retire(rows, nfe, acc, rej, conv_idx)

            # 2. shard-local compaction: each sample's per-slot key moves
            #    with it, so trajectories are unchanged by the permutation.
            perm = self._compaction_perm()
            permute = not np.array_equal(perm, np.arange(self.n))

            # 3. admit queued requests into freed slots: fresh prior draw at
            #    t = T under the request's own key — per-slot keys mean the
            #    admission cannot perturb any in-flight trajectory. The
            #    request's condition payload (or the neutral one) is written
            #    into the same rows (DESIGN.md §9).
            admit_pos, reqs = self._admit_from_queue()
            priors, noise_keys, conds = [], [], []
            with self.tracer.span("serve/keys"):
                for req in reqs:
                    k_prior, k_noise = jax.random.split(jax.random.PRNGKey(req.seed))
                    priors.append(self.sde.prior_sample(k_prior, self.shape))
                    noise_keys.append(k_noise)
                    if self.conditioner is not None:
                        conds.append(self._request_cond(req))

            with self.tracer.span("serve/update"):
                # a retired-but-unrefilled slot needs no explicit marking: the
                # device loop already left it at t ≤ t_eps with done=True, which
                # is exactly the chunk predicate's idle state
                def update(leaf, admit_val=None):
                    if permute:
                        leaf = jnp.take(leaf, jnp.asarray(perm), axis=0)
                    if admit_pos and admit_val is not None:
                        leaf = leaf.at[jnp.asarray(admit_pos)].set(admit_val)
                    return leaf

                x_admit = jnp.stack(priors).astype(c.x.dtype) if admit_pos else None
                h0 = min(self.cfg.h_init, self.sde.T - self.sde.t_eps)
                # tiered admission (DESIGN.md §14): each admitted request's
                # tolerance-class (atol, rtol, h0) rows scatter into the same
                # positions as its prior/key rows; untiered servers keep the
                # scalar h0 write below, bit for bit
                tol_a = tol_r = tol_h = None
                if self.tiered and admit_pos:
                    tols = [self._request_tol(r) for r in reqs]
                    tol_a = jnp.asarray([t[0] for t in tols], jnp.float32)
                    tol_r = jnp.asarray([t[1] for t in tols], jnp.float32)
                    tol_h = jnp.asarray([t[2] for t in tols], jnp.float32)
                # condition leaves move with their samples (permute + row scatter
                # like every other per-slot leaf — the DESIGN.md §9 compaction
                # rule: payloads travel shard-locally, like keys)
                cond_new = c.cond
                if c.cond is not None:
                    if admit_pos:
                        cond_admit = jax.tree_util.tree_map(
                            lambda *rows: jnp.stack(rows), conds[0], *conds[1:]
                        )
                        cond_new = jax.tree_util.tree_map(
                            lambda leaf, av: update(leaf, admit_val=av.astype(leaf.dtype)),
                            c.cond, cond_admit,
                        )
                    else:
                        cond_new = jax.tree_util.tree_map(update, c.cond)
                self._carry = self._shard_carry(SolverCarry(
                    x=update(c.x, admit_val=x_admit),
                    x_prev=update(c.x_prev, admit_val=x_admit),
                    t=update(c.t, admit_val=jnp.float32(self.sde.T)),
                    h=update(c.h,
                             admit_val=jnp.float32(h0) if tol_h is None else tol_h),
                    key=update(c.key,
                               admit_val=jnp.stack(noise_keys) if admit_pos else None),
                    nfe=update(c.nfe, admit_val=jnp.int32(0)),
                    accepted=update(c.accepted, admit_val=jnp.int32(0)),
                    rejected=update(c.rejected, admit_val=jnp.int32(0)),
                    done=update(c.done, admit_val=False),
                    # the carry's iteration counter is per-chunk in serving: fold
                    # it into the host total and reset so cfg.max_iters never
                    # trips on a long-lived server
                    iterations=jnp.asarray(0, jnp.int32),
                    cond=cond_new,
                    atol=(update(c.atol, admit_val=tol_a) if self.tiered else None),
                    rtol=(update(c.rtol, admit_val=tol_r) if self.tiered else None),
                    # telemetry rows permute with their sample and are never
                    # cleared at admission (DESIGN.md §15) — see event_update
                    telemetry=(None if c.telemetry is None else StepTelemetry(
                        t=update(c.telemetry.t), h=update(c.telemetry.h),
                        err=update(c.telemetry.err),
                        accept=update(c.telemetry.accept),
                        head=c.telemetry.head,
                    )),
                ))
            self._host_iters = 0

    # ------------------------------------------------------------------
    def _process_events(self, deliver: bool = True) -> None:
        """Device-resident event handler (DESIGN.md §12): one host visit
        that retires, compacts, and admits in a single donated device
        update.

        ``deliver=False`` is the admission-only form (new submissions
        into already-free slots — no delivery pending, so the (B,)
        convergence bookkeeping is not pulled; only the iteration
        counter is folded). All device→host reads go through ``_d2h``:
        one bookkeeping pull, plus one retired-rows pull when something
        converged — O(events), never O(horizons).
        """
        with self.tracer.span("serve/event", deliver=deliver):
            c = self._carry
            if deliver:
                done, nfe, acc, rej, iters = self._d2h(
                    (c.done, c.nfe, c.accepted, c.rejected, c.iterations)
                )
            else:
                iters = self._d2h(c.iterations)
                done = np.zeros(self.n, bool)
                acc = rej = None
            # fold-and-reset (cf. event_update): the device counter restarts
            # at every host visit, so add it exactly once here
            self._c_iters.inc(int(iters))
            self._host_iters = 0
            occupied = [r is not None for r in self._slot_req]
            conv_idx = [i for i in range(self.n) if occupied[i] and bool(done[i])]
            if conv_idx:
                rows_j = c.x[jnp.asarray(conv_idx)].astype(jnp.float32)
                if self.conditioner is not None:
                    cond_rows = jax.tree_util.tree_map(
                        lambda l: l[jnp.asarray(conv_idx)], c.cond
                    )
                    rows_j = self.conditioner.finalize_project(rows_j, cond_rows)
                self._retire(self._d2h(rows_j), nfe, acc, rej, conv_idx)

            perm = self._compaction_perm()
            permute = not np.array_equal(perm, np.arange(self.n))
            can_admit = self.compaction or not any(
                r is not None for r in self._slot_req
            )
            admit_pos, reqs = self._admit_from_queue() if can_admit else ([], [])
            # the admission reaches the device as full-B host arrays; the
            # event program derives each request's keys from its seed word
            # (the low 32 bits, all an x64-off PRNGKey keeps)
            admit = np.zeros((self.n, 3), np.uint32)
            with self.tracer.span("serve/keys"):
                for i, r in zip(admit_pos, reqs):
                    admit[i, 1:] = 1, r.seed & 0xFFFFFFFF
            with self.tracer.span("serve/update"):
                if permute or admit_pos:
                    admit[:, 0] = perm
                    ops = [self._carry, self._h2d(admit)]
                    if self.tiered:
                        tol = np.zeros((self.n, 3), np.float32)
                        for i, r in zip(admit_pos, reqs):
                            tol[i] = self._request_tol(r)
                        ops.append(self._h2d(tol))
                    self._c_events.inc()
                    self._carry = self._event_fn(*ops)
                    if self.conditioner is not None and admit_pos:
                        # admission payloads stay per-request: the ragged cond
                        # rows are scattered outside the fixed-shape event jit
                        # (DESIGN.md §12)
                        rows = [self._request_cond(r) for r in reqs]
                        cond_admit = jax.tree_util.tree_map(
                            lambda *ls: jnp.stack(ls), rows[0], *rows[1:]
                        )
                        idx = jnp.asarray(admit_pos, jnp.int32)
                        self._carry = dataclasses.replace(
                            self._carry,
                            cond=jax.tree_util.tree_map(
                                lambda leaf, av: leaf.at[idx].set(av.astype(leaf.dtype)),
                                self._carry.cond, cond_admit,
                            ),
                        )
                elif int(iters):
                    # nothing moved, but the pulled counter was folded above —
                    # restart the device counter so it is never double-counted
                    self._carry = dataclasses.replace(
                        c, iterations=jnp.asarray(0, jnp.int32)
                    )
                self._set_occupied()

    def _device_step(self) -> int:
        """One device-resident window: ≤ max_horizons · sync_horizon
        iterations per host visit, one scalar event-flag read."""
        occupied = [r is not None for r in self._slot_req]
        if self.queue and not all(occupied) and (
                self.compaction or not any(occupied)):
            # admission is host knowledge (queue + occupancy): seat the
            # newcomers before launching the driver — no slot frees up
            # mid-driver, so there is nothing to poll for
            self._process_events(deliver=False)
        busy = sum(1 for r in self._slot_req if r is not None)
        if busy == 0:
            return 0
        with self.tracer.span(
            "serve/solve", window=self.horizon_windows, busy=busy
        ):
            self._carry, ev = self._driver_fn(
                self.params, self._carry, self._occupied
            )
            ev = bool(self._d2h(ev))
        self.horizon_windows += 1
        if ev:
            self._process_events()
        return busy

    def step(self) -> int:
        """One serve-loop turn; returns the number of busy slots
        entering the device work. Host-driven: one sync-horizon chunk
        (≤ sync_horizon device iterations, DESIGN.md §7).
        Device-resident: one driver window (DESIGN.md §12)."""
        if self.device_resident:
            return self._device_step()
        self._sync()
        busy = sum(1 for r in self._slot_req if r is not None)
        if busy == 0:
            return 0
        with self.tracer.span(
            "serve/solve", window=self.horizon_windows, busy=busy
        ):
            self._carry = self.step_fn(self.params, self._carry)
            cur = int(self._d2h(self._carry.iterations))
        self.horizon_windows += 1
        self._c_iters.inc(cur - self._host_iters)
        self._host_iters = cur
        return busy

    def run_to_completion(self, max_steps: int = 100_000) -> Dict[int, ImageRequest]:
        """Drain the queue: step until every submitted request is
        delivered (DESIGN.md §4/§7 serving loop)."""
        steps = 0
        while (self.queue or any(r is not None for r in self._slot_req)) \
                and steps < max_steps:
            if self.step() == 0 and not self.queue:
                break
            steps += 1
        # deliver stragglers
        if self.device_resident:
            self._process_events()
        else:
            self._sync()
        return self.finished

    # ------------------------------------------------------------------
    def metrics_snapshot(self) -> MetricsRegistry:
        """Refresh the point-in-time serve gauges (queue depth,
        occupancy, waste fractions, acceptance rate — DESIGN.md §15)
        and return the registry; the counters are already live."""
        m = self.metrics
        m.gauge("serve_queue_depth").set(float(len(self.queue)))
        m.gauge("serve_slots_occupied").set(
            float(sum(1 for r in self._slot_req if r is not None))
        )
        m.gauge("serve_slots_total").set(float(self.n))
        m.gauge("serve_wasted_nfe_fraction").set(self.wasted_nfe_fraction)
        m.gauge("serve_passenger_nfe_fraction").set(
            self.passenger_nfe_fraction
        )
        acc = self._c_accept.value
        rej = self._c_reject.value
        m.gauge("serve_acceptance_rate").set(
            acc / (acc + rej) if (acc + rej) else 0.0
        )
        m.gauge("serve_horizon_windows").set(float(self.horizon_windows))
        return m

    def trace_record(self) -> Dict[str, Any]:
        """One JSON-ready record of everything this server observed
        (DESIGN.md §15): delivered requests with their per-request NFE /
        accept / reject books, the metrics registry, the tracer's spans
        and per-stage latency histograms, the per-class delivery stats,
        and — when the telemetry ring is on — the drained chronological
        step history (``repro.analysis.telemetry`` renders this record
        as the markdown report)."""
        self.metrics_snapshot()
        requests = [
            {
                "uid": r.uid,
                "tier": tier_name(r),
                "nfe": r.nfe,
                "accepted": r.accepted,
                "rejected": r.rejected,
                "resident_iters": r.resident_iters,
                "deadline_missed": bool(r.deadline_missed),
            }
            for r in sorted(self.finished.values(), key=lambda r: r.uid)
        ]
        rec: Dict[str, Any] = {
            "requests": requests,
            "metrics": self.metrics.to_json(),
            "trace": self.tracer.to_json(),
            "class_stats": self.class_stats,
        }
        if self._carry.telemetry is not None:
            hist = telemetry_history(self._d2h(self._carry.telemetry))
            rec["telemetry"] = {
                "t": hist["t"].tolist(),
                "h": hist["h"].tolist(),
                "err": hist["err"].tolist(),
                "accept": hist["accept"].astype(int).tolist(),
                "iterations": int(hist["iterations"]),
                "records": int(hist["records"]),
                "t_eps": float(self.sde.t_eps),
            }
        return rec
