"""Observability-layer conformance (DESIGN.md §15).

The tentpole contract, pinned from four directions:

  * **Telemetry is structurally invisible when off, and inert when on.**
    For every solver family the Algorithm-1 body hosts (adaptive,
    heavy-ball momentum, probability-flow/Heun) × every serving mode
    (sync_horizon 1 / 8, device-resident), a telemetry-on drain delivers
    bitwise-identical samples, NFE, and accept/reject books to the
    telemetry-off drain — and adds zero host transfers.
  * **The ring records the truth.** A host-replayed oracle — the same
    solve advanced one iteration per host visit, reading (t, h,
    accepted) off the carry before each step — must match the on-device
    ring record for record, including wraparound and chunk-boundary
    invariance of the monotone head cursor.
  * **The books reconcile.** A mixed-tier wave's ``trace_record()``
    must reconcile exactly: ring accept/reject sums == Σ per-request
    books == registry counters == the delivery stage's per-tier stats,
    with ``nfe == 2·(accepted + rejected)`` per request and
    ``head == total_iterations``.
  * **Request ids survive compaction.** Admission spans and delivery
    spans tell one consistent story per uid even as slot compaction
    permutes seats under the requests.

Plus the satellite guards: the ``benchmarks.run`` BENCH_*.json artifact
contract, the quality-proxy gauges (proxy-FID, dynamics-consistency),
and the metrics registry's JSON/Prometheus export.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.telemetry import (
    active_records, nfe_percentiles, step_size_vs_t, telemetry_markdown,
)
from repro.core import AdaptiveConfig, VPSDE
from repro.core.analytic import class_gaussian_noise_pred, gaussian_noise_pred
from repro.core.solvers.adaptive import init_carry, solve_chunk
from repro.launch.sample import make_sample_step
from repro.models.dit import DiTConfig
from repro.observability import (
    NULL_TRACER, MetricsRegistry, StageTracer, dynamics_consistency,
    proxy_fid, telemetry_history,
)
from repro.planning import PlannerConfig, RecedingHorizonPlanner
from repro.planning.envs import OUEnv, PointMassEnv
from repro.serving.diffusion_server import DiffusionBatcher, ImageRequest
from repro.serving.scheduler import tier_name

MU, S0 = 0.3, 0.5
D = 32
N_REQ = 6
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: solver families routed through the Algorithm-1 body (DESIGN.md §11):
#: telemetry must be a pure observer for each of them
FAMILIES = {
    "adaptive": {},
    "momentum": dict(momentum=0.3),
    "heun": dict(probability_flow=True),
}
#: serving modes the off==on guarantee must hold under
MODES = {
    "h1": dict(sync_horizon=1),
    "h8": dict(sync_horizon=8),
    "device-resident": dict(sync_horizon=4, device_resident=True),
}
#: the §14 mixed wave (tier names + tier-less defaults) for the
#: reconciliation test
WAVE = ["draft", "high_fidelity", None, "standard", "draft", None,
        "high_fidelity", "draft", "standard", None]


def _active_threshold(t_eps) -> float:
    """The device's activity test (``t > t_eps + 1e-12``) runs in fp32;
    idle serving slots sit at exactly fp32(t_eps), so host-side replicas
    must compare against the fp32-rounded threshold."""
    return float(np.float32(float(t_eps) + 1e-12))


@pytest.fixture(scope="module")
def families():
    sde = VPSDE()
    net = DiTConfig(image_size=4, patch=4, d_model=8, num_layers=1,
                    num_heads=1, d_ff=8)  # unused shapes; signature holder
    out = {}
    for name, over in FAMILIES.items():
        cfg = dataclasses.replace(AdaptiveConfig(eps_rel=0.05), **over)
        step = make_sample_step(net, sde, cfg,
                                forward_fn=gaussian_noise_pred(sde, MU, S0))
        out[name] = (cfg, step)
    return sde, out


def _score_fn(sde):
    """The exact score math make_sample_step builds from the noise-pred
    forward_fn (same ops, same casts — see test_tolerance_tiers)."""
    fwd = gaussian_noise_pred(sde, MU, S0)

    def score(x, t):
        _, std = sde.marginal(t)
        out = fwd(None, x, t).astype(jnp.float32)
        return -out / std.reshape((-1,) + (1,) * (x.ndim - 1))

    return score


def _serve(sde, cfg, step, n_req=N_REQ, tiers=None, **kw):
    b = DiffusionBatcher(sde, step, params=None, sample_shape=(D,),
                         slots=4, cfg=cfg, **kw)
    for uid in range(n_req):
        tier = tiers[uid % len(tiers)] if tiers else None
        b.submit(ImageRequest(uid=uid, seed=1000 + uid, tier=tier))
    done = b.run_to_completion()
    assert len(done) == n_req
    return b, done


# --------------------------------------------------------------------------
# telemetry-off == telemetry-on, bit for bit, across families × modes
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", list(MODES), ids=list(MODES))
@pytest.mark.parametrize("family", list(FAMILIES), ids=list(FAMILIES))
def test_telemetry_off_on_bitwise_identical(families, family, mode):
    """Recording never feeds back: a telemetry-on drain is sample-,
    NFE-, and accept/reject-identical to the telemetry-off drain, adds
    no host transfers, and its monotone ring head equals the serve
    loop's folded iteration counter."""
    sde, fam = families
    cfg, step = fam[family]
    kw = MODES[mode]
    b_off, off = _serve(sde, cfg, step, **kw)
    b_on, on = _serve(sde, cfg, step, telemetry=256, **kw)
    for uid in off:
        np.testing.assert_array_equal(
            np.asarray(off[uid].result), np.asarray(on[uid].result),
            err_msg=f"family={family} mode={mode} uid={uid}")
        assert off[uid].nfe == on[uid].nfe, (family, mode, uid)
        assert off[uid].accepted == on[uid].accepted, (family, mode, uid)
        assert off[uid].rejected == on[uid].rejected, (family, mode, uid)
    assert b_on.host_transfers == b_off.host_transfers, (family, mode)
    assert b_off._carry.telemetry is None
    head = int(np.asarray(b_on._carry.telemetry.head))
    assert head == b_on.total_iterations == b_off.total_iterations


# --------------------------------------------------------------------------
# ring vs host-replayed oracle (+ wraparound, chunk-boundary invariance)
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def oracle_runs():
    """One batch-4 solve run four ways: telemetry-off one-iteration-at-
    a-time replay (the oracle), monolithic telemetry-on, small-capacity
    telemetry-on (forced wraparound), and h1-chunked telemetry-on."""
    sde = VPSDE()
    cfg = AdaptiveConfig(eps_rel=0.05)
    score = _score_fn(sde)
    B = 4
    kp, kn = jax.random.split(jax.random.PRNGKey(7))
    x0 = sde.prior_sample(kp, (B, D))
    nk = jax.random.split(kn, B)
    eps = _active_threshold(sde.t_eps)

    step1 = jax.jit(
        lambda c: solve_chunk(sde, score, c, max_sync_iters=1, config=cfg))
    solve_all = jax.jit(
        lambda c: solve_chunk(sde, score, c, max_sync_iters=4096, config=cfg))

    # oracle: telemetry-off, host reads (t, h, accepted) before each
    # single-iteration chunk — exactly what the ring should have written
    c = init_carry(sde, x0, nk, config=cfg)
    ts, hs, dacc = [], [], []
    for _ in range(10_000):
        t_prev, h_prev = np.asarray(c.t), np.asarray(c.h)
        active = t_prev > eps
        if not active.any():
            break
        acc_prev = np.asarray(c.accepted)
        c = step1(c)
        ts.append(t_prev.astype(np.float32))
        hs.append(np.where(active, h_prev, 0.0).astype(np.float32))
        dacc.append((np.asarray(c.accepted) - acc_prev).astype(bool))
    oracle = {
        "t": np.stack(ts, axis=1),
        "h": np.stack(hs, axis=1),
        "accept": np.stack(dacc, axis=1),
        "x": np.asarray(c.x),
        "accepted": np.asarray(c.accepted),
        "rejected": np.asarray(c.rejected),
        "n": len(ts),
    }

    c_on = solve_all(init_carry(sde, x0, nk, config=cfg, telemetry=512))
    assert bool(np.asarray(c_on.done).all())

    c_small = solve_all(init_carry(sde, x0, nk, config=cfg, telemetry=8))

    c_ch = init_carry(sde, x0, nk, config=cfg, telemetry=512)
    while not bool(np.asarray(c_ch.done).all()):
        c_ch = step1(c_ch)

    return sde, oracle, c_on, c_small, c_ch


def test_ring_matches_host_replay_oracle(oracle_runs):
    """Every ring record equals what a host replaying the solve one
    iteration at a time observes: raw entry t, the active-clamped
    attempted h, and the accept delta — with err self-consistent
    (accept ⇔ err ≤ 1 on active records) and the solution untouched."""
    sde, oracle, c_on, _, _ = oracle_runs
    hist = telemetry_history(jax.device_get(c_on.telemetry))
    n = oracle["n"]
    assert hist["iterations"] == hist["records"] == n
    np.testing.assert_array_equal(hist["t"], oracle["t"])
    np.testing.assert_array_equal(hist["h"], oracle["h"])
    np.testing.assert_array_equal(hist["accept"], oracle["accept"])
    active = oracle["t"] > _active_threshold(sde.t_eps)
    np.testing.assert_array_equal(
        hist["accept"], (hist["err"] <= 1.0) & active)
    # the ring's aggregate books == the carry's fold counters
    assert hist["accept"].sum(axis=1).tolist() == oracle["accepted"].tolist()
    np.testing.assert_array_equal(
        (active & ~hist["accept"]).sum(axis=1), oracle["rejected"])
    np.testing.assert_array_equal(np.asarray(c_on.x), oracle["x"])


def test_ring_wraparound_keeps_most_recent_records(oracle_runs):
    """A capacity-8 ring on a >8-iteration solve holds exactly the last
    8 records (head keeps the all-time count), and wrapping perturbs
    nothing about the solve itself."""
    _, oracle, c_on, c_small, _ = oracle_runs
    full = telemetry_history(jax.device_get(c_on.telemetry))
    small = telemetry_history(jax.device_get(c_small.telemetry))
    assert oracle["n"] > 8  # the solve must actually wrap the small ring
    assert small["iterations"] == oracle["n"] and small["records"] == 8
    for k in ("t", "h", "err", "accept"):
        np.testing.assert_array_equal(small[k], full[k][:, -8:], err_msg=k)
    np.testing.assert_array_equal(np.asarray(c_small.x), oracle["x"])


def test_ring_is_chunk_boundary_invariant(oracle_runs):
    """Chaining max_sync_iters=1 chunks writes the identical ring the
    monolithic solve writes — head is monotone across host visits, so
    the record has no seam at chunk boundaries."""
    _, _, c_on, _, c_ch = oracle_runs
    full = telemetry_history(jax.device_get(c_on.telemetry))
    chunked = telemetry_history(jax.device_get(c_ch.telemetry))
    assert chunked["iterations"] == full["iterations"]
    for k in ("t", "h", "err", "accept"):
        np.testing.assert_array_equal(chunked[k], full[k], err_msg=k)


# --------------------------------------------------------------------------
# stage tracing: request-id propagation through compaction
# --------------------------------------------------------------------------

def test_request_id_propagation_through_compaction(families):
    """Every uid admitted is delivered under the same uid with its
    per-request NFE on the delivery span — and compaction visibly moved
    at least one request to a different slot between the two spans."""
    sde, fam = families
    cfg, step = fam["adaptive"]
    tracer = StageTracer()
    b, done = _serve(sde, cfg, step, n_req=10, tracer=tracer,
                     sync_horizon=4)
    admit_slot, deliver_slot, deliver_nfe = {}, {}, {}
    for sp in tracer.spans:
        if sp["name"] == "serve/admission":
            for uid, slot in zip(sp["attrs"]["uids"], sp["attrs"]["slots"]):
                admit_slot[uid] = slot
        elif sp["name"] == "serve/delivery":
            for uid, slot, nfe in zip(sp["attrs"]["uids"],
                                      sp["attrs"]["slots"],
                                      sp["attrs"]["nfe"]):
                deliver_slot[uid] = slot
                deliver_nfe[uid] = nfe
    assert set(admit_slot) == set(deliver_slot) == set(range(10))
    for uid, req in done.items():
        assert deliver_nfe[uid] == req.nfe, uid
    moved = [u for u in admit_slot if admit_slot[u] != deliver_slot[u]]
    assert moved, "no request ever crossed slots — compaction untested"
    # spans carry wall-clock structure: every stage shows up, timed
    hist = tracer.stage_histograms()
    for stage in ("serve/admission", "serve/solve", "serve/delivery"):
        assert hist[stage]["count"] > 0, stage
        assert hist[stage]["total_s"] >= 0.0


# --------------------------------------------------------------------------
# the acceptance-criterion reconciliation: trace record vs device counters
# --------------------------------------------------------------------------

def _reconcile(b, rec):
    """One trace record's cross-ledger identities (DESIGN.md §15)."""
    reqs = rec["requests"]
    m = b.metrics
    for r in reqs:
        assert r["nfe"] == 2 * (r["accepted"] + r["rejected"]), r
    acc_req = sum(r["accepted"] for r in reqs)
    rej_req = sum(r["rejected"] for r in reqs)

    tel = rec["telemetry"]
    t = np.asarray(tel["t"])
    acc = np.asarray(tel["accept"]).astype(bool)
    active = t > _active_threshold(tel["t_eps"])
    # nothing wrapped (capacity >> iterations): the ring is the full
    # history, so its sums are exact, not windowed
    assert tel["records"] == tel["iterations"]
    assert tel["iterations"] == b.total_iterations \
        == int(m.value("serve_iterations_total"))
    # idle-slot records never accept: the unfiltered sum agrees too
    assert int(acc.sum()) == int((acc & active).sum()) == acc_req \
        == int(m.value("serve_accepted_total"))
    assert int((active & ~acc).sum()) == rej_req \
        == int(m.value("serve_rejected_total"))
    assert int(m.value("serve_nfe_useful_total")) \
        == sum(r["nfe"] for r in reqs)

    # seam unification: delivery-stage tier books == registry series
    by_tier = {}
    for r in reqs:
        by_tier.setdefault(r["tier"], []).append(r)
    for tier, rs in by_tier.items():
        stats = b.class_stats[tier]
        assert stats["delivered"] == len(rs) \
            == int(m.value("serve_delivered_total", tier=tier))
        assert int(m.value("serve_tier_nfe_total", tier=tier)) \
            == sum(r["nfe"] for r in rs)
        assert stats["deadline_misses"] \
            == int(m.value("serve_deadline_misses_total", tier=tier))
    assert int(m.total("serve_delivered_total")) == len(reqs)
    assert int(m.total("serve_tier_nfe_total")) \
        == int(m.value("serve_nfe_useful_total"))

    stages = {s["name"] for s in rec["trace"]["spans"]}
    assert {"serve/admission", "serve/solve", "serve/delivery"} <= stages


def test_mixed_wave_trace_reconciles_and_renders(families):
    """The ISSUE's acceptance criterion: a mixed 10-request wave with
    telemetry + tracing on yields a JSON trace whose per-request NFE,
    accept/reject counts, and per-stage spans reconcile exactly with
    the device-side counters — and the record renders to the telemetry
    markdown report CI publishes."""
    sde, fam = families
    cfg, step = fam["adaptive"]
    tracer = StageTracer()
    b = DiffusionBatcher(sde, step, params=None, sample_shape=(D,),
                         slots=4, cfg=cfg, sync_horizon=4,
                         tolerance_classes=True, telemetry=4096,
                         tracer=tracer)
    for uid, tier in enumerate(WAVE):
        b.submit(ImageRequest(uid=uid, seed=1000 + uid, tier=tier))
    done = b.run_to_completion()
    assert len(done) == len(WAVE)

    # the record is JSON end to end (what launch/serve --trace-out writes)
    rec = json.loads(json.dumps(b.trace_record()))
    assert [r["uid"] for r in rec["requests"]] == list(range(len(WAVE)))
    _reconcile(b, rec)

    # snapshot gauges recompute from the same counters
    g = rec["metrics"]["gauges"]
    assert g["serve_wasted_nfe_fraction"] == pytest.approx(
        b.wasted_nfe_fraction)
    acc = int(b.metrics.value("serve_accepted_total"))
    rej = int(b.metrics.value("serve_rejected_total"))
    assert g["serve_acceptance_rate"] == pytest.approx(acc / (acc + rej))

    # analysis helpers digest the record with the same fp32 idle filter
    live = active_records(rec["telemetry"])
    t = np.asarray(rec["telemetry"]["t"])
    assert live["t"].size == int(
        (t > _active_threshold(rec["telemetry"]["t_eps"])).sum())
    np.testing.assert_array_equal(live["accept"], live["err"] <= 1.0)
    assert step_size_vs_t(rec["telemetry"])  # non-empty binned curves
    pct = nfe_percentiles(rec["requests"])
    assert pct[0]["nfe"] <= pct[-1]["nfe"]

    md = telemetry_markdown(rec)
    for needle in ("# Serve-loop telemetry report", "## Per-stage latency",
                   "## Per-request NFE CDF",
                   "## Step size and accept rate vs t",
                   "## Per-tier delivery", "draft"):
        assert needle in md, needle
    out_dir = os.path.join(ROOT, "experiments", "observability")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "TELEMETRY.md"), "w") as f:
        f.write(md)


def test_device_resident_trace_reconciles(families):
    """The same reconciliation holds on the device-resident path, whose
    iteration counter folds at a different seam (the multi-horizon
    driver) — one registry, same identities."""
    sde, fam = families
    cfg, step = fam["adaptive"]
    tracer = StageTracer()
    b, done = _serve(sde, cfg, step, sync_horizon=4, device_resident=True,
                     telemetry=4096, tracer=tracer)
    rec = json.loads(json.dumps(b.trace_record()))
    _reconcile(b, rec)
    assert sum(r["nfe"] for r in rec["requests"]) \
        == sum(r.nfe for r in done.values())


def test_no_retrace_with_telemetry_on(families):
    """Telemetry is carry *data*: tier churn and ring writes never
    retrace the host-driven step or the device-resident driver/event
    programs (the §12/§14 no-retrace discipline extends to §15)."""
    sde, fam = families
    cfg, step = fam["adaptive"]
    b, _ = _serve(sde, cfg, step, n_req=len(WAVE), tiers=WAVE,
                  sync_horizon=4, tolerance_classes=True, telemetry=128)
    assert b.step_fn._cache_size() == 1
    bd, _ = _serve(sde, cfg, step, sync_horizon=4, device_resident=True,
                   telemetry=128)
    assert bd._driver_fn._cache_size() == 1
    assert bd._event_fn._cache_size() == 1


# --------------------------------------------------------------------------
# serve-loop spans on the profiler's clock: every host visit between
# device programs, nested as the records say
# --------------------------------------------------------------------------

#: the spans each serve path must put into the profiler's host plane
PATH_SPANS = {
    "device-resident": {"serve/event", "serve/pull", "serve/keys",
                        "serve/update", "serve/solve", "serve/admission",
                        "serve/delivery"},
    "host-driven": {"serve/sync", "serve/pull", "serve/keys",
                    "serve/update", "serve/solve", "serve/admission",
                    "serve/delivery", "plan/request"},
}
SPAN_NAMES = set().union(*PATH_SPANS.values()) | {"plan/round"}


def _run_path(families, path, tracer):
    """One tiny drain of ``path`` with more requests than slots: the
    tiered device-resident server, or the receding-horizon planner over
    its host-driven batcher. Returns the batcher."""
    sde, fam = families
    cfg, step = fam["adaptive"]
    if path == "device-resident":
        b, _ = _serve(sde, cfg, step, n_req=len(WAVE), tiers=WAVE,
                      sync_horizon=4, device_resident=True,
                      tolerance_classes=True, tracer=tracer)
        return b
    rh = RecedingHorizonPlanner(
        sde, class_gaussian_noise_pred(sde, jnp.linspace(-1.0, 1.0, 5)),
        None, PlannerConfig(horizon=8, obs_dim=2, act_dim=2,
                            guidance_scale=1.5),
        OUEnv(obs_dim=2), slots=4, sync_horizon=4, tracer=tracer)
    rh.rollout(jax.random.PRNGKey(1), n_envs=6, n_steps=2, returns_label=1)
    return rh.batcher


def _profiled_run(families, path, tracer, log_dir):
    """Run ``path`` under ``jax.profiler``; returns the batcher and the
    host-plane events named like a serve-loop span, as (start_ns,
    end_ns, name)."""
    with jax.profiler.trace(str(log_dir)):
        b = _run_path(families, path, tracer)
    (xplane,) = log_dir.rglob("*.xplane.pb")
    host = []
    for plane in jax.profiler.ProfileData.from_file(str(xplane)).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(int(ev.start_ns),
                          int(ev.start_ns + ev.duration_ns), ev.name)
                         for ev in line.events if ev.name in SPAN_NAMES]
    return b, host


@pytest.fixture(scope="module", params=list(PATH_SPANS))
def profiled(request, families, tmp_path_factory):
    """A traced drain of each serve path under the profiler: (path,
    batcher, tracer, host-plane span events)."""
    tracer = StageTracer()
    b, host = _profiled_run(families, request.param, tracer,
                            tmp_path_factory.mktemp("profile"))
    return request.param, b, tracer, host


def test_serve_spans_nest_on_the_profiler_clock(profiled):
    """Each host visit of the path is a span in the profiler's host
    plane under its bare name (no attrs in the name), one trace event
    per record; the records name their enclosing span, and the trace
    nests exactly as the records say: each child inside its parent."""
    path, _, tracer, host = profiled
    assert PATH_SPANS[path] <= {name for _, _, name in host}
    recs = sorted(tracer.spans, key=lambda r: r["id"])  # opening order
    assert [r["id"] for r in recs] == list(range(len(recs)))
    events = sorted(host, key=lambda ev: (ev[0], -ev[1]))  # opening order
    assert [ev[2] for ev in events] == [r["name"] for r in recs]
    stack = []
    for rec, (start, end, _) in zip(recs, events):
        while stack and events[stack[-1]][1] <= start:
            stack.pop()
        assert rec["parent"] == (stack[-1] if stack else None), rec
        if stack:
            p_start, p_end, _ = events[stack[-1]]
            assert p_start <= start and end <= p_end, rec
            parent = recs[rec["parent"]]
            assert parent["start"] <= rec["start"] <= rec["end"] \
                <= parent["end"], rec
        stack.append(rec["id"])
    # the device→host reads the batcher counted are the pulls it traced
    assert sum(r["name"] == "serve/pull" for r in recs) \
        == profiled[1].host_transfers


def test_traced_serve_is_bitwise_identical_to_untraced(profiled, families,
                                                       tmp_path):
    """Spans observe and change nothing: the untraced drain of the same
    path delivers bit-identical samples with the same NFE and reads, and
    the null tracer puts no annotation into a running profiler."""
    path, traced, _, _ = profiled
    plain, host = _profiled_run(families, path, NULL_TRACER, tmp_path)
    assert host == [] and NULL_TRACER.spans == []
    assert list(plain.finished) == list(traced.finished)
    for uid, req in traced.finished.items():
        np.testing.assert_array_equal(req.result, plain.finished[uid].result)
        assert req.nfe == plain.finished[uid].nfe
    assert plain.host_transfers == traced.host_transfers
    assert plain.total_iterations == traced.total_iterations


def test_queue_wait_is_a_public_reading_behind_the_histogram(profiled):
    """``queue_wait_s`` is None until a request is seated, then the
    seconds from submit to seat (>= 0, > 0 for the requests that queued
    behind full slots); the delivery stage's per-tier
    ``serve_queue_wait_seconds`` histogram sums exactly those readings."""
    assert ImageRequest(uid=0, seed=0).queue_wait_s is None
    _, b, _, _ = profiled
    waits = {}
    for req in b.finished.values():
        assert req.queue_wait_s is not None and req.queue_wait_s >= 0.0
        waits.setdefault(tier_name(req), []).append(req.queue_wait_s)
    assert max(max(w) for w in waits.values()) > 0.0
    for tier, w in waits.items():
        h = b.metrics.histogram("serve_queue_wait_seconds", tier=tier)
        assert h.count == len(w) and h.total == sum(w), tier


def test_stage_tracer_records_parent_ids():
    """Span ids count up in opening order; ``parent`` is the enclosing
    open span, None at top level, and a span that raised closes
    cleanly."""
    tr = StageTracer()
    with tr.span("a"):
        with tr.span("b"):
            pass
        with pytest.raises(ValueError):
            with tr.span("c"):
                raise ValueError
    with tr.span("d"):
        pass
    by = {r["name"]: r for r in tr.spans}
    assert [by[n]["id"] for n in "abcd"] == [0, 1, 2, 3]
    assert [by[n]["parent"] for n in "abcd"] == [None, 0, 0, None]
    assert json.loads(json.dumps(tr.to_json()))["spans"] == tr.spans


# --------------------------------------------------------------------------
# benchmark artifact contract (BENCH_*.json at the repo root)
# --------------------------------------------------------------------------

def test_bench_artifact_contract(tmp_path):
    """benchmarks.run: every suite maps to a distinct repo-root
    BENCH_<suite>.json, emit()-CSV parses into structured gated rows,
    and the written artifact carries the stable schema."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import benchmarks.run as bench_run

    assert len(bench_run.SUITES) >= 16
    paths = {bench_run.artifact_path(n) for n in bench_run.SUITES}
    assert len(paths) == len(bench_run.SUITES)
    for n in bench_run.SUITES:
        p = bench_run.artifact_path(n, tmp_path)
        assert p.name == f"BENCH_{n}.json" and p.parent == tmp_path
    # default location is the repo root, beside README.md
    assert bench_run.artifact_path("x").parent == bench_run.ROOT
    assert (bench_run.ROOT / "README.md").exists()

    rows, notes = bench_run.parse_rows(
        "suite/a,12.5,w2=0.1;pass=1\n"
        "suite/b,3.0,compliant=0|note=x\n"
        "free-form report line\n"
        "name,us_per_call,derived\n")
    assert [r["name"] for r in rows] == ["suite/a", "suite/b"]
    assert rows[0]["us_per_call"] == 12.5
    assert rows[0]["gates"] == {"pass": True}
    assert rows[1]["gates"] == {"compliant": False}
    # non-row lines (incl. the CSV header) are kept verbatim as notes
    assert notes == ["free-form report line", "name,us_per_call,derived"]

    pg = bench_run._parse_gates
    assert pg("mean=3;passed=1") == {"passed": True}
    assert pg("ok=yes|speed=2x") == {"ok": True}
    assert pg("gate_lower_nfe_at_equal_error_pass=0") \
        == {"gate_lower_nfe_at_equal_error_pass": False}
    assert pg("pass=maybe") == {}  # unparseable values skipped, not guessed
    assert pg("w2=0.5") == {}

    path = bench_run.write_artifact("unit", rows, notes, 1.25,
                                    out_dir=tmp_path)
    doc = json.loads(path.read_text())
    assert doc["name"] == "unit" and doc["schema_version"] == 1
    assert set(doc) >= {"name", "schema_version", "config", "wall_time_s",
                        "rows", "notes", "gates"}
    assert {"argv", "backend", "device_count"} <= set(doc["config"])
    assert doc["gates"]["tokens"] == {"suite/a:pass": True,
                                      "suite/b:compliant": False}
    assert doc["gates"]["all_pass"] is False


# --------------------------------------------------------------------------
# quality-proxy gauges
# --------------------------------------------------------------------------

def test_proxy_fid_gauge_properties():
    """proxy-FID: ~0 on identical sets, deterministic in (shape, dim,
    seed), monotone under distribution shift, and shape-strict."""
    rng = np.random.default_rng(0)
    a = rng.standard_normal((256, 16))
    b = rng.standard_normal((256, 16))
    assert proxy_fid(a, a) == pytest.approx(0.0, abs=1e-9)
    near = proxy_fid(a, b)
    far = proxy_fid(a, b + 1.0)
    wide = proxy_fid(a, 3.0 * b)
    assert 0.0 <= near < far
    assert near < wide  # covariance drift moves it, not just the mean
    assert proxy_fid(a, b, dim=8, seed=3) == proxy_fid(a, b, dim=8, seed=3)
    # image-shaped samples flatten through the same extractor
    img = rng.standard_normal((64, 4, 4, 2))
    assert proxy_fid(img, img) == pytest.approx(0.0, abs=1e-9)
    with pytest.raises(ValueError):
        proxy_fid(a, rng.standard_normal((64, 8)))


def test_dynamics_consistency_floors_and_regressions():
    """dynamics-consistency: a true deterministic rollout scores ~0, a
    perturbed one scores high, and a stochastic OU rollout sits at the
    σ√dt noise floor."""
    pm = PointMassEnv(dim=2)
    rng = np.random.default_rng(1)
    trajs = []
    for i in range(4):
        s = np.asarray(pm.reset(jax.random.PRNGKey(i)))
        rows = []
        for _ in range(6):
            a = 0.5 * rng.standard_normal(pm.act_dim)
            rows.append(np.concatenate([s, a]))
            s = np.asarray(pm.step(jnp.asarray(s), jnp.asarray(a))[0])
        trajs.append(np.stack(rows))
    trajs = np.stack(trajs)
    dyn_true = dynamics_consistency(pm, trajs, obs_dim=pm.obs_dim,
                                    act_dim=pm.act_dim)
    assert dyn_true <= 1e-6, dyn_true

    bad = trajs.copy()
    bad[:, :, :pm.obs_dim] += 0.5 * rng.standard_normal(
        bad[:, :, :pm.obs_dim].shape)
    dyn_bad = dynamics_consistency(pm, bad, obs_dim=pm.obs_dim,
                                   act_dim=pm.act_dim)
    assert dyn_bad > 0.1, dyn_bad

    ou = OUEnv(obs_dim=2)
    floor = ou.sigma * np.sqrt(ou.dt)
    trajs = []
    for i in range(8):
        key = jax.random.PRNGKey(100 + i)
        s = np.asarray(ou.reset(key))
        rows = []
        for j in range(8):
            key, sk = jax.random.split(key)
            a = 0.3 * rng.standard_normal(ou.act_dim)
            rows.append(np.concatenate([s, a]))
            s = np.asarray(ou.step(jnp.asarray(s), jnp.asarray(a), sk)[0])
        trajs.append(np.stack(rows))
    dyn_ou = dynamics_consistency(ou, np.stack(trajs), obs_dim=ou.obs_dim,
                                  act_dim=ou.act_dim)
    assert 0.5 * floor < dyn_ou < 2.0 * floor, (dyn_ou, floor)
    # (H, D) single-trajectory form accepted too
    assert dynamics_consistency(ou, trajs[0], obs_dim=ou.obs_dim,
                                act_dim=ou.act_dim) > 0.0


# --------------------------------------------------------------------------
# metrics registry + tracer unit behaviour
# --------------------------------------------------------------------------

def test_metrics_registry_export_roundtrip():
    reg = MetricsRegistry()
    reg.counter("reqs_total", tier="draft").inc(3)
    reg.counter("reqs_total", tier="hf").inc()
    assert reg.counter("reqs_total", tier="draft") is reg.counter(
        "reqs_total", tier="draft")  # get-or-create, one series per labels
    reg.gauge("depth").set(2.5)
    h = reg.histogram("wait_seconds", bounds=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    with pytest.raises(ValueError):
        reg.counter("reqs_total", tier="draft").inc(-1)

    assert reg.value("reqs_total", tier="draft") == 3
    assert reg.total("reqs_total") == 4
    with pytest.raises(KeyError):
        reg.value("reqs_total")  # label-less series was never created

    j = json.loads(json.dumps(reg.to_json()))
    assert j["counters"]['reqs_total{tier="draft"}'] == 3
    assert j["gauges"]["depth"] == 2.5
    assert j["histograms"]["wait_seconds"]["count"] == 3
    assert j["histograms"]["wait_seconds"]["buckets"] == [1, 1, 1]

    prom = reg.to_prometheus()
    assert "# TYPE reqs_total counter" in prom
    assert 'reqs_total{tier="draft"} 3' in prom
    assert "# TYPE wait_seconds histogram" in prom
    # cumulative le buckets ending at +Inf == count
    assert 'wait_seconds_bucket{le="0.1"} 1' in prom
    assert 'wait_seconds_bucket{le="1.0"} 2' in prom
    assert 'wait_seconds_bucket{le="+Inf"} 3' in prom
    assert "wait_seconds_count 3" in prom


def test_stage_tracer_and_null_tracer():
    ticks = (x * 0.5 for x in range(100))
    tr = StageTracer(clock=lambda: next(ticks))
    with tr.span("a", uid=1) as sp:
        sp["attrs"]["extra"] = 2  # serve loop adds attrs mid-span
    with tr.span("b"):
        pass
    assert [s["name"] for s in tr.spans] == ["a", "b"]
    assert tr.spans[0]["duration_s"] == 0.5
    assert tr.spans[0]["attrs"] == {"uid": 1, "extra": 2}
    hist = tr.stage_histograms()
    assert hist["a"]["count"] == 1 and hist["a"]["mean_s"] == 0.5
    j = json.loads(json.dumps(tr.to_json()))
    assert len(j["spans"]) == 2 and j["bucket_bounds_s"][0] == 1e-4

    with NULL_TRACER.span("x", uid=9) as sp:
        sp["attrs"]["k"] = 1  # the yielded dict is writable on both paths
    assert NULL_TRACER.spans == []
    assert NULL_TRACER.enabled is False and StageTracer.enabled is True
