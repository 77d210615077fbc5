"""Property tests for the serving-stage policy seam (DESIGN.md §14):
admission ordering (FIFO / EDF-within-priority-bands), anti-starvation
aging, and the delivery stage's deadline accounting.

Runs under hypothesis when installed; otherwise a deterministic
fallback shim replays each property over a fixed-seed sweep of examples
(same pattern as test_property_hypothesis.py).
"""

import dataclasses
import random as _random
from collections import deque
from typing import Optional

import pytest  # noqa: F401

try:
    from hypothesis import given, settings, strategies as st

    settings.register_profile("ci", deadline=None, max_examples=30)
    settings.load_profile("ci")
except ImportError:  # pragma: no cover — dep-less fallback
    _N_EXAMPLES = 10

    class _Strategy:
        def __init__(self, draw):
            self.draw = draw

    class st:  # noqa: N801 — mimics hypothesis.strategies
        @staticmethod
        def floats(min_value, max_value, **_kw):
            return _Strategy(lambda r: r.uniform(min_value, max_value))

        @staticmethod
        def integers(min_value, max_value):
            return _Strategy(lambda r: r.randint(min_value, max_value))

        @staticmethod
        def booleans():
            return _Strategy(lambda r: r.random() < 0.5)

        @staticmethod
        def sampled_from(seq):
            seq = list(seq)
            return _Strategy(lambda r: r.choice(seq))

        @staticmethod
        def lists(elems, min_size, max_size):
            return _Strategy(
                lambda r: [elems.draw(r)
                           for _ in range(r.randint(min_size, max_size))]
            )

        @staticmethod
        def tuples(*elems):
            return _Strategy(lambda r: tuple(e.draw(r) for e in elems))

        @staticmethod
        def one_of(*strats):
            return _Strategy(lambda r: r.choice(strats).draw(r))

        @staticmethod
        def none():
            return _Strategy(lambda r: None)

    def given(*strategies):
        def deco(fn):
            def wrapper(*args, **kwargs):
                rnd = _random.Random(0xC0FFEE)
                for _ in range(_N_EXAMPLES):
                    drawn = tuple(s.draw(rnd) for s in strategies)
                    fn(*args, *drawn, **kwargs)
            wrapper.__name__ = fn.__name__
            wrapper.__doc__ = fn.__doc__
            return wrapper
        return deco

from repro.serving.scheduler import (  # noqa: E402
    EdfPriorityAdmission, FifoAdmission, TierAccounting,
)


@dataclasses.dataclass
class Req:
    """Minimal duck-typed request for the policy seam."""
    uid: int
    priority: int = 0
    deadline_at: Optional[float] = None
    _submit_t: float = 0.0
    queue_wait_s: float = 0.0
    tier: Optional[str] = None
    nfe: int = 0
    deadline_missed: bool = False


#: (priority band, deadline offset or None, submit time) draws
req_specs = st.lists(
    st.tuples(
        st.integers(0, 3),
        st.one_of(st.none(), st.floats(0.0, 100.0)),
        st.floats(0.0, 50.0),
    ),
    min_size=1, max_size=24,
)


def _queue_of(specs):
    return deque(
        Req(uid=i, priority=p, deadline_at=d, _submit_t=s)
        for i, (p, d, s) in enumerate(specs)
    )


@given(req_specs, st.integers(1, 8), st.floats(0.0, 200.0))
def test_fifo_is_exactly_popleft(specs, n_free, now):
    """The base policy must reproduce the pre-policy batcher behaviour
    bit for bit: first n_free in submission order, queue order of the
    rest untouched."""
    q = _queue_of(specs)
    want = list(q)[:n_free]
    rest = list(q)[n_free:]
    chosen = FifoAdmission().select(q, n_free, now)
    assert chosen == want
    assert list(q) == rest


@given(req_specs, st.integers(1, 8), st.floats(0.0, 200.0))
def test_edf_bands_never_inverted(specs, n_free, now):
    """No skipped request may rank strictly ahead of a seated one: the
    chosen set is exactly the n_free smallest by the policy's order key
    (bands first, then deadline) and is returned in key order."""
    policy = EdfPriorityAdmission()  # no aging: static bands
    q = _queue_of(specs)
    everyone = list(q)
    chosen = policy.select(q, n_free, now)
    keys = {r.uid: policy.order_key(r, now) for r in everyone}
    # returned in key order …
    got = [keys[r.uid] for r in chosen]
    assert got == sorted(got)
    # … and no unchosen request outranks any chosen one
    left = list(q)
    assert len(chosen) == min(n_free, len(everyone))
    if chosen and left:
        assert max(got) <= min(keys[r.uid] for r in left)
    # bands specifically never invert
    if chosen and left:
        assert max(r.priority for r in chosen) <= \
            min(r.priority for r in left) or any(
                r.priority <= min(x.priority for x in left)
                for r in chosen)


@given(req_specs, st.floats(0.0, 200.0))
def test_edf_within_band(specs, now):
    """Inside one priority band the seated order is
    earliest-deadline-first, no-deadline requests last, submission time
    breaking ties (FIFO among equals)."""
    policy = EdfPriorityAdmission()
    q = _queue_of(specs)
    chosen = policy.select(q, len(specs), now)  # seat everyone: full sort
    for a, b in zip(chosen, chosen[1:]):
        if a.priority == b.priority:
            da = float("inf") if a.deadline_at is None else a.deadline_at
            db = float("inf") if b.deadline_at is None else b.deadline_at
            assert (da, a._submit_t, a.uid) <= (db, b._submit_t, b.uid)
        else:
            assert a.priority < b.priority


def _saturating_flood(aging_s, rounds=40):
    """One old low-urgency request vs a fresh urgent arrival every tick,
    one free slot per tick. Returns the tick the victim was seated, or
    None."""
    policy = EdfPriorityAdmission(aging_s=aging_s)
    q = deque([Req(uid=0, priority=3, _submit_t=0.0)])
    for t in range(1, rounds + 1):
        q.append(Req(uid=1000 + t, priority=0,
                     deadline_at=t + 0.5, _submit_t=float(t)))
        for r in policy.select(q, 1, float(t)):
            if r.uid == 0:
                return t
    return None


def test_aging_prevents_starvation_and_its_absence_demonstrates_it():
    """Under a saturating flood of urgent traffic, static bands starve
    the background request forever; with aging its effective band drops
    without floor, so it must eventually be seated."""
    assert _saturating_flood(aging_s=None) is None
    seated_at = _saturating_flood(aging_s=1.0)
    assert seated_at is not None
    # band 3 decays by 1/s: seated once it drops below fresh band 0
    assert seated_at <= 5


@given(st.lists(
    st.tuples(st.one_of(st.none(), st.floats(0.0, 10.0)),
              st.floats(0.0, 20.0),
              st.integers(0, 500),
              st.sampled_from(["draft", "standard", None])),
    min_size=1, max_size=32,
))
def test_deadline_miss_counters_match_oracle_replay(items):
    """The delivery stage's per-class counters must agree exactly with
    an independent replay of (deadline, delivery-time) pairs: misses are
    deliveries strictly after the deadline, everything else counts as
    met, NFE totals are plain sums."""
    acc = TierAccounting()
    oracle = {}
    for uid, (deadline, deliver_t, nfe, tier) in enumerate(items):
        req = Req(uid=uid, deadline_at=deadline, nfe=nfe, tier=tier)
        acc.on_deliver(req, now=deliver_t)
        name = tier or "default"
        o = oracle.setdefault(name, dict(n=0, miss=0, nfe=0))
        o["n"] += 1
        o["nfe"] += nfe
        missed = deadline is not None and deliver_t > deadline
        o["miss"] += int(missed)
        assert req.deadline_missed is missed
    assert set(acc.stats) == set(oracle)
    for name, o in oracle.items():
        s = acc.stats[name]
        assert s.delivered == o["n"]
        assert s.deadline_misses == o["miss"]
        assert s.deadline_met == o["n"] - o["miss"]
        assert s.nfe_total == o["nfe"]
        assert s.mean_nfe == pytest.approx(o["nfe"] / o["n"])


def test_server_deadline_accounting_matches_request_stamps():
    """End-to-end oracle replay through the batcher with an injected
    fake clock: the per-class miss counters must equal a recount over
    the delivered requests' own (deadline_at, delivery-time) stamps."""
    from repro.core import AdaptiveConfig, VPSDE
    from repro.core.analytic import gaussian_noise_pred
    from repro.launch.sample import make_sample_step
    from repro.models.dit import DiTConfig
    from repro.serving.diffusion_server import (
        DiffusionBatcher, ImageRequest,
    )

    sde = VPSDE()
    cfg = AdaptiveConfig(eps_rel=0.05)
    net = DiTConfig(image_size=4, patch=4, d_model=8, num_layers=1,
                    num_heads=1, d_ff=8)
    step = make_sample_step(net, sde, cfg,
                            forward_fn=gaussian_noise_pred(sde, MU := 0.3,
                                                           S0 := 0.5))

    ticks = iter(range(1, 100_000))
    clock = lambda: float(next(ticks))  # 1s per observation

    delivered_log = []

    class LoggingAccounting(TierAccounting):
        def on_deliver(self, req, now):
            delivered_log.append((req.uid, req.deadline_at, now))
            super().on_deliver(req, now)

    acc = LoggingAccounting()
    b = DiffusionBatcher(sde, step, params=None, sample_shape=(16,),
                         slots=4, cfg=cfg, sync_horizon=4,
                         tolerance_classes=True, delivery=acc, clock=clock)
    # deadline 0ms ⇒ certain miss; huge ⇒ certain met; None ⇒ met
    deadlines = [0.0, None, 1e9, 0.0, None, 1e9, 0.0, None]
    for uid, dl in enumerate(deadlines):
        b.submit(ImageRequest(uid=uid, seed=uid, tier="draft",
                              deadline_ms=dl))
    done = b.run_to_completion()
    assert len(done) == len(deadlines)
    oracle_misses = sum(
        1 for _, dl, now in delivered_log if dl is not None and now > dl
    )
    s = acc.stats["draft"]
    assert s.delivered == len(deadlines)
    assert s.deadline_misses == oracle_misses == 3
    assert s.deadline_met == len(deadlines) - 3
    for uid, dl, now in delivered_log:
        assert done[uid].deadline_missed is (dl is not None and now > dl)
