"""The one traffic generator: reads a mix's parameters and draws every
request from ``--seed``.

Requests come in spans. Every seed gets the same spans with the same
work in another order, so that runs with different seeds differ only by
where the work falls:

* open loop: span 0 is the pre-roll (``preroll_s`` seconds), each later
  span is as long as the measured window. A span of ``s`` seconds holds
  ``round(rate_per_s * s)`` requests due at times drawn i.i.d. uniform
  over it: a Poisson process at ``rate_per_s`` given its count, so the
  window holds a fixed number of requests while their bursts and gaps
  are those of Poisson arrivals;
* closed loop: spans of ``block`` requests (default 20);
* tiers: ``tiers`` gives each tier's share; a span of ``n`` requests
  holds each tier ``share * n`` times (largest remainders), in an order
  drawn from the seed;
* per-request noise seeds, observations and returns labels are drawn
  from the seed and the request's index alone.

The stream is unbounded: a closed loop draws client ``c``'s ``k``-th
request as index ``k * clients + c``, and an open loop's traffic goes on
past the window.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

_TIERS, _GAPS, _REQ, _SAMPLE = range(4)


def _rng(seed: int, *words: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 64), *words])


def apportion(shares: Dict[str, float], n: int) -> List[str]:
    """``n`` tier names, each tier ``share * n`` times, the remainders
    going to the largest fractions (ties by name)."""
    names = sorted(shares)
    total = sum(float(shares[k]) for k in names)
    want = [n * float(shares[k]) / total for k in names]
    counts = [int(w) for w in want]
    order = sorted(range(len(names)), key=lambda j: (counts[j] - want[j], j))
    for j in order[: n - sum(counts)]:
        counts[j] += 1
    return [k for k, c in zip(names, counts) for _ in range(c)]


class Traffic:
    """Seeded request stream of one mix (``params`` is its data file);
    an open loop needs the length of the measured window."""

    def __init__(self, params: Dict[str, Any], seed: int, *,
                 window_s: Optional[float] = None, obs_dim: int = 0,
                 returns_bins: int = 0):
        self.p = params
        self.seed = int(seed)
        self.loop = params["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"loop must be open or closed, not {self.loop!r}")
        self.tiers: Optional[Dict[str, float]] = params.get("tiers") or None
        self.obs_dim = obs_dim
        self.obs_std = float(params.get("obs_std", 1.0))
        self.returns_bins = returns_bins
        # spans: (first index, count, start s, length s)
        self._spans: List[Tuple[int, int, float, float]] = []
        self._cache: Dict[int, Tuple[np.ndarray, Optional[List[str]]]] = {}
        if self.loop == "open":
            if not window_s or window_s <= 0:
                raise ValueError("an open loop needs the window's length")
            self.rate = float(params["rate_per_s"])
            self.window_s = float(window_s)
            self.preroll_s = float(params.get("preroll_s", 0.0))
            if self._span_shape(1)[0] < 1:
                raise ValueError("the window holds no request at this rate")
        else:
            self.clients = int(params["clients"])
            self.block = int(params.get("block", 20))

    # -- spans ------------------------------------------------------------
    def _span_shape(self, k: int) -> Tuple[int, float]:
        """(requests, seconds) of span ``k``."""
        if self.loop == "closed":
            return self.block, 0.0
        length = self.preroll_s if k == 0 else self.window_s
        return int(round(self.rate * length)), length

    def _span_of(self, i: int) -> int:
        while not self._spans or i >= self._spans[-1][0] + self._spans[-1][1]:
            k = len(self._spans)
            first = self._spans[-1][0] + self._spans[-1][1] if k else 0
            start = self._spans[-1][2] + self._spans[-1][3] if k else 0.0
            n, length = self._span_shape(k)
            self._spans.append((first, n, start, length))
        lo, hi = 0, len(self._spans) - 1
        while lo < hi:  # the last span whose first index is <= i
            mid = (lo + hi + 1) // 2
            if self._spans[mid][0] <= i:
                lo = mid
            else:
                hi = mid - 1
        return lo

    def _span(self, k: int):
        """Due times and tiers of span ``k``'s requests, in index order."""
        if k not in self._cache:
            _, n, start, length = self._spans[k]
            due = start + np.sort(
                _rng(self.seed, _GAPS, k).uniform(0.0, length, n))
            tiers = None
            if self.tiers is not None:
                names = apportion(self.tiers, n)
                perm = _rng(self.seed, _TIERS, k).permutation(n)
                tiers = [names[j] for j in perm]
            self._cache[k] = (due, tiers)
        return self._cache[k]

    # -- per request ------------------------------------------------------
    def spec(self, i: int) -> Dict[str, Any]:
        """Request ``i``: noise seed, tier, and (for plans) observation
        and returns label."""
        r = _rng(self.seed, _REQ, i)
        out: Dict[str, Any] = {
            "index": i, "seed": int(r.integers(0, 1 << 31)), "tier": None}
        if self.tiers is not None:
            k = self._span_of(i)
            out["tier"] = self._span(k)[1][i - self._spans[k][0]]
        if self.obs_dim:
            out["obs"] = (self.obs_std * r.standard_normal(self.obs_dim)
                          ).astype(np.float32)
        if self.returns_bins:
            out["label"] = int(r.integers(0, self.returns_bins))
        return out

    # -- open loop --------------------------------------------------------
    def due(self, i: int) -> float:
        """Seconds from the start of traffic at which request ``i`` is due."""
        k = self._span_of(i)
        return float(self._span(k)[0][i - self._spans[k][0]])

    @property
    def window_start(self) -> float:
        """Open loop: the window opens when the pre-roll ends."""
        return self.preroll_s

    # -- closed loop ------------------------------------------------------
    def client_request(self, client: int, k: int) -> int:
        return k * self.clients + client


def sample_indices(seed: int, n: int, k: int, must: int) -> List[int]:
    """``k`` of ``range(n)`` drawn from the seed, always holding ``must``."""
    rest = [i for i in range(n) if i != must]
    pick = _rng(seed, _SAMPLE).permutation(len(rest))[: max(k - 1, 0)]
    return sorted([must] + [rest[j] for j in pick])
