"""What every configuration's server gives the harness, and the check that
decides ``correct``.

A family module (``families/<family>.py``) builds a :class:`Server` around
the program's ``DiffusionBatcher``: it turns a traffic spec into the
program's request, names the step program for the trace, counts the FLOPs
of one score evaluation, and gives the plain reference of its score
network. The check here compares what the timed path delivered with the
plain reference's solve of the same requests.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from chipbench import reference


@dataclasses.dataclass
class Delivered:
    """One delivered request of the measured population."""

    spec: Dict[str, Any]
    result: np.ndarray
    nfe: int


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


class Server:
    #: set by the family's ``build``
    batcher: Any
    device_resident: bool
    step_program: str
    flop_per_eval: float
    sample_shape: tuple

    def __init__(self, cfg: Dict[str, Any]):
        self.cfg = cfg

    def request(self, uid: int, spec: Dict[str, Any]):
        raise NotImplementedError

    def warm_request(self, uid: int):
        """A request whose trajectory every warm-up request shares."""
        raise NotImplementedError

    def warm_tail(self, uid: int) -> list:
        """Requests that end the warm-up, for paths the groups miss."""
        return []

    # -- the plain reference -----------------------------------------------
    def reference_aux(self, specs):
        """The reference's weights and per-request data, one pytree."""
        raise NotImplementedError

    @staticmethod
    def reference_score(aux, x, t):
        raise NotImplementedError

    reference_projection: Optional[Callable] = None

    def finalize(self, x: np.ndarray, specs) -> np.ndarray:
        """What the server delivers for a solved state (identity unless
        the family pins coordinates)."""
        return x

    def tolerances(self, specs):
        """(eps_rel, h0) per request."""
        tiers = self.cfg["solver"]["tiers"]
        eps = [tiers[s["tier"] or "default"]["eps_rel"] for s in specs]
        return np.asarray(eps, np.float32), self.cfg["solver"]["h_init"]

    def reference_solve(self, specs) -> np.ndarray:
        with jax.default_matmul_precision("highest"):
            step = jax.jit(reference.make_step(self.reference_score,
                                               self.reference_projection))
            eps, h0 = self.tolerances(specs)
            x, _ = reference.solve(step, self.reference_aux(specs),
                                   [s["seed"] for s in specs],
                                   self.sample_shape, eps, h0)
        return self.finalize(x, specs)

    def extra_checks(self, delivered: List[Delivered], limits) -> List[Check]:
        return []

    def release(self) -> None:
        """Drop the program's server, so that its device state is freed
        before the reference runs."""
        self.batcher = None
        self.planner = None


def relative_gap(got: np.ndarray, want: np.ndarray) -> float:
    """max |got - want| / max |want| of one request."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))
