"""Reduction of a JAX profiler trace to the benchmark's device numbers.

On a TPU the trace has one plane per chip (``/device:TPU:<n>``) with a
line ``XLA Modules`` (one event per program execution, named
``jit_<fn>(<id>)``) and a line ``XLA Ops`` (one event per operation,
named by its HLO text). Host threads are lines of the ``/host:CPU``
plane; ``jax.profiler.TraceAnnotation`` spans appear there by name.

* busy time: the union of the ``XLA Ops`` intervals of a chip;
* program time: the summed durations of a program's ``XLA Modules``
  events;
* idle gaps: the holes in the busy union, each labelled by the innermost
  host span that covers its midpoint.

Times are in seconds. Numbers over several chips are means per chip.
"""

from __future__ import annotations

import collections
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[int, int]  # (start_ns, end_ns)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_short_name(hlo_text: str) -> str:
    """``%fusion.4 = f32[16,768]{1,0:T(8,128)} fusion(...), kind=kOutput``
    -> ``fusion.4 kOutput f32[16,768]``: the operation, its fusion kind
    (kOutput: rooted in a matrix product) and the (first) type it makes."""
    name, _, rest = hlo_text.partition(" = ")
    out = rest.split("{", 1)[0].split(" ", 1)[0].lstrip("(")
    kind = rest.split("kind=", 1)[1].split(",", 1)[0] if "kind=" in rest else ""
    return " ".join(p for p in (name.lstrip("%"), kind, out) if p)


def self_times(events: Sequence[Tuple[int, int, str]]) -> List[Tuple[int, int, str]]:
    """(start, self ns, name) of nested events: an operation that holds
    others (a ``while`` and the operations of its body) keeps only the
    time none of them covers."""
    order = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    child = [0] * len(order)
    stack: List[int] = []
    for i, (s, e, _) in enumerate(order):
        while stack and order[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += e - s
        stack.append(i)
    return [(s, e - s - c, n) for (s, e, n), c in zip(order, child)]


class Chip:
    """The events of one chip's plane."""

    def __init__(self, ops: List[Tuple[int, int, str]],
                 modules: List[Tuple[int, int, str]]):
        self.ops = ops
        self.modules = modules
        self.busy = union((s, e) for s, e, _ in ops)

    def busy_s(self, t0: int, t1: int) -> float:
        return sum(max(0, min(e, t1) - max(s, t0))
                   for s, e in self.busy) / 1e9

    def program_s(self, prefix: str, t0: int, t1: int) -> float:
        return sum(e - s for s, e, n in self.modules
                   if n.startswith(prefix + "(") and t0 <= s < t1) / 1e9

    def program_runs(self, prefix: str, t0: int, t1: int) -> int:
        return sum(1 for s, _, n in self.modules
                   if n.startswith(prefix + "(") and t0 <= s < t1)

    def gaps(self, t0: int, t1: int) -> List[Interval]:
        """Idle holes of the busy union inside ``[t0, t1]``."""
        out, cur = [], t0
        for s, e in self.busy:
            if s > cur:
                out.append((cur, min(s, t1)))
            cur = max(cur, e)
            if cur >= t1:
                break
        if cur < t1:
            out.append((cur, t1))
        return [(s, e) for s, e in out if e > s]


def _events(line) -> List[Tuple[int, int, str]]:
    return [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns), ev.name)
            for ev in line.events]


def load(path: str):
    """(chips, host spans) of an ``.xplane.pb`` file. Host spans are the
    events of the host plane named ``<stage>/...`` with a stage of
    ``STAGE_PREFIXES``."""
    import jax

    pdata = jax.profiler.ProfileData.from_file(path)
    chips: Dict[int, Chip] = {}
    host: List[Tuple[int, int, str]] = []
    for plane in pdata.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            ops = _events(lines["XLA Ops"]) if "XLA Ops" in lines else []
            mods = (_events(lines["XLA Modules"])
                    if "XLA Modules" in lines else [])
            chips[int(plane.name.rsplit(":", 1)[1])] = Chip(ops, mods)
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                host.extend(ev for ev in _events(ln) if "/" in ev[2]
                            and ev[2].split("/", 1)[0] in STAGE_PREFIXES)
    return chips, host


#: host spans that label idle gaps: the benchmark's own generator phases
#: and the serve loop's stages
STAGE_PREFIXES = ("bench", "serve", "plan")


def labels(times: Sequence[int],
           spans: Sequence[Tuple[int, int, str]]) -> List[str]:
    """For each of the sorted ``times``, the innermost span covering it,
    or ``(no span)``. The spans come from one thread, so they nest: the
    open ones form a stack whose top is the innermost."""
    order = sorted(spans)
    stack: List[Tuple[int, int, str]] = []
    out, j = [], 0
    for t in times:
        while j < len(order) and order[j][0] <= t:
            while stack and stack[-1][1] < order[j][0]:
                stack.pop()
            stack.append(order[j])
            j += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        out.append(stack[-1][2] if stack else "(no span)")
    return out


def reduce(chips: Dict[int, Chip], host: Sequence[Tuple[int, int, str]],
           t0: int, t1: int, *, programs: Sequence[str] = (),
           top: int = 10) -> Dict:
    """Device numbers over the traced window ``[t0, t1]`` (trace ns).

    Returns ``busy_s`` and per-program seconds as means per chip, the
    ``top`` operations by summed self time, and idle seconds summed by the
    host span the idle gaps fell in (the ``top`` largest).
    """
    n = len(chips)
    if not n:
        raise ValueError("the trace holds no TPU plane")
    ops = collections.Counter()
    idle = collections.Counter()
    for chip in chips.values():
        for s, self_ns, name in self_times(chip.ops):
            if t0 <= s < t1:
                ops[op_short_name(name)] += self_ns / 1e9 / n
        gaps = chip.gaps(t0, t1)
        for (s, e), label in zip(gaps, labels([(s + e) // 2 for s, e in gaps],
                                              host)):
            idle[label] += (e - s) / 1e9 / n
    return {
        "chips": n,
        "busy_s": sum(c.busy_s(t0, t1) for c in chips.values()) / n,
        "window_s": (t1 - t0) / 1e9,
        "program_s": {p: sum(c.program_s(p, t0, t1)
                             for c in chips.values()) / n for p in programs},
        "program_runs": {p: sum(c.program_runs(p, t0, t1)
                                for c in chips.values()) / n
                         for p in programs},
        "device_ops": [[k, v] for k, v in ops.most_common(top)],
        "idle_gaps": [[k, v] for k, v in idle.most_common(top)],
    }
