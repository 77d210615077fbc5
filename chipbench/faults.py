"""Faults planted under the timed path (``run.py --fault <name>``), to show
that the check that decides ``correct`` catches them (``chipbench/tests``):
``state_unchanged``, ``half_batch``, ``answer_altered``. A run of the
benchmark plants none."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def wrap_step(fault):
    """A wrapper of the solver step ``step(params, carry, max_sync_iters)``
    for the step faults, or None."""
    if fault == "state_unchanged":
        return lambda step: (lambda p, c, max_sync_iters=1: c)
    if fault == "half_batch":
        # the step computes the first half of the slots and hands its rows
        # to the second half as well
        def wrap(step):
            def broken(p, c, max_sync_iters=1):
                new = step(p, c, max_sync_iters=max_sync_iters)
                n = c.x.shape[0]

                def leaf(cur):
                    if jnp.ndim(cur) == 0 or cur.shape[0] != n:
                        return cur
                    return jnp.concatenate([cur[: n // 2]] * 2)[:n]
                return jax.tree_util.tree_map(leaf, new)
            return broken
        return wrap
    return None


def plant_delivery(fault, batcher) -> None:
    """``answer_altered``: every delivered request gets the sample the
    previous delivery carried (the first gets zeros), as a stale buffer
    would hand it out."""
    if fault != "answer_altered":
        return
    retire = batcher._retire
    last = []

    def altered(rows, *args, **kwargs):
        rows = np.asarray(rows)
        prev = last[0] if last else np.zeros_like(rows[0])
        out = np.concatenate([prev[None], rows[:-1]])
        last[:] = [rows[-1]]
        return retire(out, *args, **kwargs)

    batcher._retire = altered
