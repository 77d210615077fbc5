"""Every mesh the repo builds (TPU v5e target).

``make_mesh`` is the one constructor: it gives every axis the ``Auto``
type. The installed JAX makes ``Explicit`` axes by default, and those
refuse the sharding constraints and unannotated gathers/scatters the
sampler and the serve loop rely on.

The helpers are functions, not module-level constants, so importing
this module never touches jax device state (the dry-run must set
XLA_FLAGS before first jax init).
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """A mesh of ``shape`` over ``axes`` with every axis ``Auto``.

    ``devices`` defaults to the first ``prod(shape)`` of
    ``jax.devices()``, the set ``jax.make_mesh`` picks.
    """
    kw = {} if devices is None else {"devices": list(devices)}
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes), **kw)


def make_data_mesh(n: int | None = None):
    """1-D ``("data",)`` mesh over the first ``n`` devices (default: all)."""
    devices = jax.devices()[: n or jax.device_count()]
    return make_mesh((len(devices),), ("data",), devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1×1 mesh for CPU smoke runs of the pjit code path."""
    return make_mesh((1, 1), ("data", "model"))
