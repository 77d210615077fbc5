"""jit'd public wrappers for the fused solver-step kernel.

Handles arbitrary trailing shapes (images (B, H, W, C), tokens (B, S, E))
by flattening to (B, D), padding D up to the lane width, and dispatching
to the Pallas kernel (interpret=True on CPU so the same code path is
exercised everywhere). Padding is with zeros, which contribute exactly 0
to the error sum (δ ≥ ε_abs > 0), and the e2 normalization uses the true
unpadded D.

Operands may be bf16 (precision policy, DESIGN.md §8): the kernel
upcasts each tile to fp32 in-register, the error accumulator and the
padded→true-D renormalization here are fp32 throughout, and x'' comes
back in the operand dtype. Zero padding is exact in every dtype.

``sharded_error_step`` is the mesh-parallel form (DESIGN.md §3): a
``shard_map`` whose per-shard body runs the same Pallas kernel on its
local batch (and optionally feature) block, keeping the error reduction
in VMEM per shard and combining across feature shards with the O(B)
collective in ``repro.parallel.collectives``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from . import kernel as _k

Array = jax.Array

_LANES = 128


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _flatten_pad_to(x: Array, multiple: int):
    """Flatten to (B, D) and zero-pad D up to ``multiple``."""
    B = x.shape[0]
    flat = x.reshape(B, -1)
    D = flat.shape[1]
    pad = (-D) % multiple
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    return flat, D


def _flatten_pad(x: Array):
    return _flatten_pad_to(x, _LANES)


def em_step(x, score, z, c0, c1, c2, *, interpret: bool | None = None) -> Array:
    """Fused x' = c0·x + c1·score + c2·z for arbitrary state shapes."""
    interpret = _on_cpu() if interpret is None else interpret
    orig_shape = x.shape
    xf, D = _flatten_pad(x)
    sf, _ = _flatten_pad(score)
    zf, _ = _flatten_pad(z)
    out = _k.em_step(xf, sf, zf, c0, c1, c2, interpret=interpret)
    return out[:, :D].reshape(orig_shape)


def _eps_is_vector(eps_abs, eps_rel) -> bool:
    """Per-sample (B,) tolerance operands (DESIGN.md §14) vs static
    floats. Tracers (jit-staged (B,) carry leaves) count as vectors;
    0-d values are treated as floats so scalar callers keep the
    compile-time-constant kernel."""
    return any(getattr(e, "ndim", 0) >= 1 for e in (eps_abs, eps_rel))


def _eps_vectors(eps_abs, eps_rel, batch: int):
    ea = jnp.broadcast_to(jnp.asarray(eps_abs, jnp.float32), (batch,))
    er = jnp.broadcast_to(jnp.asarray(eps_rel, jnp.float32), (batch,))
    return ea, er


def error_step(
    x, x_prime, score2, z, x_prev, e0, d1, d2,
    *,
    eps_abs,
    eps_rel,
    use_prev: bool = True,
    interpret: bool | None = None,
):
    """Fused x̃/x''/δ/error. Returns (x'' with x's shape, e2 (B,)).

    ``eps_abs``/``eps_rel`` are floats (static tolerance, compile-time
    kernel constants — the pre-tier path, bitwise unchanged) or (B,)
    arrays (per-slot tolerance classes, DESIGN.md §14 — dispatched to
    the vector-ε kernel where they ride as two more coeff blocks).
    Zero padding stays exact either way: padded columns have mag 0 and
    residual 0, contributing 0 to the error sum for any δ ≥ ε_abs > 0.
    """
    interpret = _on_cpu() if interpret is None else interpret
    orig_shape = x.shape
    xf, D = _flatten_pad(x)
    xpf, _ = _flatten_pad(x_prime)
    s2f, _ = _flatten_pad(score2)
    zf, _ = _flatten_pad(z)
    xvf, _ = _flatten_pad(x_prev)
    if _eps_is_vector(eps_abs, eps_rel):
        ea, er = _eps_vectors(eps_abs, eps_rel, xf.shape[0])
        x_high, acc_e2 = _k.error_step_vec(
            xf, xpf, s2f, zf, xvf, e0, d1, d2, ea, er,
            use_prev=use_prev, interpret=interpret,
        )
    else:
        x_high, acc_e2 = _k.error_step(
            xf, xpf, s2f, zf, xvf, e0, d1, d2,
            eps_abs=float(eps_abs), eps_rel=float(eps_rel), use_prev=use_prev,
            interpret=interpret,
        )
    # kernel normalized by padded D; rescale to the true dimension count.
    Dpad = xf.shape[1]
    e2 = acc_e2 * jnp.sqrt(Dpad / D)
    return x_high[:, :D].reshape(orig_shape), e2


def sharded_error_step(
    x, x_prime, score2, z, x_prev, e0, d1, d2,
    *,
    eps_abs,
    eps_rel,
    mesh: Mesh,
    batch_axes,
    feature_axis: str | None = None,
    use_prev: bool = True,
    interpret: bool | None = None,
):
    """``error_step`` with the batch axis sharded over ``batch_axes``.

    Each shard dispatches the Pallas kernel on its local (B/n, Dpad/f)
    block, so the ~10-pass elementwise math and the squared-residual
    reduction never leave the shard's VMEM. With ``feature_axis`` the
    flattened feature dim additionally shards and the per-sample error is
    combined exactly across shards via
    ``repro.parallel.collectives.scaled_error_l2_psum`` (zero padding
    contributes 0 to every partial sum). Numerics match ``error_step``
    bit-for-bit in the batch-only case: rows are independent and each
    shard walks the same D-grid sequence.

    Returns (x'' with x's shape, e2 (B,)). Per-slot (B,) tolerances
    shard over the batch axes like every other per-sample coefficient,
    so each device reads only its own slots' ε (DESIGN.md §14).
    """
    from repro.parallel.collectives import scaled_error_l2_psum

    interpret = _on_cpu() if interpret is None else interpret
    batch_axes = (batch_axes,) if isinstance(batch_axes, str) else tuple(batch_axes)
    fsize = mesh.shape[feature_axis] if feature_axis else 1
    orig_shape = x.shape

    xf, D = _flatten_pad_to(x, fsize * _LANES)
    xpf, _ = _flatten_pad_to(x_prime, fsize * _LANES)
    s2f, _ = _flatten_pad_to(score2, fsize * _LANES)
    zf, _ = _flatten_pad_to(z, fsize * _LANES)
    xvf, _ = _flatten_pad_to(x_prev, fsize * _LANES)
    Dpad = xf.shape[1]
    vec_eps = _eps_is_vector(eps_abs, eps_rel)

    def _local(xl, xpl, s2l, zl, xvl, e0l, d1l, d2l, eal=None, erl=None):
        if vec_eps:
            return _k.error_step_vec(
                xl, xpl, s2l, zl, xvl, e0l, d1l, d2l, eal, erl,
                use_prev=use_prev, interpret=interpret,
            )
        return _k.error_step(
            xl, xpl, s2l, zl, xvl, e0l, d1l, d2l,
            eps_abs=float(eps_abs), eps_rel=float(eps_rel), use_prev=use_prev,
            interpret=interpret,
        )

    def body(xl, xpl, s2l, zl, xvl, e0l, d1l, d2l, *eps_loc):
        x_high, e2_loc = _local(xl, xpl, s2l, zl, xvl, e0l, d1l, d2l, *eps_loc)
        D_loc = xl.shape[1]
        if feature_axis is None:
            # per-sample reduction is shard-local; renormalize padded→true D
            return x_high, e2_loc * jnp.sqrt(D_loc / D)
        acc = e2_loc * e2_loc * D_loc  # undo the kernel's local normalization
        return x_high, scaled_error_l2_psum(acc, D / fsize, feature_axis)

    state_spec = P(batch_axes, feature_axis)
    coeff_spec = P(batch_axes)
    n_eps = 2 if vec_eps else 0
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(state_spec,) * 5 + (coeff_spec,) * (3 + n_eps),
        out_specs=(state_spec, coeff_spec),
        check_vma=False,  # pallas_call has no varying-axis rule
    )
    operands = (xf, xpf, s2f, zf, xvf, e0, d1, d2)
    if vec_eps:
        operands += _eps_vectors(eps_abs, eps_rel, xf.shape[0])
    x_high, e2 = fn(*operands)
    return x_high[:, :D].reshape(orig_shape), e2
