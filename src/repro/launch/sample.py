"""Diffusion-sampling launcher + production-mesh dry-run of the paper's
technique itself (beyond the assigned 40 combos).

Three entry points:

  * run mode (CPU or mesh): train-free demo sampling from a DiT score
    net with any solver;
  * ``--dryrun``: lower + compile ONE adaptive-solver iteration
    ("sample_step": two score-net forwards + the fused step math +
    per-sample accept/adapt) for the high-res DiT on the 16×16 / 2×16×16
    meshes, with the batch sharded over data axes and the DiT weights
    tensor-parallel — proving the paper's sampler distributes on the
    same production mesh as the LM stack, and feeding §Roofline;
  * ``--dryrun-loop``: lower + compile the ENTIRE adaptive sampling
    loop — ``sample(..., mesh=...)``: sharded prior draw, the
    lax.while_loop with its per-sample carry, both score forwards, and
    the final Tweedie denoise — on a fake multi-device data mesh
    (DESIGN.md §3). This is the full distributed program the serving
    path repeats, checkable on a CPU-only host.

  PYTHONPATH=src python -m repro.launch.sample --dryrun [--multi-pod]
  PYTHONPATH=src python -m repro.launch.sample --dryrun-loop [--loop-devices 64]

All modes take ``--precision {fp32,bf16,bf16_full}`` (DESIGN.md §8):
the score net / solver state run at the policy's dtypes (error control
always fp32) and the dry-run JSONs record the per-device byte savings.
"""

import os  # noqa: E402
import sys  # noqa: E402

from repro.launch._argv import argv_value  # noqa: E402

if "--dryrun" in sys.argv:
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        "--xla_backend_optimization_level=0 "
        + os.environ.get("XLA_FLAGS", "")
    )
elif "--dryrun-loop" in sys.argv:
    _n = argv_value("--loop-devices", "64")
    if not (_n.isdigit() and int(_n) > 0):
        _n = "64"  # argparse reports the malformed value after imports
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_n} "
        "--xla_backend_optimization_level=0 "
        + os.environ.get("XLA_FLAGS", "")
    )

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.analysis.hlo import collective_bytes_from_text, summarize_cost
from repro.configs.diffusion import CIFAR_DIT, HIGHRES_DIT
from repro.core import VESDE, VPSDE, AdaptiveConfig, sample
from repro.core.precision import PRESETS, resolve_policy
from repro.core.solvers.adaptive import SolverCarry, solve_chunk
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_data_mesh
from repro.models.dit import DiTConfig, dit_forward, init_dit, make_score_fn

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _dit_param_shardings(params_abs, mesh, *, pipeline_axis=None):
    """DiT tensor-parallel rules: attention heads + ffn over "model";
    with ``pipeline_axis``, stacked layer weights additionally shard
    their repeat (dim 0) over that axis (GPipe stages)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def fn(path, leaf):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        msize = mesh.shape.get("model", 1)
        shape = leaf.shape
        stage = pipeline_axis if (
            pipeline_axis and name.startswith("layers")
            and shape[0] % mesh.shape.get(pipeline_axis, 1) == 0
        ) else None

        def ok(d):
            return shape[d] % msize == 0

        if name.endswith(("attn/wq", "attn/wk", "attn/wv")) and ok(2):
            return NamedSharding(mesh, P(stage, None, "model", None))
        if name.endswith("attn/wo") and ok(1):
            return NamedSharding(mesh, P(stage, "model", None, None))
        if name.endswith(("mlp/w_in", "mlp/w_gate")) and ok(2):
            return NamedSharding(mesh, P(stage, None, "model"))
        if name.endswith("mlp/w_out") and ok(1):
            return NamedSharding(mesh, P(stage, "model", None))
        if name.endswith("/ada") and leaf.ndim == 3 and ok(2):
            return NamedSharding(mesh, P(stage, None, "model"))
        if stage:
            return NamedSharding(mesh, P(stage))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(fn, params_abs)


def make_sample_step(net: DiTConfig, sde, cfg: AdaptiveConfig,
                     forward_fn=None):
    """Resumable Algorithm-1 chunk as a pjit-able step function.

    Returns ``step(params, carry, max_sync_iters=1) -> carry`` over the
    solver's ``SolverCarry`` pytree — the exact ``solve_chunk`` body the
    monolithic ``adaptive()`` runs, so serving inherits every solver
    feature (fused kernel, per-slot keys, NFE accounting) and chaining
    chunks reproduces the monolithic solve bit-for-bit. This is the unit
    the serving loop repeats until all samples land at t_eps, retiring
    and refilling slots at each sync horizon.

    ``forward_fn(params, x, t)`` is noise-prediction: score = -out/std.
    ``cfg.precision`` threads through (DESIGN.md §8): the default DiT
    forward runs in the policy's compute dtype, the 1/std rescale is
    fp32, and ``solve_chunk`` keeps the carry at the state dtype. A
    custom ``forward_fn`` is responsible for its own compute casting
    (``solve_chunk`` still casts its x input / score output).

    ``cfg.conditioner`` threads through the same way (DESIGN.md §9):
    ``solve_chunk`` consumes the carry's per-slot condition payload.
    With a ``ClassifierFree`` conditioner the score must be label-aware,
    so the step's score_fn forwards ``y`` whenever ``forward_fn``
    declares it (the default DiT forward does).
    """
    policy = resolve_policy(cfg.precision)
    if forward_fn is None:
        forward_fn = lambda p, x, t, y=None: dit_forward(
            p, x, t, net, policy=policy, y=y)
    import inspect

    accepts_y = "y" in inspect.signature(forward_fn).parameters

    def sample_step(params, carry, max_sync_iters: int = 1):
        def score_fn(x, t, y=None):
            _, std = sde.marginal(t)
            out = (forward_fn(params, x, t, y=y) if accepts_y
                   else forward_fn(params, x, t)).astype(jnp.float32)
            return -out / std.reshape((-1,) + (1,) * (x.ndim - 1))

        return solve_chunk(
            sde, score_fn, carry,
            max_sync_iters=max_sync_iters, config=cfg,
        )

    return sample_step


def make_pipelined_dit_forward(net: DiTConfig, *, num_microbatches: int = 4,
                               axis: str = "pod", policy=None):
    """DiT forward with the layer stack pipelined over ``axis`` (GPipe).

    The per-sample time embedding rides along as an extra token so the
    (activations, conditioning) pair crosses stage boundaries together.
    ``policy`` mirrors ``dit_forward``'s precision seams (DESIGN.md §8):
    activations and the weight copies in compute dtype, fp32
    timestep-embedding math from the stored weights.
    """
    import jax.numpy as jnp

    from repro.models.dit import _patchify, _unpatchify
    from repro.models.layers import apply_norm, timestep_embedding
    from repro.parallel.pipeline import pipeline_forward

    def body(stage_layers, hm):
        # hm (mb, S+1, D): last token is the time-conditioning vector
        h, temb = hm[:, :-1, :], hm[:, -1, :]

        def layer(h, lp):
            import jax
            from repro.models.attention import _ref_attention
            from repro.models.layers import apply_mlp

            mod = jax.nn.silu(temb) @ lp["ada"] + lp["ada_b"]
            s1, b1, g1, s2, b2, g2 = jnp.split(mod[:, None, :], 6, axis=-1)
            hn = apply_norm(lp["norm1"], h, "layernorm_np") * (1 + s1) + b1
            q = jnp.einsum("bse,ehd->bshd", hn, lp["attn"]["wq"])
            k = jnp.einsum("bse,ehd->bshd", hn, lp["attn"]["wk"])
            v = jnp.einsum("bse,ehd->bshd", hn, lp["attn"]["wv"])
            att = _ref_attention(q, k, v, causal=False, window=None, softcap=0.0)
            h = h + g1 * jnp.einsum("bshd,hde->bse", att, lp["attn"]["wo"])
            hn = apply_norm(lp["norm2"], h, "layernorm_np") * (1 + s2) + b2
            h = h + g2 * apply_mlp(lp["mlp"], hn, "silu", True)
            return h, None

        h, _ = jax.lax.scan(layer, h, stage_layers)
        return jnp.concatenate([h, temb[:, None, :]], axis=1)

    def fwd(params, x, t):
        # fp32 timestep-embedding math from the stored (master) weights
        f32 = lambda w: w.astype(jnp.float32)
        temb = timestep_embedding(t, 256)
        temb = jax.nn.silu(temb @ f32(params["t_mlp1"])) @ f32(params["t_mlp2"])
        if policy is not None:
            x = x.astype(policy.compute)
            params = policy.params_for_compute(params)
        h = _patchify(x, net) @ params["patch_in"] + params["pos_emb"]
        temb = temb.astype(h.dtype)
        hm = jnp.concatenate([h, temb[:, None, :]], axis=1)
        hm = pipeline_forward(params["layers"], hm, body, axis=axis,
                              num_microbatches=num_microbatches)
        h, temb = hm[:, :-1, :], hm[:, -1, :]
        mod = jax.nn.silu(temb) @ params["final_ada"] + params["final_ada_b"]
        s, b = jnp.split(mod[:, None, :], 2, axis=-1)
        h = apply_norm(params["final_norm"], h, "layernorm_np") * (1 + s) + b
        return _unpatchify(h @ params["patch_out"], net)

    return fwd


def _precision_record(policy, params_abs, state_x_abs, mesh) -> dict:
    """Policy dtypes + the per-device byte footprint they imply, so the
    bf16 memory/collective savings are visible in experiments/dryrun/
    next to the fp32 artifacts. ``state_x_abs`` is the (B, ...) x spec;
    the carry holds two such tensors (x and x_prev)."""
    import numpy as np

    from repro.parallel.sharding import data_axes

    axes = data_axes(mesh)
    n_data = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    leaves = jax.tree_util.tree_leaves(params_abs)
    param_bytes = int(sum(l.size * jnp.dtype(l.dtype).itemsize for l in leaves))
    state_bytes = int(
        2 * state_x_abs.size * jnp.dtype(state_x_abs.dtype).itemsize
    )
    rec = policy.as_dict()
    rec["param_bytes_total"] = param_bytes
    rec["state_bytes_per_device"] = state_bytes // n_data
    return rec


def dryrun(multi_pod: bool, batch: int = 512, pipeline: bool = False,
           precision: str = "fp32") -> dict:
    from repro.launch.mesh import make_production_mesh

    net = HIGHRES_DIT  # 256×256×3, ~100M-param DiT
    sde = VESDE(sigma_max=50.0)  # paper's high-res process
    mesh = make_production_mesh(multi_pod=multi_pod)
    policy = resolve_policy(precision)

    if pipeline:
        assert multi_pod, "pipeline stages live on the pod axis (2-pod mesh)"
    params_abs = jax.eval_shape(lambda k: init_dit(net, k),
                                jax.random.PRNGKey(0))
    # weights lowered at the policy's storage dtype (bf16 halves both the
    # per-device weight HBM and the weight-collective bytes)
    params_abs = jax.eval_shape(policy.cast_params, params_abs)
    p_shard = _dit_param_shardings(
        params_abs, mesh, pipeline_axis="pod" if pipeline else None)
    shp = (batch, net.image_size, net.image_size, net.channels)
    arr = lambda s, d=jnp.float32: jax.ShapeDtypeStruct(s, d)
    state_abs = SolverCarry(
        x=arr(shp, policy.state), x_prev=arr(shp, policy.state),
        t=arr((batch,)), h=arr((batch,)),
        key=arr((batch, 2), jnp.uint32),  # per-slot keys: the serving form
        nfe=arr((batch,), jnp.int32),
        accepted=arr((batch,), jnp.int32),
        rejected=arr((batch,), jnp.int32),
        done=arr((batch,), jnp.bool_),
        iterations=arr((), jnp.int32),
    )
    from repro.parallel.sharding import solver_carry_shardings

    s_shard = solver_carry_shardings(mesh, batch, len(shp),
                                     per_slot_keys=True)

    fwd = (make_pipelined_dit_forward(net, axis="pod", policy=policy)
           if pipeline else None)
    step = make_sample_step(net, sde,
                            AdaptiveConfig(eps_rel=0.02, precision=precision),
                            forward_fn=fwd)
    t0 = time.time()
    with jax.set_mesh(mesh):
        compiled = jax.jit(
            step, in_shardings=(p_shard, s_shard), out_shardings=s_shard,
            donate_argnums=(1,),
        ).lower(params_abs, state_abs).compile()
    mem = compiled.memory_analysis()
    cost = summarize_cost(compiled.cost_analysis())
    coll = collective_bytes_from_text(compiled.as_text())
    rec = {
        "arch": "dit-highres-sampler" + ("-pipelined" if pipeline else ""),
        "shape": f"sample_b{batch}_256px",
        "mesh": "2pod" if multi_pod else "1pod",
        "devices": int(len(mesh.devices.flat)),
        "compile_s": round(time.time() - t0, 1),
        "memory": {"peak_bytes": getattr(mem, "peak_memory_in_bytes", None)},
        "cost": cost,
        "collectives": coll,
        "precision": _precision_record(policy, params_abs, state_abs.x, mesh),
        "note": "one Algorithm-1 chunk iteration (2 score-net fwd + step math)",
    }
    os.makedirs(OUT_DIR, exist_ok=True)
    suffix = "" if policy.is_fp32 else f"_{policy.name}"
    with open(os.path.join(
            OUT_DIR,
            f"{rec['arch']}_{rec['shape']}_{rec['mesh']}{suffix}.json"), "w") as f:
        json.dump(rec, f, indent=1, sort_keys=True)  # stable key order across regenerations
    gb = 1024 ** 3
    print(f"[{rec['arch']} × {rec['shape']} × {rec['mesh']}] OK  "
          f"compile {rec['compile_s']}s  "
          f"flops/dev {cost.get('flops', 0):.3e}  "
          f"peak/dev {(rec['memory']['peak_bytes'] or 0) / gb:.2f} GiB  "
          f"coll {coll['total_bytes'] / gb:.3f} GiB")
    return rec


def dryrun_loop(batch: int = 256, precision: str = "fp32") -> dict:
    """Lower + compile the whole sharded sampling loop on a fake data mesh.

    Unlike ``dryrun`` (one solver iteration), this compiles the complete
    distributed program of ``sample(..., mesh=...)``: sharded prior draw,
    the adaptive lax.while_loop with its per-sample (B,) carry, both
    score-net forwards per iteration, and the Tweedie denoise — verifying
    that GSPMD keeps every iteration data-parallel (collective bytes
    should stay O(loop-bookkeeping), not O(activations)).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    net = CIFAR_DIT
    sde = VPSDE()
    ndev = len(jax.devices())
    mesh = make_data_mesh()
    assert batch % ndev == 0, f"batch {batch} must divide {ndev} devices"
    policy = resolve_policy(precision)

    params_abs = jax.eval_shape(lambda k: init_dit(net, k),
                                jax.random.PRNGKey(0))
    params_abs = jax.eval_shape(policy.cast_params, params_abs)
    rep = NamedSharding(mesh, P())
    p_shard = jax.tree_util.tree_map(lambda _: rep, params_abs)
    shp = (batch, net.image_size, net.image_size, net.channels)

    def run(params, key):
        def score_fn(x, t):
            _, std = sde.marginal(t)
            out = dit_forward(params, x, t, net, policy=policy)
            return -out.astype(jnp.float32) / std.reshape(-1, 1, 1, 1)

        return sample(sde, score_fn, shp, key, method="adaptive", mesh=mesh,
                      config=AdaptiveConfig(eps_rel=0.02, precision=precision))

    key_abs = jax.ShapeDtypeStruct((2,), jnp.uint32)
    t0 = time.time()
    compiled = jax.jit(
        run, in_shardings=(p_shard, rep),
    ).lower(params_abs, key_abs).compile()
    mem = compiled.memory_analysis()
    cost = summarize_cost(compiled.cost_analysis())
    coll = collective_bytes_from_text(compiled.as_text())
    rec = {
        "arch": "dit-cifar-sampler-whole-loop",
        "shape": f"sample_b{batch}_32px",
        "mesh": f"data{ndev}",
        "devices": ndev,
        "compile_s": round(time.time() - t0, 1),
        "memory": {"peak_bytes": getattr(mem, "peak_memory_in_bytes", None)},
        "cost": cost,
        "collectives": coll,
        "precision": _precision_record(
            policy, params_abs, jax.ShapeDtypeStruct(shp, policy.state), mesh,
        ),
        "note": "full adaptive while_loop (prior + solver + denoise), batch sharded",
    }
    os.makedirs(OUT_DIR, exist_ok=True)
    suffix = "" if policy.is_fp32 else f"_{policy.name}"
    with open(os.path.join(
            OUT_DIR,
            f"{rec['arch']}_{rec['shape']}_{rec['mesh']}{suffix}.json"), "w") as f:
        json.dump(rec, f, indent=1, sort_keys=True)  # stable key order across regenerations
    gb = 1024 ** 3
    print(f"[{rec['arch']} × {rec['shape']} × {rec['mesh']}] OK  "
          f"compile {rec['compile_s']}s  "
          f"flops/dev {cost.get('flops', 0):.3e}  "
          f"peak/dev {(rec['memory']['peak_bytes'] or 0) / gb:.2f} GiB  "
          f"coll {coll['total_bytes'] / gb:.3f} GiB")
    return rec


def demo(precision: str = "fp32", flash: bool = False) -> None:
    net = DiTConfig(image_size=16, patch=4, d_model=96, num_layers=2,
                    num_heads=4, d_ff=256, use_flash=flash)
    sde = VPSDE()
    key = jax.random.PRNGKey(0)
    policy = resolve_policy(precision)
    params = init_dit(net, key)
    score = make_score_fn(params, net, sde, policy=policy)
    for method, kw in [
        ("adaptive", dict(eps_rel=0.05, precision=precision)),
        ("em", dict(n_steps=100)),
    ]:
        res = jax.jit(lambda k, kw=kw, method=method: sample(
            sde, score, (8, 16, 16, 3), k, method=method, **kw))(key)
        print(f"{method}[{policy.name}]: NFE {float(res.mean_nfe):.0f} "
              f"finite={bool(jnp.all(jnp.isfinite(res.x)))}")


def demo_cfg(scale: float, precision: str = "fp32") -> None:
    """Class-conditional demo (DESIGN.md §9): a train-free class-
    conditional DiT sampled with classifier-free guidance — one doubled
    batched forward per score evaluation, labels cycling 0..9."""
    from repro.core.guidance import class_conditional

    net = DiTConfig(image_size=16, patch=4, d_model=96, num_layers=2,
                    num_heads=4, d_ff=256, num_classes=10)
    sde = VPSDE()
    key = jax.random.PRNGKey(0)
    policy = resolve_policy(precision)
    params = init_dit(net, key)
    score = make_score_fn(params, net, sde, policy=policy)
    conditioner, cond = class_conditional(jnp.arange(8) % 10, scale)
    res = jax.jit(lambda k: sample(
        sde, score, (8, 16, 16, 3), k, method="adaptive",
        config=AdaptiveConfig(eps_rel=0.05, precision=precision,
                              conditioner=conditioner),
        cond=cond))(key)
    print(f"cfg[scale={scale}, {policy.name}]: "
          f"NFE {float(res.mean_nfe):.0f} "
          f"finite={bool(jnp.all(jnp.isfinite(res.x)))}")


def demo_inpaint(precision: str = "fp32") -> None:
    """Inpainting demo (DESIGN.md §9): checkerboard-mask inpainting on
    the train-free DiT — observed pixels are projected (re-noised to
    each slot's own t) after every accepted step and pinned exactly at
    delivery. No checkpoint needed; see examples/inpaint_adaptive.py
    for the analytic-score version with exactness checks."""
    from repro.core.guidance import inpaint as make_inpaint

    net = DiTConfig(image_size=16, patch=4, d_model=96, num_layers=2,
                    num_heads=4, d_ff=256)
    sde = VPSDE()
    key = jax.random.PRNGKey(0)
    policy = resolve_policy(precision)
    params = init_dit(net, key)
    score = make_score_fn(params, net, sde, policy=policy)
    yy, xx = jnp.mgrid[:16, :16]
    mask = jnp.broadcast_to(
        (((yy // 4 + xx // 4) % 2) == 0)[None, :, :, None],
        (8, 16, 16, 3)).astype(jnp.float32)
    observed = jnp.broadcast_to(
        jnp.linspace(-0.5, 0.5, 16)[None, :, None, None], (8, 16, 16, 3))
    conditioner, cond = make_inpaint(mask, observed)
    res = jax.jit(lambda k: sample(
        sde, score, (8, 16, 16, 3), k, method="adaptive",
        config=AdaptiveConfig(eps_rel=0.05, precision=precision,
                              conditioner=conditioner),
        cond=cond))(key)
    resid = float(jnp.abs((res.x - observed) * mask).max())
    print(f"inpaint[{policy.name}]: NFE {float(res.mean_nfe):.0f} "
          f"observed-pixel residual {resid:.2e} "
          f"finite={bool(jnp.all(jnp.isfinite(res.x)))}")


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", action="store_true")
    ap.add_argument("--dryrun-loop", action="store_true",
                    help="compile the whole sharded sampling loop")
    ap.add_argument("--loop-devices", type=int, default=64,
                    help="fake host devices for --dryrun-loop (set pre-init)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--pipeline", action="store_true",
                    help="GPipe the DiT layer stack over the pod axis")
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--precision", choices=sorted(PRESETS), default="fp32",
                    help="precision policy (DESIGN.md §8): network/state "
                         "dtypes; error control always stays fp32")
    ap.add_argument("--cfg-scale", type=float, default=None,
                    help="demo classifier-free guidance at this scale "
                         "on a class-conditional DiT (DESIGN.md §9)")
    ap.add_argument("--inpaint", action="store_true",
                    help="demo checkerboard-mask inpainting "
                         "(post-accept projection, DESIGN.md §9)")
    ap.add_argument("--flash", action="store_true",
                    help="route the demo DiT's attention through the "
                         "Pallas flash kernel (DESIGN.md §13; "
                         "interpreter mode on CPU)")
    args = ap.parse_args()
    if args.dryrun:
        dryrun(args.multi_pod, args.batch, pipeline=args.pipeline,
               precision=args.precision)
    elif args.dryrun_loop:
        dryrun_loop(args.batch, precision=args.precision)
    elif args.cfg_scale is not None:
        demo_cfg(args.cfg_scale, precision=args.precision)
    elif args.inpaint:
        demo_inpaint(precision=args.precision)
    else:
        demo(precision=args.precision, flash=args.flash)


if __name__ == "__main__":
    main()
