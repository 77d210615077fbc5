"""LM training launcher (runs for real on whatever mesh fits the host).

On the production mesh this is the same code path the dry-run lowers;
on CPU it runs reduced configs end-to-end (the per-arch smoke tests and
the quickstart example call into this).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
      --steps 20 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.launch.cache import use_compile_cache
from repro.checkpoint import save_checkpoint
from repro.configs import ARCH_IDS, get_config
from repro.data.tokens import TokenPipelineConfig, synth_batch
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step
from repro.models import init_model
from repro.models.config import ModelConfig
from repro.optim import AdamW, warmup_cosine
from repro.parallel.sharding import batch_sharding, param_shardings


def train_loop(
    cfg: ModelConfig,
    *,
    steps: int = 20,
    batch: int = 8,
    seq: int = 128,
    lr: float = 3e-4,
    seed: int = 0,
    mesh=None,
    ckpt_dir: str | None = None,
    log_every: int = 5,
):
    mesh = mesh or make_host_mesh()
    optimizer = AdamW(lr=warmup_cosine(lr, max(steps // 10, 1), steps))
    key = jax.random.PRNGKey(seed)

    with jax.set_mesh(mesh):
        p_shard = param_shardings(
            jax.eval_shape(lambda k: init_model(cfg, k), key),
            mesh,
            cfg.moe.num_experts if cfg.moe else None,
        )
        params = jax.jit(lambda k: init_model(cfg, k), out_shardings=p_shard)(key)
        opt_state = optimizer.init(params)
        step_fn = jax.jit(make_train_step(cfg, optimizer))

        pipe = TokenPipelineConfig(
            vocab_size=cfg.vocab_size,
            seq_len=seq,
            global_batch=batch,
            num_codebooks=cfg.num_codebooks,
            seed=seed,
        )
        cross = (
            jax.random.normal(key, (batch, cfg.num_patches, cfg.vision_dim),
                              jnp.dtype(cfg.dtype))
            if cfg.vision_dim else None
        )

        losses = []
        t0 = time.time()
        for step in range(steps):
            tokens = synth_batch(pipe, step)
            b = {"tokens": tokens}
            if cross is not None:
                b["cross_embeds"] = cross
            params, opt_state, metrics = step_fn(params, opt_state, b)
            losses.append(float(metrics["ce"]))
            if step % log_every == 0 or step == steps - 1:
                print(
                    f"step {step:4d}  ce {losses[-1]:.4f}  "
                    f"moe_aux {float(metrics['moe_aux']):.4f}  "
                    f"({(time.time() - t0) / (step + 1):.2f}s/step)"
                )
        if ckpt_dir:
            save_checkpoint(ckpt_dir, steps, {"params": params},
                            metadata={"arch": cfg.name})
    return params, losses


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced (smoke-scale) variant")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.scaled_down()
    _, losses = train_loop(
        cfg, steps=args.steps, batch=args.batch, seq=args.seq,
        lr=args.lr, ckpt_dir=args.ckpt_dir,
    )
    print(f"final ce {losses[-1]:.4f} (from {losses[0]:.4f})")


if __name__ == "__main__":
    main()
