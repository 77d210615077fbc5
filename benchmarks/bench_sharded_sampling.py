"""Sharded-sampling scaling: samples/sec for 1 vs N devices.

Captures the data-parallel scaling axis of ``sample(..., mesh=...)``
(DESIGN.md §3) in the ``name,us_per_call,derived`` CSV the perf
trajectory tracks. Device counts are faked with
``xla_force_host_platform_device_count`` — on a CPU host the shards
share the same cores, so absolute samples/sec is NOT expected to scale;
what this captures is the overhead of the sharded program (partitioned
prior draw, constrained while-loop carry, shard_map'd fused kernel)
relative to the single-device run, and it becomes a true scaling curve
the moment it runs on real accelerators.

On an accelerator every device count runs in this process, over a mesh
of ``jax.devices()[:n]``: the process that holds the chips is the only
one that can use them. On the CPU each count runs in a child process
with that many fake devices (the count locks at jax init). A count that
fails fails the suite.

  PYTHONPATH=src python -m benchmarks.bench_sharded_sampling [--devices 1,4]
"""

from __future__ import annotations

# Child mode must set XLA_FLAGS before jax initializes.
import os  # noqa: E402
import sys  # noqa: E402

if __name__ == "__main__" and "--child" in sys.argv:
    _n = sys.argv[sys.argv.index("--child") + 1]
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_n} "
        + os.environ.get("XLA_FLAGS", "")
    )

import argparse
import subprocess

BATCH = 64
DIM = 256
EPS_REL = 0.05


def _measure(n_devices: int, use_fused: bool) -> None:
    import jax

    from benchmarks.common import emit, timed
    from repro.core import AdaptiveConfig, VPSDE, sample
    from repro.launch.mesh import make_data_mesh

    mu, s0 = 0.3, 0.5
    sde = VPSDE()

    def score(x, t):
        m, std = sde.marginal(t)
        m = m.reshape((-1, 1))
        std = std.reshape((-1, 1))
        return -(x - m * mu) / (m * m * s0 * s0 + std * std)

    if n_devices > jax.device_count():
        raise ValueError(f"{n_devices} devices requested, "
                         f"{jax.device_count()} present")
    mesh = make_data_mesh(n_devices) if n_devices > 1 else None
    cfg = AdaptiveConfig(eps_rel=EPS_REL, use_fused_kernel=use_fused)
    fn = jax.jit(
        lambda k: sample(sde, score, (BATCH, DIM), k, config=cfg, mesh=mesh)
    )
    us, res = timed(fn, jax.random.PRNGKey(0), repeats=3)
    sps = BATCH / (us / 1e6)
    tag = "fused" if use_fused else "jnp"
    emit(
        f"sharded_sampling/{tag}/dev{n_devices}", us,
        f"samples_per_sec={sps:.1f};batch={BATCH};mean_nfe={float(res.mean_nfe):.0f}",
    )


def main(device_counts=None) -> None:
    import jax

    if jax.default_backend() != "cpu":
        counts = device_counts or sorted({1, jax.device_count()})
        for n in counts:
            for fused in (False, True):
                _measure(n, fused)
        return
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    for n in device_counts or (1, 4):
        for fused in (False, True):
            cmd = [sys.executable, "-m", "benchmarks.bench_sharded_sampling",
                   "--child", str(n)]
            if fused:
                cmd.append("--fused")
            r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                               timeout=560, cwd=root)
            if r.returncode != 0:
                raise RuntimeError(
                    f"sharded_sampling dev{n} fused={fused} failed:\n"
                    + r.stderr)
            for line in r.stdout.strip().splitlines():
                if line.startswith("sharded_sampling/"):
                    print(line)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", type=int, default=None,
                    help="(internal, CPU) run one measurement on N fake "
                         "devices")
    ap.add_argument("--fused", action="store_true")
    ap.add_argument("--devices", default=None,
                    help="comma-separated device counts for the sweep "
                         "(default: 1,4 on the CPU, 1 and all devices on "
                         "an accelerator)")
    args = ap.parse_args()
    if args.child is not None:
        _measure(args.child, args.fused)
    else:
        main(tuple(int(x) for x in args.devices.split(","))
             if args.devices else None)
