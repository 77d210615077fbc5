"""Where compiled programs are cached: ``JAX_COMPILATION_CACHE_DIR``
when it is set, else ``<checkout>/.jax_cache`` — and only
``repro.launch.cache`` decides."""

import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

_PROBE = ("import jax; from repro.launch.cache import use_compile_cache; "
          "use_compile_cache(); print(jax.config.jax_compilation_cache_dir)")


@pytest.mark.parametrize("preset", [True, False])
def test_cache_dir_follows_the_environment(tmp_path, preset):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    if preset:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    r = subprocess.run([sys.executable, "-c", _PROBE], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    want = tmp_path if preset else ROOT / ".jax_cache"
    assert r.stdout.strip() == str(want)


def test_only_the_helper_sets_a_cache_dir():
    files = [ROOT / "chip_smoke.py"] + [
        p for d in ("src", "benchmarks", "tests", "examples", "tools")
        for p in (ROOT / d).rglob("*.py")
    ]
    setters = sorted(
        str(p.relative_to(ROOT)) for p in files
        if p != pathlib.Path(__file__).resolve()
        and ("jax_compilation_cache_dir" in p.read_text(encoding="utf-8")
             or "initialize_cache" in p.read_text(encoding="utf-8"))
    )
    assert setters == ["src/repro/launch/cache.py"]
