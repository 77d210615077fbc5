import jax
import pytest

from repro.launch.cache import use_compile_cache

# The default suite is jit-compile dominated, so persist XLA's
# compilation cache across runs: a warm `pytest -q` re-run skips most
# compiles. Numerics are unaffected — the cache stores compiled
# executables keyed on the exact HLO, compile options and compiler
# version.
use_compile_cache()

# Smoke tests and benches must see the single real CPU device — the 512
# placeholder devices are requested by dryrun.py only (in subprocesses).
jax.config.update("jax_platform_name", "cpu")
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
