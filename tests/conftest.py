"""Test harness config.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding is
exercised without TPU hardware (the driver separately dry-runs the
multichip path).  x64 is enabled so oracle/parity tests can request
float64; all library code uses explicit dtypes, so the float32 TPU path
is still what gets tested unless a test opts in to f64.
"""
import os
import pathlib
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

# the suite is a CPU suite: JAX_PLATFORMS=cpu, which the installed JAX
# honours as it is, plus eight virtual devices for the mesh tests
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)
# Persistent compile cache: the suite compiles hundreds of distinct
# programs, and subprocess tests (CLI roundtrips, bench smokes) reuse what
# the main process already compiled.  Placement follows the one rule of
# gymfx_tpu/compile_cache.py — JAX_COMPILATION_CACHE_DIR if set, else the
# fixed .jax_cache/ in the checkout — and the directory is exported so
# that the children land in the same one.
from gymfx_tpu.compile_cache import CACHE_ENV, enable_compile_cache  # noqa: E402

os.environ[CACHE_ENV] = enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


import gc  # noqa: E402

import pytest  # noqa: E402

# A process may hold 65,530 memory mappings (``vm.max_map_count``), and every
# executable XLA:CPU compiles or loads from the cache maps its code: a decoder
# trunk's CLI train-resume-serve test leaves some 12,000 behind (its Pallas
# kernels run interpreted: large programs), two test files 58,000.  A worker
# that reached the limit died of a segmentation fault inside the next compile or
# cache read, whichever test that was (the driver's run of PR 34's tree:
# ``ServingEngine.warmup``; PR 35's first whole run: that and IMPALA's
# ``train_many``), and passed alone.  JAX's in-memory caches keep the
# executables alive; dropping them releases the mappings (30,726 -> 1,223), and
# what a later test needs again comes out of the persistent cache.
MAPPINGS_BEFORE_A_CLEAR = 12_000


def _mappings() -> int:
    try:
        with open("/proc/self/maps") as maps:
            return sum(1 for _ in maps)
    except OSError:     # no procfs here: nothing to count, nothing to clear
        return 0


@pytest.fixture(autouse=True)
def _executables_do_not_pile_up():
    yield
    if _mappings() > MAPPINGS_BEFORE_A_CLEAR:
        jax.clear_caches()
        gc.collect()
