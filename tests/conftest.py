"""Test configuration: force an 8-device CPU mesh so every test — including
the multi-chip sharding suite — runs without TPU hardware (the 'fake backend'
CI strategy, SURVEY.md §4: the reference's test-nd4j-native profile analog).
"""
import os
import sys

# The tests never use an accelerator: the platform and the virtual device
# count must be fixed before the first JAX operation, through the one
# bootstrap that lives next to the driver entry point.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from __graft_entry__ import _force_virtual_cpu_mesh  # noqa: E402

_force_virtual_cpu_mesh(8)

import jax  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from deeplearning4j_tpu.util import compile_cache  # noqa: E402

# Float64 available suite-wide: gradient checks need reference-grade
# precision (models default to float32 internally regardless; they cast
# inputs to their configured dtype).
jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache (util/compile_cache.py says where):
# tier-1 compiles thousands of tiny CPU programs, and on a slow
# container the aggregate compile time alone can blow the driver's
# wall-clock budget, so the keep-threshold is 0 and one warm run speeds
# every later one (entries are keyed by jaxlib version + backend +
# program hash, so a stale cache misses instead of misbehaving).
# Engine-level compile accounting (serving_compiles_total,
# assert_no_recompiles) sits ABOVE jax's dispatch layer and is
# unaffected. Opt out: DL4J_TEST_JAX_CACHE=0.
#
# It is switched off for the modules whose subject is serializing
# executables (serving/compile_cache.py): an executable that JAX loaded
# from its persistent cache does not survive a second serialization on
# the CPU backend — the AOT entry loads, then fails at its first call
# with "Function ... not found" — and the engine memoises it
# process-wide, so every later engine of that geometry fails too.
_JAX_CACHE_ON = os.environ.get(
    "DL4J_TEST_JAX_CACHE", "1") not in ("0", "false")
_JAX_CACHE_OFF_MODULES = ("test_compile_cache", "test_profiling")
if _JAX_CACHE_ON:
    compile_cache.enable(min_compile_time_secs=0.0)


@pytest.fixture(autouse=True, scope="module")
def _scoped_jax_compile_cache(request):
    name = os.path.basename(str(request.fspath))
    if not (_JAX_CACHE_ON and name.startswith(_JAX_CACHE_OFF_MODULES)):
        yield
        return
    from jax.experimental.compilation_cache import (
        compilation_cache as _jcc)
    cache_dir = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    _jcc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        _jcc.reset_cache()


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("need 8 virtual devices")
    return devs[:8]


@pytest.fixture
def rng():
    return np.random.RandomState(12345)


def pytest_configure(config):
    # hermeticity (ISSUE-12 satellite): a crashed or interrupted run —
    # exactly what --continue-on-collection-errors sessions tolerate —
    # can leave AOT compile-cache directories (and their staging
    # files) under the system temp dir; a later run must never load a
    # previous run's executables, so sweep them before collection.
    from deeplearning4j_tpu.serving.compile_cache import \
        sweep_stray_caches
    sweep_stray_caches(prefix="dl4j-aot-test-")
    config.addinivalue_line(
        "markers", "slow: multi-process / long-running tests")
    config.addinivalue_line(
        "markers",
        "multiproc: spawns real subprocess replicas (tier-1-eligible; "
        "every blocking wait is hard-bounded and fixtures kill child "
        "processes on teardown, so a wedged replica cannot hang the "
        "suite)")
