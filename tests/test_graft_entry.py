"""Driver-contract regression tests for __graft_entry__.py.

dryrun_multichip must bootstrap its own virtual CPU backend: the driver
calls it from a fresh interpreter that no conftest has prepared. The
bootstrap is public-API only (jax_platforms + jax_num_cpu_devices, set
before the backend starts) and refuses, instead of tearing a backend
down, when one that will not do is already live.
"""
import os
import subprocess
import sys

import pytest


@pytest.mark.slow
def test_dryrun_multichip_subprocess_ambient_env():
    """dryrun_multichip(8) must succeed from a fresh interpreter with NO
    conftest bootstrap — exactly how the driver calls it — and with no
    XLA_FLAGS to size the CPU backend for it."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "-c",
         "from __graft_entry__ import dryrun_multichip; dryrun_multichip(8)"],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-2000:]
    # all four composite-parallel configs must report OK
    assert proc.stdout.count("OK") >= 4, proc.stdout


def test_force_virtual_cpu_mesh_idempotent_on_cpu():
    """Under the test env (8 CPU devices already live) the bootstrap must
    be a no-op — no backend reset, same client before and after."""
    import jax

    from __graft_entry__ import _force_virtual_cpu_mesh

    before = jax.devices()
    _force_virtual_cpu_mesh(8)
    after = jax.devices()
    assert before == after and len(after) >= 8


def test_force_virtual_cpu_mesh_refuses_a_live_backend_too_small():
    """A live backend is never torn down: asking for more devices than
    it has raises, and leaves it as it was."""
    import jax

    from __graft_entry__ import _force_virtual_cpu_mesh

    before = jax.devices()
    with pytest.raises(RuntimeError, match="already live"):
        _force_virtual_cpu_mesh(len(before) + 1)
    assert jax.devices() == before


def test_bootstrap_uses_public_api_only():
    """No private JAX module, no backend-factory surgery, no cache
    clearing in the entry point (the old installation's recipe)."""
    import __graft_entry__

    src = open(__graft_entry__.__file__).read()
    for needle in ("jax._src", "_backend_factories", "_clear_backends",
                   "cache_clear"):
        assert needle not in src, needle
