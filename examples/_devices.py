"""Shared by the multi-device examples: they run on the devices JAX
finds, and say what they need when there are too few."""
import sys


def require_devices(n: int) -> None:
    """Exit, saying how to get them, unless JAX sees ``n`` devices."""
    import jax
    devs = jax.devices()
    if len(devs) >= n:
        return
    sys.exit(
        f"this example builds a {n}-device mesh and JAX sees {len(devs)} "
        f"{devs[0].platform} device(s). On a host with no accelerator run "
        f"it on virtual CPU devices:\n  JAX_PLATFORMS=cpu XLA_FLAGS="
        f"--xla_force_host_platform_device_count={n} python {sys.argv[0]}"
        " ...\nor ask for a smaller mesh with the example's own options.")
