"""Force a virtual n-device CPU JAX platform in this process.

Shared by tests/conftest.py and __graft_entry__'s multichip dry run so
the sharded paths are exercised on hosts without (enough) chips.
"""

from __future__ import annotations

import os


def force_cpu_devices(n: int) -> None:
    """Make jax.devices() return n virtual CPU devices, nothing else.

    Must run before the first device query (backend instantiation) to
    take effect. The environment is set as well as the config so child
    processes inherit the same platform.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [
        f
        for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)
