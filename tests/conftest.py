"""Test harness: force an 8-device virtual CPU mesh BEFORE jax imports.

Multi-chip sharding is validated on host-platform virtual devices
(no TPU needed for the test suite), per the framework's test strategy:
N in-process nodes + loopback transports for distributed tests.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from spacedrive_tpu.utils.jaxenv import force_cpu_devices  # noqa: E402

force_cpu_devices(8)

# Persistent XLA compile cache for the CPU-mesh programs: the slow
# suite's device-shape matrix costs ~1 h of single-core compiles COLD,
# and milliseconds warm. Same rule as production (ops/__init__.py):
# JAX_COMPILATION_CACHE_DIR places it, else <checkout>/.jax_cache —
# subprocess tests inherit the variable and derive the same default.
from spacedrive_tpu.ops import configure_compilation_cache  # noqa: E402

configure_compilation_cache()

# Preload sklearn's native stack (scipy/openmp) BEFORE test modules pull
# in torch/cv2/av during collection. train.digits_demo_dataset imports
# sklearn lazily at call time; with the full suite's native libraries
# already resident that late dlopen segfaults (static-TLS exhaustion).
# Loading it first — while TLS slots are still free — is benign.
try:  # pragma: no cover - environment-dependent
    import sklearn.datasets  # noqa: E402,F401
except Exception:
    pass

# Minimal async-test support (pytest-asyncio isn't in the image):
# coroutine test functions run under asyncio.run with a fresh loop.
import asyncio  # noqa: E402
import inspect  # noqa: E402


def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(fn(**kwargs))
        return True
    return None


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: async test (built-in runner)")
    config.addinivalue_line(
        "markers", "slow: long-running (training / full device-shape matrix); "
        "deselected by default, run with -m slow"
    )
