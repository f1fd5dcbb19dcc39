"""The compile-cache placement rule (ops.configure_compilation_cache):
JAX_COMPILATION_CACHE_DIR places the cache from outside and the program
sets no directory in code; unset, the cache is <checkout>/.jax_cache.

JAX reads the variable once at import, so each case runs in a fresh
interpreter.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = (
    "import jax\n"
    "seen = []\n"
    "real = jax.config.update\n"
    "def spy(name, value):\n"
    "    seen.append(name)\n"
    "    real(name, value)\n"
    "jax.config.update = spy\n"
    "from spacedrive_tpu.ops import configure_compilation_cache\n"
    "print(configure_compilation_cache())\n"
    "print(configure_compilation_cache())\n"  # idempotent
    "print(jax.config.jax_compilation_cache_dir)\n"
    "print(','.join(seen))\n"
)


def _run(env_dir: str | None) -> list[str]:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", _CHILD], env=env, cwd="/",
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()


def test_cache_dir_from_environment_is_left_alone(tmp_path):
    placed = str(tmp_path / "placed")
    first, second, in_effect, updates = _run(placed)
    assert first == second == in_effect == placed
    # only the threshold is set in code — never a directory
    assert updates == "jax_persistent_cache_min_compile_time_secs"
    assert not os.path.exists(placed)  # JAX makes it on first write


def test_cache_dir_defaults_to_the_checkout():
    first, second, in_effect, updates = _run(None)
    want = os.path.join(REPO, ".jax_cache")
    assert first == second == in_effect == want
    assert updates.split(",") == [
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
    ]
