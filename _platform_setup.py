"""Platform setup that must happen before any JAX backend exists.

Lives at the repo root (NOT inside paddle_tpu/) on purpose: importing the
paddle_tpu package initializes the backend as a side effect of building the
eager op surface, and both helpers here only work before that.

- `force_cpu_platform(n)`: the virtual n-device CPU mesh the tests and the
  multi-chip dry run use (the reference's fake-device rig,
  `test/custom_runtime/test_custom_cpu_plugin.py:27-47`: a CPU masquerading
  as the accelerator drives the same code paths).
- `configure_compile_cache()`: where the persistent XLA compile cache lives.
"""

import os
import re

__all__ = ["force_cpu_platform", "configure_compile_cache", "CACHE_DIR"]

# compile cache + kernel tuning cache. A FIXED path inside the checkout:
# the directory is part of the compile-cache key's environment, so a
# tempfile/pid/timestamp path would never hit.
CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".jax_cache")


def force_cpu_platform(n_devices: int) -> None:
    """Force a virtual n-device CPU platform. Must run before the JAX backend
    initializes — afterwards the flags are a no-op (callers should check
    ``jax.devices('cpu')`` and error with guidance)."""
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        flags += f" --xla_force_host_platform_device_count={n_devices}"
    elif int(m.group(1)) < n_devices:
        flags = flags.replace(
            m.group(0), f"--xla_force_host_platform_device_count={n_devices}")
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    # jax reads JAX_PLATFORMS once, at import; a caller that imported jax
    # first (a driver calling dryrun_multichip after entry()) needs the
    # config itself moved
    jax.config.update("jax_platforms", "cpu")


def configure_compile_cache():
    """Point JAX's persistent compilation cache somewhere that survives the
    process. Where `JAX_COMPILATION_CACHE_DIR` is set, jax reads it itself
    and nothing is set in code; otherwise the cache goes to `CACHE_DIR`.
    Returns the directory in use. Call before the first compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
