"""XLA platform configuration — the repo's ONLY XLA_FLAGS writer.

XLA reads ``XLA_FLAGS`` exactly once, when the first backend initializes;
any write after that is silently dead.  The one flag this repository
needs is the fake host device count of the multi-device CPU test and
bench harness, so every entry point that sets it routes through this
module, which

* merges the flag into ``os.environ["XLA_FLAGS"]`` without clobbering
  caller-provided flags,
* is a guarded **no-op once jax is initialized** (returns ``False`` and
  warns instead of planting a flag that can never take effect), and
* is the single allowed ``XLA_FLAGS`` write site, enforced by
  ``tools/check_api.py`` (the ``set_platform`` idiom from SNIPPETS.md).

It also places JAX's persistent compilation cache
(:func:`enable_compile_cache`), so that repeated runs on a chip reuse
compiled programs.
"""
from __future__ import annotations

import os
import sys
import warnings
from typing import Optional

__all__ = [
    "jax_initialized", "host_device_count_flag", "set_platform",
    "set_host_device_count", "subprocess_env", "enable_compile_cache",
    "COMPILE_CACHE_DIR",
]

# Fixed in-repo cache location: the path is part of the cache key, so it
# must not move between runs (no temporary, per-process or dated dirs).
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def jax_initialized() -> bool:
    """True once any jax backend exists (flags can no longer take effect)."""
    mod = sys.modules.get("jax")
    if mod is None:
        return False
    from jax._src import xla_bridge
    return bool(xla_bridge._backends)


def host_device_count_flag(n: int) -> str:
    """The fake-device flag string (for building *subprocess* envs)."""
    return f"--xla_force_host_platform_device_count={int(n)}"


def _merge_flag(flag: str, env) -> str:
    """Append a flag to the env's XLA_FLAGS, replacing an older setting
    of the same ``--flag=`` stem."""
    stem = flag.split("=", 1)[0]
    kept = [f for f in env.get("XLA_FLAGS", "").split()
            if f.split("=", 1)[0] != stem]
    env["XLA_FLAGS"] = " ".join(kept + [flag])
    return env["XLA_FLAGS"]


def set_platform(platform: Optional[str] = None, *,
                 host_device_count: Optional[int] = None) -> bool:
    """Configure the XLA platform before jax initializes.

    ``platform`` ("cpu" | "tpu") sets ``jax_platform_name``;
    ``host_device_count`` plants the fake-CPU-device flag (the
    multi-device test/bench harness).  Returns ``True`` if the flag was
    planted while it can still take effect, ``False`` (with a warning,
    and without touching the environment) once jax is already
    initialized.
    """
    if jax_initialized():
        warnings.warn(
            "repro.runtime.platform.set_platform: jax is already "
            "initialized; XLA flags would be ignored (no-op)",
            RuntimeWarning, stacklevel=2)
        return False
    if host_device_count is not None:
        _merge_flag(host_device_count_flag(host_device_count), os.environ)
    if platform is not None:
        import jax
        jax.config.update("jax_platform_name", platform)
    return True


def set_host_device_count(n: int) -> bool:
    """Fake-device entry point for benches/selftests (pre-jax-init only)."""
    return set_platform(host_device_count=n)


def subprocess_env(host_device_count: Optional[int] = None, *,
                   base: Optional[dict] = None) -> dict:
    """A child-process environment with the fake-device flag merged in.

    Unlike :func:`set_platform` this never touches the current process
    (the child's jax is by definition uninitialized), so it needs no
    init guard — it is how ``benchmarks/run.py`` launches its
    fixed-device-count workers.
    """
    env = dict(os.environ if base is None else base)
    if host_device_count is not None:
        _merge_flag(host_device_count_flag(host_device_count), env)
    return env


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing.  Otherwise the cache goes to the fixed in-repo
    :data:`COMPILE_CACHE_DIR` (listed in ``.gitignore``), the same path
    on every call, so a later run finds what an earlier one compiled.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
