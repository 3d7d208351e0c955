"""Process-level JAX setup shared by the entry points.

* :func:`use_compile_cache` points JAX's persistent compilation cache at
  ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it itself, so
  nothing is set here), else at the fixed ``<repo>/.jax_cache``: the cache
  key includes the path, so a directory that moves never hits.
* :func:`refuse_child_processes_on_accelerator` guards the code paths that
  start child processes which each need the device.  A chip serves one
  process at a time, and a parent that has touched JAX already holds it, so
  such a child would fail or hang; on an accelerator these paths refuse.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

__all__ = ["refuse_child_processes_on_accelerator", "use_compile_cache"]


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def refuse_child_processes_on_accelerator(what: str) -> None:
    """Raise unless this process runs JAX on the CPU backend."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "cpu":
        raise RuntimeError(
            f"{what} starts child processes that each need the {platform}, "
            f"which this process holds; a chip serves one process at a "
            f"time. Run it in one process, or on the CPU backend.")
