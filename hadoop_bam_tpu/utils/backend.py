"""Which JAX platform this process runs on, and where its compile cache is.

Two rules shared by every entry point that touches a device (the ``hbam``
device verbs, ``chip_smoke.py``):

- **The platform is what JAX gives.**  No code path switches platform.  A
  process that lands on the CPU without having asked for it — JAX falls
  back to the CPU on its own when it finds no accelerator — is refused:
  a CPU run has to be requested through ``JAX_PLATFORMS=cpu``.
- **The compile cache is placeable from outside.**  When
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
  directory is set in code.  When it is not, one fixed path inside the
  checkout is used (the path is part of the cache key's lookup, so a
  directory that moves never hits).
"""
from __future__ import annotations

import os
from typing import Optional

from hadoop_bam_tpu.utils.errors import PlanError

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def cpu_requested() -> bool:
    """True when this process was told to run on the CPU
    (``JAX_PLATFORMS`` — or the ``jax_platforms`` config it seeds —
    names cpu)."""
    import jax

    wanted = jax.config.jax_platforms or ""
    return "cpu" in [p.strip().lower() for p in wanted.split(",")]


def require_backend() -> str:
    """The platform of this process's default JAX devices.  Raises
    ``PlanError`` when that is the CPU and nobody asked for it."""
    import jax

    platform = jax.devices()[0].platform
    if platform == "cpu" and not cpu_requested():
        raise PlanError(
            "JAX found no accelerator and fell back to the CPU; set "
            "JAX_PLATFORMS=cpu to run on the CPU on purpose")
    return platform


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache under the placement
    rule above; returns the directory in effect."""
    import jax

    # cache every program, small ones included: a CLI verb is a fresh
    # process each time and re-pays every compile the cache lacks
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from_env: Optional[str] = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
