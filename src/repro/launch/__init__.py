"""Launch entry points: mesh construction, dry-run, train/serve drivers."""

from __future__ import annotations

import os


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one place and
    return that directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is set.  Otherwise the cache is ``<repo>/.jax_cache``:
    a fixed path (the path is part of the cache key, so a per-run
    directory would never hit), listed in ``.gitignore``.  Call after
    any ``XLA_FLAGS`` set-up: this imports jax.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    from repro import REPO_ROOT
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def force_host_device_count(n: int) -> None:
    """Fake ``n`` host devices for a CPU-container mesh run.

    Appends ``--xla_force_host_platform_device_count=n`` to
    ``XLA_FLAGS``, preserving whatever flags are already set.  MUST run
    before jax first initialises (device count locks at first init) —
    the drivers call it before their lazy ``import jax``; this module
    itself stays jax-import-free for the same reason.  No-op for
    ``n <= 1``.
    """
    if n <= 1:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    flag = f"--xla_force_host_platform_device_count={n}"
    if flag not in flags.split():
        os.environ["XLA_FLAGS"] = f"{flags} {flag}".strip()
