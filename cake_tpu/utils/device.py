"""Device bring-up shared by ``cli.main`` and ``chip_smoke.py``: where the
compile cache lives, and which device the process ended up on.

Nothing here selects a platform. JAX does that from ``JAX_PLATFORMS``; these
helpers make what it selected visible, so a run that did not use the chip
cannot pass for one that did.
"""

from __future__ import annotations

import os
from pathlib import Path

# One fixed directory inside the checkout (ignored by git): a second run from
# the same tree finds the first run's programs. Never derived from a pid, the
# time or tempfile — a cache that moves never hits.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def setup_compile_cache() -> str | None:
    """Turn JAX's persistent compilation cache on; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads the directory
    from the environment and this sets none. Otherwise the cache is
    ``COMPILE_CACHE_DIR``. Every program is kept (the default keeps only
    those that took a second to compile; a server start is hundreds of
    smaller ones).

    Not on the CPU (returns None): its programs compile in seconds, and
    XLA:CPU (jax 0.9.0) logs a machine-feature error for every cached
    program it loads."""
    import jax

    if cpu_requested():
        return None
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(COMPILE_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def describe_devices() -> dict:
    """The device as JAX reports it. Initialises the backend."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "jax_version": jax.__version__,
    }


def cpu_requested() -> bool:
    """True when the environment asks for the CPU by name. ``--cpu`` sets
    the same variable, so this is the one test for "the CPU on purpose"."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
