"""Backend, chip and compile-cache placement.

The device is whatever ``jax.devices()`` gives in the process itself:
nothing here probes it in a child or falls back to the CPU behind the
caller's back.  ``JAX_PLATFORMS=cpu`` selects the CPU backend (the
tests do so); :func:`force_cpu_platform` does the same from code and
can add virtual host devices for mesh rehearsals.

A TPU chip belongs to one process at a time.  A process that has
initialized the TPU backend holds every chip it can see until it
exits, so a child that needs a chip must be started before the parent
touches the device, or be given chips of its own through libtpu's
per-process visibility variables (:func:`chip_env`).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Optional

#: the persistent compile cache's home when ``JAX_COMPILATION_CACHE_DIR``
#: is unset: a fixed, gitignored directory in the checkout (the path is
#: part of what makes a cache hit, so it must not move between runs)
CACHE_DIRNAME = ".jax_cache"

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: first libtpu process port handed to fleet members; member i listens
#: on ``_TPU_BASE_PORT + i`` so co-resident processes never collide
_TPU_BASE_PORT = 8476


def force_cpu_platform(n_devices: Optional[int] = None) -> None:
    """Force jax onto the CPU backend, optionally with ``n_devices``
    virtual host devices.  Must run before the first jax backend use
    (device queries, array ops); importing jax beforehand is fine."""
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        want = f"--xla_force_host_platform_device_count={n_devices}"
        if "xla_force_host_platform_device_count" in flags:
            # rewrite a stale value (e.g. =1 inherited from another harness)
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", want, flags
            )
        else:
            flags = (flags + " " + want).strip()
        os.environ["XLA_FLAGS"] = flags

    import jax

    jax.config.update("jax_platforms", "cpu")


def configure_compile_cache() -> str:
    """Place jax's persistent compilation cache and return its path.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    nothing is set here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``, the same path on every call and every
    run.  The CLI, ``serve`` and ``chip_smoke.py`` call this once at
    start-up."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_REPO_ROOT, CACHE_DIRNAME)
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    return path


def holds_accelerator() -> bool:
    """True when this process has initialized a non-CPU jax backend,
    and so holds the chip: a child started now cannot have it."""
    import sys

    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return False
    import jax

    return jax.default_backend() != "cpu"


def local_tpu_chips() -> int:
    """TPU chips this process may open, counted from their device files
    (``/dev/vfio/<n>`` on v5e and later, ``/dev/accel<n>`` before) —
    without loading libtpu, so a supervisor can size a fleet without
    taking the chips its members need.  PCI ids in sysfs would count
    chips of the host that this container cannot open."""
    return len(glob.glob("/dev/vfio/[0-9]*") + glob.glob("/dev/accel[0-9]*"))


def chip_env(i: int) -> Dict[str, str]:
    """libtpu's per-process visibility variables giving a process chip
    ``i`` alone: one-chip process bounds, that chip visible, and a
    process port of its own.  With bounds that are a subset of the
    host, libtpu lets several processes load it, one chip each."""
    return {
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_VISIBLE_CHIPS": str(i),
        "TPU_PROCESS_PORT": str(_TPU_BASE_PORT + i),
    }
