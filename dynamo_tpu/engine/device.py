"""Which device this engine is on, and where its compiled programs go.

Everything here exists so that a run without a chip, or without its
kernels, cannot look like a run with them: the engine refuses a backend
nobody asked for, says in one line what it came up on, and keeps its
compile cache where the next process will find it.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

import jax

from ..ops.attention import pallas_interpret
from ..telemetry.flight import mark_process

# <checkout>/.jax_cache (in .gitignore). The directory is part of the
# cache key's environment: a temp name, pid or timestamp never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; call before the first
    backend touch. Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache
    was placed from outside (jax reads the variable itself) and no other
    directory is set in code; otherwise it is ``<checkout>/.jax_cache``.
    Returns the directory in use.

    Every compile is cached, however short: with jax's default 1 s
    floor a program that compiles in about a second is written on one
    start and not on the next, and a warm start that adds entries cannot
    be told from a cold one."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def check_serving_device() -> None:
    """Refuse to build an engine on a backend nobody asked for.

    jax falls back to the CPU with a warning when it finds no
    accelerator; a server that then starts and answers is a chip-less
    run that looks like success. A non-TPU backend is accepted only when
    ``JAX_PLATFORMS`` asks for the CPU explicitly — names it first, which
    is what makes it jax's default backend (tests, dry runs).

    Its first return is the start-up timeline's ``backend`` mark: jax is
    imported and the runtime of the device is up."""
    backend = jax.default_backend()
    asked = os.environ.get("JAX_PLATFORMS", "").lower().split(",")[0].strip()
    if backend != "tpu" and asked != "cpu":
        raise RuntimeError(
            f"the jax engine came up on backend {backend!r} with no TPU "
            "visible; set JAX_PLATFORMS=cpu to run on the CPU on purpose"
        )
    pallas_interpret()  # raises when set on a TPU backend
    mark_process("backend")


def device_report(mesh) -> Dict:
    """Platform, device kind, count, mesh axes, per-device
    ``bytes_in_use`` (None where the backend reports no memory stats)
    and the compile cache directory in effect."""
    devices = list(mesh.devices.flat)

    def in_use(d) -> Optional[int]:
        stats = d.memory_stats()
        return stats.get("bytes_in_use") if stats else None

    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(jax.devices()),
        "mesh": {a: n for a, n in mesh.shape.items() if n > 1},
        "bytes_in_use": [in_use(d) for d in devices],
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    }
