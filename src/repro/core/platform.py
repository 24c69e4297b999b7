"""What the platform decides for the whole program, in one place.

  * ``pallas_interpret`` — Pallas kernels run compiled on a TPU and
    interpreted everywhere else (the CPU tests run the same kernel bodies
    through the interpreter). No caller chooses this.
  * ``flat_walk`` — the ``flat-jax`` program walks level-packed tables by
    selects on a TPU, which serializes per-element gathers, and gathers
    everywhere else, where they are cheap (``core/forest_jax.py``).
  * ``enable_compile_cache`` — JAX's persistent compilation cache. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing is set
    here; otherwise the cache lives at ``.jax_cache/`` in the checkout. The
    path is part of the cache key, so it is fixed, never a temp name.

None of them touches a JAX backend until called, so a process that only
imports this module leaves the device free for a child process.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def pallas_interpret(device=None) -> bool:
    """True unless ``device`` (default: the first JAX device) is a TPU."""
    device = device if device is not None else jax.devices()[0]
    return device.platform != "tpu"


def flat_walk(device=None) -> str:
    """``"levels"`` if ``device`` (default: the first JAX device) is a TPU,
    else ``"gathers"``."""
    device = device if device is not None else jax.devices()[0]
    return "levels" if device.platform == "tpu" else "gathers"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory. Call
    before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
