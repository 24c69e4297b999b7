"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel ships as a triple: ``kernel.py`` (pl.pallas_call + BlockSpec),
``ops.py`` (public wrapper with padding), ``ref.py`` (pure-jnp oracle used
by the allclose test sweeps). Kernels run compiled on a TPU and interpreted
elsewhere; ``core.platform.pallas_interpret`` alone decides which.

  forest/    level-synchronous random-forest inference (the paper's
             prediction latency hot spot, §7.1 — ms -> us)
  attention/ flash attention (prefill hot spot)
  mamba/     chunked SSD scan (Mamba2/zamba2 + long-context)
"""
from . import attention, forest, mamba  # noqa: F401
