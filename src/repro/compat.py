"""Backend compatibility: where Pallas kernels cannot be compiled.

Kernels selected via ``attention_impl="pallas"`` /
``quantize_impl="pallas"`` compile on a TPU/GPU backend. XLA's CPU
backend only runs Pallas in interpret mode, so on CPU those paths run
interpreted — same numerics, no fused-kernel performance.
"""
from __future__ import annotations

import logging
from typing import Set

import jax

logger = logging.getLogger(__name__)

# Backends whose Pallas pipeline can *compile* pallas_call.
PALLAS_COMPILED_BACKENDS = ("tpu", "gpu", "cuda", "rocm")

_warned_pallas_fallbacks: Set[str] = set()


def pallas_interpret_fallback(what: str) -> bool:
    """True when Pallas kernels must run interpreted on this backend.

    The fallback is LOUD, not silent: the first call per ``what`` logs a
    warning that the requested kernel path still runs (same numerics,
    the parity tests stay meaningful) but without the fused-kernel
    performance, so a serving deployment on the wrong backend cannot
    quietly think it is getting the in-kernel block gather. Mirrors the
    ``quantize_impl`` precedent: the knob keeps meaning "pallas", only
    the execution mode degrades.
    """
    if jax.default_backend() in PALLAS_COMPILED_BACKENDS:
        return False
    if what not in _warned_pallas_fallbacks:
        _warned_pallas_fallbacks.add(what)
        logger.warning(
            "%s: backend %r cannot compile Pallas kernels; running the "
            "pallas path in interpret mode (numerics preserved, fused-"
            "kernel performance lost). Deploy on a TPU/GPU backend for "
            "the compiled kernel.", what, jax.default_backend())
    return True
