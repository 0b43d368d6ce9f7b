"""Dependency gate for the control-plane code.

The SDP solver backends and the fused rounding path gate their device
paths on ``jax_available`` instead of importing JAX eagerly, so the numpy
float64 reference paths keep working in a JAX-less environment.
"""

from __future__ import annotations

import functools


@functools.lru_cache(maxsize=1)
def jax_available() -> bool:
    """True when JAX imports cleanly."""
    try:
        import jax  # noqa: F401
    except Exception:
        return False
    return True
