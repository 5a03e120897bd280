"""The one timing helper: host clock around work that ends on the device."""

from __future__ import annotations

import time


def timed(fn, *args):
    """(result, seconds) of ``fn(*args)``, waiting for the device: JAX returns
    before the device finishes, so a timing without ``block_until_ready``
    measures the enqueue."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0
