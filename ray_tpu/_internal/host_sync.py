"""The serving plane's one device->host read."""

import numpy as np


def host_sync(x) -> np.ndarray:
    """The ONE audited device->host materialization point on the serving
    hot path. Everything the engine and the KV-cache manager move to the
    host (sampled token ids, a shipment's blocks) funnels through here, so
    the RT009 lint rule can forbid ad-hoc
    ``jax.device_get``/``np.asarray(jnp...)``/``float(jnp...)`` round-trips
    everywhere else in engine/kvcache code (each one is a device sync that
    stalls the decode pipeline)."""
    return np.asarray(x)
