"""Open-loop traffic generation for serve-plane scaling experiments.

Compose an arrival process (``PoissonArrivals`` / ``BurstyRampArrivals``)
with a workload (``RequestClass`` mix over ``ZipfPrefixes``) into a
replayable ``Trace``, then drive it open loop with ``LoadGenerator``
against a serve handle, HTTP proxy, or plain callable. The bundled
ramp-burst-decay trace (``bundled_trace()``) is what the closed-loop
autoscaling test replays (``tests/test_autoscale_serve.py``).
"""

from .arrival import BurstyRampArrivals, PoissonArrivals
from .runner import (
    CallableTarget,
    HandleTarget,
    HTTPTarget,
    LoadGenerator,
    LoadResult,
    RequestResult,
)
from .trace import Trace, TraceRecord, bundled_trace
from .workload import (
    RequestClass,
    ZipfPrefixes,
    echo_trace,
    long_prefill_mix,
    multi_tenant_mix,
    synthesize,
)

__all__ = [
    "BurstyRampArrivals",
    "CallableTarget",
    "HTTPTarget",
    "HandleTarget",
    "LoadGenerator",
    "LoadResult",
    "PoissonArrivals",
    "RequestClass",
    "RequestResult",
    "Trace",
    "TraceRecord",
    "ZipfPrefixes",
    "bundled_trace",
    "echo_trace",
    "long_prefill_mix",
    "multi_tenant_mix",
    "synthesize",
]
