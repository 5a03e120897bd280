"""ray_tpu: a TPU-native distributed computing framework.

A brand-new system with the capabilities of Ray (tasks, actors, objects with
distributed ownership, placement groups, collective communication, Train/Data/
Serve/Tune libraries) designed TPU-first: chips, hosts, and ICI-connected
slices are first-class scheduling primitives, the tensor plane is XLA
collectives over ICI, and trainers compile to pjit/GSPMD.
"""

from .actor import method
from .api import (
    available_resources,
    cancel,
    cluster_resources,
    get,
    get_actor,
    init,
    is_initialized,
    kill,
    nodes,
    put,
    remote,
    shutdown,
    wait,
)
from ._internal.ids import (
    ActorID,
    JobID,
    NodeID,
    ObjectID,
    PlacementGroupID,
    TaskID,
    UniqueID,
    WorkerID,
)
from .object_ref import ObjectRef, ObjectRefGenerator
from .runtime_context import get_runtime_context
from . import exceptions

__version__ = "0.5.0"


def get_tpu_ids():
    """Chip indices the raylet granted the current worker (reference role:
    ray.get_gpu_ids, _private/worker.py:1170, for the TPU resource). The
    worker pool starts a chip-owning worker with them in its environment;
    every other process gets ``[]``."""
    import os

    from ._internal.accelerators import GRANTED_CHIPS_ENV

    raw = os.environ.get(GRANTED_CHIPS_ENV, "")
    return [int(x) for x in raw.split(",") if x.strip().isdigit()]


def get_gpu_ids():
    """GPU analogue kept for API familiarity; this framework schedules TPU
    chips (see get_tpu_ids)."""
    import os

    raw = os.environ.get("CUDA_VISIBLE_DEVICES", "")
    return [int(x) for x in raw.split(",") if x.strip().isdigit()]


def timeline(filename=None):
    """Chrome-trace export of the cluster task timeline (reference:
    ray.timeline)."""
    from .util.tracing import timeline as _timeline

    return _timeline(filename)


# Lazy subpackages (PEP 562): `import ray_tpu; ray_tpu.data...` works like
# the reference's eager subpackage attributes without importing the heavy
# jax-dependent libraries at top-level import time.
_LAZY_SUBMODULES = (
    "autoscaler", "client", "collective", "dag", "data", "experimental",
    "kvcache", "llm", "models", "ops", "parallel", "rllib", "serve",
    "testing", "train", "tune", "util", "cross_language",
)


def __getattr__(name):
    if name in _LAZY_SUBMODULES:
        import importlib

        module = importlib.import_module(f".{name}", __name__)
        globals()[name] = module
        return module
    raise AttributeError(f"module 'ray_tpu' has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_LAZY_SUBMODULES))

__all__ = [
    "init",
    "shutdown",
    "is_initialized",
    "remote",
    "method",
    "get",
    "put",
    "wait",
    "kill",
    "cancel",
    "get_actor",
    "nodes",
    "cluster_resources",
    "available_resources",
    "ObjectRef",
    "ObjectRefGenerator",
    "get_runtime_context",
    "get_tpu_ids",
    "get_gpu_ids",
    "timeline",
    "ActorID",
    "TaskID",
    "ObjectID",
    "NodeID",
    "JobID",
    "WorkerID",
    "PlacementGroupID",
    "UniqueID",
    "exceptions",
    "__version__",
]
