"""Accelerator managers.

Role-equivalent of the reference's accelerator plugin layer
(_private/accelerators/accelerator.py:18 AcceleratorManager ABC and
tpu.py:267 TPUAcceleratorManager): detect chips on the node, validate
topologies, derive pod types, export node labels and extra resources, and
control per-worker chip visibility.

TPU-first: this is where chips/hosts/slices become scheduling state. A node
that is part of a TPU slice advertises:
  resources: {"TPU": <chips>}  (+ {"TPU-<pod_type>-head": 1} on worker 0)
  labels:    ray.io/tpu-slice-name, ray.io/tpu-worker-id,
             ray.io/tpu-pod-type, ray.io/tpu-topology
(reference: constants.h:131-142 label keys; tpu.py:576 head resource,
 :642 labels)
"""

from __future__ import annotations

import abc
import glob
import os
from typing import Dict, List, Optional, Tuple, Type

# label keys (reference: common/constants.h:131-142)
TPU_SLICE_NAME_LABEL = "ray.io/tpu-slice-name"
TPU_WORKER_ID_LABEL = "ray.io/tpu-worker-id"
TPU_POD_TYPE_LABEL = "ray.io/tpu-pod-type"
TPU_TOPOLOGY_LABEL = "ray.io/tpu-topology"

# generation -> chips per host (reference: tpu.py topology tables :90)
_CHIPS_PER_HOST = {
    "v2": 4,
    "v3": 4,
    "v4": 4,
    "v5p": 4,
    "v5e": 8,  # v5litepod: up to 8 chips/host
    "v6e": 8,
}

# accelerator-type constants (reference: util/accelerators/accelerators.py:31-36)
TPU_V2 = "TPU-V2"
TPU_V3 = "TPU-V3"
TPU_V4 = "TPU-V4"
TPU_V5P = "TPU-V5P"
TPU_V5E = "TPU-V5E"
TPU_V6E = "TPU-V6E"


def pod_type_num_chips(pod_type: str) -> int:
    """'v5e-64' -> 64 chips (reference: tpu.py get_num_tpu_chips_from_pod_type)."""
    gen, _, count = pod_type.partition("-")
    if not count.isdigit():
        raise ValueError(f"malformed TPU pod type {pod_type!r}")
    n = int(count)
    if gen in ("v2", "v3"):
        # v2/v3 pod types count cores (2 per chip)
        return max(n // 2, 1)
    return n


def pod_type_generation(pod_type: str) -> str:
    return pod_type.partition("-")[0]


def chips_per_host(pod_type: str) -> int:
    gen = pod_type_generation(pod_type)
    if gen not in _CHIPS_PER_HOST:
        raise ValueError(f"unknown TPU generation {gen!r}")
    return min(_CHIPS_PER_HOST[gen], pod_type_num_chips(pod_type))


def pod_type_num_hosts(pod_type: str) -> int:
    return max(pod_type_num_chips(pod_type) // chips_per_host(pod_type), 1)


def infer_pod_type_from_topology(generation: str, topology: str) -> str:
    """'v4' + '2x2x2' -> 'v4-8' (chip product; v2/v3 counted in cores)."""
    dims = 1
    for part in topology.lower().split("x"):
        dims *= int(part)
    if generation in ("v2", "v3"):
        dims *= 2
    return f"{generation}-{dims}"


def tpu_head_resource(pod_type: str) -> str:
    """Extra resource injected on worker 0 of a multi-host slice so whole
    slices can be reserved by scheduling one head bundle (reference:
    tpu.py:576)."""
    return f"TPU-{pod_type}-head"


class AcceleratorManager(abc.ABC):
    """Accelerator plugin interface (reference: the AcceleratorManager ABC,
    _private/accelerators/accelerator.py:18, behind which the reference
    registers 8 accelerator families). A plugin answers: what resource name
    do I contribute, how many units does THIS node have, which labels and
    extra resources ride along, and how is a worker restricted to a subset.

    Register implementations with ``register_accelerator_manager`` —
    ``detect_node_accelerators()`` folds every registered plugin into the
    node's resources/labels at startup, so heterogeneous clusters (CPU-only
    rollout nodes next to TPU learner nodes) fall out of per-node detection
    rather than hardcoding."""

    @staticmethod
    @abc.abstractmethod
    def get_resource_name() -> str:
        """e.g. "TPU" / "GPU"."""

    @staticmethod
    @abc.abstractmethod
    def get_current_node_num_accelerators() -> int:
        """Units detected on this node (0 = plugin contributes nothing)."""

    @staticmethod
    def get_current_node_labels() -> Dict[str, str]:
        return {}

    @staticmethod
    def get_current_node_additional_resources() -> Dict[str, float]:
        """Extra resources beyond <name>: count (e.g. the slice-head
        reservation resource)."""
        return {}

    @staticmethod
    def get_visibility_env(instance_ids) -> Dict[str, str]:
        """Env vars restricting a worker process to specific units."""
        return {}


_ACCELERATOR_MANAGERS: List[Type[AcceleratorManager]] = []


def register_accelerator_manager(cls: Type[AcceleratorManager]) -> Type:
    if cls not in _ACCELERATOR_MANAGERS:
        _ACCELERATOR_MANAGERS.append(cls)
    return cls


def all_accelerator_managers() -> List[Type[AcceleratorManager]]:
    return list(_ACCELERATOR_MANAGERS)


def detect_node_accelerators(
    exclude: Optional[set] = None,
) -> Tuple[Dict[str, float], Dict[str, str]]:
    """Fold every registered plugin into (resources, labels) for this node.
    ``exclude`` suppresses plugins by resource name ENTIRELY — count,
    additional resources, and labels: a user who passed num_tpus=0 opted
    out of being a TPU node; leaking the slice-head resource/labels anyway
    would make reserve_tpu_slice pick a chipless head."""
    resources: Dict[str, float] = {}
    labels: Dict[str, str] = {}
    for manager in _ACCELERATOR_MANAGERS:
        name = manager.get_resource_name()
        if exclude and name in exclude:
            continue
        # the whole plugin is fault-isolated: a misbehaving third-party
        # detection (count, extras, OR labels) must not abort init()
        try:
            count = manager.get_current_node_num_accelerators()
            if count <= 0:
                continue
            # stage all three contributions; merge only once the whole
            # plugin succeeded (a label fetch failing after the head
            # resource merged would otherwise leave a chipless slice head)
            extra = dict(manager.get_current_node_additional_resources())
            plugin_labels = dict(manager.get_current_node_labels())
        except Exception:
            continue
        resources[name] = float(count)
        resources.update(extra)
        labels.update(plugin_labels)
    return resources, labels


@register_accelerator_manager
class TpuAcceleratorManager(AcceleratorManager):
    """Detection for the current node."""

    @staticmethod
    def get_resource_name() -> str:
        return "TPU"

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        return TpuAcceleratorManager.detect_num_chips()

    @staticmethod
    def get_current_node_labels() -> Dict[str, str]:
        return TpuAcceleratorManager.current_node_identity()

    @staticmethod
    def get_current_node_additional_resources() -> Dict[str, float]:
        labels = TpuAcceleratorManager.current_node_identity()
        pod_type = labels.get(TPU_POD_TYPE_LABEL)
        if pod_type and labels.get(TPU_WORKER_ID_LABEL, "0") == "0":
            return {tpu_head_resource(pod_type): 1.0}
        return {}

    @staticmethod
    def detect_num_chips() -> int:
        """Chips attached to this node. The device files are what is
        attached; the runtime's ``TPU_CHIPS_PER_HOST_BOUNDS`` describes the
        host the VM was cut from (a one-chip machine of a 2x2 host still
        says "2,2,1") and only counts where no device file exists."""
        chips = count_chip_devices()
        if chips:
            return chips
        env = os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS")
        if not env:
            return 0
        total = 1
        for part in env.split(","):
            total *= int(part)
        return total

    @staticmethod
    def current_node_identity() -> Dict[str, str]:
        """Labels for this node from the TPU VM metadata environment
        (reference: tpu.py reading TPU_* env vars set by the TPU runtime)."""
        labels = {}
        slice_name = os.environ.get("TPU_NAME") or os.environ.get(
            "TPU_WORKER_HOSTNAMES", ""
        ).split(",")[0]
        if slice_name:
            labels[TPU_SLICE_NAME_LABEL] = slice_name
        worker_id = os.environ.get("TPU_WORKER_ID")
        if worker_id is not None:
            labels[TPU_WORKER_ID_LABEL] = worker_id
        accel_type = os.environ.get("TPU_ACCELERATOR_TYPE")  # e.g. "v5litepod-16"
        if accel_type:
            labels[TPU_POD_TYPE_LABEL] = accel_type.replace("litepod", "5e").replace(
                "v55e", "v5e"
            )
        topology = os.environ.get("TPU_TOPOLOGY")
        if topology:
            labels[TPU_TOPOLOGY_LABEL] = topology
        return labels



def count_chip_devices() -> int:
    """TPU device files on this node: ``/dev/accel*``, or numbered vfio
    groups (``/dev/vfio/vfio`` is the always-present control node, not a
    chip)."""
    return len(glob.glob("/dev/accel*")) or len(glob.glob("/dev/vfio/[0-9]*"))


# chip ids the raylet granted this worker; what get_tpu_ids() reads
GRANTED_CHIPS_ENV = "RAY_TPU_GRANTED_CHIPS"


def set_visible_chips(instance_ids, node_chips: int) -> Dict[str, str]:
    """Env vars that make a worker process the owner of exactly the granted
    chips of a ``node_chips``-chip node (reference: tpu.py
    set_current_process_visible_accelerator_ids). A worker granted the
    whole host keeps the host's own settings. One chip is carved out with
    single-chip, single-host bounds, which is also what lets several such
    processes load the TPU runtime side by side (four of them each own a
    chip of a v5e 2x2 host). Both spellings of the bounds are set: libtpu
    reads ``*_PROCESS_BOUNDS`` first, and the ``*_HOST_BOUNDS`` a TPU VM
    image exports would otherwise still describe the whole host. Other
    sizes only name their chips: the bounds that fit depend on where the
    chips sit in the host's topology (a 2-chip carve-out with the
    reference's "1,2,1" did not initialise on that host)."""
    ids = ",".join(str(int(i)) for i in instance_ids)
    env = {GRANTED_CHIPS_ENV: ids}
    if len(instance_ids) >= node_chips:
        return env
    env["TPU_VISIBLE_CHIPS"] = ids
    if len(instance_ids) == 1:
        env.update({
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_HOST_BOUNDS": "1,1,1",
        })
    return env


@register_accelerator_manager
class GpuAcceleratorManager(AcceleratorManager):
    """GPU count plugin (reference: nvidia_gpu.py behind the same ABC):
    CUDA_VISIBLE_DEVICES wins when set, else /proc/driver/nvidia/gpus.
    Deliberately count-only — this framework's compute path is TPU; the
    plugin exists so heterogeneous clusters (GPU rollout nodes, CPU-only
    nodes, TPU learners) model every node's resources correctly."""

    @staticmethod
    def get_resource_name() -> str:
        return "GPU"

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        env = os.environ.get("CUDA_VISIBLE_DEVICES")
        if env is not None:
            # "-1" is the standard disable-GPUs convention; count only
            # non-negative device tokens
            return len([
                d for d in env.split(",")
                if d.strip() and not d.strip().startswith("-")
            ])
        return len(glob.glob("/proc/driver/nvidia/gpus/*"))

    @staticmethod
    def get_visibility_env(instance_ids) -> Dict[str, str]:
        # logical instance ids remap through a pre-existing parent mask:
        # with CUDA_VISIBLE_DEVICES="2,3" the node's logical GPUs 0,1 ARE
        # physical 2,3 — emitting raw logical ids would grant devices the
        # parent explicitly excluded
        parent = os.environ.get("CUDA_VISIBLE_DEVICES")
        if parent:
            physical = [
                d.strip() for d in parent.split(",")
                if d.strip() and not d.strip().startswith("-")
            ]
            # an id past the parent mask is an upstream scheduling bug;
            # drop it rather than widen the mask to a device the parent
            # explicitly excluded
            mapped = [
                physical[int(i)] for i in instance_ids
                if int(i) < len(physical)
            ]
        else:
            mapped = [str(i) for i in instance_ids]
        return {"CUDA_VISIBLE_DEVICES": ",".join(mapped)}
