"""Which JAX platform a process is on, and who may own the chip.

A TPU chip belongs to one process at a time: the first process whose JAX
backend initialises on it holds it until exit, and any other process that
tries fails or hangs. So the runtime decides ownership (reference role:
TPUAcceleratorManager + the worker pool's per-accelerator workers): a
worker leased TPU instances is the chip's owner, and every other process
of the cluster — driver, in-process GCS/raylet, serve controller, proxies,
CPU workers — is pinned to the CPU platform before it can touch a backend.

Pallas kernels compile natively on platform ``tpu`` and run interpreted on
platform ``cpu`` (tests, rehearsals). Anything else is an error, never a
silent fallback.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, List, Optional

# what JAX_PLATFORMS held before this process pinned itself to the CPU;
# chip-owning workers are spawned with it restored (None = was unset)
_inherited_platforms: Optional[str] = None
_pinned = False


def backend_initialized() -> bool:
    """True iff this process already holds a JAX backend. Importing jax is
    not initialising it — only the latter claims the chip. Imports nothing
    itself: callers include background threads (the metrics pusher), and
    an import racing the main thread's own ``import jax`` can hand either
    of them a half-initialised module."""
    bridge = sys.modules.get("jax._src.xla_bridge")
    return bool(getattr(bridge, "_backends", None))


def pin_cpu_platform() -> None:
    """Keep this process off the accelerator: JAX_PLATFORMS=cpu for any
    later jax import, and the live config when jax is already imported. A
    process whose backend is already initialised keeps what it has (it is
    too late, and a driver that owns the chip on purpose says so by
    touching jax before ``init()``)."""
    global _inherited_platforms, _pinned
    if _pinned or backend_initialized():
        return
    _inherited_platforms = os.environ.get("JAX_PLATFORMS")
    _pinned = True
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "jax" in sys.modules:
        import jax

        jax.config.update("jax_platforms", "cpu")


def chip_worker_platforms() -> Optional[str]:
    """JAX_PLATFORMS for a worker that owns chips: what this process
    inherited before pinning itself (tests force ``cpu`` and keep it;
    unset lets JAX pick the TPU)."""
    return _inherited_platforms if _pinned else os.environ.get("JAX_PLATFORMS")


def is_tpu_backend() -> bool:
    """True iff the default JAX backend is a TPU. A backend that cannot be
    reached raises: "could not reach the device" is not "no device"."""
    import jax

    return jax.default_backend() == "tpu"


def decode_step_compiler_options() -> Dict[str, str]:
    """Compiler options of the engine's decode step: on a TPU, the
    memory-bound loop optimiser off; none elsewhere (another backend
    refuses a TPU option).

    The layers of a decode step are an unrolled loop to the TPU compiler,
    and that optimiser plans its prefetches into on-chip memory for them:
    it keeps the widest matrix of a layer's MLP in HBM and fetches the
    attention weights under the MLP's own matmuls, which are bound by the
    same HBM, so nothing is hidden. Left to its general pass the compiler
    fetches that MLP matrix while the attention kernel runs, which is
    bound by its grid steps and leaves the HBM free: 1.1 ms of a 9.8 ms
    step at Mistral-7B widths (0.4 by itself, the rest once the cache
    write shares the attention kernel's lengths operand,
    ops/kv_row_write.py), nothing at OLMoE's or Moonlight's (PERF.md,
    finding 31.2). Until PR 31 the compiled loops of the per-row cache
    write sat in every layer and kept the optimiser from seeing the
    layers as one loop."""
    if not is_tpu_backend():
        return {}
    return {"xla_tpu_memory_bound_loop_optimizer_options": "enabled:false"}


# kernel name -> interpret modes it was traced with in this process
_kernel_modes: Dict[str, set] = {}


def pallas_interpret(kernel: str) -> bool:
    """Whether ``kernel``'s pallas_call is traced in interpret mode:
    natively on a TPU, interpreted on the CPU, an error anywhere else. The
    decision is recorded so the process that ran a model can prove which
    mode its kernels were traced in (``traced_kernel_modes``)."""
    import jax

    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernel {kernel!r} has no lowering for platform "
            f"{platform!r}: TPU compiles natively, CPU runs interpreted"
        )
    interpret = platform == "cpu"
    _kernel_modes.setdefault(kernel, set()).add(interpret)
    return interpret


def traced_kernel_modes() -> Dict[str, List[bool]]:
    """{kernel: sorted interpret modes traced so far}; ``[False]`` means
    every trace of that kernel compiled natively."""
    return {k: sorted(v) for k, v in _kernel_modes.items()}
