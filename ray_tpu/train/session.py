"""Worker-side training session: get_context() / report().

Role-equivalent of the reference's ray.train.get_context + report
(train/v2/_internal/execution/context.py, train/context.py): inside
``train_loop_per_worker`` the user asks for ranks/world size, reports
metrics+checkpoints, and fetches dataset shards. Reports are queued in the
worker and drained by the controller's poll loop (reference: thread_runner +
ReportCallbackHandler).
"""

from __future__ import annotations

import os
import shutil
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

from .checkpoint import Checkpoint


@dataclass
class TrainingReport:
    metrics: Dict[str, Any]
    checkpoint: Optional[Checkpoint]
    index: int
    world_rank: int


@dataclass
class TrainContext:
    world_rank: int
    local_rank: int
    node_rank: int
    world_size: int
    local_world_size: int
    experiment_name: str
    run_dir: str
    collective_group: str = ""
    # bumped by the controller on every elastic resize; scopes the
    # collective rendezvous keys so a re-formed gang never reads an
    # aborted epoch's state
    collective_epoch: int = 0
    # int8-with-error-feedback collectives for this run's group, and the
    # default codec for publish_train_state — must be gang-uniform, so it
    # rides in the context rather than per-call arguments
    collective_quantized: bool = False
    # overlapped gradient reduction (collective/scheduler.py): when True,
    # train.collective.reduce_gradients() dispatches bucketized async
    # allreduces instead of one blocking op. All gang-uniform for the same
    # reason quantized is — every rank must bucketize and dispatch
    # identically or the rendezvous sequence desyncs.
    collective_overlap: bool = False
    collective_bucket_bytes: Optional[int] = None
    collective_stale_grad: int = 0
    # hierarchical topology: ranks per slice (None = flat group)
    collective_slice_size: Optional[int] = None
    latest_checkpoint: Optional[Checkpoint] = None
    dataset_shards: Dict[str, Any] = field(default_factory=dict)

    # report queue drained by TrainWorker.poll()
    _reports: list = field(default_factory=list)
    _lock: threading.Lock = field(default_factory=threading.Lock)
    _report_count: int = 0
    # report-to-report step telemetry (compute/collective split +
    # scaling-efficiency gauge; util/metrics.StepBreakdown)
    _step_breakdown: Any = None
    # per-worker step-time series (util/timeseries.py) — the straggler
    # detector's cross-worker input; wall-clock of the previous report
    _step_series: Any = None
    _last_report_t: Optional[float] = None
    # lazily-built GradientReduceScheduler for this run's group (one per
    # context: the re-formed gang's context rebuilds it at the new epoch)
    _grad_scheduler: Any = None

    # -- user-facing accessors (reference: TrainContext methods) ----------

    def get_world_rank(self) -> int:
        return self.world_rank

    def get_local_rank(self) -> int:
        return self.local_rank

    def get_node_rank(self) -> int:
        return self.node_rank

    def get_world_size(self) -> int:
        return self.world_size

    def get_local_world_size(self) -> int:
        return self.local_world_size

    def get_experiment_name(self) -> str:
        return self.experiment_name

    def get_storage_path(self) -> str:
        return self.run_dir

    # -- report -----------------------------------------------------------

    def report(
        self,
        metrics: Dict[str, Any],
        checkpoint: Optional[Checkpoint] = None,
    ):
        """Queue metrics (and persist a checkpoint) for the controller.

        A reported checkpoint directory is *uploaded* (copied) into the
        run's storage as ``checkpoint_{index:06d}``; all ranks reporting the
        same index merge into one logical sharded checkpoint (files must be
        rank-unique, which orbax guarantees via per-process shards).
        """
        index = self._report_count
        self._report_count += 1
        # each report marks a train-step boundary: record the interval's
        # compute/collective breakdown for the scaling-efficiency gauge
        if self._step_breakdown is None:
            from ..util.metrics import StepBreakdown

            self._step_breakdown = StepBreakdown(role="train")
        self._step_breakdown.mark()
        self._record_step_series()
        from ..util import tracing as _tracing

        # a profiler session opened since the step program was traced gets
        # what that trace decided (train.remat_plan)
        _tracing.replay_program_facts()
        persisted: Optional[Checkpoint] = None
        if checkpoint is not None:
            dest = os.path.join(self.run_dir, f"checkpoint_{index:06d}")
            if os.path.abspath(checkpoint.path) != dest:
                os.makedirs(dest, exist_ok=True)
                shutil.copytree(checkpoint.path, dest, dirs_exist_ok=True)
            persisted = Checkpoint(dest)
            self.latest_checkpoint = persisted
        with self._lock:
            self._reports.append(
                TrainingReport(dict(metrics), persisted, index, self.world_rank)
            )

    def _record_step_series(self):
        """Publish this worker's report-to-report wall clock into the
        telemetry plane. Labels name the run/group/rank so the GCS-side
        MAD detector can compare ranks inside one gang; the point carries
        the worker's root trace id as an exemplar so a STRAGGLER_DETECTED
        event links straight to its trace timeline. Never raises."""
        import time as _time

        now = _time.time()
        last, self._last_report_t = self._last_report_t, now
        if last is None:
            return
        try:
            if self._step_series is None:
                from ..util import timeseries as _ts

                self._step_series = _ts.register_series(
                    _ts.STEP_TIME_S,
                    labels={
                        "run": self.experiment_name,
                        "group": self.collective_group,
                        "rank": str(self.world_rank),
                    },
                )
            from ..util import tracing as _tracing

            ctx = _tracing.current_context()
            self._step_series.record(
                now - last, ts=now,
                exemplar=ctx["trace_id"] if ctx else None,
            )
        except Exception:
            pass  # telemetry is best-effort; never fail a report

    def drain_reports(self):
        with self._lock:
            out, self._reports = self._reports, []
        return out


_context: Optional[TrainContext] = None


def set_context(ctx: Optional[TrainContext]):
    global _context
    _context = ctx


def get_context() -> TrainContext:
    if _context is None:
        raise RuntimeError(
            "ray_tpu.train.get_context() called outside a training worker"
        )
    return _context


def in_session() -> bool:
    return _context is not None


def report(metrics: Dict[str, Any], checkpoint: Optional[Checkpoint] = None):
    get_context().report(metrics, checkpoint=checkpoint)


def get_checkpoint() -> Optional[Checkpoint]:
    return get_context().latest_checkpoint


def get_dataset_shard(name: str = "train"):
    """Per-worker dataset shard (reference: ray.train.get_dataset_shard,
    fed by Dataset.streaming_split — data/dataset.py:1863)."""
    shards = get_context().dataset_shards
    if name not in shards:
        raise KeyError(
            f"no dataset shard {name!r}; pass datasets={{'{name}': ds}} to the "
            f"trainer"
        )
    return shards[name]
