"""Ranked worker group gang-scheduled onto the cluster.

Role-equivalent of the reference's Train v2 WorkerGroup
(train/v2/_internal/execution/worker_group/worker_group.py:104): N actor
workers placed by one placement group, assigned ranks sorted by node
(worker_group.py:728-813 rank sorting), each running the user train fn on a
background thread (worker_group/thread_runner.py) while the controller polls
statuses.

TPU-first: with a slice reservation the PG bundles carry the slice's label
selector so every ranked worker lands on one ICI domain, one worker per
host.
"""

from __future__ import annotations

import logging
import threading
import traceback
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from .. import api as ray_api
from ..util.placement_group import (
    PlacementGroup,
    placement_group,
    remove_placement_group,
)
from ..util.scheduling_strategies import PlacementGroupSchedulingStrategy
from .config import ScalingConfig
from .session import TrainContext, set_context

logger = logging.getLogger(__name__)


class TrainWorker:
    """Actor hosting one ranked training process."""

    def __init__(self):
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[str] = None
        self._error_exc: Optional[Exception] = None
        self._done = False
        self._ctx: Optional[TrainContext] = None
        # bumped on reset_for_restart: a zombie train thread from a previous
        # generation (join timed out mid-abort) must not write done/error
        # state into the restarted run
        self._gen = 0

    def get_metadata(self) -> dict:
        import os
        import socket

        from .. import get_tpu_ids
        from ..runtime_context import get_runtime_context

        rc = get_runtime_context()
        return {
            "node_id": rc.get_node_id(),
            "hostname": socket.gethostname(),
            "pid": os.getpid(),
            "tpu_chips": len(get_tpu_ids()),
        }

    def init_context(self, ctx_fields: dict):
        self._ctx = TrainContext(**ctx_fields)
        set_context(self._ctx)
        if self._ctx.collective_group:
            from .. import collective

            kwargs = dict(
                group_name=self._ctx.collective_group,
                epoch=self._ctx.collective_epoch,
                quantized=self._ctx.collective_quantized,
            )
            slice_size = self._ctx.collective_slice_size
            if slice_size and self._ctx.world_size % slice_size == 0:
                # two-tier topology: intra-slice + inter-slice leader reduce
                backend = "hier"
                kwargs["slice_size"] = slice_size
            else:
                # flat group; also the fallback when an elastic resize
                # leaves a world size the slice shape no longer divides
                backend = "gcs"
            collective.init_collective_group(
                self._ctx.world_size,
                self._ctx.world_rank,
                backend=backend,
                **kwargs,
            )
        return True

    def set_dataset_shard(self, name: str, shard):
        self._ctx.dataset_shards[name] = shard
        return True

    def execute(self, fn: Callable, *args, **kwargs):
        """Run an arbitrary function in this worker (backend setup etc.)."""
        return fn(*args, **kwargs)

    def start_training(self, train_fn: Callable, config: Optional[dict]):
        """Launch the user loop on a thread so poll() stays responsive
        (reference: thread_runner.py)."""
        if self._thread is not None:
            raise RuntimeError("training already started")
        gen = self._gen

        def _run():
            try:
                import inspect

                from ..util import tracing

                tracing.startup_ready()  # worker.startup: the loop is entered
                sig = inspect.signature(train_fn)
                if len(sig.parameters) >= 1:
                    train_fn(config if config is not None else {})
                else:
                    train_fn()
            except BaseException as e:  # noqa: BLE001
                if self._gen == gen:
                    self._error = traceback.format_exc()
                    self._error_exc = (
                        e if isinstance(e, Exception) else RuntimeError(str(e))
                    )
                    logger.error("train fn failed:\n%s", self._error)
            finally:
                if self._gen == gen:
                    self._done = True

        self._thread = threading.Thread(target=_run, daemon=True, name="train_fn")
        self._thread.start()
        return True

    def poll(self) -> dict:
        # read done/error BEFORE draining: if the train thread finishes
        # between a drain and the done check, its final report would be
        # dropped — capturing done first means a done=True answer can only
        # accompany a complete drain
        done = self._done
        error = self._error
        error_exc = self._error_exc
        reports = self._ctx.drain_reports() if self._ctx else []
        return {
            "reports": reports,
            "done": done,
            "error": error,
            "error_exc": error_exc,
        }

    def reset_for_restart(self, join_timeout: float = 30.0) -> dict:
        """Prepare this surviving worker for an elastic re-form: wait for
        the (aborted) train thread to exit, tear down the poisoned
        collective group, and clear run state — WITHOUT killing the actor
        process. The controller then re-ranks, re-inits contexts at the
        next epoch, and restarts training."""
        self._gen += 1
        thread_exited = True
        if self._thread is not None:
            self._thread.join(timeout=join_timeout)
            thread_exited = not self._thread.is_alive()
        if self._ctx and self._ctx.collective_group:
            from .. import collective

            try:
                collective.destroy_collective_group(self._ctx.collective_group)
            except Exception:
                pass
        self._thread = None
        self._error = None
        self._error_exc = None
        self._done = False
        return {"thread_exited": thread_exited}

    def shutdown(self):
        if self._ctx and self._ctx.collective_group:
            from .. import collective

            try:
                collective.destroy_collective_group(self._ctx.collective_group)
            except Exception:
                pass
        set_context(None)
        return True


@dataclass
class WorkerInfo:
    actor: Any
    world_rank: int
    local_rank: int
    node_rank: int
    node_id: str
    metadata: dict = field(default_factory=dict)


class WorkerGroup:
    """Create, rank, command, and tear down the gang of train workers."""

    def __init__(
        self,
        scaling_config: ScalingConfig,
        *,
        placement_group_override: Optional[PlacementGroup] = None,
        bundle_label_selector: Optional[Dict[str, str]] = None,
    ):
        self._scaling = scaling_config
        self._pg: Optional[PlacementGroup] = placement_group_override
        self._owns_pg = placement_group_override is None
        self._label_selector = bundle_label_selector
        self.workers: List[WorkerInfo] = []

    def create(self, pg_timeout: float = 60.0):
        n = self._scaling.num_workers
        res = self._scaling._resources_per_worker_not_none
        if res.get("TPU"):
            ray_api.require_chips(res["TPU"], "a train worker")
        if self._pg is None:
            selectors = (
                [dict(self._label_selector) for _ in range(n)]
                if self._label_selector
                else None
            )
            self._pg = placement_group(
                [dict(res) for _ in range(n)],
                strategy=self._scaling.placement_strategy,
                bundle_label_selector=selectors,
            )
        if not self._pg.ready(timeout=pg_timeout):
            raise TimeoutError(
                f"placement group for {n} train workers "
                f"({res} each, {self._scaling.placement_strategy}) not ready "
                f"in {pg_timeout}s — cluster lacks resources"
            )
        worker_cls = ray_api.remote(TrainWorker)
        actors = []
        for i in range(n):
            actors.append(
                worker_cls.options(
                    num_cpus=res.get("CPU", 0),
                    resources={k: v for k, v in res.items() if k != "CPU"},
                    scheduling_strategy=PlacementGroupSchedulingStrategy(
                        self._pg, placement_group_bundle_index=i
                    ),
                ).remote()
            )
        metas = ray_api.get([a.get_metadata.remote() for a in actors])
        self.workers = self._assign_ranks(list(zip(actors, metas)))
        return self

    @staticmethod
    def _assign_ranks(pairs: List[tuple]) -> List[WorkerInfo]:
        """Rank assignment: group by node, sort nodes by id for determinism,
        rank 0 first (reference: worker_group rank sorting :728-813).
        ``pairs`` is (actor, metadata) in a stable pre-order."""
        n = len(pairs)
        order = sorted(range(n), key=lambda i: (pairs[i][1]["node_id"], i))
        node_ids: List[str] = []
        workers: List[WorkerInfo] = []
        local_counts: Dict[str, int] = {}
        for world_rank, idx in enumerate(order):
            actor, meta = pairs[idx]
            node_id = meta["node_id"]
            if node_id not in node_ids:
                node_ids.append(node_id)
            local_rank = local_counts.get(node_id, 0)
            local_counts[node_id] = local_rank + 1
            workers.append(
                WorkerInfo(
                    actor=actor,
                    world_rank=world_rank,
                    local_rank=local_rank,
                    node_rank=node_ids.index(node_id),
                    node_id=node_id,
                    metadata=meta,
                )
            )
        return workers

    @property
    def placement_group(self) -> Optional[PlacementGroup]:
        return self._pg

    def init_contexts(self, run_fields: dict):
        local_sizes: Dict[str, int] = {}
        for w in self.workers:
            local_sizes[w.node_id] = local_sizes.get(w.node_id, 0) + 1
        refs = []
        for w in self.workers:
            fields = dict(
                world_rank=w.world_rank,
                local_rank=w.local_rank,
                node_rank=w.node_rank,
                world_size=len(self.workers),
                local_world_size=local_sizes[w.node_id],
                **run_fields,
            )
            refs.append(w.actor.init_context.remote(fields))
        ray_api.get(refs)

    def execute(self, fn: Callable, *args, **kwargs) -> List[Any]:
        """Run fn on every worker, return results ordered by world rank."""
        return ray_api.get(
            [w.actor.execute.remote(fn, *args, **kwargs) for w in self.workers]
        )

    def execute_single(self, world_rank: int, fn: Callable, *args, **kwargs):
        return ray_api.get(
            self.workers[world_rank].actor.execute.remote(fn, *args, **kwargs)
        )

    def start_training(self, train_fn: Callable, config: Optional[dict]):
        ray_api.get(
            [w.actor.start_training.remote(train_fn, config) for w in self.workers]
        )

    def poll(self) -> List[dict]:
        return ray_api.get([w.actor.poll.remote() for w in self.workers])

    def poll_each(self, timeout: float = 30.0) -> List[Any]:
        """Per-worker poll: each entry is the status dict OR the exception
        that poll raised (a dead actor yields ActorDiedError instead of
        failing the whole batch — the elastic controller needs to know
        exactly which ranks died)."""
        refs = [w.actor.poll.remote() for w in self.workers]
        out: List[Any] = []
        for ref in refs:
            try:
                out.append(ray_api.get(ref, timeout=timeout))
            except Exception as e:  # noqa: BLE001
                out.append(e)
        return out

    def ping(self, timeout: float = 10.0) -> List[bool]:
        """Liveness probe ordered like ``workers``: False = actor dead or
        unresponsive."""
        refs = [w.actor.get_metadata.remote() for w in self.workers]
        alive = []
        for ref in refs:
            try:
                ray_api.get(ref, timeout=timeout)
                alive.append(True)
            except Exception:
                alive.append(False)
        return alive

    def remove_workers(self, indices: List[int]) -> List[WorkerInfo]:
        """Drop the given (current-list) indices — killing their actors
        best-effort — and re-rank the survivors. Returns the removed
        WorkerInfos. The placement group is kept as-is: removing it would
        tear down the surviving placed actors, and the dead ranks' bundles
        stay reserved as grow-back capacity for a later full restart."""
        doomed = set(indices)
        removed = []
        survivors = []
        for i, w in enumerate(self.workers):
            (removed if i in doomed else survivors).append(w)
        for w in removed:
            try:
                ray_api.kill(w.actor)
            except Exception:
                pass
        # survivors keep their relative rank order (stable re-rank): pass
        # them in current world_rank order so rank gaps close without
        # reshuffling the remaining ranks
        self.workers = self._assign_ranks(
            [(w.actor, w.metadata) for w in survivors]
        )
        return removed

    def reset_for_restart(self, join_timeout: float = 30.0) -> List[dict]:
        """Elastic re-form step: every surviving worker joins its aborted
        train thread and clears run state (see TrainWorker.reset_for_restart)."""
        return ray_api.get(
            [
                w.actor.reset_for_restart.remote(join_timeout)
                for w in self.workers
            ],
            timeout=join_timeout + 30.0,
        )

    def shutdown(self):
        for w in self.workers:
            try:
                ray_api.get(w.actor.shutdown.remote(), timeout=5)
            except Exception:
                pass
        for w in self.workers:
            try:
                ray_api.kill(w.actor)
            except Exception:
                pass
        self.workers = []
        if self._pg is not None and self._owns_pg:
            try:
                remove_placement_group(self._pg)
            except Exception:
                pass
        self._pg = None
