"""User-facing metrics: Counter / Gauge / Histogram.

Role-equivalent of the reference's ray.util.metrics (python/ray/util/
metrics.py backed by the per-node metrics agent + Prometheus export,
_private/metrics_agent.py). Metrics record locally and are pushed to the
GCS KV under ``metrics:<worker>`` every few seconds; ``prometheus_text()``
aggregates every worker's push into Prometheus exposition format.
"""

from __future__ import annotations

import bisect
import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

from ..runtime.gcs import keys as gcs_keys

_registry_lock = threading.Lock()
_registry: Dict[str, "Metric"] = {}
_pusher_started = False


class Metric:
    def __init__(
        self,
        name: str,
        description: str = "",
        tag_keys: Tuple[str, ...] = (),
    ):
        self._name = name
        self._description = description
        self._tag_keys = tuple(tag_keys)
        self._default_tags: Dict[str, str] = {}
        self._values: Dict[Tuple[str, ...], float] = {}
        self._lock = threading.Lock()
        with _registry_lock:
            _registry[name] = self
        _ensure_pusher()

    def set_default_tags(self, tags: Dict[str, str]):
        self._default_tags = dict(tags)
        return self

    def _tag_tuple(self, tags: Optional[Dict[str, str]]) -> Tuple[str, ...]:
        merged = {**self._default_tags, **(tags or {})}
        return tuple(merged.get(k, "") for k in self._tag_keys)

    def _snapshot(self) -> dict:
        with self._lock:
            return {
                "name": self._name,
                "type": type(self).__name__.lower(),
                "description": self._description,
                "tag_keys": self._tag_keys,
                "values": {json.dumps(k): v for k, v in self._values.items()},
            }


class _BoundCounter:
    """Counter pre-bound to one tag combination: the tag dict merge and
    tuple build happen ONCE at bind time, so the per-request hot path
    (e.g. the ingress proxy) is a lock + dict-slot add with zero
    allocation. Obtain via ``Counter.bind(**tags)``."""

    __slots__ = ("_metric", "_key")

    def __init__(self, metric: "Counter", key: Tuple[str, ...]):
        self._metric = metric
        self._key = key

    def inc(self, value: float = 1.0):
        m = self._metric
        with m._lock:
            m._values[self._key] = m._values.get(self._key, 0.0) + value


class _BoundGauge:
    """See _BoundCounter; obtain via ``Gauge.bind(**tags)``."""

    __slots__ = ("_metric", "_key")

    def __init__(self, metric: "Gauge", key: Tuple[str, ...]):
        self._metric = metric
        self._key = key

    def set(self, value: float):
        m = self._metric
        with m._lock:
            m._values[self._key] = float(value)


class _BoundHistogram:
    """See _BoundCounter; obtain via ``Histogram.bind(**tags)``. No
    exemplar support — exemplars belong to traced paths, and bound handles
    exist for the untraced fast path."""

    __slots__ = ("_metric", "_key")

    def __init__(self, metric: "Histogram", key: Tuple[str, ...]):
        self._metric = metric
        self._key = key

    def observe(self, value: float):
        m = self._metric
        with m._lock:
            counts = m._counts.get(self._key)
            if counts is None:
                counts = m._counts[self._key] = \
                    [0] * (len(m._boundaries) + 1)
            counts[bisect.bisect_left(m._boundaries, value)] += 1
            total = m._sums.get(self._key, 0.0) + value
            m._sums[self._key] = total
            m._values[self._key] = total


class Counter(Metric):
    def inc(self, value: float = 1.0, tags: Optional[Dict[str, str]] = None):
        key = self._tag_tuple(tags)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + value

    def bind(self, **tags: str) -> _BoundCounter:
        return _BoundCounter(self, self._tag_tuple(tags))


class Gauge(Metric):
    def set(self, value: float, tags: Optional[Dict[str, str]] = None):
        with self._lock:
            self._values[self._tag_tuple(tags)] = float(value)

    def bind(self, **tags: str) -> _BoundGauge:
        return _BoundGauge(self, self._tag_tuple(tags))


class Histogram(Metric):
    def __init__(
        self,
        name: str,
        description: str = "",
        boundaries: Optional[List[float]] = None,
        tag_keys: Tuple[str, ...] = (),
    ):
        super().__init__(name, description, tag_keys)
        self._boundaries = sorted(boundaries or [0.1, 1, 10, 100, 1000])
        self._counts: Dict[Tuple[str, ...], List[int]] = {}
        self._sums: Dict[Tuple[str, ...], float] = {}
        # per-bucket exemplars (OpenMetrics-style): the last trace_id (and
        # its value) observed in each bucket, so a bad p99 bucket links to
        # a concrete trace in the span store instead of just a count
        self._exemplars: Dict[Tuple[str, ...], Dict[int, dict]] = {}

    def observe(self, value: float, tags: Optional[Dict[str, str]] = None,
                exemplar: Optional[str] = None):
        key = self._tag_tuple(tags)
        with self._lock:
            counts = self._counts.setdefault(
                key, [0] * (len(self._boundaries) + 1)
            )
            bucket = bisect.bisect_left(self._boundaries, value)
            counts[bucket] += 1
            self._sums[key] = self._sums.get(key, 0.0) + value
            self._values[key] = self._sums[key]
            if exemplar:
                self._exemplars.setdefault(key, {})[bucket] = {
                    "trace_id": exemplar, "value": value, "ts": time.time(),
                }

    def bind(self, **tags: str) -> _BoundHistogram:
        return _BoundHistogram(self, self._tag_tuple(tags))

    def _snapshot(self) -> dict:
        snap = super()._snapshot()
        with self._lock:
            snap["boundaries"] = self._boundaries
            snap["counts"] = {
                json.dumps(k): v for k, v in self._counts.items()
            }
            if self._exemplars:
                snap["exemplars"] = {
                    json.dumps(k): dict(v)
                    for k, v in self._exemplars.items()
                }
        return snap


# ---------------------------------------------------------------------------
# Control-plane RPC metrics (the lease-reuse / v2-framing proof layer):
# per-method client-call latency histograms plus an RPCs-per-task counter
# pair, recorded from _internal/rpc.py on every client call and surfaced by
# the microbenchmark CLI and the lease-reuse regression tests.
# ---------------------------------------------------------------------------

_RPC_LATENCY_BOUNDARIES_MS = [
    0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 25, 50, 100, 250, 1000,
]

_rpc_latency: Optional["Histogram"] = None
_rpc_calls: Optional["Counter"] = None
_tasks_submitted: Optional["Counter"] = None
_rpc_init_lock = threading.Lock()


def _ensure_rpc_metrics():
    global _rpc_latency, _rpc_calls, _tasks_submitted
    if _rpc_latency is None:
        with _rpc_init_lock:
            if _rpc_latency is None:
                _rpc_calls = Counter(
                    "rpc_client_calls_total",
                    "Client RPCs issued by this process, by method",
                    tag_keys=("method",),
                )
                _tasks_submitted = Counter(
                    "tasks_submitted_total",
                    "Normal tasks submitted by this process",
                )
                # assigned last: its non-None-ness gates the fast path, so
                # the other two must already exist when readers see it
                _rpc_latency = Histogram(
                    "rpc_client_latency_ms",
                    "Client RPC round-trip latency by method (ms)",
                    boundaries=_RPC_LATENCY_BOUNDARIES_MS,
                    tag_keys=("method",),
                )
    return _rpc_latency, _rpc_calls, _tasks_submitted


def record_rpc(method: str, latency_s: float):
    """Called from RpcClient.call / call_oneway (hot path — keep cheap)."""
    latency, calls, _ = _ensure_rpc_metrics()
    tags = {"method": method}
    latency.observe(latency_s * 1000.0, tags)
    calls.inc(1.0, tags)


def note_task_submitted(n: float = 1.0):
    """Called from CoreWorker._launch_task; pairs with rpc_call counts to
    derive RPCs-per-task."""
    _, _, tasks = _ensure_rpc_metrics()
    tasks.inc(n)


def rpc_calls_by_method() -> Dict[str, float]:
    """Process-local snapshot: method -> client calls issued."""
    _, calls, _ = _ensure_rpc_metrics()
    with calls._lock:
        return {k[0]: v for k, v in calls._values.items()}


def tasks_submitted_total() -> float:
    _, _, tasks = _ensure_rpc_metrics()
    with tasks._lock:
        return sum(tasks._values.values())


def rpc_latency_summary() -> Dict[str, dict]:
    """Process-local per-method latency summary: count, mean ms, and the
    cumulative histogram buckets ({le: count}) — the machine-readable shape
    the microbenchmark CLI emits."""
    latency, _, _ = _ensure_rpc_metrics()
    out: Dict[str, dict] = {}
    with latency._lock:
        for key, counts in latency._counts.items():
            method = key[0]
            total = sum(counts)
            if not total:
                continue
            cum = 0
            buckets = {}
            for bound, c in zip(latency._boundaries, counts):
                cum += c
                buckets[str(bound)] = cum
            buckets["+Inf"] = total
            out[method] = {
                "count": total,
                "mean_ms": latency._sums.get(key, 0.0) / total,
                "buckets": buckets,
            }
    return out


# -- flight-recorder health ---------------------------------------------------

_events_dropped: Optional["Counter"] = None
_events_dropped_lock = threading.Lock()


def _ensure_events_dropped():
    global _events_dropped
    if _events_dropped is None:
        with _events_dropped_lock:
            if _events_dropped is None:
                _events_dropped = Counter(
                    "events_dropped_total",
                    "Flight-recorder ring overflows: oldest events dropped "
                    "when the cap was hit, truncating the post-mortem window",
                )
    return _events_dropped


def record_events_dropped(n: float = 1.0):
    """Called from util/events.py when the ring drops its oldest events."""
    _ensure_events_dropped().inc(float(n))


def events_dropped_total() -> float:
    """Process-local readback."""
    c = _ensure_events_dropped()
    with c._lock:
        return sum(c._values.values())


def events_dropped_from_payloads(payloads) -> float:
    """Cluster rollup over pushed metric payloads: total events every
    process's ring has dropped (the /api/events truncation banner)."""
    total = 0.0
    for payload in payloads:
        for snap in payload.get("metrics", ()):
            if snap.get("name") == "events_dropped_total":
                total += sum(snap.get("values", {}).values())
    return total


# ---------------------------------------------------------------------------
# Object-serialization accounting: how many times (and how many bytes) this
# process serialized values into the object plane, by context — "put"
# (api.put / CoreWorker.put) vs "task_arg" (inline task-argument packing).
# The rllib put-once regression guard asserts train() serializes the params
# pytree at most once per iteration instead of once per env-runner.
# ---------------------------------------------------------------------------

_ser_count: Optional["Counter"] = None
_ser_bytes: Optional["Counter"] = None
_ser_init_lock = threading.Lock()


def _ensure_serialization_metrics():
    global _ser_count, _ser_bytes
    if _ser_bytes is None:
        with _ser_init_lock:
            if _ser_bytes is None:
                _ser_count = Counter(
                    "object_serializations_total",
                    "Object-plane serializations by context (put | task_arg)",
                    tag_keys=("context",),
                )
                # assigned last: gates the fast path (see _ensure_rpc_metrics)
                _ser_bytes = Counter(
                    "object_serialization_bytes_total",
                    "Bytes serialized into the object plane by context",
                    tag_keys=("context",),
                )
    return _ser_count, _ser_bytes


def record_object_serialization(context: str, nbytes: int):
    """Called from CoreWorker.put and prepare_args (hot path — keep cheap)."""
    count, total = _ensure_serialization_metrics()
    tags = {"context": context}
    count.inc(1.0, tags)
    total.inc(float(nbytes), tags)


def object_serializations() -> Dict[str, Dict[str, float]]:
    """Process-local snapshot: context -> {count, bytes}."""
    count, total = _ensure_serialization_metrics()
    out: Dict[str, Dict[str, float]] = {}
    with count._lock:
        for key, v in count._values.items():
            out.setdefault(key[0], {"count": 0.0, "bytes": 0.0})["count"] = v
    with total._lock:
        for key, v in total._values.items():
            out.setdefault(key[0], {"count": 0.0, "bytes": 0.0})["bytes"] = v
    return out


# ---------------------------------------------------------------------------
# Weight-plane metrics (ray_tpu.weights): publish latency, broadcast volume,
# tree depth, and subscriber staleness, tagged by model name. Surfaced via
# the GCS pusher / prometheus_text like every other metric, and snapshotted
# process-locally by the weights microbenchmark + tests.
# ---------------------------------------------------------------------------

_WEIGHTS_LATENCY_BOUNDARIES_MS = [
    1, 5, 10, 25, 50, 100, 250, 500, 1000, 2500, 5000, 10000,
]

_weights_metrics: Optional[dict] = None
_weights_init_lock = threading.Lock()


def _ensure_weights_metrics() -> dict:
    global _weights_metrics
    if _weights_metrics is None:
        with _weights_init_lock:
            if _weights_metrics is None:
                _weights_metrics = {
                    "publish_latency": Histogram(
                        "weights_publish_latency_ms",
                        "WeightPublisher.publish wall time by model (ms)",
                        boundaries=_WEIGHTS_LATENCY_BOUNDARIES_MS,
                        tag_keys=("model",),
                    ),
                    "fetch_latency": Histogram(
                        "weights_fetch_latency_ms",
                        "WeightSubscriber full-version fetch wall time (ms)",
                        boundaries=_WEIGHTS_LATENCY_BOUNDARIES_MS,
                        tag_keys=("model",),
                    ),
                    "broadcast_bytes": Counter(
                        "weights_broadcast_bytes_total",
                        "Logical weight bytes moved by direction "
                        "(publish | fetch) — raw leaf bytes, pre-codec",
                        tag_keys=("model", "direction"),
                    ),
                    # wire vs logical split: with the int8 chunk codec the
                    # store/broadcast bytes are ~2-4x smaller than the leaf
                    # bytes; conflating them would silently hide (or
                    # double-count) the compression win
                    "wire_bytes": Counter(
                        "weights_wire_bytes_total",
                        "Encoded on-the-wire weight bytes by direction "
                        "(publish | fetch)",
                        tag_keys=("model", "direction"),
                    ),
                    "codec_publishes": Counter(
                        "weights_codec_publish_total",
                        "Published versions by chunk codec (raw | int8)",
                        tag_keys=("model", "codec"),
                    ),
                    "tree_depth": Gauge(
                        "weights_broadcast_tree_depth",
                        "Depth of the binomial broadcast tree by model",
                        tag_keys=("model",),
                    ),
                    "staleness": Gauge(
                        "weights_staleness_versions",
                        "Versions behind head for this subscriber, by model",
                        tag_keys=("model",),
                    ),
                }
    return _weights_metrics


def record_weights_publish(
    model: str, latency_s: float, nbytes: int,
    wire_nbytes: Optional[int] = None, codec: str = "raw",
):
    m = _ensure_weights_metrics()
    tags = {"model": model, "direction": "publish"}
    m["publish_latency"].observe(latency_s * 1000.0, {"model": model})
    m["broadcast_bytes"].inc(float(nbytes), tags)
    m["wire_bytes"].inc(
        float(wire_nbytes if wire_nbytes is not None else nbytes), tags
    )
    m["codec_publishes"].inc(1.0, {"model": model, "codec": codec})


def record_weights_fetch(
    model: str, latency_s: float, nbytes: int,
    wire_nbytes: Optional[int] = None,
):
    m = _ensure_weights_metrics()
    tags = {"model": model, "direction": "fetch"}
    m["fetch_latency"].observe(latency_s * 1000.0, {"model": model})
    m["broadcast_bytes"].inc(float(nbytes), tags)
    m["wire_bytes"].inc(
        float(wire_nbytes if wire_nbytes is not None else nbytes), tags
    )


def set_weights_tree_depth(model: str, depth: int):
    _ensure_weights_metrics()["tree_depth"].set(float(depth), {"model": model})


def set_weights_staleness(model: str, versions_behind: int):
    _ensure_weights_metrics()["staleness"].set(
        float(versions_behind), {"model": model}
    )


def weights_staleness(model: str) -> Optional[float]:
    """Process-local staleness gauge readback (tests + state CLI)."""
    gauge = _ensure_weights_metrics()["staleness"]
    with gauge._lock:
        return gauge._values.get(gauge._tag_tuple({"model": model}))


# ---------------------------------------------------------------------------
# Collective / ICI instrumentation (the scaling-efficiency proof layer):
# every out-of-graph collective op (collective/xla_group.py, cpu_group.py)
# records bytes moved and wall latency; the achieved-bandwidth gauge is the
# last op's bytes/latency. Per-step compute/collective/idle breakdowns come
# from train/rllib learner steps and roll up into a scaling-efficiency
# gauge (achieved useful-compute fraction vs. the linear-scaling ideal of
# 1.0 — the step-time decomposition Podracer/MLPerf-TPU attribute scaling
# wins to).
# ---------------------------------------------------------------------------

_COLLECTIVE_LATENCY_BOUNDARIES_MS = [
    0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 25, 50, 100, 250, 1000, 5000,
]

_collective_metrics: Optional[dict] = None
_collective_init_lock = threading.Lock()


def _ensure_collective_metrics() -> dict:
    global _collective_metrics
    if _collective_metrics is None:
        with _collective_init_lock:
            if _collective_metrics is None:
                _collective_metrics = {
                    "latency": Histogram(
                        "collective_op_latency_ms",
                        "Out-of-graph collective op wall time (ms)",
                        boundaries=_COLLECTIVE_LATENCY_BOUNDARIES_MS,
                        tag_keys=("op", "backend", "group"),
                    ),
                    "bytes": Counter(
                        "collective_bytes_total",
                        "Logical bytes moved through collective ops "
                        "(operand bytes, pre-codec)",
                        tag_keys=("op", "backend", "group"),
                    ),
                    "wire_bytes": Counter(
                        "collective_wire_bytes_total",
                        "Encoded on-the-wire bytes of collective ops "
                        "(== logical when transport is full-width)",
                        tag_keys=("op", "backend", "group"),
                    ),
                    "bandwidth": Gauge(
                        "collective_bandwidth_gb_s",
                        "Achieved wire bandwidth of the last collective "
                        "op (GB/s, encoded bytes / wall time)",
                        tag_keys=("op", "backend", "group"),
                    ),
                }
    return _collective_metrics


def record_collective(
    op: str, backend: str, group: str, nbytes: int, latency_s: float,
    wire_nbytes: Optional[int] = None,
):
    """Called from every collective backend op (hot path — keep cheap).
    ``nbytes`` is the logical operand size; ``wire_nbytes`` the encoded
    size when the transport compresses (None: wire == logical). The
    bandwidth gauge is wire-basis — it reports what the link carried."""
    m = _ensure_collective_metrics()
    tags = {"op": op, "backend": backend, "group": group}
    wire = wire_nbytes if wire_nbytes is not None else nbytes
    m["latency"].observe(latency_s * 1000.0, tags)
    m["bytes"].inc(float(nbytes), tags)
    m["wire_bytes"].inc(float(wire), tags)
    if latency_s > 0:
        m["bandwidth"].set(wire / latency_s / 1e9, tags)


def collective_seconds_total() -> float:
    """Process-local cumulative wall time spent in collective ops; step
    breakdowns diff this across a step to split compute from collective."""
    m = _ensure_collective_metrics()
    hist = m["latency"]
    with hist._lock:
        return sum(hist._sums.values()) / 1000.0


def collective_summary() -> Dict[str, Dict[str, float]]:
    """Process-local snapshot: op -> {count, bytes, mean_ms} (tests + CLI)."""
    m = _ensure_collective_metrics()
    out: Dict[str, Dict[str, float]] = {}
    hist = m["latency"]
    with hist._lock:
        for key, counts in hist._counts.items():
            total = sum(counts)
            if total:
                out[key[0]] = {
                    "count": float(total),
                    "mean_ms": hist._sums.get(key, 0.0) / total,
                }
    with m["bytes"]._lock:
        for key, v in m["bytes"]._values.items():
            out.setdefault(key[0], {})["bytes"] = v
    with m["wire_bytes"]._lock:
        for key, v in m["wire_bytes"]._values.items():
            out.setdefault(key[0], {})["wire_bytes"] = v
    return out


# -- overlap split: the overlapped-reduction scheduler
# (collective/scheduler.py) attributes every async op's latency to either
# "exposed" (caller blocked in wait) or "overlapped" (ran under compute).
# collective_seconds_total above keeps recording FULL op latencies — under
# overlap that clock overstates critical-path cost, and this split is the
# number that actually proves the win.

_overlap_metrics: Optional[dict] = None
_overlap_init_lock = threading.Lock()


def _ensure_overlap_metrics() -> dict:
    global _overlap_metrics
    if _overlap_metrics is None:
        with _overlap_init_lock:
            if _overlap_metrics is None:
                _overlap_metrics = {
                    "exposed": Counter(
                        "collective_exposed_seconds_total",
                        "Async collective time the caller actually "
                        "blocked on (critical-path cost)",
                        tag_keys=("group",),
                    ),
                    "overlapped": Counter(
                        "collective_overlapped_seconds_total",
                        "Async collective time hidden under the "
                        "caller's compute",
                        tag_keys=("group",),
                    ),
                    "fraction": Gauge(
                        "collective_overlap_fraction",
                        "Hidden fraction of the last gradient "
                        "reduction's collective time (1.0 = fully "
                        "overlapped, 0.0 = fully exposed)",
                        tag_keys=("group",),
                    ),
                }
    return _overlap_metrics


def record_collective_overlap(group: str, exposed_s: float,
                              overlapped_s: float):
    """One gradient reduction's exposure split, summed over its buckets
    (called from PendingReduce.wait on every path, including sync mode
    where overlapped_s is 0 — the A/B baseline shows fraction 0.0)."""
    m = _ensure_overlap_metrics()
    tags = {"group": group}
    exposed_s = max(exposed_s, 0.0)
    overlapped_s = max(overlapped_s, 0.0)
    m["exposed"].inc(exposed_s, tags)
    m["overlapped"].inc(overlapped_s, tags)
    total = exposed_s + overlapped_s
    if total > 0:
        m["fraction"].set(overlapped_s / total, tags)


def collective_exposed_seconds_total() -> float:
    metric = _ensure_overlap_metrics()["exposed"]
    with metric._lock:
        return float(sum(metric._values.values()))


def collective_overlapped_seconds_total() -> float:
    metric = _ensure_overlap_metrics()["overlapped"]
    with metric._lock:
        return float(sum(metric._values.values()))


def collective_overlap_summary() -> Dict[str, Dict[str, float]]:
    """Process-local snapshot: group -> {exposed_s, overlapped_s,
    overlap_fraction} (tests + bench + CLI)."""
    m = _ensure_overlap_metrics()
    out: Dict[str, Dict[str, float]] = {}
    for label, metric in (("exposed_s", m["exposed"]),
                          ("overlapped_s", m["overlapped"])):
        with metric._lock:
            for key, v in metric._values.items():
                out.setdefault(key[0], {})[label] = v
    for group, entry in out.items():
        total = entry.get("exposed_s", 0.0) + entry.get("overlapped_s", 0.0)
        entry["overlap_fraction"] = (
            entry.get("overlapped_s", 0.0) / total if total > 0 else 0.0
        )
    return out


_step_metrics: Optional[dict] = None
_step_init_lock = threading.Lock()


def _ensure_step_metrics() -> dict:
    global _step_metrics
    if _step_metrics is None:
        with _step_init_lock:
            if _step_metrics is None:
                _step_metrics = {
                    "seconds": Gauge(
                        "step_time_seconds",
                        "Last train-step wall time by component "
                        "(compute | collective | idle | total)",
                        tag_keys=("role", "component"),
                    ),
                    "efficiency": Gauge(
                        "scaling_efficiency_ratio",
                        "Useful-compute fraction of the last step "
                        "(1.0 = linear-scaling ideal: zero collective/idle)",
                        tag_keys=("role",),
                    ),
                }
    return _step_metrics


def record_step_breakdown(
    role: str, compute_s: float, collective_s: float, idle_s: float,
    exposed_s: Optional[float] = None, overlapped_s: Optional[float] = None,
):
    """``collective_s`` is the full-latency collective clock delta (the
    pre-overlap decomposition). When the step ran under the overlapped
    scheduler, ``exposed_s``/``overlapped_s`` additionally split that time
    into critical-path vs hidden-under-compute components."""
    m = _ensure_step_metrics()
    compute_s = max(compute_s, 0.0)
    collective_s = max(collective_s, 0.0)
    idle_s = max(idle_s, 0.0)
    total = compute_s + collective_s + idle_s
    components = [
        ("compute", compute_s),
        ("collective", collective_s),
        ("idle", idle_s),
        ("total", total),
    ]
    if exposed_s is not None:
        components.append(("collective_exposed", max(exposed_s, 0.0)))
    if overlapped_s is not None:
        components.append(("collective_overlapped", max(overlapped_s, 0.0)))
    for component, value in components:
        m["seconds"].set(value, {"role": role, "component": component})
    if total > 0:
        m["efficiency"].set(compute_s / total, {"role": role})


def scaling_efficiency(role: str) -> Optional[float]:
    """Process-local efficiency gauge readback (tests + state CLI)."""
    gauge = _ensure_step_metrics()["efficiency"]
    with gauge._lock:
        return gauge._values.get(gauge._tag_tuple({"role": role}))


class StepBreakdown:
    """Per-step compute/collective/idle decomposition for a train loop.

    ``step()`` wraps one learner step: collective time is the delta of the
    process-local collective clock across the block, compute is the rest of
    the block, and idle is the gap since the previous step ended (data
    stall / rollout wait). ``mark()`` is the boundary-only variant for
    loops that can't wrap their step body (ray_tpu.train session.report):
    it treats report-to-report intervals as steps with unknown idle."""

    def __init__(self, role: str):
        self.role = role
        self._last_end: Optional[float] = None
        self._last_coll: Optional[float] = None
        self._last_exposed: Optional[float] = None
        self._last_overlapped: Optional[float] = None

    @contextmanager
    def step(self):
        start = time.perf_counter()
        coll0 = collective_seconds_total()
        exp0 = collective_exposed_seconds_total()
        ovl0 = collective_overlapped_seconds_total()
        try:
            yield
        finally:
            end = time.perf_counter()
            coll = collective_seconds_total() - coll0
            idle = (
                start - self._last_end if self._last_end is not None else 0.0
            )
            self._last_end = end
            # under the overlapped scheduler only the EXPOSED share of the
            # collective clock actually left the critical path's compute —
            # the overlapped share ran under it and stays counted as compute
            exposed = collective_exposed_seconds_total() - exp0
            overlapped = collective_overlapped_seconds_total() - ovl0
            critical_coll = min(coll, exposed) if overlapped > 0 else coll
            record_step_breakdown(
                self.role, (end - start) - critical_coll, critical_coll,
                idle, exposed_s=exposed, overlapped_s=overlapped,
            )

    def mark(self):
        now = time.perf_counter()
        coll_now = collective_seconds_total()
        exp_now = collective_exposed_seconds_total()
        ovl_now = collective_overlapped_seconds_total()
        if self._last_end is not None:
            total = now - self._last_end
            coll = coll_now - (self._last_coll or 0.0)
            exposed = exp_now - (self._last_exposed or 0.0)
            overlapped = ovl_now - (self._last_overlapped or 0.0)
            critical_coll = min(coll, exposed) if overlapped > 0 else coll
            record_step_breakdown(
                self.role, total - critical_coll, critical_coll, 0.0,
                exposed_s=exposed, overlapped_s=overlapped,
            )
        self._last_end = now
        self._last_coll = coll_now
        self._last_exposed = exp_now
        self._last_overlapped = ovl_now


# ---------------------------------------------------------------------------
# Train fault-tolerance telemetry: elastic resizes, gang restarts, collective
# aborts, and kill-to-resumed recovery time. Raw recovery samples are kept
# process-locally alongside the histogram so bench/CLI readers get exact
# p50/p99 (buckets alone can't give those).
# ---------------------------------------------------------------------------

_TRAIN_RECOVERY_BOUNDARIES_S = [
    0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60, 120, 300,
]

_train_ft_metrics: Optional[dict] = None
_train_ft_init_lock = threading.Lock()
_recovery_samples: List[float] = []


def _ensure_train_ft_metrics() -> dict:
    global _train_ft_metrics
    if _train_ft_metrics is None:
        with _train_ft_init_lock:
            if _train_ft_metrics is None:
                _train_ft_metrics = {
                    "resize": Counter(
                        "train_resize_total",
                        "Elastic worker-group resizes (survivors kept, "
                        "group re-formed at a new epoch)",
                        tag_keys=("run",),
                    ),
                    "restart": Counter(
                        "train_restart_total",
                        "Full gang restarts (all workers respawned)",
                        tag_keys=("run",),
                    ),
                    "abort": Counter(
                        "collective_abort_total",
                        "In-flight collective ops aborted by member "
                        "death or explicit abort",
                        tag_keys=("group",),
                    ),
                    "recovery": Histogram(
                        "train_recovery_seconds",
                        "Failure-detected to training-resumed wall time",
                        boundaries=_TRAIN_RECOVERY_BOUNDARIES_S,
                        tag_keys=("run", "kind"),
                    ),
                }
    return _train_ft_metrics


def record_train_resize(run: str):
    _ensure_train_ft_metrics()["resize"].inc(1.0, {"run": run})


def record_train_restart(run: str):
    _ensure_train_ft_metrics()["restart"].inc(1.0, {"run": run})


def record_collective_abort(group: str):
    _ensure_train_ft_metrics()["abort"].inc(1.0, {"group": group})


def record_train_recovery(run: str, seconds: float, kind: str = "resize"):
    _ensure_train_ft_metrics()["recovery"].observe(
        seconds, {"run": run, "kind": kind}
    )
    with _train_ft_init_lock:
        _recovery_samples.append(seconds)
        # bounded: a pathological kill-loop must not grow memory forever
        if len(_recovery_samples) > 10_000:
            del _recovery_samples[:5_000]


def train_recovery_percentiles() -> Dict[str, float]:
    """Process-local exact recovery-time percentiles (bench + CLI)."""
    with _train_ft_init_lock:
        samples = sorted(_recovery_samples)
    if not samples:
        return {}

    def _pct(p: float) -> float:
        return samples[min(len(samples) - 1, int(p * len(samples)))]

    return {
        "count": float(len(samples)),
        "p50_s": _pct(0.50),
        "p99_s": _pct(0.99),
        "max_s": samples[-1],
    }


def train_ft_counters() -> Dict[str, float]:
    """Process-local totals across all tag values (tests + CLI)."""
    m = _ensure_train_ft_metrics()
    out: Dict[str, float] = {}
    for label, metric in (
        ("resizes", m["resize"]),
        ("restarts", m["restart"]),
        ("aborts", m["abort"]),
    ):
        with metric._lock:
            out[label] = float(sum(metric._values.values()))
    return out


def train_ft_summary(
    payloads: List[dict],
    stragglers: Optional[List[dict]] = None,
) -> Dict[str, object]:
    """Cluster rollup of the train fault-tolerance plane from every
    worker's pushed snapshot (state.metrics_summary / dashboard).
    ``stragglers`` joins the timeseries plane's MAD verdicts (GCS
    ``straggler_verdicts`` RPC) into the same rollup, so the dashboard's
    train table answers "is anyone slow" next to "did anyone die"."""
    out = {
        "resizes": 0.0,
        "restarts": 0.0,
        "aborts": 0.0,
        "recoveries": 0.0,
        "recovery_mean_s": 0.0,
        "collective_exposed_s": 0.0,
        "collective_overlapped_s": 0.0,
        "overlap_fraction": 0.0,
    }
    recovery_sum = 0.0
    for payload in payloads:
        for snap in payload.get("metrics", []):
            name = snap.get("name")
            if name == "train_resize_total":
                out["resizes"] += sum(snap["values"].values())
            elif name == "train_restart_total":
                out["restarts"] += sum(snap["values"].values())
            elif name == "collective_abort_total":
                out["aborts"] += sum(snap["values"].values())
            elif name == "train_recovery_seconds":
                for counts in snap.get("counts", {}).values():
                    out["recoveries"] += float(sum(counts))
                recovery_sum += sum(snap.get("values", {}).values())
            elif name == "collective_exposed_seconds_total":
                out["collective_exposed_s"] += sum(snap["values"].values())
            elif name == "collective_overlapped_seconds_total":
                out["collective_overlapped_s"] += sum(
                    snap["values"].values()
                )
    if out["recoveries"]:
        out["recovery_mean_s"] = recovery_sum / out["recoveries"]
    overlap_total = (
        out["collective_exposed_s"] + out["collective_overlapped_s"]
    )
    if overlap_total > 0:
        out["overlap_fraction"] = (
            out["collective_overlapped_s"] / overlap_total
        )
    if stragglers is not None:
        out["stragglers"] = [v for v in stragglers if v.get("straggler")]
        out["straggler_verdicts"] = stragglers
    return out


# ---------------------------------------------------------------------------
# Serve fault-tolerance plane: handle-side failover retries, replica-side
# sheds (admission queue cap) and dead-on-arrival rejections, and graceful
# drain durations. Same shape as the train_ft section above: pushed
# snapshots roll up cluster-wide via serve_ft_summary; process-local
# serve_ft_counters back tests and bench.
# ---------------------------------------------------------------------------

_SERVE_DRAIN_BOUNDARIES_S = [
    0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60,
]

_serve_ft_metrics: Optional[dict] = None
_serve_ft_init_lock = threading.Lock()


def _ensure_serve_ft_metrics() -> dict:
    global _serve_ft_metrics
    if _serve_ft_metrics is None:
        with _serve_ft_init_lock:
            if _serve_ft_metrics is None:
                _serve_ft_metrics = {
                    "retry": Counter(
                        "serve_retry_total",
                        "Handle-side failover resubmissions (replica "
                        "death, drain race, transport failure, or "
                        "retried backpressure)",
                        tag_keys=("deployment", "reason", "replica"),
                    ),
                    "shed": Counter(
                        "serve_shed_total",
                        "Requests shed by replica admission control "
                        "(queue cap reached -> BackPressureError)",
                        tag_keys=("deployment",),
                    ),
                    "doa": Counter(
                        "serve_doa_total",
                        "Dead-on-arrival rejections (request deadline "
                        "already passed at admission)",
                        tag_keys=("deployment",),
                    ),
                    "drain": Histogram(
                        "serve_drain_seconds",
                        "Graceful replica drain duration (stop-routing "
                        "to last in-flight request finished)",
                        boundaries=_SERVE_DRAIN_BOUNDARIES_S,
                        tag_keys=("deployment",),
                    ),
                }
    return _serve_ft_metrics


def record_serve_retry(deployment: str, reason: str, replica: str = ""):
    """``replica`` is the OUTCOME replica the retry was resubmitted to —
    tagging it answers "which replica absorbed the failover" without
    joining against the span store."""
    _ensure_serve_ft_metrics()["retry"].inc(
        1.0, {"deployment": deployment, "reason": reason, "replica": replica}
    )


def record_serve_shed(deployment: str):
    _ensure_serve_ft_metrics()["shed"].inc(1.0, {"deployment": deployment})


def record_serve_doa(deployment: str):
    _ensure_serve_ft_metrics()["doa"].inc(1.0, {"deployment": deployment})


def record_serve_drain(deployment: str, seconds: float):
    _ensure_serve_ft_metrics()["drain"].observe(
        seconds, {"deployment": deployment}
    )


def serve_ft_counters() -> Dict[str, float]:
    """Process-local totals across all tag values (tests + bench). Note:
    retries count in the CALLING process (the handle runs the envelope),
    sheds/DOA/drains count in the replica process."""
    m = _ensure_serve_ft_metrics()
    out: Dict[str, float] = {}
    for label, metric in (
        ("retries", m["retry"]),
        ("sheds", m["shed"]),
        ("doa", m["doa"]),
    ):
        with metric._lock:
            out[label] = float(sum(metric._values.values()))
    drain = m["drain"]
    with drain._lock:
        out["drains"] = float(
            sum(sum(c) for c in drain._counts.values())
        )
    return out


def serve_ft_summary(payloads: List[dict]) -> Dict[str, object]:
    """Cluster rollup of the serve fault-tolerance plane from every
    worker's pushed snapshot (state.metrics_summary / dashboard)."""
    out = {
        "retries": 0.0,
        "sheds": 0.0,
        "doa": 0.0,
        "drains": 0.0,
        "drain_mean_s": 0.0,
        "retry_reasons": {},
    }
    drain_sum = 0.0
    for payload in payloads:
        for snap in payload.get("metrics", []):
            name = snap.get("name")
            if name == "serve_retry_total":
                out["retries"] += sum(snap["values"].values())
                for tag_json, value in snap["values"].items():
                    tags = dict(zip(snap["tag_keys"], json.loads(tag_json)))
                    reason = tags.get("reason", "?")
                    out["retry_reasons"][reason] = (
                        out["retry_reasons"].get(reason, 0.0) + value
                    )
            elif name == "serve_shed_total":
                out["sheds"] += sum(snap["values"].values())
            elif name == "serve_doa_total":
                out["doa"] += sum(snap["values"].values())
            elif name == "serve_drain_seconds":
                for counts in snap.get("counts", {}).values():
                    out["drains"] += float(sum(counts))
                drain_sum += sum(snap.get("values", {}).values())
    if out["drains"]:
        out["drain_mean_s"] = drain_sum / out["drains"]
    return out


# ---------------------------------------------------------------------------
# Partition-tolerance plane: control-plane retry counts (retry_call),
# per-peer circuit-breaker state, and node self-fence transitions. Same
# shape as the serve_ft section above: process-local partition_counters
# back tests and bench, pushed snapshots roll up via partition_summary.
# ---------------------------------------------------------------------------

_partition_metrics: Optional[dict] = None
_partition_init_lock = threading.Lock()


def _ensure_partition_metrics() -> dict:
    global _partition_metrics
    if _partition_metrics is None:
        with _partition_init_lock:
            if _partition_metrics is None:
                _partition_metrics = {
                    "retry": Counter(
                        "rpc_retry_total",
                        "Control-plane RPC retries performed by retry_call "
                        "after a transport-level failure",
                        tag_keys=("method",),
                    ),
                    "circuit": Gauge(
                        "rpc_circuit_state",
                        "Per-peer circuit-breaker state: 0 closed, 1 open "
                        "(failing fast), 2 half-open (probe in flight)",
                        tag_keys=("peer",),
                    ),
                    "fenced": Counter(
                        "node_fenced_total",
                        "Raylet self-fence transitions (GCS unreachable "
                        "past the liveness window)",
                        tag_keys=("node",),
                    ),
                }
    return _partition_metrics


def record_rpc_retry(method: str):
    _ensure_partition_metrics()["retry"].inc(1.0, {"method": method})


def set_rpc_circuit_state(peer: str, state: int):
    _ensure_partition_metrics()["circuit"].set(float(state), {"peer": peer})


def record_node_fenced(node: str):
    _ensure_partition_metrics()["fenced"].inc(1.0, {"node": node})


def partition_counters() -> Dict[str, float]:
    """Process-local totals (tests + bench): retries count in the calling
    process, fence transitions in the raylet's process. circuits_open is
    the number of peers whose breaker is currently not closed."""
    m = _ensure_partition_metrics()
    out: Dict[str, float] = {}
    for label, metric in (("retries", m["retry"]), ("fenced", m["fenced"])):
        with metric._lock:
            out[label] = float(sum(metric._values.values()))
    circuit = m["circuit"]
    with circuit._lock:
        out["circuits_open"] = float(
            sum(1 for v in circuit._values.values() if v)
        )
    return out


def partition_summary(payloads: List[dict]) -> Dict[str, object]:
    """Cluster rollup of the partition-tolerance plane from every worker's
    pushed snapshot (state.metrics_summary / dashboard)."""
    out = {
        "retries": 0.0,
        "fenced": 0.0,
        "circuits_open": 0.0,
        "retry_methods": {},
    }
    for payload in payloads:
        for snap in payload.get("metrics", []):
            name = snap.get("name")
            if name == "rpc_retry_total":
                out["retries"] += sum(snap["values"].values())
                for tag_json, value in snap["values"].items():
                    tags = dict(zip(snap["tag_keys"], json.loads(tag_json)))
                    method = tags.get("method", "?")
                    out["retry_methods"][method] = (
                        out["retry_methods"].get(method, 0.0) + value
                    )
            elif name == "node_fenced_total":
                out["fenced"] += sum(snap["values"].values())
            elif name == "rpc_circuit_state":
                out["circuits_open"] += sum(
                    1 for v in snap["values"].values() if v
                )
    return out


# ---------------------------------------------------------------------------
# Device telemetry: per-device HBM used/limit gauges sampled from
# jax.local_devices() memory stats, tagged by node and device. Sampled by
# the metrics pusher whenever jax is already imported in this process (no
# forced jax import for pure control-plane workers).
# ---------------------------------------------------------------------------

_device_metrics: Optional[dict] = None
_device_init_lock = threading.Lock()


def _ensure_device_metrics() -> dict:
    global _device_metrics
    if _device_metrics is None:
        with _device_init_lock:
            if _device_metrics is None:
                _device_metrics = {
                    "used": Gauge(
                        "tpu_hbm_used_bytes",
                        "Device memory in use (HBM on TPU)",
                        tag_keys=("node", "device", "kind"),
                    ),
                    "limit": Gauge(
                        "tpu_hbm_limit_bytes",
                        "Device memory capacity (HBM on TPU)",
                        tag_keys=("node", "device", "kind"),
                    ),
                }
    return _device_metrics


def sample_device_memory() -> Dict[str, Dict[str, float]]:
    """Set the per-device HBM gauges from jax.local_devices() memory stats
    and return {device: {used, limit}}. Devices without memory stats (CPU
    backend) report zeros so the series exist on every platform.

    Only a backend this process has ALREADY initialised is read. Imported
    is not initialised: asking jax for its devices initialises the default
    backend, and on a TPU host that claims the chip — the pusher thread of
    a driver, controller or proxy would take it from the worker that was
    leased it."""
    from .._internal.platform import backend_initialized

    if not backend_initialized():
        return {}
    import jax

    node = _node_hex()
    m = _ensure_device_metrics()
    out: Dict[str, Dict[str, float]] = {}
    try:
        devices = jax.local_devices()
    except Exception:
        return {}
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        used = float(stats.get("bytes_in_use", 0) or 0)
        limit = float(stats.get("bytes_limit", 0) or 0)
        dev = f"{getattr(d, 'platform', 'dev')}:{getattr(d, 'id', 0)}"
        kind = str(getattr(d, "device_kind", ""))
        tags = {"node": node, "device": dev, "kind": kind}
        m["used"].set(used, tags)
        m["limit"].set(limit, tags)
        out[dev] = {"used": used, "limit": limit}
    return out


# ---------------------------------------------------------------------------
# KV-cache plane instrumentation (the paged prefix cache's proof layer):
# the engine records per-admission hit/computed token counts and TTFT
# (tagged hit | miss), the KVCacheManager keeps the block-pool gauges and
# eviction/backpressure counters current. kvcache_summary() is the one
# aggregation shared by state.metrics_summary(), the `ray_tpu kvcache`
# CLI, and the dashboard's /api/kvcache.
# ---------------------------------------------------------------------------

_KVCACHE_TTFT_BOUNDARIES_MS = [
    1, 5, 10, 25, 50, 100, 250, 500, 1000, 2500, 5000, 10000,
]

_kvcache_metrics: Optional[dict] = None
_kvcache_init_lock = threading.Lock()


def _ensure_kvcache_metrics() -> dict:
    global _kvcache_metrics
    if _kvcache_metrics is None:
        with _kvcache_init_lock:
            if _kvcache_metrics is None:
                # every kvcache metric carries the replica's mesh shape
                # ("tp=1", "tp=2", ...) so sharded and single-device
                # replicas separate cleanly in one cluster rollup
                _kvcache_metrics = {
                    "hit_tokens": Counter(
                        "kvcache_prefix_hit_tokens_total",
                        "Prompt tokens served from the prefix cache "
                        "instead of prefilled",
                        tag_keys=("mesh",),
                    ),
                    "prefill_tokens": Counter(
                        "kvcache_prefill_tokens_total",
                        "Prompt tokens actually computed at admission",
                        tag_keys=("mesh",),
                    ),
                    "evictions": Counter(
                        "kvcache_evictions_total",
                        "KV blocks LRU-evicted from the prefix index",
                        tag_keys=("mesh",),
                    ),
                    "blocked": Counter(
                        "kvcache_admission_blocked_total",
                        "Admissions deferred: block pool exhausted "
                        "(backpressure, not OOM)",
                        tag_keys=("mesh",),
                    ),
                    "blocks_in_use": Gauge(
                        "kvcache_blocks_in_use",
                        "Allocated KV blocks in this engine's pool",
                        tag_keys=("mesh",),
                    ),
                    "blocks_capacity": Gauge(
                        "kvcache_blocks_capacity",
                        "Total KV blocks in this engine's pool",
                        tag_keys=("mesh",),
                    ),
                    # "tier" separates where the prefix came from:
                    # local (this replica's radix), peer (pulled through
                    # the cluster KV tier), miss (computed from scratch)
                    "ttft": Histogram(
                        "kvcache_ttft_ms",
                        "Time to first token (ms) by prefix-cache outcome",
                        boundaries=_KVCACHE_TTFT_BOUNDARIES_MS,
                        tag_keys=("cache", "mesh", "tier"),
                    ),
                }
    return _kvcache_metrics


def record_kvcache_prefill(
    hit_tokens: int, computed_tokens: int, mesh: str = "tp=1"
):
    m = _ensure_kvcache_metrics()
    m["hit_tokens"].inc(float(hit_tokens), {"mesh": mesh})
    m["prefill_tokens"].inc(float(computed_tokens), {"mesh": mesh})


def record_kvcache_eviction(n: int = 1, mesh: str = "tp=1"):
    _ensure_kvcache_metrics()["evictions"].inc(float(n), {"mesh": mesh})


def record_kvcache_blocked(mesh: str = "tp=1"):
    _ensure_kvcache_metrics()["blocked"].inc(1.0, {"mesh": mesh})


def set_kvcache_blocks(in_use: int, capacity: int, mesh: str = "tp=1"):
    m = _ensure_kvcache_metrics()
    m["blocks_in_use"].set(float(in_use), {"mesh": mesh})
    m["blocks_capacity"].set(float(capacity), {"mesh": mesh})
    if capacity > 0:
        try:
            from . import timeseries as _ts

            _ts.register_series(
                _ts.KV_POOL_OCCUPANCY, labels={"mesh": mesh}
            ).record(float(in_use) / float(capacity))
        except Exception:
            pass  # telemetry is best-effort; the gauges above are canonical


def record_kvcache_ttft(
    seconds: float, hit: bool, mesh: str = "tp=1", tier: str = "local"
):
    _ensure_kvcache_metrics()["ttft"].observe(
        seconds * 1000.0,
        {"cache": "hit" if hit else "miss", "mesh": mesh, "tier": tier},
    )


def kvcache_counters() -> Dict[str, float]:
    """Process-local counter readback (tests + bench; no cluster needed)."""
    m = _ensure_kvcache_metrics()

    def _total(metric) -> float:
        with metric._lock:
            return float(sum(metric._values.values()))

    return {
        "prefix_hit_tokens": _total(m["hit_tokens"]),
        "prefill_tokens_computed": _total(m["prefill_tokens"]),
        "evictions": _total(m["evictions"]),
        "admission_blocked": _total(m["blocked"]),
    }


def kvcache_summary(payloads: List[dict]) -> Dict[str, object]:
    """Cluster-wide KV-cache rollup from pushed payloads: counters and
    block gauges summed across engines (each engine owns its own pool, so
    the cluster total is the sum), TTFT mean by hit/miss tag."""
    out: Dict[str, object] = {
        "prefix_hit_tokens": 0.0,
        "prefill_tokens_computed": 0.0,
        "evictions": 0.0,
        "admission_blocked": 0.0,
        "blocks_in_use": 0.0,
        "blocks_capacity": 0.0,
        "ttft_ms": {},
    }
    simple = {
        "kvcache_prefix_hit_tokens_total": "prefix_hit_tokens",
        "kvcache_prefill_tokens_total": "prefill_tokens_computed",
        "kvcache_evictions_total": "evictions",
        "kvcache_admission_blocked_total": "admission_blocked",
        "kvcache_blocks_in_use": "blocks_in_use",
        "kvcache_blocks_capacity": "blocks_capacity",
    }
    ttft: Dict[str, Dict[str, float]] = out["ttft_ms"]  # type: ignore[assignment]
    ttft_buckets: Dict[str, List[float]] = {}
    ttft_bounds: Dict[str, List[float]] = {}
    for payload in payloads:
        for snap in payload.get("metrics", []):
            name = snap["name"]
            if name in simple:
                out[simple[name]] += float(sum(snap["values"].values()))
            elif name == "kvcache_ttft_ms":
                for tag_json, counts in snap.get("counts", {}).items():
                    tags = dict(zip(snap["tag_keys"], json.loads(tag_json)))
                    cache = tags.get("cache", "?")
                    row = ttft.setdefault(
                        cache, {"count": 0.0, "sum_ms": 0.0}
                    )
                    row["count"] += float(sum(counts))
                    row["sum_ms"] += float(
                        snap["values"].get(tag_json, 0.0)
                    )
                    merged = ttft_buckets.setdefault(cache, [0.0] * len(counts))
                    if len(merged) < len(counts):
                        merged.extend([0.0] * (len(counts) - len(merged)))
                    for i, c in enumerate(counts):
                        merged[i] += c
                    ttft_bounds.setdefault(
                        cache,
                        list(snap.get("boundaries")
                             or _KVCACHE_TTFT_BOUNDARIES_MS),
                    )
    for cache, row in ttft.items():
        if row["count"]:
            row["mean_ms"] = row["sum_ms"] / row["count"]
            counts = ttft_buckets.get(cache)
            if counts:
                bounds = ttft_bounds[cache]
                row["p50_ms"] = quantile_from_buckets(bounds, counts, 0.50)
                row["p99_ms"] = quantile_from_buckets(bounds, counts, 0.99)
    return out


# ---------------------------------------------------------------------------
# Cluster KV-tier instrumentation (kvtier's proof layer): per-request
# resolution outcomes (hit = registry had a deeper prefix, peer_pull =
# the blocks actually arrived and decoded, recompute = tier consulted
# but the prefix was prefilled anyway — miss, lease conflict, dead
# holder), plus the logical/wire byte split so the int8 shipment codec's
# compression is visible instead of silently folded into one number.
# kvtier_summary() is shared by the `ray_tpu kvtier` CLI and the
# dashboard's /api/kvtier; the per-tier TTFT split rides the kvcache
# histogram's "tier" tag rather than a second histogram.
# ---------------------------------------------------------------------------

_kvtier_metrics: Optional[dict] = None
_kvtier_init_lock = threading.Lock()

_KVTIER_OUTCOMES = ("hit", "peer_pull", "recompute")


def _ensure_kvtier_metrics() -> dict:
    global _kvtier_metrics
    if _kvtier_metrics is None:
        with _kvtier_init_lock:
            if _kvtier_metrics is None:
                _kvtier_metrics = {
                    "hit": Counter(
                        "kvtier_hit_total",
                        "Tier resolutions that found a registered prefix "
                        "deeper than the local radix",
                        tag_keys=("model",),
                    ),
                    "peer_pull": Counter(
                        "kvtier_peer_pull_total",
                        "Warm prefixes successfully pulled from a peer "
                        "replica and adopted",
                        tag_keys=("model",),
                    ),
                    "recompute": Counter(
                        "kvtier_recompute_total",
                        "Tier consultations that fell back to prefill "
                        "(miss, lease conflict, or dead holder)",
                        tag_keys=("model",),
                    ),
                    "transfer_bytes": Counter(
                        "kvtier_transfer_bytes_total",
                        "KV bytes moved through the tier by kind "
                        "(logical = raw leaf bytes, wire = encoded)",
                        tag_keys=("model", "kind"),
                    ),
                }
    return _kvtier_metrics


def record_kvtier(outcome: str, model: str = ""):
    """One tier resolution outcome: hit | peer_pull | recompute."""
    if outcome not in _KVTIER_OUTCOMES:
        raise ValueError(
            f"kvtier outcome must be one of {_KVTIER_OUTCOMES}, "
            f"got {outcome!r}"
        )
    _ensure_kvtier_metrics()[outcome].inc(1.0, {"model": model})


def record_kvtier_transfer(
    logical_nbytes: int, wire_nbytes: int, model: str = ""
):
    m = _ensure_kvtier_metrics()
    m["transfer_bytes"].inc(float(logical_nbytes),
                            {"model": model, "kind": "logical"})
    m["transfer_bytes"].inc(float(wire_nbytes),
                            {"model": model, "kind": "wire"})


def kvtier_counters() -> Dict[str, float]:
    """Process-local readback (tests + bench; no cluster needed)."""
    m = _ensure_kvtier_metrics()

    def _total(metric) -> float:
        with metric._lock:
            return float(sum(metric._values.values()))

    def _kind(kind: str) -> float:
        tm = m["transfer_bytes"]
        with tm._lock:
            return float(sum(
                v for k, v in tm._values.items() if kind in k
            ))

    return {
        "hit": _total(m["hit"]),
        "peer_pull": _total(m["peer_pull"]),
        "recompute": _total(m["recompute"]),
        "transfer_logical_bytes": _kind("logical"),
        "transfer_wire_bytes": _kind("wire"),
    }


def kvtier_summary(payloads: List[dict]) -> Dict[str, object]:
    """Cluster-wide KV-tier rollup from pushed payloads: outcome counters
    and byte totals summed across replicas, plus the per-tier TTFT split
    (local | peer | miss) read off the kvcache histogram's tier tag."""
    out: Dict[str, object] = {
        "hit": 0.0,
        "peer_pull": 0.0,
        "recompute": 0.0,
        "transfer_bytes": {"logical": 0.0, "wire": 0.0},
        "ttft_ms_by_tier": {},
    }
    simple = {
        "kvtier_hit_total": "hit",
        "kvtier_peer_pull_total": "peer_pull",
        "kvtier_recompute_total": "recompute",
    }
    ttft: Dict[str, Dict[str, float]] = out["ttft_ms_by_tier"]  # type: ignore[assignment]
    ttft_buckets: Dict[str, List[float]] = {}
    ttft_bounds: Dict[str, List[float]] = {}
    xfer: Dict[str, float] = out["transfer_bytes"]  # type: ignore[assignment]
    for payload in payloads:
        for snap in payload.get("metrics", []):
            name = snap["name"]
            if name in simple:
                out[simple[name]] += float(sum(snap["values"].values()))
            elif name == "kvtier_transfer_bytes_total":
                for tag_json, v in snap["values"].items():
                    tags = dict(zip(snap["tag_keys"], json.loads(tag_json)))
                    kind = tags.get("kind", "?")
                    xfer[kind] = xfer.get(kind, 0.0) + float(v)
            elif name == "kvcache_ttft_ms":
                for tag_json, counts in snap.get("counts", {}).items():
                    tags = dict(zip(snap["tag_keys"], json.loads(tag_json)))
                    tier = tags.get("tier", "local")
                    row = ttft.setdefault(
                        tier, {"count": 0.0, "sum_ms": 0.0}
                    )
                    row["count"] += float(sum(counts))
                    row["sum_ms"] += float(
                        snap["values"].get(tag_json, 0.0)
                    )
                    merged = ttft_buckets.setdefault(
                        tier, [0.0] * len(counts)
                    )
                    if len(merged) < len(counts):
                        merged.extend([0.0] * (len(counts) - len(merged)))
                    for i, c in enumerate(counts):
                        merged[i] += c
                    ttft_bounds.setdefault(
                        tier,
                        list(snap.get("boundaries")
                             or _KVCACHE_TTFT_BOUNDARIES_MS),
                    )
    for tier, row in ttft.items():
        if row["count"]:
            row["mean_ms"] = row["sum_ms"] / row["count"]
            counts = ttft_buckets.get(tier)
            if counts:
                bounds = ttft_bounds[tier]
                row["p50_ms"] = quantile_from_buckets(bounds, counts, 0.50)
                row["p99_ms"] = quantile_from_buckets(bounds, counts, 0.99)
    return out


# ---------------------------------------------------------------------------
# Histogram quantiles from pushed buckets. The push plane ships bucket
# counts, not raw samples, so cluster rollups (state.metrics_summary, the
# autoscale controller, the dashboard) estimate percentiles by linear
# interpolation inside the containing bucket — the same estimator
# Prometheus's histogram_quantile uses. Exact sample percentiles stay
# available only where a process kept raw samples (e.g. train recovery).
# ---------------------------------------------------------------------------


def quantile_from_buckets(
    boundaries: List[float], counts: List[float], q: float
) -> Optional[float]:
    """Estimate the q-quantile from non-cumulative histogram buckets.

    Bucket i spans (boundaries[i-1], boundaries[i]]; the first bucket's
    lower edge is 0 (all recorded values are non-negative) and the overflow
    bucket clamps to the last boundary since it has no upper edge to
    interpolate toward. Returns None for an empty histogram."""
    total = float(sum(counts))
    if total <= 0:
        return None
    rank = min(max(q, 0.0), 1.0) * total
    cum = 0.0
    lo = 0.0
    for i, c in enumerate(counts):
        if c and cum + c >= rank:
            if i >= len(boundaries):
                return float(lo)
            hi = float(boundaries[i])
            return lo + (hi - lo) * ((rank - cum) / c)
        cum += c
        if i < len(boundaries):
            lo = float(boundaries[i])
    return float(lo)


def merged_histogram(
    payloads: List[dict],
    name: str,
    tag_filter: Optional[Dict[str, str]] = None,
) -> Optional[dict]:
    """Merge one histogram's buckets across every pushed payload, keeping
    only series whose tags include ``tag_filter``. Returns {boundaries,
    counts, sum, count} or None if no matching series was pushed."""
    boundaries: Optional[List[float]] = None
    merged: Optional[List[float]] = None
    total_sum = 0.0
    for payload in payloads:
        for snap in payload.get("metrics", []):
            if snap.get("name") != name:
                continue
            for tag_json, counts in snap.get("counts", {}).items():
                if tag_filter:
                    tags = dict(
                        zip(snap.get("tag_keys", ()), json.loads(tag_json))
                    )
                    if any(tags.get(k) != v for k, v in tag_filter.items()):
                        continue
                if merged is None:
                    boundaries = list(snap.get("boundaries") or [])
                    merged = [0.0] * len(counts)
                if len(merged) < len(counts):
                    merged.extend([0.0] * (len(counts) - len(merged)))
                for i, c in enumerate(counts):
                    merged[i] += c
                total_sum += float(snap.get("values", {}).get(tag_json, 0.0))
    if merged is None:
        return None
    return {
        "boundaries": boundaries or [],
        "counts": merged,
        "sum": total_sum,
        "count": float(sum(merged)),
    }


# ---------------------------------------------------------------------------
# Serve latency plane: per-deployment TTFT (admission to first output:
# first stream item, or completion for unary calls) and replica warmup
# (actor start to ready-to-serve, including weight-plane resolution). The
# TTFT p99 here is the SLO signal the autoscale controller evaluates.
# ---------------------------------------------------------------------------

_SERVE_TTFT_BOUNDARIES_S = [
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5,
    10, 30,
]

_SERVE_WARMUP_BOUNDARIES_S = [
    0.01, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60, 120,
]

_serve_latency_metrics: Optional[dict] = None
_serve_latency_init_lock = threading.Lock()


def _ensure_serve_latency_metrics() -> dict:
    global _serve_latency_metrics
    if _serve_latency_metrics is None:
        with _serve_latency_init_lock:
            if _serve_latency_metrics is None:
                _serve_latency_metrics = {
                    "ttft": Histogram(
                        "serve_ttft_seconds",
                        "Replica-side time to first output: admission "
                        "(queue wait included) to first stream item or "
                        "unary completion",
                        boundaries=_SERVE_TTFT_BOUNDARIES_S,
                        tag_keys=("deployment",),
                    ),
                    "warmup": Histogram(
                        "serve_replica_warmup_seconds",
                        "Replica cold-start: constructor entry to "
                        "ready-to-serve (user init + weight resolution "
                        "+ warmup hook)",
                        boundaries=_SERVE_WARMUP_BOUNDARIES_S,
                        tag_keys=("deployment",),
                    ),
                }
    return _serve_latency_metrics


def record_serve_ttft(deployment: str, seconds: float,
                      trace_id: Optional[str] = None):
    """``trace_id`` (when the request is traced) becomes the bucket's
    exemplar, so a bad p99 bucket links to a concrete trace."""
    _ensure_serve_latency_metrics()["ttft"].observe(
        seconds, {"deployment": deployment}, exemplar=trace_id
    )


def record_serve_replica_warmup(deployment: str, seconds: float):
    _ensure_serve_latency_metrics()["warmup"].observe(
        seconds, {"deployment": deployment}
    )


def serve_latency_summary(payloads: List[dict]) -> Dict[str, object]:
    """Cluster rollup: per-deployment TTFT (ms) and warmup (s) with
    bucket-derived p50/p99 (state.metrics_summary / dashboard / CLI)."""
    out: Dict[str, object] = {"ttft_ms": {}, "warmup_s": {}}
    specs = (
        ("serve_ttft_seconds", "ttft_ms", 1000.0),
        ("serve_replica_warmup_seconds", "warmup_s", 1.0),
    )
    deployments: Dict[str, set] = {key: set() for _, key, _ in specs}
    for payload in payloads:
        for snap in payload.get("metrics", []):
            for name, key, _scale in specs:
                if snap.get("name") != name:
                    continue
                for tag_json in snap.get("counts", {}):
                    tags = dict(
                        zip(snap.get("tag_keys", ()), json.loads(tag_json))
                    )
                    deployments[key].add(tags.get("deployment", "?"))
    for name, key, scale in specs:
        section: Dict[str, dict] = out[key]  # type: ignore[assignment]
        for dep in sorted(deployments[key]):
            m = merged_histogram(payloads, name, {"deployment": dep})
            if not m or not m["count"]:
                continue
            section[dep] = {
                "count": m["count"],
                "mean": m["sum"] / m["count"] * scale,
                "p50": _scaled_quantile(m, 0.50, scale),
                "p99": _scaled_quantile(m, 0.99, scale),
            }
    return out


def _scaled_quantile(m: dict, q: float, scale: float) -> Optional[float]:
    est = quantile_from_buckets(m["boundaries"], m["counts"], q)
    return None if est is None else est * scale


# ---------------------------------------------------------------------------
# LLM decode plane: inter-token latency (the per-token cadence a streaming
# client sees — TTFT's sibling for everything after the first token).
# Engines record through ray_tpu.llm.engine's _record_itl; llm_summary() is
# the one rollup shared by state.metrics_summary()["llm"] and the
# dashboard's /api/serve.
# ---------------------------------------------------------------------------

_SERVE_ITL_BOUNDARIES_S = [
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1, 2.5, 5,
]

_llm_metrics: Optional[dict] = None
_llm_init_lock = threading.Lock()


def _ensure_llm_metrics() -> dict:
    global _llm_metrics
    if _llm_metrics is None:
        with _llm_init_lock:
            if _llm_metrics is None:
                _llm_metrics = {
                    "itl": Histogram(
                        "serve_itl_seconds",
                        "Inter-token latency: gap between consecutive "
                        "emitted tokens of one request",
                        boundaries=_SERVE_ITL_BOUNDARIES_S,
                        tag_keys=("mesh",),
                    ),
                }
    return _llm_metrics


def record_serve_itl(seconds: float, mesh: str = "tp=1"):
    _ensure_llm_metrics()["itl"].observe(seconds, {"mesh": mesh})


def llm_summary(payloads: List[dict]) -> Dict[str, object]:
    """Cluster rollup: ITL percentiles (ms)."""
    out: Dict[str, object] = {"itl_ms": None}
    m = merged_histogram(payloads, "serve_itl_seconds")
    if m and m["count"]:
        out["itl_ms"] = {
            "count": m["count"],
            "mean": m["sum"] / m["count"] * 1000.0,
            "p50": _scaled_quantile(m, 0.50, 1000.0),
            "p99": _scaled_quantile(m, 0.99, 1000.0),
        }
    return out


# ---------------------------------------------------------------------------
# Adapter plane (ray_tpu.lora): per-replica AdapterStore hit/cold-attach/
# evict counters, a live-slots gauge, and the cold-attach latency histogram
# — the number that tells an operator whether max_live is sized right
# (thrashing shows up as evictions + cold-attach p99, a healthy fleet shows
# hits). Stores record through lora/store.py's lazy hooks; adapter_summary()
# is the one rollup shared by state.metrics_summary()["adapters"], the
# `ray_tpu adapters` CLI, and the dashboard's /api/serve.
# ---------------------------------------------------------------------------

_ADAPTER_ATTACH_BOUNDARIES_S = [
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10,
]

_adapter_metrics: Optional[dict] = None
_adapter_init_lock = threading.Lock()


def _ensure_adapter_metrics() -> dict:
    global _adapter_metrics
    if _adapter_metrics is None:
        with _adapter_init_lock:
            if _adapter_metrics is None:
                _adapter_metrics = {
                    "hits": Counter(
                        "adapter_hit_total",
                        "Adapter lease acquisitions served by a resident "
                        "slot (no weight-plane pull)",
                        tag_keys=("mesh",),
                    ),
                    "cold": Counter(
                        "adapter_cold_attach_total",
                        "Adapter lease acquisitions that pulled and wrote "
                        "the adapter into a slot",
                        tag_keys=("mesh",),
                    ),
                    "evict": Counter(
                        "adapter_evict_total",
                        "Idle adapters evicted from their slot (LRU) to "
                        "make room for a cold attach",
                        tag_keys=("mesh",),
                    ),
                    "live": Gauge(
                        "adapter_slots_live",
                        "Adapters currently resident in this process's "
                        "slot banks (pinned + idle)",
                        tag_keys=("mesh",),
                    ),
                    "attach": Histogram(
                        "adapter_cold_attach_seconds",
                        "Cold-attach latency: source fetch + normalize + "
                        "slot write, the TTFT tax of an adapter's first "
                        "request on a replica",
                        boundaries=_ADAPTER_ATTACH_BOUNDARIES_S,
                        tag_keys=("mesh",),
                    ),
                }
    return _adapter_metrics


def record_adapter_hit(mesh: str = "tp=1"):
    _ensure_adapter_metrics()["hits"].inc(1.0, {"mesh": mesh})


def record_adapter_cold_attach(seconds: float, mesh: str = "tp=1"):
    m = _ensure_adapter_metrics()
    m["cold"].inc(1.0, {"mesh": mesh})
    m["attach"].observe(seconds, {"mesh": mesh})


def record_adapter_evict(mesh: str = "tp=1"):
    _ensure_adapter_metrics()["evict"].inc(1.0, {"mesh": mesh})


def set_adapter_slots_live(n: int, mesh: str = "tp=1"):
    _ensure_adapter_metrics()["live"].set(float(n), {"mesh": mesh})


def adapter_counters() -> Dict[str, float]:
    """Process-local readback (tests + bench; no cluster needed)."""
    m = _ensure_adapter_metrics()

    def _total(metric) -> float:
        with metric._lock:
            return float(sum(metric._values.values()))

    return {
        "adapter_hits": _total(m["hits"]),
        "adapter_cold_attaches": _total(m["cold"]),
        "adapter_evictions": _total(m["evict"]),
    }


def adapter_summary(payloads: List[dict]) -> Dict[str, object]:
    """Cluster rollup: hit rate + cold-attach latency percentiles (ms)."""
    out: Dict[str, object] = {
        "hits": 0.0,
        "cold_attaches": 0.0,
        "evictions": 0.0,
        "slots_live": 0.0,
        "hit_rate": None,
        "cold_attach_ms": None,
    }
    simple = {
        "adapter_hit_total": "hits",
        "adapter_cold_attach_total": "cold_attaches",
        "adapter_evict_total": "evictions",
        "adapter_slots_live": "slots_live",
    }
    for payload in payloads:
        for snap in payload.get("metrics", []):
            name = snap.get("name")
            if name in simple:
                out[simple[name]] += float(sum(snap["values"].values()))
    acquired = out["hits"] + out["cold_attaches"]
    if acquired:
        out["hit_rate"] = out["hits"] / acquired
    m = merged_histogram(payloads, "adapter_cold_attach_seconds")
    if m and m["count"]:
        out["cold_attach_ms"] = {
            "count": m["count"],
            "mean": m["sum"] / m["count"] * 1000.0,
            "p50": _scaled_quantile(m, 0.50, 1000.0),
            "p99": _scaled_quantile(m, 0.99, 1000.0),
        }
    return out


# ---------------------------------------------------------------------------
# Ingress plane: per-proxy request counters / inflight gauge / end-to-end
# proxy latency, tagged proxy_id so the multi-proxy data plane shows per-
# listener load spread. The proxies record through pre-bound handles
# (ingress_handles) — at saturation the data plane runs thousands of
# requests a second per proxy, and the per-call tag-dict merge is real
# overhead there.
# ---------------------------------------------------------------------------

_INGRESS_LATENCY_BOUNDARIES_MS = [
    0.5, 1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000, 2500, 5000,
]

_ingress_metrics: Optional[dict] = None
_ingress_init_lock = threading.Lock()


def _ensure_ingress_metrics() -> dict:
    global _ingress_metrics
    if _ingress_metrics is None:
        with _ingress_init_lock:
            if _ingress_metrics is None:
                _ingress_metrics = {
                    "requests": Counter(
                        "proxy_requests_total",
                        "Requests completed by an ingress proxy, by "
                        "outcome (ok/error/shed/timeout/drain)",
                        tag_keys=("proxy_id", "outcome"),
                    ),
                    "inflight": Gauge(
                        "proxy_inflight",
                        "Requests currently being served by this proxy",
                        tag_keys=("proxy_id",),
                    ),
                    "latency": Histogram(
                        "proxy_request_latency_ms",
                        "End-to-end proxy latency: request read to "
                        "response write",
                        boundaries=_INGRESS_LATENCY_BOUNDARIES_MS,
                        tag_keys=("proxy_id",),
                    ),
                }
    return _ingress_metrics


def ingress_handles(proxy_id: str) -> dict:
    """Pre-bound per-proxy metric handles for the proxy request loop:
    {ok, error, shed, timeout, drain} counters plus {inflight, latency}.
    Bind once at proxy start; each record is then a lock + slot update."""
    m = _ensure_ingress_metrics()
    req = m["requests"]
    return {
        "ok": req.bind(proxy_id=proxy_id, outcome="ok"),
        "error": req.bind(proxy_id=proxy_id, outcome="error"),
        "shed": req.bind(proxy_id=proxy_id, outcome="shed"),
        "timeout": req.bind(proxy_id=proxy_id, outcome="timeout"),
        "drain": req.bind(proxy_id=proxy_id, outcome="drain"),
        "inflight": m["inflight"].bind(proxy_id=proxy_id),
        "latency": m["latency"].bind(proxy_id=proxy_id),
    }


def ingress_summary(payloads: List[dict]) -> Dict[str, object]:
    """Cluster rollup for state.metrics_summary()["ingress"]: per-proxy
    request counts by outcome, current inflight, and latency p50/p99
    (ms), plus fleet totals."""
    proxies: Dict[str, dict] = {}

    def row(proxy_id: str) -> dict:
        return proxies.setdefault(
            proxy_id, {"requests": {}, "inflight": 0.0}
        )

    for payload in payloads:
        for snap in payload.get("metrics", []):
            name = snap.get("name")
            tag_keys = snap.get("tag_keys", ())
            if name == "proxy_requests_total":
                for tag_json, value in snap.get("values", {}).items():
                    tags = dict(zip(tag_keys, json.loads(tag_json)))
                    outcomes = row(tags.get("proxy_id", "?"))["requests"]
                    outcome = tags.get("outcome", "?")
                    outcomes[outcome] = outcomes.get(outcome, 0.0) + value
            elif name == "proxy_inflight":
                for tag_json, value in snap.get("values", {}).items():
                    tags = dict(zip(tag_keys, json.loads(tag_json)))
                    row(tags.get("proxy_id", "?"))["inflight"] = value
    total_requests = 0.0
    for proxy_id, entry in proxies.items():
        entry["total"] = sum(entry["requests"].values())
        total_requests += entry["total"]
        m = merged_histogram(
            payloads, "proxy_request_latency_ms", {"proxy_id": proxy_id}
        )
        if m and m["count"]:
            entry["latency_ms"] = {
                "count": m["count"],
                "mean": m["sum"] / m["count"],
                "p50": _scaled_quantile(m, 0.50, 1.0),
                "p99": _scaled_quantile(m, 0.99, 1.0),
            }
    return {
        "proxies": {k: proxies[k] for k in sorted(proxies)},
        "num_proxies": len(proxies),
        "requests_total": total_requests,
    }


# ---------------------------------------------------------------------------
# Hang-watchdog plane (util/watchdog.py): how many watched units of work
# (replica requests, collective epochs) are currently past their stuck
# threshold in this process. A nonzero value is the "look at the flight
# recorder's watchdog_stuck stack captures" signal.
# ---------------------------------------------------------------------------

_watchdog_metrics: Optional[dict] = None
_watchdog_init_lock = threading.Lock()


def _ensure_watchdog_metrics() -> dict:
    global _watchdog_metrics
    if _watchdog_metrics is None:
        with _watchdog_init_lock:
            if _watchdog_metrics is None:
                _watchdog_metrics = {
                    "stuck": Gauge(
                        "stuck_requests",
                        "Watched in-flight work currently past its hang "
                        "threshold (deadline x watchdog multiple)",
                    ),
                }
    return _watchdog_metrics


def set_stuck_requests(count: int):
    _ensure_watchdog_metrics()["stuck"].set(float(count))


# ---------------------------------------------------------------------------
# Autoscale decision telemetry: scale-up/down counters per deployment and
# the breach-to-decision latency histogram (how long pressure persisted
# before the controller acted — the "reacting in seconds, not minutes"
# proof). Recorded in the serve controller process; events themselves live
# in the controller's event log (GCS key serve:autoscale_log).
# ---------------------------------------------------------------------------

_AUTOSCALE_DECISION_BOUNDARIES_S = [
    0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60, 120, 300,
]

_autoscale_metrics: Optional[dict] = None
_autoscale_init_lock = threading.Lock()


def _ensure_autoscale_metrics() -> dict:
    global _autoscale_metrics
    if _autoscale_metrics is None:
        with _autoscale_init_lock:
            if _autoscale_metrics is None:
                _autoscale_metrics = {
                    "up": Counter(
                        "autoscale_scale_up_total",
                        "SLO-autoscaler scale-up decisions applied",
                        tag_keys=("deployment",),
                    ),
                    "down": Counter(
                        "autoscale_scale_down_total",
                        "SLO-autoscaler scale-down decisions applied",
                        tag_keys=("deployment",),
                    ),
                    "decision": Histogram(
                        "autoscale_decision_seconds",
                        "Pressure-onset (or idle-onset) to applied "
                        "decision wall time",
                        boundaries=_AUTOSCALE_DECISION_BOUNDARIES_S,
                        tag_keys=("deployment", "direction"),
                    ),
                }
    return _autoscale_metrics


def record_autoscale_decision(
    deployment: str, direction: str, breach_age_s: float
):
    m = _ensure_autoscale_metrics()
    m["up" if direction == "up" else "down"].inc(
        1.0, {"deployment": deployment}
    )
    m["decision"].observe(
        max(breach_age_s, 0.0),
        {"deployment": deployment, "direction": direction},
    )


def autoscale_counters() -> Dict[str, float]:
    """Process-local totals across deployments (tests + bench)."""
    m = _ensure_autoscale_metrics()
    out: Dict[str, float] = {}
    for label, metric in (("scale_ups", m["up"]), ("scale_downs", m["down"])):
        with metric._lock:
            out[label] = float(sum(metric._values.values()))
    return out


def autoscale_summary(payloads: List[dict]) -> Dict[str, object]:
    """Cluster rollup of autoscaler activity from pushed snapshots
    (state.metrics_summary / dashboard /api/autoscale / CLI)."""
    out: Dict[str, object] = {
        "scale_ups": 0.0,
        "scale_downs": 0.0,
        "by_deployment": {},
        "decision_p50_s": None,
        "decision_p99_s": None,
    }
    by_dep: Dict[str, dict] = out["by_deployment"]  # type: ignore[assignment]
    for payload in payloads:
        for snap in payload.get("metrics", []):
            field = {
                "autoscale_scale_up_total": "scale_ups",
                "autoscale_scale_down_total": "scale_downs",
            }.get(snap.get("name", ""))
            if field is None:
                continue
            for tag_json, value in snap["values"].items():
                out[field] += value
                tags = dict(
                    zip(snap.get("tag_keys", ()), json.loads(tag_json))
                )
                row = by_dep.setdefault(
                    tags.get("deployment", "?"),
                    {"scale_ups": 0.0, "scale_downs": 0.0},
                )
                row[field] += value
    m = merged_histogram(payloads, "autoscale_decision_seconds")
    if m and m["count"]:
        out["decision_p50_s"] = quantile_from_buckets(
            m["boundaries"], m["counts"], 0.50
        )
        out["decision_p99_s"] = quantile_from_buckets(
            m["boundaries"], m["counts"], 0.99
        )
    return out


def weights_summary(payloads: List[dict]) -> Dict[str, object]:
    """Cluster rollup of weight-plane traffic with the logical/wire byte
    split (state.metrics_summary()["weights"]): per direction
    (publish | fetch) the raw leaf bytes, the encoded bytes that actually
    crossed the store/broadcast tree, and their ratio — the compression
    win the int8 chunk codec is buying — plus publish counts by codec
    and a per-model breakdown."""
    out: Dict[str, object] = {
        "publish": {"logical_bytes": 0.0, "wire_bytes": 0.0},
        "fetch": {"logical_bytes": 0.0, "wire_bytes": 0.0},
        "publishes_by_codec": {},
        "by_model": {},
    }
    by_codec: Dict[str, float] = out["publishes_by_codec"]  # type: ignore[assignment]
    by_model: Dict[str, dict] = out["by_model"]  # type: ignore[assignment]
    for payload in payloads:
        for snap in payload.get("metrics", []):
            name = snap.get("name", "")
            field = {
                "weights_broadcast_bytes_total": "logical_bytes",
                "weights_wire_bytes_total": "wire_bytes",
            }.get(name)
            if field is not None:
                for tag_json, value in snap["values"].items():
                    tags = dict(
                        zip(snap.get("tag_keys", ()), json.loads(tag_json))
                    )
                    direction = tags.get("direction", "?")
                    if direction in ("publish", "fetch"):
                        out[direction][field] += value  # type: ignore[index]
                    row = by_model.setdefault(
                        tags.get("model", "?"),
                        {"logical_bytes": 0.0, "wire_bytes": 0.0},
                    )
                    row[field] += value
            elif name == "weights_codec_publish_total":
                for tag_json, value in snap["values"].items():
                    tags = dict(
                        zip(snap.get("tag_keys", ()), json.loads(tag_json))
                    )
                    codec = tags.get("codec", "?")
                    by_codec[codec] = by_codec.get(codec, 0.0) + value
    for direction in ("publish", "fetch"):
        row = out[direction]  # type: ignore[index]
        row["compression_ratio"] = (
            row["logical_bytes"] / row["wire_bytes"]
            if row["wire_bytes"] else None
        )
    return out


def _node_hex() -> str:
    from .. import _worker_api

    worker = _worker_api.maybe_get_core_worker()
    node_id = getattr(worker, "node_id", None) if worker else None
    return node_id.hex() if node_id is not None else ""


def _ensure_pusher():
    """Background thread pushing this process's metrics to the GCS KV."""
    global _pusher_started
    if _pusher_started:
        return
    _pusher_started = True

    def _push_loop():
        from .. import _worker_api

        while True:
            time.sleep(3.0)
            worker = _worker_api.maybe_get_core_worker()
            if worker is None:
                continue
            try:
                # piggyback device telemetry on the push cadence; only when
                # this process already holds a jax backend (no forced
                # import, no forced backend initialisation)
                sample_device_memory()
            except Exception:
                pass
            with _registry_lock:
                snaps = [m._snapshot() for m in _registry.values()]
            if not snaps:
                continue
            # identity-tagged payload: prometheus_text renders gauges as
            # per-worker series, and the GCS reaps this key when it observes
            # this worker's (or node's) death
            payload = {
                "worker_id": worker.worker_id.hex(),
                "node_id": _node_hex(),
                "pid": os.getpid(),
                "ts": time.time(),
                "metrics": snaps,
            }
            try:
                _worker_api.run_on_worker_loop(
                    worker.client_pool.get(*worker.gcs_address).call(
                        "kv_put",
                        gcs_keys.METRICS.key(worker.worker_id.hex()),
                        json.dumps(payload).encode(),
                        True,
                    ),
                    timeout=5,
                )
            except Exception:
                pass

    threading.Thread(target=_push_loop, daemon=True, name="metrics-push").start()


def fetch_metric_payloads(gcs_call) -> List[dict]:
    """Fetch every worker's pushed snapshot through ``gcs_call(method,
    *args)`` and normalize to identity-tagged payload dicts. Shared by
    prometheus_text (driver side) and the dashboard (GCS-client side)."""
    payloads: List[dict] = []
    for key in gcs_call("kv_keys", gcs_keys.METRICS.scan):
        raw = gcs_call("kv_get", key)
        if raw is None:
            continue
        doc = json.loads(raw)
        if isinstance(doc, list):  # legacy untagged push
            doc = {"worker_id": key.split(":", 1)[-1], "node_id": "",
                   "metrics": doc}
        payloads.append(doc)
    return payloads


def render_prometheus(payloads: List[dict]) -> str:
    """Aggregate pushed snapshots into Prometheus exposition format
    (reference: metrics agent -> /metrics endpoint). Counters and
    histograms with the same (name, labels) across workers are summed into
    ONE series; GAUGES are per-worker facts (summing ``weights_staleness``
    over N workers is meaningless), so each worker's gauge renders as its
    own series distinguished by a ``worker`` label. Histograms render
    cumulative ``_bucket``/``_sum``/``_count`` series as the format
    requires."""
    # merged[name] = {"snap": first snapshot, "values": {label_tuple: sum},
    #                 "counts": {label_tuple: [bucket sums]},
    #                 "series": {(worker, tag_json): value}}  (gauges only)
    merged: Dict[str, dict] = {}
    for payload in payloads:
        worker_tag = str(payload.get("worker_id", ""))[:12]
        for snap in payload.get("metrics", []):
            name = snap["name"]
            m = merged.setdefault(
                name, {"snap": snap, "values": {}, "counts": {},
                       "series": {}}
            )
            if snap["type"] == "gauge":
                for tag_json, value in snap["values"].items():
                    m["series"][(worker_tag, tag_json)] = value
                continue
            for tag_json, value in snap["values"].items():
                m["values"][tag_json] = m["values"].get(tag_json, 0.0) + value
            for tag_json, counts in snap.get("counts", {}).items():
                cur = m["counts"].get(tag_json)
                if cur is None:
                    m["counts"][tag_json] = list(counts)
                else:
                    m["counts"][tag_json] = [
                        a + b for a, b in zip(cur, counts)
                    ]
    lines: List[str] = []
    for name, m in merged.items():
        snap = m["snap"]
        kind = {"counter": "counter", "gauge": "gauge"}.get(
            snap["type"], "histogram"
        )
        lines.append(f"# HELP {name} {snap['description']}")
        lines.append(f"# TYPE {name} {kind}")
        if kind == "gauge":
            for (worker_tag, tag_json), value in m["series"].items():
                label_pairs = [
                    (k, v)
                    for k, v in zip(snap["tag_keys"], json.loads(tag_json))
                    if v
                ]
                if worker_tag:
                    label_pairs.append(("worker", worker_tag))
                lines.append(_sample(name, label_pairs, value))
            continue
        for tag_json in m["values"]:
            label_pairs = [
                (k, v)
                for k, v in zip(snap["tag_keys"], json.loads(tag_json))
                if v
            ]
            if kind == "histogram":
                counts = m["counts"].get(tag_json, [])
                bounds = snap.get("boundaries", [])
                cum = 0
                for bound, c in zip(bounds, counts):
                    cum += c
                    lines.append(
                        _sample(
                            f"{name}_bucket",
                            label_pairs + [("le", str(bound))],
                            cum,
                        )
                    )
                cum += counts[len(bounds)] if len(counts) > len(bounds) else 0
                lines.append(
                    _sample(
                        f"{name}_bucket", label_pairs + [("le", "+Inf")], cum
                    )
                )
                lines.append(_sample(f"{name}_count", label_pairs, cum))
                lines.append(
                    _sample(f"{name}_sum", label_pairs, m["values"][tag_json])
                )
            else:
                lines.append(
                    _sample(name, label_pairs, m["values"][tag_json])
                )
    return "\n".join(lines) + "\n"


def prometheus_text() -> str:
    """Cluster-wide /metrics payload, aggregated from every worker's GCS
    push (see render_prometheus for the aggregation semantics)."""
    from .. import _worker_api

    worker = _worker_api.get_core_worker()

    def _call(method, *args):
        return _worker_api.run_on_worker_loop(
            worker.client_pool.get(*worker.gcs_address).call(method, *args)
        )

    return render_prometheus(fetch_metric_payloads(_call))


def device_rows(payloads: List[dict]) -> List[dict]:
    """Per-device HBM rows aggregated from pushed snapshots (dashboard
    /api/devices): one row per (node, device) with used/limit bytes."""
    rows: Dict[tuple, dict] = {}
    for payload in payloads:
        for snap in payload.get("metrics", []):
            field = {
                "tpu_hbm_used_bytes": "used",
                "tpu_hbm_limit_bytes": "limit",
            }.get(snap["name"])
            if field is None:
                continue
            for tag_json, value in snap["values"].items():
                tags = dict(zip(snap["tag_keys"], json.loads(tag_json)))
                key = (tags.get("node", ""), tags.get("device", ""))
                row = rows.setdefault(
                    key,
                    {
                        "node": key[0],
                        "device": key[1],
                        "kind": tags.get("kind", ""),
                        "used": 0.0,
                        "limit": 0.0,
                    },
                )
                row[field] = value
    return [rows[k] for k in sorted(rows)]


def _escape_label_value(value) -> str:
    """Prometheus exposition escaping for label values: backslash, double
    quote, and newline (a model name with a quote must not corrupt the
    scrape)."""
    return (
        str(value)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def _sample(name: str, label_pairs, value) -> str:
    labels = ",".join(
        f'{k}="{_escape_label_value(v)}"' for k, v in label_pairs
    )
    label_str = f"{{{labels}}}" if labels else ""
    return f"{name}{label_str} {value}"
