"""Tracing: spans around task submission/execution + timeline export.

Role-equivalent of the reference's tracing helper
(python/ray/util/tracing/tracing_helper.py:165-221 — OpenTelemetry spans
patched around ``.remote()`` and task execution) and of ``ray timeline``
(chrome-trace export of per-task profile events). Spans here are recorded
by a dependency-free in-process recorder; the cluster-wide timeline is
reconstructed from the GCS task-event store (per-state timestamps), and
device-side profiling delegates to ``jax.profiler`` (the TPU-native
equivalent of NVTX ranges).
"""

from __future__ import annotations

import contextvars
import json
import os
import sys
import threading
import time
import uuid
from contextlib import contextmanager, nullcontext
from typing import Any, Dict, List, Optional, Tuple

_lock = threading.Lock()
_spans: List[dict] = []
_spans_cap = 50000  # local backstop mirroring the GCS store's cap
_enabled = os.environ.get("RAY_TPU_TRACE", "") not in ("", "0")

# -- distributed trace context ----------------------------------------------
# Every span carries (trace_id, span_id, parent_id). The ACTIVE context is a
# per-thread stack of open spans; when a thread has no open span the task
# context (restored from TaskSpec.trace_context around task execution) is the
# parent. The task context is a ContextVar, NOT a module global: the worker
# RPC server dispatches each push_task/actor_task via asyncio.ensure_future,
# so many task-execution coroutines interleave on one event loop — a
# ContextVar is coroutine-local under asyncio, so concurrent tasks can't
# clobber each other's context and exits can't restore a stale one. User code
# running in executor threads inherits it via contextvars.copy_context()
# handoff at the run_in_executor call sites (core_worker._run_traced).
_tls = threading.local()
_task_context: contextvars.ContextVar[Optional[Dict[str, str]]] = (
    contextvars.ContextVar("ray_tpu_task_context", default=None)
)
# one trace per process for submissions with no enclosing span, so all
# root-level tasks of one driver loop correlate in the timeline
_root_trace_id: Optional[str] = None

# spans not yet streamed to the GCS span store
_flush_cursor = 0
_flush_lock = threading.Lock()  # serializes read-push-advance in flush_spans
_span_pusher_started = False


def _new_id() -> str:
    return uuid.uuid4().hex[:16]


def enable_tracing():
    """Turn on span recording in this process (reference:
    ray.init(_tracing_startup_hook=...))."""
    global _enabled
    _enabled = True


def is_tracing_enabled() -> bool:
    """True when this process records spans — either statically (the
    RAY_TPU_TRACE env / enable_tracing()) or dynamically because it is
    executing a task whose submitter propagated a trace context (workers
    need no env of their own: the trace follows the task)."""
    return _enabled or _task_context.get() is not None


def current_context() -> Optional[Dict[str, str]]:
    """The active span context: innermost open span of this thread, else
    the restored task context."""
    stack = getattr(_tls, "stack", None)
    if stack:
        return stack[-1]
    return _task_context.get()


def inject_context() -> Optional[Dict[str, str]]:
    """Context to stamp into a TaskSpec at .remote() time; None when
    tracing is off (zero per-task cost on the untraced hot path)."""
    if not is_tracing_enabled():
        return None
    ctx = current_context()
    if ctx is None:
        # root of the process-wide trace: submissions with no enclosing span
        # still correlate (every task of one driver loop shares a trace)
        return {"trace_id": _root_trace(), "span_id": ""}
    return dict(ctx)


def _root_trace() -> str:
    """The per-process trace_id for spans/submissions with no enclosing
    context, created once so all root-level work of one driver correlates."""
    global _root_trace_id
    if _root_trace_id is None:
        with _lock:
            if _root_trace_id is None:
                _root_trace_id = _new_id()
    return _root_trace_id


@contextmanager
def trace_span(name: str, category: str = "app", **attrs):
    """Record one span (reference: tracing_helper span context managers),
    linked to the enclosing span/task context."""
    if not is_tracing_enabled():
        yield
        return
    parent = current_context()
    ctx = {
        "trace_id": parent["trace_id"] if parent else _root_trace(),
        "span_id": _new_id(),
    }
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    stack.append(ctx)
    start = time.perf_counter()
    wall = time.time()
    try:
        yield
    finally:
        dur = time.perf_counter() - start
        stack.pop()
        _record_span(
            name, category, wall, dur,
            ctx["trace_id"], ctx["span_id"],
            (parent or {}).get("span_id", ""), attrs,
        )


@contextmanager
def task_execution_span(name: str, ctx: Optional[Dict[str, str]], **attrs):
    """Restore a propagated trace context around task execution and record
    the execute span. Installed in the coroutine-local task context so
    nested ``.remote()`` submissions from user code parent to this
    execution (executor threads see it via copy_context handoff)."""
    if ctx is None and not _enabled:
        yield
        return
    span_ctx = {
        "trace_id": (ctx or {}).get("trace_id") or _root_trace(),
        "span_id": _new_id(),
    }
    token = _task_context.set(span_ctx)
    start = time.perf_counter()
    wall = time.time()
    try:
        yield
    finally:
        _task_context.reset(token)
        _record_span(
            name, "ray_tpu.execute", wall, time.perf_counter() - start,
            span_ctx["trace_id"], span_ctx["span_id"],
            (ctx or {}).get("span_id", ""), attrs,
        )


def new_trace_context(trace_id: Optional[str] = None) -> Dict[str, str]:
    """Mint a root request context (proxy ingress: honor an inbound
    X-Trace-Id or start a fresh trace). The empty span_id marks it a trace
    root; the first span opened under it becomes the top of the tree."""
    return {"trace_id": trace_id or _new_id(), "span_id": ""}


@contextmanager
def request_span(name: str, ctx: Optional[Dict[str, str]],
                 category: str = "serve", **attrs):
    """Adopt a propagated request context (or mint one when this process
    traces statically) around one serve-request stage, recording the stage
    span. Yields the active span context so callers can read the trace_id
    for histogram exemplars / response headers. Installed in the
    coroutine-local task context, so nested ``.remote()`` submissions and
    ``trace_span`` blocks opened downstream parent to this stage — the
    serve-side twin of ``task_execution_span``.

    ``ctx is None`` with static tracing off is the untraced hot path: no
    allocation, no span, yields None.
    """
    if ctx is None and not _enabled:
        yield None
        return
    span_ctx = {
        "trace_id": (ctx or {}).get("trace_id") or _root_trace(),
        "span_id": _new_id(),
    }
    token = _task_context.set(span_ctx)
    start = time.perf_counter()
    wall = time.time()
    try:
        yield span_ctx
    finally:
        _task_context.reset(token)
        _record_span(
            name, category, wall, time.perf_counter() - start,
            span_ctx["trace_id"], span_ctx["span_id"],
            (ctx or {}).get("span_id", ""), attrs,
        )


def child_context(ctx: Optional[Dict[str, str]]) -> Optional[Dict[str, str]]:
    """Mint a child span context under ``ctx`` (or the root trace) WITHOUT
    touching the coroutine-local task context — for async generators,
    where a set/reset token pair cannot legally bracket the yields (each
    step may run in a different caller context). Children parent to the
    returned ctx as it streams; :func:`emit_closed_span` records the span
    itself once the stream ends. None on the untraced path."""
    if ctx is None and not _enabled:
        return None
    return {
        "trace_id": (ctx or {}).get("trace_id") or _root_trace(),
        "span_id": _new_id(),
    }


def emit_closed_span(name: str, span_ctx: Dict[str, str],
                     parent_ctx: Optional[Dict[str, str]], start_wall: float,
                     dur_s: float, category: str = "serve", **attrs) -> None:
    """Record a span whose identity (:func:`child_context`) was minted
    before it closed, so spans emitted while it was open could already
    parent to it."""
    _record_span(
        name, category, start_wall, dur_s,
        span_ctx["trace_id"], span_ctx["span_id"],
        (parent_ctx or {}).get("span_id", ""), attrs,
    )


def emit_span(name: str, ctx: Optional[Dict[str, str]], start_wall: float,
              dur_s: float, category: str = "serve",
              **attrs) -> Optional[str]:
    """Record one already-completed span against an explicit parent
    context. For stages whose start and end happen on different threads
    (the continuous-batching engine admits and retires requests under its
    lock on whichever caller thread steps it), where no context manager
    can bracket the interval. Returns the new span_id (usable as a parent
    for follow-on stages), or None when the span was not recorded."""
    if ctx is None:
        if not _enabled:
            return None
        ctx = {"trace_id": _root_trace(), "span_id": ""}
    span_id = _new_id()
    _record_span(
        name, category, start_wall, dur_s,
        ctx.get("trace_id") or _root_trace(), span_id,
        ctx.get("span_id", ""), attrs,
    )
    return span_id


def _record_span(name, category, wall, dur_s, trace_id, span_id, parent_id,
                 attrs):
    span = {
        "name": name,
        "cat": category,
        "ph": "X",
        "ts": wall * 1e6,
        "dur": dur_s * 1e6,
        "pid": os.getpid(),
        "tid": threading.get_ident() % 100000,
        "trace_id": trace_id,
        "span_id": span_id,
        "parent_id": parent_id,
        "args": {**attrs, "trace_id": trace_id, "span_id": span_id,
                 "parent_id": parent_id},
    }
    global _flush_cursor
    with _lock:
        _spans.append(span)
        if len(_spans) > _spans_cap:
            # backstop when no pusher can drain (no core worker yet):
            # drop the oldest spans, keeping the flush cursor aligned
            drop = len(_spans) - _spans_cap
            del _spans[:drop]
            _flush_cursor = max(0, _flush_cursor - drop)
    if not _span_pusher_started:
        _ensure_span_pusher()


def get_spans() -> List[dict]:
    with _lock:
        return list(_spans)


def clear_spans():
    global _flush_cursor
    with _lock:
        _spans.clear()
        _flush_cursor = 0


# -- span streaming to the GCS span store -----------------------------------


def flush_spans():
    """Push spans recorded since the last flush to the GCS span store and
    trim the flushed prefix from the local buffer (flushed spans live in
    the GCS store; keeping them here would leak for the worker's lifetime).
    Called from the background pusher; also public so a short-lived task
    can flush deterministically before returning."""
    global _flush_cursor
    from .. import _worker_api

    worker = _worker_api.maybe_get_core_worker()
    if worker is None:
        return
    # one flusher at a time: concurrent read-push-trim would double-push
    # the same batch (consuming the capped GCS store with duplicates)
    with _flush_lock:
        with _lock:
            batch = _spans[_flush_cursor:]
            cursor = len(_spans)
        if not batch:
            return
        try:
            _worker_api.run_on_worker_loop(
                worker.client_pool.get(*worker.gcs_address).call(
                    "report_spans", batch
                ),
                timeout=5,
            )
            with _lock:
                # clear_spans may have raced the push; never trim past the
                # current buffer
                del _spans[: min(cursor, len(_spans))]
                _flush_cursor = 0
        except Exception:
            pass  # spans are best-effort observability


def _ensure_span_pusher():
    """Background thread streaming finished spans to the GCS (reference:
    worker-side TaskEventBuffer flushes; here for spans, so a WORKER's
    spans outlive its process and join the cluster timeline)."""
    global _span_pusher_started
    with _lock:
        if _span_pusher_started:
            return
        _span_pusher_started = True

    def _loop():
        while True:
            time.sleep(1.0)
            flush_spans()

    threading.Thread(target=_loop, daemon=True, name="span-push").start()


def export_spans(filename: str):
    """Write this process's spans as a chrome trace."""
    with open(filename, "w") as f:
        json.dump({"traceEvents": get_spans()}, f)


def build_chrome_trace(events: List[dict]) -> List[dict]:
    """GCS task-event records -> chrome-trace complete ("X") events.
    Shared by ``timeline()`` and the dashboard's /api/timeline."""
    trace: List[dict] = []
    for ev in events:
        start = ev.get("ts_running")
        if start is None:
            continue
        end = ev.get("ts_finished") or ev.get("ts_failed") or time.time()
        trace.append(
            {
                "name": ev.get("name", ev.get("task_id", "?")),
                "cat": ev.get("type", "TASK"),
                "ph": "X",
                "ts": start * 1e6,
                "dur": max(end - start, 0.0) * 1e6,
                "pid": ev.get("node_id", "node"),
                "tid": ev.get("worker_pid", 0),
                "args": {
                    "task_id": ev.get("task_id"),
                    "state": ev.get("state"),
                    "attempt": ev.get("attempt", 0),
                },
            }
        )
    return trace


def merge_span_events(trace: List[dict], *span_lists: List[dict]) -> List[dict]:
    """Append span lists onto a chrome trace, deduplicating by span_id (a
    driver's spans exist both locally and in the GCS store). Shared by
    ``timeline()`` and the dashboard's /api/timeline."""
    seen = set()
    for spans in span_lists:
        for span in spans:
            sid = span.get("span_id")
            if sid and sid in seen:
                continue
            if sid:
                seen.add(sid)
            trace.append(span)
    return trace


def timeline(filename: Optional[str] = None) -> List[dict]:
    """Cluster-wide timeline as chrome-trace events: GCS task-state events
    plus EVERY node's spans from the GCS span store, plus this process's
    not-yet-flushed spans (reference: `ray timeline` building a chrome
    trace from profile events). Returns the events; also writes
    ``filename`` if given."""
    from .. import _worker_api

    worker = _worker_api.get_core_worker()
    gcs = worker.client_pool.get(*worker.gcs_address)
    events = _worker_api.run_on_worker_loop(
        gcs.call("list_task_events", None, 100000)
    )
    trace = build_chrome_trace(events)
    try:
        cluster_spans = _worker_api.run_on_worker_loop(
            gcs.call("list_spans", 100000)
        )
    except Exception:
        cluster_spans = []
    merge_span_events(trace, cluster_spans, get_spans())
    if filename:
        with open(filename, "w") as f:
            json.dump({"traceEvents": trace}, f)
    return trace


# -- device profiling (TPU): jax.profiler passthrough -----------------------


@contextmanager
def device_profile(logdir: str):
    """Capture a device (TPU/XLA) profile around a block of jax work
    (SURVEY §5: 'jax.profiler traces + XPlane export' as the TPU analogue of
    the reference's NVTX/torch profiling flags). Writes an XPlane trace a
    TensorBoard profiler plugin can open:

        with ray_tpu.util.tracing.device_profile("/tmp/prof"):
            train_step(...)

    The Python tracer is off (it slows the host loop it would be
    measuring); the host plane still carries every ``step_span`` /
    ``annotate_device_trace`` region with its counts, on the device
    planes' clock.
    """
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


_TraceAnnotation = None
_NO_REGION = nullcontext()


def annotate_device_trace(name: str, **counts):
    """Named region in the profiler's own trace (jax.profiler.TraceAnnotation):
    lands in the ``/host:CPU`` plane of any running ``jax.profiler`` session,
    on the clock the device planes use, with ``counts`` as the event's
    stats; the region's ``set_metadata(**counts)`` adds, before it closes,
    the ones known only inside it. With no session running
    it is a no-op of about a microsecond, so hot paths call it
    unconditionally. A process that has not imported jax has no session to
    write to, and is not made to import it."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        if "jax" not in sys.modules:
            return _NO_REGION
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(name, **counts)


_program_facts: Dict[Tuple[str, tuple], dict] = {}


def program_fact(name: str, **counts) -> None:
    """What a jitted program fixed while it was traced (``train.remat_plan``:
    which values its backward pass keeps), as an instant region with
    ``counts`` as its stats. A profiler session is as a rule opened long
    after the trace that made the program, so the fact is also kept, once a
    distinct set of counts (the newest sixteen of a process that keeps
    tracing new shapes), for ``replay_program_facts``."""
    _program_facts[name, tuple(sorted(counts.items()))] = counts
    while len(_program_facts) > 16:
        del _program_facts[next(iter(_program_facts))]
    with annotate_device_trace(name, **counts):
        pass


# ``worker.startup``: what this process did before its first step. Whole
# microseconds, each measured once where the work happens: milestones
# (``startup_reached``) tile the time from the kernel's start of the process,
# phases (``startup_phase``) are stopwatches after the last milestone, and
# ``startup_ready`` closes the record with what they leave (``other_us``).
_startup: Dict[str, Any] = {}
_startup_clock: Dict[str, int] = {}  # start_ns (perf_counter's), reached_us


def _since_process_start_us() -> int:
    if not _startup_clock:
        # /proc/self/stat field 22 (ticks since boot) against CLOCK_BOOTTIME:
        # the stat file's btime has a second's resolution
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age_us = round(1e6 * (time.clock_gettime(time.CLOCK_BOOTTIME)
                              - ticks / os.sysconf("SC_CLK_TCK")))
        _startup_clock["start_ns"] = time.perf_counter_ns() - age_us * 1000
        _startup_clock["reached_us"] = 0
        _startup["process_start_wall_us"] = time.time_ns() // 1000 - age_us
    return (time.perf_counter_ns() - _startup_clock["start_ns"]) // 1000


def startup_reached(key: str) -> None:
    """A milestone of this process's start: ``<key>_us`` is the time since
    the one before it (the first: since the process started). Once a key,
    and not after ``startup_ready``."""
    name = key + "_us"
    if name in _startup or "ready_us" in _startup:
        return
    now = _since_process_start_us()
    _startup[name] = now - _startup_clock["reached_us"]
    _startup_clock["reached_us"] = now


class startup_phase:
    """A stopwatch around one phase of the start (never nested in another):
    ``us`` as it closes, and into the record as ``<key>_us`` with what
    ``count()`` added, unless the process is ready already (a second replica of one process
    times its own weights and leaves the record alone)."""

    __slots__ = ("_key", "_counts", "_t0", "us")

    def __init__(self, key: str):
        self._key, self._counts = key, {}

    def count(self, **counts) -> None:
        self._counts.update(counts)

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.us = (time.perf_counter_ns() - self._t0) // 1000
        if exc_type is None and "ready_us" not in _startup:
            name = self._key + "_us"
            _startup[name] = _startup.get(name, 0) + self.us
            _startup.update(self._counts)
        return False


def startup_ready() -> None:
    """The process can do what it was started for (a replica is built, a
    training loop is entered): ``ready_us`` since the process started,
    ``other_us``, what the milestones and phases leave of it, and
    ``compile_at_ready_us``, what making programs had cost by now (the
    compile totals a write carries less this came after ``ready_us``)."""
    if "ready_us" in _startup:
        return
    from .._internal import compile_cache

    now = _since_process_start_us()
    named = sum(v for k, v in _startup.items()
                if k.endswith("_us") and k != "process_start_wall_us")
    _startup["other_us"] = now - named
    _startup["ready_us"] = now
    totals = compile_cache.totals_us()
    if totals:
        _startup["compile_at_ready_us"] = (
            totals["compile_us"] + totals["trace_lower_us"])


def startup_record() -> Optional[Dict[str, Any]]:
    """``worker.startup`` as it stands: the phases, and the compile totals
    of this moment (programs compile lazily, so they run on after
    ``ready_us``; absent where nothing counts them). None before
    ``startup_ready``."""
    if "ready_us" not in _startup:
        return None
    from .._internal import compile_cache

    return {**_startup, **compile_cache.totals_us()}


def replay_program_facts() -> None:
    """Write every kept ``program_fact``, and ``worker.startup`` once the
    process is ready, into the profiler's trace again, at a step boundary
    (``train.report``, every 32nd turn of the engine's stepping thread): a
    session that opened after the programs were traced and the process
    started then carries what they decided and what it cost. Microseconds
    with no session running."""
    # a copy: the stepping thread replays while another thread may trace
    for (name, _), counts in list(_program_facts.items()):
        with annotate_device_trace(name, **counts):
            pass
    record = startup_record()
    if record is not None:
        with annotate_device_trace("worker.startup", **record):
            pass


class step_span:
    """One boundary of a serving step, instrumented once: always a region
    in the profiler's trace (``annotate_device_trace(name, **counts)``), and
    on exit also the wall-clock request span ``request_span`` when the
    request carries a trace context (``trace`` = ``{"ctx": ...}`` as the
    engine keeps it per traced request, else None). The request span's
    attributes are ``counts`` plus ``attrs`` plus whatever ``set()`` added
    inside the block; ``cancel()`` drops it (the region stays). ``count()``
    adds counts known only inside the block to both."""

    __slots__ = ("_region", "_trace", "_name", "_category", "_attrs", "_wall")

    def __init__(self, name: str, trace: Optional[dict] = None, *,
                 request_span: Optional[str] = None, category: str = "engine",
                 attrs: Optional[dict] = None, **counts):
        self._region = annotate_device_trace(name, **counts)
        self._trace = trace
        if trace is not None:
            self._name = request_span or name
            self._category = category
            self._attrs = {**counts, **(attrs or {})}

    def set(self, **attrs) -> None:
        if self._trace is not None:
            self._attrs.update(attrs)

    def count(self, **counts) -> None:
        if self._region is not _NO_REGION:
            self._region.set_metadata(**counts)
        self.set(**counts)

    def cancel(self) -> None:
        self._trace = None

    def __enter__(self):
        if self._trace is not None:
            self._wall = time.time()
        self._region.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._region.__exit__(exc_type, exc, tb)
        if self._trace is not None and exc_type is None:
            emit_span(
                self._name, self._trace["ctx"], self._wall,
                time.time() - self._wall, category=self._category,
                **self._attrs,
            )
        return False
