"""End-to-end serve request tracing, flight recorder, and hang watchdog
(reference model: python/ray/serve request-context propagation tests +
export-event tests). One request entering the HTTP proxy must come out
as ONE chrome trace — proxy, handle-route, replica-admission, and (for
LLM deployments) engine/kvcache spans under a single trace_id — and the
flight recorder + watchdog must make a killed or hung replica explainable
after the fact."""

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu import testing
from ray_tpu.util import events
from ray_tpu.util import state
from ray_tpu.util import tracing
from ray_tpu.util import watchdog


@pytest.fixture(scope="module")
def cluster(http_port):
    """Yields the base URL of the cluster's HTTP proxy, on a port of this
    module's own (``conftest.http_port``)."""
    ray_tpu.init(num_cpus=8, resources={"TPU": 4})
    serve.start(http_port=http_port)
    yield f"http://127.0.0.1:{http_port}"
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.fixture(autouse=True)
def _cleanup_apps():
    yield
    try:
        for app in list(serve.status().keys()):
            serve.delete(app)
    except Exception:
        pass


def _wait_replicas(app, n, timeout=30):
    deadline = time.time() + timeout
    while time.time() < deadline:
        rows = [
            r for r in testing.list_serve_replicas(app)
            if r["state"] == "RUNNING" and r["pid"]
        ]
        if len(rows) == n:
            return rows
        time.sleep(0.1)
    raise TimeoutError(f"{app}: never reached {n} RUNNING replicas with pids")


def _spans_for_trace(trace_id):
    """All spans in the merged cluster timeline carrying ``trace_id``."""
    return [
        s for s in tracing.timeline()
        if s.get("span_id") and s.get("trace_id") == trace_id
    ]


# ---------------------------------------------------------------------------
# tentpole: one HTTP request -> one trace, proxy to replica
# ---------------------------------------------------------------------------


def test_http_trace_chain_end_to_end(cluster):
    """POST with an X-Trace-Id header: the proxy honors it as the trace
    root, the id is echoed back, and the merged timeline shows
    serve.proxy -> serve.route / serve.replica -> serve.admission all
    sharing that trace_id with intact parent links — across the proxy,
    driver, and replica processes."""

    @serve.deployment
    class Echo:
        def __call__(self, body):
            return {"echo": body}

    serve.run(Echo.bind(), name="traceapp", route_prefix="/traced")
    _wait_replicas("traceapp", 1)

    trace_id = "trace-chain-e2e-test"
    payload = json.dumps({"x": 1}).encode()
    req = urllib.request.Request(
        f"{cluster}/traced", data=payload,
        headers={"Content-Type": "application/json",
                 "X-Trace-Id": trace_id},
    )
    deadline = time.time() + 30
    resp = None
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(req, timeout=5) as r:
                echoed = r.headers.get("X-Trace-Id")
                body = json.loads(r.read())
                resp = (echoed, body)
            break
        except Exception:
            time.sleep(0.5)
    assert resp is not None, "proxy never answered"
    echoed, body = resp
    # the caller joins its latency record to server spans via this echo
    assert echoed == trace_id
    assert body["result"] == {"echo": {"x": 1}}

    # spans flush to the GCS span store on a 1s cadence from the proxy
    # actor AND the replica worker; poll the merged timeline for the chain
    wanted = {"serve.proxy", "serve.route", "serve.replica",
              "serve.admission"}
    deadline = time.time() + 20
    by_name = {}
    while time.time() < deadline:
        spans = _spans_for_trace(trace_id)
        by_name = {s["name"]: s for s in spans}
        if wanted <= set(by_name):
            break
        time.sleep(0.5)
    assert wanted <= set(by_name), (
        f"missing spans: {wanted - set(by_name)}"
    )

    proxy = by_name["serve.proxy"]
    route = by_name["serve.route"]
    replica = by_name["serve.replica"]
    admission = by_name["serve.admission"]
    # proxy span is the trace top (parent = the minted root, empty span_id)
    assert proxy["parent_id"] == ""
    # the handle's route span and the replica span both parent under it
    assert route["parent_id"] == proxy["span_id"]
    assert replica["parent_id"] == proxy["span_id"]
    # admission nests inside the replica stage
    assert admission["parent_id"] == replica["span_id"]
    # proxy, route (proxy process), and replica spans span >= 2 processes
    assert len({proxy["pid"], replica["pid"]}) == 2
    # the route span records where the request was sent
    assert route["args"]["deployment"]


def test_handle_failover_attempt_span_and_replica_id(cluster, monkeypatch):
    """Chaos kill mid-request: the retry appears in the trace as a sibling
    serve.attempt span tagged with the excluded replica and the reason,
    and DeploymentResponse.replica_id() names the replica the FINAL
    resubmission landed on."""
    # keep driver spans in the local ring: the 1s pusher trims flushed
    # spans into the GCS store, racing the get_spans() reads below
    monkeypatch.setattr(tracing, "flush_spans", lambda: None)

    @serve.deployment(num_replicas=2)
    class Slow:
        def __call__(self, x):
            time.sleep(0.8)
            return x * 2

    tracing.enable_tracing()
    try:
        handle = serve.run(Slow.bind(), name="killtrace", _proxy=False)
        rows = _wait_replicas("killtrace", 2)
        known = {r["replica_id"] for r in rows}

        # The kill only produces a failover if a request was in flight on
        # the doomed replica — under host load the dispatch window can
        # race the kill, so retry the round (the controller reconciles
        # the pool back to 2 replicas) until an attempt span appears.
        attempts = []
        final_rids = []
        for _ in range(3):
            responses = [handle.remote(i) for i in range(8)]
            time.sleep(0.3)  # let requests land on both replicas
            killed_rid, pid = testing.kill_serve_replica("killtrace")
            assert killed_rid is not None and pid

            results = [r.result(timeout_s=30) for r in responses]
            assert sorted(results) == [i * 2 for i in range(8)]

            # every response knows its outcome replica, and none of them
            # name the corpse — failover re-points replica_id at the
            # survivor
            final_rids = [r.replica_id() for r in responses]
            assert all(rid is not None for rid in final_rids)
            assert killed_rid not in final_rids

            # the failover is a span, not just a counter: sibling attempt
            # spans under the request trace, tagged with what was excluded
            attempts = [
                s for s in tracing.get_spans()
                if s["name"] == "serve.attempt"
            ]
            if attempts:
                break
            rows = _wait_replicas("killtrace", 2)
            known |= {r["replica_id"] for r in rows}
        assert attempts, "no serve.attempt span after 3 chaos kills"
        att = attempts[-1]["args"]
        assert att["deployment"].endswith("Slow")
        assert att["attempt"] >= 1
        assert att["reason"]
        assert killed_rid in att["excluded"]
        assert att["replica"] in known | set(final_rids)
        assert attempts[-1]["trace_id"]
    finally:
        tracing._enabled = os.environ.get(
            "RAY_TPU_TRACE", "") not in ("", "0")


def test_engine_kvcache_spans_join_request_trace(monkeypatch):
    """Clusterless engine: a traced generate() emits queue-wait, prefill,
    decode, and kvcache acquire/assemble/commit spans that all join the
    caller's trace (the stages `ray_tpu timeline` shows inside the
    replica span for an LLM deployment)."""
    import jax

    # the suite-wide span pusher (started by earlier cluster tests in this
    # process) trims flushed spans from the local ring; pin them here
    monkeypatch.setattr(tracing, "flush_spans", lambda: None)

    from ray_tpu.kvcache import KVCacheManager
    from ray_tpu.llm.engine import ContinuousBatchingEngine, GenerationRequest
    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.parallel.sharding import unbox_params

    cfg = LlamaConfig.tiny(max_seq_len=128)
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
    kv = KVCacheManager(num_blocks=16, block_size=16)
    eng = ContinuousBatchingEngine(cfg, params, num_slots=2, kv_cache=kv)
    prompt = list(range(7, 7 + 56))

    tracing.enable_tracing()
    tracing.clear_spans()
    try:
        ctx = tracing.new_trace_context()
        with tracing.request_span("test.request", ctx):
            eng.generate([GenerationRequest(token_ids=prompt,
                                            max_new_tokens=2,
                                            temperature=0.0)])
            # second pass hits the cached prefix -> kvcache.assemble
            eng.generate([GenerationRequest(token_ids=prompt,
                                            max_new_tokens=2,
                                            temperature=0.0)])
        spans = tracing.get_spans()
        mine = [s for s in spans if s["trace_id"] == ctx["trace_id"]]
        names = {s["name"] for s in mine}
        wanted = {"engine.queue_wait", "engine.prefill", "engine.decode",
                  "kvcache.acquire", "kvcache.assemble", "kvcache.commit"}
        assert wanted <= names, f"missing: {wanted - names}"
        # the second prefill rode the prefix cache, and the span says so
        prefills = [s for s in mine if s["name"] == "engine.prefill"]
        assert any(s["args"]["hit"] for s in prefills)
        assert any(
            s["args"]["cached_tokens"] == 48 for s in prefills
        )
        # kvcache spans carry the kvcache category for timeline grouping
        assert all(
            s["cat"] == "kvcache" for s in mine
            if s["name"].startswith("kvcache.")
        )
    finally:
        tracing._enabled = os.environ.get(
            "RAY_TPU_TRACE", "") not in ("", "0")
        tracing.clear_spans()


# ---------------------------------------------------------------------------
# flight recorder: always-on events, SIGKILL-surviving, queryable
# ---------------------------------------------------------------------------


def _gcs(method, *args):
    worker = ray_tpu._worker_api.get_core_worker()
    return ray_tpu._worker_api.run_on_worker_loop(
        worker.client_pool.get(*worker.gcs_address).call(method, *args)
    )


def test_flight_recorder_streams_to_gcs(cluster):
    """record_event is always-on (no tracing flag) and the 1s pusher lands
    the event in the GCS store, queryable via state.list_events."""
    marker = f"obs-flight-{os.getpid()}-{time.time_ns()}"
    events.record_event(events.REPLICA_STATE, state="TESTING", marker=marker)

    deadline = time.time() + 15
    found = []
    while time.time() < deadline:
        found = [
            e for e in state.list_events(name="replica_state")
            if e.get("marker") == marker
        ]
        if found:
            break
        time.sleep(0.5)
    assert found, "event never reached the GCS event store"
    ev = found[0]
    assert ev["pid"] == os.getpid()
    assert ev["state"] == "TESTING"
    assert ev["ts"] > 0


def test_serve_lifecycle_events_recorded(cluster):
    """Controller state transitions land in the cluster event store: a
    deploy produces replica_start events post-mortem-queryable by name."""

    @serve.deployment(num_replicas=2)
    class Lifecycled:
        def __call__(self, x):
            return x

    serve.run(Lifecycled.bind(), name="lifeapp", _proxy=False)
    _wait_replicas("lifeapp", 2)

    deadline = time.time() + 15
    starts = []
    while time.time() < deadline:
        starts = [
            e for e in state.list_events(name="replica_start")
            if e.get("deployment", "").endswith("Lifecycled")
        ]
        if len(starts) >= 2:
            break
        time.sleep(0.5)
    assert len(starts) >= 2, "replica_start events never reached the GCS"


def test_flight_recorder_crash_dump_retrievable(cluster):
    """Acceptance: after a worker dies by SIGKILL, its death is stitched
    into the event stream as a synthetic worker_death marker, retrievable
    via the state API and the `ray_tpu events` CLI."""
    from ray_tpu._internal.ids import WorkerID

    ghost = WorkerID.from_random()
    _gcs("report_worker_death", ghost, "chaos-test-kill")

    rows = [
        e for e in state.list_events(name="worker_death")
        if e.get("worker_id") == ghost.hex()
    ]
    assert rows, "no synthetic worker_death event in the GCS store"
    assert rows[0]["reason"] == "chaos-test-kill"
    assert rows[0]["synthetic"] is True

    node = ray_tpu._worker_api.get_node()
    host, port = node.gcs_address
    out = subprocess.run(
        [
            sys.executable, "-m", "ray_tpu.scripts.cli", "events",
            "--address", f"{host}:{port}", "--name", "worker_death",
            "--limit", "1000",
        ],
        capture_output=True, text=True, timeout=60,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr
    listed = json.loads(out.stdout)
    assert any(e.get("worker_id") == ghost.hex() for e in listed)


# ---------------------------------------------------------------------------
# hang watchdog: stuck-request detection with stack capture
# ---------------------------------------------------------------------------


def test_watchdog_captures_stuck_stacks():
    """A watch past its deadline multiple trips the watchdog: all-thread
    stacks land in the flight recorder, the stuck_requests gauge rises,
    and completing the work emits a recovery event and lowers it."""
    from ray_tpu.util import metrics

    before_stuck = watchdog.stuck_count()
    token = watchdog.watch(
        "obs_test_wait", timeout_s=0.01, multiple=1.0,
        deployment="obsapp", replica="r-test",
    )
    time.sleep(0.05)
    watchdog._scan_once()  # deterministic: don't wait for the 1s scanner

    assert watchdog.stuck_count() == before_stuck + 1
    stuck = [
        e for e in events.get_events(name=str(events.WATCHDOG_STUCK))
        if e.get("watch") == "obs_test_wait"
    ]
    assert stuck, "no watchdog_stuck event recorded"
    ev = stuck[-1]
    assert ev["deployment"] == "obsapp" and ev["replica"] == "r-test"
    assert ev["elapsed_s"] >= ev["deadline_s"]
    # the capture is the post-mortem payload: every thread's stack, and
    # this very test frame is in it
    assert "Thread" in ev["stacks"]
    assert "test_watchdog_captures_stuck_stacks" in ev["stacks"]
    # the gauge mirrors the live count
    gauge = metrics._ensure_watchdog_metrics()["stuck"]
    assert gauge._values[()] == float(before_stuck + 1)

    watchdog.unwatch(token)
    assert watchdog.stuck_count() == before_stuck
    rec = [
        e for e in events.get_events(name=str(events.WATCHDOG_RECOVERED))
        if e.get("watch") == "obs_test_wait"
    ]
    assert rec, "no recovery event after unwatch"
    assert rec[-1]["elapsed_s"] >= 0.01
    assert gauge._values[()] == float(before_stuck)


def test_watchdog_fast_requests_never_trip():
    """The common path — watch/unwatch inside the deadline — records
    nothing and leaves the gauge untouched."""
    base = len(events.get_events(name=str(events.WATCHDOG_STUCK)))
    token = watchdog.watch("obs_fast_op", timeout_s=30.0)
    watchdog._scan_once()
    watchdog.unwatch(token)
    assert len(events.get_events(name=str(events.WATCHDOG_STUCK))) == base
    rec = [
        e for e in events.get_events(name=str(events.WATCHDOG_RECOVERED))
        if e.get("watch") == "obs_fast_op"
    ]
    assert not rec  # never stuck -> no recovery noise


def test_event_name_registry():
    """The taxonomy is closed and snake_case: every constant in
    util/events.py is registered, and the registry is what RT007 audits."""
    names = events.registered_event_names()
    assert "replica_state" in names
    assert "watchdog_stuck" in names
    assert "worker_death" in names
    assert "engine_admission_blocked" in names
    assert names == sorted(names)
    for n in names:
        assert n == n.lower() and " " not in n, n
