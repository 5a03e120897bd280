"""Tests for state API, task events, metrics, tracing, CLI (reference
model: python/ray/util/state tests + tests/test_metrics_agent.py +
tests/test_tracing.py)."""

import json
import os
import re
import time

import pytest

import ray_tpu
from ray_tpu.util import state


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=4, resources={"TPU": 4})
    yield
    ray_tpu.shutdown()


def test_list_nodes(cluster):
    nodes = state.list_nodes()
    assert len(nodes) == 1
    assert nodes[0]["state"] == "ALIVE"
    assert nodes[0]["is_head_node"] is True
    assert nodes[0]["resources_total"]["CPU"] == 4.0


def test_task_events_flow(cluster):
    @ray_tpu.remote
    def tracked(x):
        return x + 1

    refs = [tracked.remote(i) for i in range(3)]
    assert ray_tpu.get(refs) == [1, 2, 3]

    @ray_tpu.remote
    def failing():
        raise ValueError("nope")

    with pytest.raises(Exception):
        ray_tpu.get(failing.options(max_retries=0).remote())

    deadline = time.time() + 10
    tasks = []
    while time.time() < deadline:
        tasks = state.list_tasks()
        finished = [t for t in tasks if t.get("state") == "FINISHED"]
        failed = [t for t in tasks if t.get("state") == "FAILED"]
        if len(finished) >= 3 and len(failed) >= 1:
            break
        time.sleep(0.5)
    names = {t.get("name") for t in tasks}
    assert "tracked" in names
    assert any(t.get("state") == "FAILED" for t in tasks)
    summary = state.summarize_tasks()
    assert summary.get("FINISHED", 0) >= 3


def test_list_actors_and_pgs(cluster):
    @ray_tpu.remote
    class Named:
        def ping(self):
            return 1

    a = Named.options(name="observable").remote()
    assert ray_tpu.get(a.ping.remote()) == 1
    actors = state.list_actors()
    assert any(x["name"] == "observable" for x in actors)

    from ray_tpu.util.placement_group import placement_group, remove_placement_group

    pg = placement_group([{"CPU": 1}], strategy="PACK")
    assert pg.ready(timeout=30)
    pgs = state.list_placement_groups()
    assert len(pgs) >= 1
    remove_placement_group(pg)
    ray_tpu.kill(a)


def test_cluster_summary(cluster):
    summary = state.cluster_summary()
    assert summary["nodes"] == 1
    assert summary["alive_nodes"] == 1
    assert "tasks" in summary


def test_metrics_push_and_prometheus(cluster):
    from ray_tpu.util.metrics import Counter, Gauge, Histogram, prometheus_text

    c = Counter("test_requests_total", "reqs", tag_keys=("route",))
    c.inc(3, tags={"route": "/a"})
    c.inc(2, tags={"route": "/b"})
    g = Gauge("test_queue_len", "queue")
    g.set(7)
    h = Histogram("test_latency", "lat", boundaries=[1, 10])
    h.observe(0.5)
    h.observe(5)

    deadline = time.time() + 15
    text = ""
    gauge_re = re.compile(r'test_queue_len\{worker="[0-9a-f]+"\} 7')
    while time.time() < deadline:
        text = prometheus_text()
        if "test_requests_total" in text and gauge_re.search(text):
            break
        time.sleep(1)
    assert 'test_requests_total{route="/a"} 3' in text
    # gauges are per-worker facts: each pushing worker renders its own
    # series under a ``worker`` label instead of a meaningless sum
    assert gauge_re.search(text), text


def test_metrics_from_workers(cluster):
    @ray_tpu.remote
    def record():
        from ray_tpu.util.metrics import Counter

        c = Counter("worker_side_counter", "from a task")
        c.inc(5)
        time.sleep(4)  # let the pusher flush
        return True

    assert ray_tpu.get(record.remote())
    from ray_tpu.util.metrics import prometheus_text

    deadline = time.time() + 10
    while time.time() < deadline:
        if "worker_side_counter" in prometheus_text():
            break
        time.sleep(1)
    assert "worker_side_counter 5" in prometheus_text()


def test_cli_status_and_list(cluster):
    node = ray_tpu._worker_api.get_node()
    host, port = node.gcs_address
    import subprocess
    import sys

    out = subprocess.run(
        [
            sys.executable,
            "-m",
            "ray_tpu.scripts.cli",
            "status",
            "--address",
            f"{host}:{port}",
        ],
        capture_output=True,
        text=True,
        timeout=60,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr
    summary = json.loads(out.stdout)
    assert summary["alive_nodes"] >= 1

    out = subprocess.run(
        [
            sys.executable,
            "-m",
            "ray_tpu.scripts.cli",
            "list",
            "nodes",
            "--address",
            f"{host}:{port}",
        ],
        capture_output=True,
        text=True,
        timeout=60,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr
    nodes = json.loads(out.stdout)
    assert len(nodes) >= 1


# ---------------------------------------------------------------------------
# cluster-wide tracing: trace propagation + timeline merge
# ---------------------------------------------------------------------------


def test_trace_propagation_across_processes(cluster, tmp_path):
    """driver submit -> worker execute -> nested submit -> worker execute:
    all four spans share one trace_id and parent-link across >=2 processes,
    and a single `ray_tpu timeline` export carries task-state bars plus
    driver AND worker spans."""
    from ray_tpu.util import tracing

    tracing.enable_tracing()
    try:

        @ray_tpu.remote
        def obs_child():
            return os.getpid()

        @ray_tpu.remote
        def obs_parent():
            import os as _os

            child_pid = ray_tpu.get(obs_child.remote())
            return _os.getpid(), child_pid

        parent_pid, child_pid = ray_tpu.get(obs_parent.remote())
        driver_pid = os.getpid()
        assert len({driver_pid, parent_pid, child_pid}) >= 2

        def _find(spans, name, pid=None):
            return [
                s for s in spans
                if s.get("name") == name and (pid is None or s["pid"] == pid)
            ]

        # workers flush spans on a 1s cadence; poll the merged timeline
        deadline = time.time() + 20
        chain = None
        while time.time() < deadline and chain is None:
            trace = tracing.timeline()
            spans = [s for s in trace if s.get("span_id")]
            exec_children = _find(spans, "execute:obs_child", child_pid)
            exec_parents = _find(spans, "execute:obs_parent", parent_pid)
            submit_parents = _find(spans, "submit:obs_parent", driver_pid)
            sub_children = _find(spans, "submit:obs_child", parent_pid)
            for ec in exec_children:
                sc = [
                    s for s in sub_children
                    if s["span_id"] == ec["parent_id"]
                ]
                ep = [
                    s for s in exec_parents
                    if sc and s["span_id"] == sc[0]["parent_id"]
                ]
                sp = [
                    s for s in submit_parents
                    if ep and s["span_id"] == ep[0]["parent_id"]
                ]
                if sp:
                    chain = (sp[0], ep[0], sc[0], ec)
                    break
            if chain is None:
                time.sleep(0.5)
        assert chain is not None, "no linked span chain in timeline"
        trace_ids = {s["trace_id"] for s in chain}
        assert len(trace_ids) == 1  # one trace end to end
        # three processes in one chain: driver, parent worker, child worker
        assert {chain[0]["pid"], chain[1]["pid"], chain[3]["pid"]} == {
            driver_pid, parent_pid, child_pid,
        }

        # acceptance: ONE `ray_tpu timeline` export has task bars + both
        # driver and worker spans with the linkage intact
        node = ray_tpu._worker_api.get_node()
        host, port = node.gcs_address
        out_file = str(tmp_path / "timeline.json")
        import subprocess
        import sys

        proc = subprocess.run(
            [
                sys.executable, "-m", "ray_tpu.scripts.cli", "timeline",
                "--address", f"{host}:{port}", "-o", out_file,
            ],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stderr
        doc = json.load(open(out_file))
        events = doc["traceEvents"]
        task_bars = [
            e for e in events
            if e.get("cat") == "NORMAL_TASK" and not e.get("span_id")
        ]
        assert task_bars, "no task-state bars in export"
        exported = {e.get("span_id") for e in events if e.get("span_id")}
        for span in chain:
            assert span["span_id"] in exported
        span_pids = {e["pid"] for e in events if e.get("span_id")}
        assert driver_pid in span_pids and parent_pid in span_pids
    finally:
        import ray_tpu.util.tracing as _t

        _t._enabled = os.environ.get("RAY_TPU_TRACE", "") not in ("", "0")


# ---------------------------------------------------------------------------
# metrics: collective/step/HBM exposure, exposition format, reaping
# ---------------------------------------------------------------------------


def test_collective_and_device_metrics_exposed(cluster):
    """Acceptance: prometheus_text carries collective bytes/latency,
    achieved-bandwidth, scaling-efficiency, and per-device HBM gauges."""
    import numpy as np

    from ray_tpu.collective.cpu_group import GcsStoreGroup
    from ray_tpu.util import metrics
    from ray_tpu.util.metrics import prometheus_text

    group = GcsStoreGroup(1, 0, "obs_group")
    out = group.allreduce(np.ones(1024, np.float32))
    assert float(out.sum()) == 1024.0
    group.barrier()

    sb = metrics.StepBreakdown(role="obs_test")
    with sb.step():
        time.sleep(0.01)
    with sb.step():
        time.sleep(0.01)
    assert metrics.scaling_efficiency("obs_test") is not None

    import jax

    # the sampler only reads a backend this process already initialised
    # (it must never be what claims a chip), so initialise it here
    jax.devices()
    metrics.sample_device_memory()

    wanted = [
        'collective_bytes_total{op="allreduce",backend="gcs_store"',
        "collective_op_latency_ms_bucket",
        "collective_bandwidth_gb_s",
        'scaling_efficiency_ratio{role="obs_test"',
        "tpu_hbm_used_bytes",
        "tpu_hbm_limit_bytes",
    ]
    deadline = time.time() + 15
    text = ""
    while time.time() < deadline:
        text = prometheus_text()
        if all(w in text for w in wanted):
            break
        time.sleep(1)
    for w in wanted:
        assert w in text, f"missing {w}"
    summary = state.metrics_summary()
    assert summary["collective"]["allreduce"]["bytes"] >= 4096
    assert 0 < summary["scaling_efficiency"]["obs_test"] <= 1.0
    assert summary["devices"], "no device HBM rows"


def _parse_exposition(text):
    """Minimal Prometheus text-format parser: [(name, labels, value)]."""
    samples = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = re.fullmatch(
            r"([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})? (.+)", line
        )
        assert m, f"unparseable exposition line: {line!r}"
        name, labels_raw, value = m.groups()
        labels = {}
        if labels_raw:
            matched = 0
            for lm in re.finditer(r'([a-zA-Z_]\w*)="((?:[^"\\]|\\.)*)"',
                                  labels_raw):
                labels[lm.group(1)] = lm.group(2)
                matched += len(lm.group(0))
            # every byte of the label block must parse (catches raw quotes
            # and newlines leaking through)
            assert matched + labels_raw.count(",") == len(labels_raw), (
                f"malformed label block: {labels_raw!r}"
            )
        samples.append((name, labels, float(value)))
    return samples


def test_exposition_round_trip_and_bucket_monotonicity(cluster):
    from ray_tpu.util.metrics import Histogram, prometheus_text

    h = Histogram(
        "obs_roundtrip_ms", "round trip", boundaries=[1, 5, 25],
        tag_keys=("which",),
    )
    for v in (0.5, 3, 3, 10, 100):
        h.observe(v, tags={"which": "a"})
    deadline = time.time() + 15
    while time.time() < deadline:
        if "obs_roundtrip_ms_bucket" in prometheus_text():
            break
        time.sleep(1)
    samples = _parse_exposition(prometheus_text())
    by_series = {}
    counts = {}
    for name, labels, value in samples:
        if name.endswith("_bucket"):
            base = name[: -len("_bucket")]
            key = (base, tuple(sorted(
                (k, v) for k, v in labels.items() if k != "le"
            )))
            le = labels["le"]
            by_series.setdefault(key, []).append(
                (float("inf") if le == "+Inf" else float(le), value)
            )
        elif name.endswith("_count"):
            counts[(name[: -len("_count")], tuple(sorted(labels.items())))] = (
                value
            )
    assert by_series, "no histogram buckets in exposition output"
    for key, buckets in by_series.items():
        buckets.sort()
        values = [v for _, v in buckets]
        assert values == sorted(values), f"non-monotonic buckets: {key}"
        assert buckets[-1][0] == float("inf")
        total = counts.get(key)
        if total is not None:
            assert buckets[-1][1] == total
    ours = [
        b for (base, labels), b in by_series.items()
        if base == "obs_roundtrip_ms"
    ]
    assert ours and ours[0][-1][1] == 5


def test_label_values_escaped(cluster):
    """A label value with quote/backslash/newline must not corrupt the
    scrape (Prometheus exposition escaping)."""
    from ray_tpu.util.metrics import Counter, prometheus_text

    c = Counter("obs_escape_total", "escaping", tag_keys=("model",))
    c.inc(1, tags={"model": 'llama "7b"\\v1\nnightly'})
    deadline = time.time() + 15
    text = ""
    while time.time() < deadline:
        text = prometheus_text()
        if "obs_escape_total" in text:
            break
        time.sleep(1)
    assert '\\"7b\\"' in text and "\\\\v1" in text and "\\nnightly" in text
    line = next(
        ln for ln in text.splitlines() if ln.startswith("obs_escape_total")
    )
    assert "\n" not in line
    # the full scrape still parses sample-by-sample
    _parse_exposition(text)


def test_dead_worker_metrics_reaped(cluster):
    """The GCS drops ``metrics:<worker_id>`` KV entries when it observes
    that worker's death — dead workers' series must not outlive them."""
    from ray_tpu._internal.ids import WorkerID

    worker = ray_tpu._worker_api.get_core_worker()

    def _gcs(method, *args):
        return ray_tpu._worker_api.run_on_worker_loop(
            worker.client_pool.get(*worker.gcs_address).call(method, *args)
        )

    ghost = WorkerID.from_random()
    key = f"metrics:{ghost.hex()}"
    payload = {"worker_id": ghost.hex(), "node_id": "", "metrics": []}
    _gcs("kv_put", key, json.dumps(payload).encode(), True)
    assert _gcs("kv_get", key) is not None
    _gcs("report_worker_death", ghost, "test-kill")
    assert _gcs("kv_get", key) is None


def test_device_profile_writes_xplane(tmp_path):
    """jax.profiler wrapper produces an XPlane trace dir (SURVEY §5)."""
    import os

    import jax
    import jax.numpy as jnp

    from ray_tpu.util import tracing

    logdir = str(tmp_path / "prof")
    with tracing.device_profile(logdir):
        with tracing.annotate_device_trace("matmul_block"):
            x = jnp.ones((64, 64))
            jax.block_until_ready(x @ x)
    found = []
    for root, _dirs, files in os.walk(logdir):
        found.extend(f for f in files if f.endswith((".pb", ".xplane.pb")))
    assert found, f"no profile artifacts under {logdir}"


def test_train_ft_metrics_units():
    """Train fault-tolerance metrics: counters, recovery histogram, and the
    exact-percentile sample path (process-local, no cluster needed)."""
    from ray_tpu.util import metrics

    before = metrics.train_ft_counters()
    metrics.record_train_resize("obs-run")
    metrics.record_train_restart("obs-run")
    metrics.record_collective_abort("obs-group")
    metrics.record_train_recovery("obs-run", 0.5, kind="resize")
    metrics.record_train_recovery("obs-run", 2.0, kind="restart")

    after = metrics.train_ft_counters()
    assert after["resizes"] == before["resizes"] + 1
    assert after["restarts"] == before["restarts"] + 1
    assert after["aborts"] == before["aborts"] + 1

    pct = metrics.train_recovery_percentiles()
    assert pct["count"] >= 2
    assert 0.0 < pct["p50_s"] <= pct["p99_s"] <= pct["max_s"]
    assert pct["max_s"] >= 2.0


def test_train_ft_summary_rollup():
    """train_ft_summary aggregates pushed metric snapshots from many
    processes into the cluster-wide fault-tolerance rollup the dashboard
    and `ray_tpu chaos list` serve."""
    from ray_tpu.util.metrics import train_ft_summary

    import json as _json

    payloads = [
        {
            "metrics": [
                {
                    "name": "train_resize_total",
                    "values": {_json.dumps(["a"]): 2.0},
                },
                {
                    "name": "collective_abort_total",
                    "values": {_json.dumps(["g"]): 3.0},
                },
                {
                    "name": "train_recovery_seconds",
                    # histogram snapshot: values = per-label sums, counts =
                    # per-label bucket observation counts
                    "values": {_json.dumps(["a", "resize"]): 3.0},
                    "counts": {_json.dumps(["a", "resize"]): [1, 1, 0]},
                },
            ]
        },
        {
            "metrics": [
                {
                    "name": "train_restart_total",
                    "values": {_json.dumps(["b"]): 1.0},
                }
            ]
        },
    ]
    out = train_ft_summary(payloads)
    assert out["resizes"] == 2.0
    assert out["restarts"] == 1.0
    assert out["aborts"] == 3.0
    assert out["recoveries"] == 2
    assert out["recovery_mean_s"] == pytest.approx(1.5)
