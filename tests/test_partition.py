"""Partition-tolerance tests: network chaos mesh, retryable transport with a
per-link circuit breaker, and split-brain fencing (reference model: the
chaos/network-failure suites driven by RAY_testing_rpc_failure plus the GCS
health-check manager's suspect/dead machinery)."""

import asyncio
import time

import pytest

import ray_tpu
from ray_tpu._internal import rpc as rpc_mod
from ray_tpu._internal.rpc import RpcError


# ---------------------------------------------------------------------------
# Unit: chaos mesh plan evaluation
# ---------------------------------------------------------------------------


def _mesh(rules, seed=42):
    rpc_mod.set_rpc_chaos({"seed": seed, "rules": rules})


def test_chaos_plan_deterministic_under_seed():
    """The same seed yields the same fault sequence — chaos runs replay."""
    rules = [{"method": "*", "fail": 0.5, "delay_ms": 1.0, "jitter_ms": 3.0}]
    try:
        _mesh(rules)
        seq1 = [rpc_mod._chaos_plan("m", None, "h:1") for _ in range(32)]
        _mesh(rules)
        seq2 = [rpc_mod._chaos_plan("m", None, "h:1") for _ in range(32)]
        assert seq1 == seq2
        assert any(a == "fail" for _, a in seq1)
        assert any(a is None for _, a in seq1)
    finally:
        rpc_mod.set_rpc_chaos({})


def test_chaos_rule_directional_match():
    """A src/dst-scoped rule drops A->B while B->A flows: directional
    partitions, not symmetric ones."""
    try:
        _mesh([{"src": "aa", "dst": "h:1", "fail": 1.0}])
        assert rpc_mod._chaos_plan("m", "aabbcc", "h:1")[1] == "fail"
        # other direction / other peer / anonymous caller: untouched
        assert rpc_mod._chaos_plan("m", "bbaacc", "h:1")[1] is None
        assert rpc_mod._chaos_plan("m", "aabbcc", "h:2")[1] is None
        assert rpc_mod._chaos_plan("m", None, "h:1")[1] is None
    finally:
        rpc_mod.set_rpc_chaos({})


def test_chaos_exempt_methods_never_faulted():
    """chaos_fetch distributes the spec itself: healing a partition must
    propagate through the partition, so the mesh never touches it."""
    try:
        _mesh([{"method": "*", "fail": 1.0, "blackhole": True}])
        assert rpc_mod._chaos_plan("chaos_fetch", "aa", "h:1") == (0.0, None)
        assert rpc_mod._chaos_plan("kv_get", "aa", "h:1")[1] is not None
    finally:
        rpc_mod.set_rpc_chaos({})


# ---------------------------------------------------------------------------
# Unit: retryable transport + circuit breaker
# ---------------------------------------------------------------------------


class _FlakyClient:
    name = "fake"

    def __init__(self, fail_times, exc=None):
        self.calls = 0
        self.fail_times = fail_times
        self.exc = exc or rpc_mod._transport_error("boom")

    async def call(self, method, *args, timeout=None, **kwargs):
        self.calls += 1
        if self.calls <= self.fail_times:
            raise self.exc
        return "ok"


def test_retry_call_recovers_from_transient_failures():
    c = _FlakyClient(2)
    out = asyncio.run(
        rpc_mod.retry_call(c, "m", attempts=3, timeout=1.0, backoff_s=0.001)
    )
    assert out == "ok"
    assert c.calls == 3


def test_retry_call_exhausts_attempts():
    c = _FlakyClient(10)
    with pytest.raises(RpcError, match="boom"):
        asyncio.run(rpc_mod.retry_call(c, "m", attempts=3, backoff_s=0.001))
    assert c.calls == 3


def test_retry_call_does_not_retry_application_errors():
    """Remote handler exceptions prove the link is alive — only transport
    failures are retried."""
    c = _FlakyClient(10, exc=ValueError("app bug"))
    with pytest.raises(ValueError):
        asyncio.run(rpc_mod.retry_call(c, "m", attempts=5, backoff_s=0.001))
    assert c.calls == 1


def test_retry_call_respects_total_timeout():
    c = _FlakyClient(1000)
    t0 = time.perf_counter()
    with pytest.raises(RpcError):
        asyncio.run(
            rpc_mod.retry_call(
                c, "m", attempts=1000, total_timeout=0.3, backoff_s=0.05
            )
        )
    assert time.perf_counter() - t0 < 2.0


def test_circuit_breaker_transitions():
    """closed -> open after N consecutive transport failures -> half_open
    probe after the cooldown -> closed on success (reopens on a half-open
    failure without re-counting to the threshold)."""
    rpc_mod.configure_circuit_breaker(3, 60.0)
    try:
        c = rpc_mod.RpcClient("127.0.0.1", 1, name="breaker-test")
        for _ in range(2):
            c._breaker_record(False)
        assert c._breaker_state == "closed"  # below threshold
        c._breaker_record(False)
        assert c._breaker_state == "open"
        with pytest.raises(RpcError, match="circuit open"):
            c._breaker_check()
        # cooldown elapses: one probe allowed through
        c._breaker_opened_at -= 120.0
        c._breaker_check()
        assert c._breaker_state == "half_open"
        c._breaker_record(False)  # failed probe reopens immediately
        assert c._breaker_state == "open"
        c._breaker_opened_at -= 120.0
        c._breaker_check()
        c._breaker_record(True)
        assert c._breaker_state == "closed"
        assert c._breaker_failures == 0
    finally:
        rpc_mod.configure_circuit_breaker(5, 2.0)


def test_batcher_fails_fast_on_closing_writer():
    """Reconnect race: a frame enqueued into a writer the recv loop is
    tearing down must fail the caller immediately, not strand its future."""

    class _ClosingWriter:
        def is_closing(self):
            return True

    async def go():
        batcher = rpc_mod._FrameBatcher(_ClosingWriter())
        with pytest.raises(ConnectionResetError):
            await batcher.enqueue([b"frame"])

    asyncio.run(go())


# ---------------------------------------------------------------------------
# Integration: blackhole -> typed error within the deadline, breaker opens
# ---------------------------------------------------------------------------


def test_blackhole_typed_error_and_circuit_opens(shutdown_only):
    """A blackholed link surfaces a typed transport error at the caller's
    deadline (never an unbounded hang); repeated failures open the per-link
    circuit so later calls fail fast; clearing the mesh lets the half-open
    probe close it again."""
    ray_tpu.init(num_cpus=2)
    from ray_tpu import _worker_api

    worker = _worker_api.get_core_worker()
    host, port = worker.gcs_address
    # a dedicated client: the pooled GCS client also carries the worker's
    # background traffic, whose successes reset the consecutive-failure
    # count mid-test on a loaded box (the breaker is per-client state)
    gcs = rpc_mod.RpcClient(host, port, name="breaker-probe")

    def call_once(timeout):
        return _worker_api.run_on_worker_loop(
            gcs.call("list_placement_groups", timeout=timeout)
        )

    rpc_mod.configure_circuit_breaker(3, 0.5)
    try:
        rpc_mod.set_rpc_chaos({
            "seed": 5,
            "rules": [{
                "method": "list_placement_groups",
                "dst": f"{host}:{port}",
                "blackhole": True,
            }],
        })
        t0 = time.perf_counter()
        with pytest.raises(RpcError, match="blackhole"):
            call_once(1.0)
        elapsed = time.perf_counter() - t0
        assert 0.9 <= elapsed < 5.0, f"blackhole surfaced in {elapsed:.2f}s"
        for _ in range(2):
            with pytest.raises(RpcError):
                call_once(0.3)
        assert gcs._breaker_state == "open"
        t0 = time.perf_counter()
        with pytest.raises(RpcError, match="circuit open"):
            call_once(5.0)
        assert time.perf_counter() - t0 < 0.2, "open circuit must fail fast"
        # heal: clear the mesh, wait out the cooldown, probe closes the link
        rpc_mod.set_rpc_chaos({})
        time.sleep(0.6)
        assert isinstance(call_once(5.0), list)
        assert gcs._breaker_state == "closed"
    finally:
        rpc_mod.set_rpc_chaos({})
        rpc_mod.configure_circuit_breaker(5, 2.0)
        _worker_api.run_on_worker_loop(gcs.close())


def test_dropped_call_does_not_stall_actor_sequence(shutdown_only):
    """A chaos-dropped actor call must not wedge the actor for its caller:
    the abandoned call leaves a hole in the per-caller in-order seq stream,
    and the next call's sequence watermark tells the executor to skip it.
    Before the watermark, every later call parked behind the hole forever
    (the exact stall the chaos soak surfaced)."""
    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote(num_cpus=0)
    class Counter:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1
            return self.n

    c = Counter.remote()
    assert ray_tpu.get(c.bump.remote(), timeout=30) == 1
    rpc_mod.set_rpc_chaos(
        {"seed": 2, "rules": [{"method": "actor_task", "fail": 1.0}]}
    )
    try:
        with pytest.raises(Exception):
            ray_tpu.get(c.bump.remote(), timeout=30)
    finally:
        rpc_mod.set_rpc_chaos({})
    # the dropped bump never executed; the next call must skip its seq
    # hole and run promptly, observing exactly one prior increment
    assert ray_tpu.get(c.bump.remote(), timeout=10) == 2


# ---------------------------------------------------------------------------
# Integration: split-brain — directional partition, fencing, failover
# ---------------------------------------------------------------------------


def _pump(handle, counts, n):
    for _ in range(n):
        try:
            assert handle.remote(21).result(timeout_s=20) == 42
            counts["ok"] += 1
        except Exception as e:  # noqa: BLE001 — tallied, asserted at the end
            counts["fail"] += 1
            counts["errors"].append(repr(e))


def test_split_brain_fencing_and_failover():
    """The headline partition scenario: a serve replica's node loses its
    route TO the GCS (directional — GCS->node probes still flow). The GCS
    marks the node SUSPECT, the controller replaces the replica, the
    partitioned raylet self-fences (its replica rejects work with the typed
    retryable NodeFencedError instead of double-serving), live clients see
    100% success throughout, and healing the partition unfences the node
    back to ALIVE."""
    from ray_tpu import serve, testing
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util import state

    cluster = Cluster(
        head_node_args={"num_cpus": 2},
        _system_config={
            "health_check_period_s": 0.5,
            "suspect_after_s": 2.5,
            "fence_after_s": 1.0,
            "health_check_timeout_s": 30.0,
            "chaos_poll_period_s": 0.25,
        },
    )
    try:
        cluster.connect()

        # Occupy one head CPU so the deployment's second replica MUST land
        # on node B; killed later to make room for the replacement.
        @ray_tpu.remote(num_cpus=1)
        class Blocker:
            def ping(self):
                return "ok"

        blocker = Blocker.remote()
        assert ray_tpu.get(blocker.ping.remote(), timeout=60) == "ok"

        node_b = cluster.add_node(num_cpus=1)
        node_b_hex = node_b.node_id.hex()
        gcs_host, gcs_port = cluster.gcs_address

        @serve.deployment(num_replicas=2)
        class Doubler:
            def __call__(self, x):
                return x * 2

        handle = serve.run(Doubler.bind(), name="splitapp", _proxy=False)

        def replica_rows():
            return [
                r for r in testing.list_serve_replicas("splitapp")
                if r["state"] == "RUNNING" and r["pid"]
            ]

        deadline = time.time() + 60
        while time.time() < deadline:
            rows = replica_rows()
            if len(rows) == 2 and any(
                r.get("node_id") == node_b_hex for r in rows
            ):
                break
            time.sleep(0.2)
        rows = replica_rows()
        victim = [r for r in rows if r.get("node_id") == node_b_hex]
        assert victim, f"no replica landed on node B: {rows}"
        victim_id = victim[0]["replica_id"]

        counts = {"ok": 0, "fail": 0, "errors": []}
        _pump(handle, counts, 10)  # steady state before the partition

        # Directional partition: node B -> GCS drops; GCS -> node B flows.
        testing.set_network_chaos({
            "seed": 1,
            "rules": [{
                "src": node_b_hex[:12],
                "dst": f"{gcs_host}:{gcs_port}",
                "fail": 1.0,
            }],
        })
        ray_tpu.kill(blocker)  # head room for the replacement replica
        t_partition = time.time()

        # GCS: stale reports + probe verdict -> SUSPECT (not yet DEAD).
        suspect_seen = False
        deadline = time.time() + 30
        while time.time() < deadline:
            _pump(handle, counts, 3)
            states = {n["node_id"]: n["state"] for n in state.list_nodes()}
            if states.get(node_b_hex) == "SUSPECT":
                suspect_seen = True
                break
        assert suspect_seen, "node B never became SUSPECT"

        # Controller: the replica on the suspect node is replaced on a
        # healthy node — back to 2 RUNNING with the victim gone.
        deadline = time.time() + 60
        replaced = False
        while time.time() < deadline:
            _pump(handle, counts, 3)
            rows = replica_rows()
            ids = {r["replica_id"] for r in rows}
            if victim_id not in ids and len(rows) == 2:
                replaced = True
                break
        assert replaced, f"victim {victim_id} never replaced: {replica_rows()}"
        assert all(r.get("node_id") != node_b_hex for r in replica_rows())

        # Heal: clear the mesh; node B's next report unfences + clears
        # SUSPECT without a restart ("clean re-register").
        testing.clear_network_chaos()
        deadline = time.time() + 30
        healed = False
        while time.time() < deadline:
            _pump(handle, counts, 3)
            states = {n["node_id"]: n["state"] for n in state.list_nodes()}
            if states.get(node_b_hex) == "ALIVE":
                healed = True
                break
        assert healed, "node B never returned to ALIVE after healing"
        assert time.time() - t_partition < 120

        # Live traffic saw 100% success through the whole partition.
        assert counts["fail"] == 0, f"client failures: {counts['errors'][:5]}"
        assert counts["ok"] >= 20

        # Flight recorder: the full suspect -> fence -> unfence lifecycle.
        deadline = time.time() + 20
        names = set()
        while time.time() < deadline:
            names = {e.get("name") for e in state.list_events(limit=5000)}
            if {"node_suspect", "node_fenced", "node_unfenced"} <= names:
                break
            time.sleep(0.5)
        assert "node_suspect" in names
        assert "node_fenced" in names
        assert "node_unfenced" in names

        # The fenced replica rejected work with the typed retryable error:
        # the handle recorded NodeFencedError failovers (not silent drops).
        retry_events = [
            e for e in state.list_events(limit=5000, name="request_retry")
            if e.get("reason") == "NodeFencedError"
        ]
        assert retry_events, "no NodeFencedError failover was recorded"
    finally:
        try:
            from ray_tpu import serve as _serve

            _serve.shutdown()
        except Exception:
            pass
        try:
            ray_tpu.shutdown()
        finally:
            cluster.shutdown()


@pytest.mark.parametrize("report_lands", [True, False])
def test_suspect_only_once_the_probe_confirms(report_lands):
    """A node whose report is late is probed, and is SUSPECT to whoever asks
    for node states only if the probe returns and the report is still
    missing: a busy process's late report lands while the probe is in
    flight, and the serve controller replaces replicas on SUSPECT."""
    from ray_tpu._internal.config import Config
    from ray_tpu._internal.ids import NodeID
    from ray_tpu._internal.protocol import NodeInfo
    from ray_tpu.runtime.gcs.server import GcsServer

    async def scenario():
        gcs = GcsServer(Config(suspect_after_s=0.05, health_check_timeout_s=30.0))
        node_id = NodeID.from_random()
        gcs._nodes[node_id] = NodeInfo(node_id, ("127.0.0.1", 1), "", {"CPU": 1.0})
        gcs._node_last_seen[node_id] = time.time() - 1.0
        answered = asyncio.Event()
        published = []
        gcs.publisher.publish = lambda channel, message: published.append(message[0])

        class SlowRaylet:
            async def call(self, method, timeout=None):
                await answered.wait()

        gcs.client_pool.get = lambda *address: SlowRaylet()

        async def state():
            return (await gcs.handle_get_node_states())[node_id.hex()]

        await gcs._health_check()
        await asyncio.sleep(0)  # the probe starts and waits for its answer
        in_flight = await state()
        await gcs._health_check()  # no second probe beside the first
        assert gcs._node_probing == {node_id}
        if report_lands:
            gcs._node_last_seen[node_id] = time.time()
        answered.set()
        for _ in range(10):
            await asyncio.sleep(0)
        after = await state()
        gcs._stopped = True
        return in_flight, after, published, gcs._node_probing

    in_flight, after, published, probing = asyncio.run(scenario())
    assert in_flight == "ALIVE"
    assert after == ("ALIVE" if report_lands else "SUSPECT")
    assert published == ([] if report_lands else ["suspect"])
    assert not probing
