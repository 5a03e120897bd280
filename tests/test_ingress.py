"""Multi-proxy ingress data plane: rendezvous-hash agreement, shared
SO_REUSEPORT listeners, proxy registry/drain/failover, and the per-proxy
metrics rollup (PR: production-scale ingress)."""

import http.client
import json
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.hash_ring import ReplicaRing


# -- ring units (no cluster) -------------------------------------------------


def test_ring_agreement_across_instances():
    """Any process building a ring from the same replica *set* — in any
    order — must pick the same winner for every key (the property that
    lets N proxies agree on the warm replica with no coordination)."""
    ids = [f"echo#replica-{i}" for i in range(8)]
    r1 = ReplicaRing(ids)
    r2 = ReplicaRing(list(reversed(ids)))
    for key in range(0, 50_000, 97):
        assert r1.lookup(key) == r2.lookup(key)


def test_ring_minimal_remap_on_membership_change():
    """Removing one replica moves ONLY the keys it owned (~1/n of them);
    every other key keeps its winner — warm KV blocks stay warm through a
    scale-down (the old sorted_ids[key % n] scheme remapped ~everything)."""
    ids = [f"r{i}" for i in range(8)]
    removed = "r3"
    before = ReplicaRing(ids)
    after = ReplicaRing([r for r in ids if r != removed])
    keys = list(range(0, 20_000, 7))
    owned = 0
    for k in keys:
        w = before.lookup(k)
        if w == removed:
            owned += 1
            assert after.lookup(k) != removed
        else:
            assert after.lookup(k) == w  # survivors keep every key
    # the removed replica owned roughly 1/8 of the keyspace
    assert 0.05 < owned / len(keys) < 0.25, owned / len(keys)


def test_ring_lookup_excluding():
    ring = ReplicaRing([f"r{i}" for i in range(4)])
    key = 123456
    winner = ring.lookup_index(key)
    alt = ring.lookup_excluding(key, {ring.ids[winner]})
    assert alt != winner
    # excluding everything falls back to the unfiltered winner (a
    # 1-replica deployment's restart is still worth a retry)
    assert ring.lookup_excluding(key, set(ring.ids)) == winner


# -- cluster tests -----------------------------------------------------------


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=6)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def _echo_deployment(num_replicas=2):
    @serve.deployment(num_replicas=num_replicas, max_ongoing_requests=32,
                      max_queued_requests=1024,
                      request_router_config=dict(prefix_affinity_tokens=4))
    class Echo:
        def __call__(self, payload):
            import os as _os

            return {"pid": _os.getpid()}

    return Echo


def _post(port, payload, timeout=10):
    """One request over a FRESH connection: the kernel re-picks which
    SO_REUSEPORT listener accepts it, so repeated calls spread across
    proxies."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/", json.dumps(payload).encode(),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        return resp.status, resp.headers.get("X-Proxy-Id"), body
    finally:
        conn.close()


def _post_retry(port, payload, deadline_s=30.0):
    """Retry connection errors and 503s (draining/dead proxy windows)
    until a 200 arrives — the client contract under proxy churn."""
    deadline = time.time() + deadline_s
    last = None
    while time.time() < deadline:
        try:
            status, proxy_id, body = _post(port, payload)
        except OSError as exc:
            last = exc
            time.sleep(0.2)
            continue
        if status == 200:
            return proxy_id, json.loads(body)
        last = (status, body)
        time.sleep(0.2)
    raise AssertionError(f"no 200 within {deadline_s}s: {last!r}")


def _fresh_serve(port, num_proxies):
    serve.shutdown()
    controller = serve.start(http_port=port, num_proxies=num_proxies)
    serve.run(_echo_deployment().bind(), name="ingress-app",
              route_prefix="/")
    return controller


def test_cross_proxy_pick_agreement_no_controller_roundtrip(cluster):
    """Two independent Routers (stand-ins for two proxy processes) warmed
    once must agree on the affinity pick for every key, and the pick loop
    itself must issue ZERO controller RPCs — the agreement comes from the
    shared rendezvous ring, not a round-trip."""
    from ray_tpu.serve.handle import Router
    from ray_tpu.util.metrics import rpc_calls_by_method

    @serve.deployment(num_replicas=3)
    class Who:
        def __call__(self, _):
            return None

    serve.run(Who.bind(), name="ringapp", _proxy=False)
    from ray_tpu.serve.api import _state as serve_state

    controller = serve_state["controller"]
    r1 = Router(controller, "ringapp")
    r2 = Router(controller, "ringapp")
    r1._refresh(force=True)
    r2._refresh(force=True)
    # suppress the periodic poll so the counters below measure ONLY the
    # pick loop (the poll is exercised elsewhere; here it would race)
    r1._REFRESH_S = r2._REFRESH_S = 1e9
    fetches = (r1.table_fetches, r2.table_fetches)
    before = rpc_calls_by_method().get("actor_task", 0.0)
    for key in range(200):
        rid1, _ = r1.pick("Who", affinity=key)
        rid2, _ = r2.pick("Who", affinity=key)
        assert rid1 == rid2, (key, rid1, rid2)
    after = rpc_calls_by_method().get("actor_task", 0.0)
    assert after == before  # no controller (actor) RPC per pick
    assert (r1.table_fetches, r2.table_fetches) == fetches
    assert r1.stats()["picks"] == 200
    serve.delete("ringapp")


def test_multiproxy_spread_affinity_metrics_drain(cluster):
    """One 2-proxy serve session, four claims (one session keeps the
    1-core tier-1 wall clock down): (a) proxies register in the GCS
    ``proxy:`` registry at start; (b) fresh connections spread across
    both SO_REUSEPORT listeners AND the same token-id prefix keeps
    landing on ONE serving replica — every proxy computes the same
    rendezvous winner locally; (c) per-proxy request counters roll up
    into metrics_summary()['ingress'] tagged by proxy_id; (d)
    drain_proxy 503s new work, deregisters, and traffic keeps
    succeeding through the survivor."""
    from ray_tpu.util import state as rt_state

    port = 18200
    controller = _fresh_serve(port, num_proxies=2)

    # (a) registry
    rows = rt_state.list_proxies()
    assert [r["proxy_id"] for r in rows] == ["http#0", "http#1"]
    assert all(r["port"] == port and r["pid"] for r in rows)

    # (b) spread + cross-proxy affinity agreement: sample the SAME
    # prefix over fresh connections until both proxies have terminated
    # at least one request (bounded) — the kernel picks the listener,
    # the rendezvous ring picks the replica
    payload = {"token_ids": [7, 7, 7, 7, 1, 2, 3]}
    pids, proxies = set(), set()
    deadline = time.time() + 30
    while time.time() < deadline and (
        len(proxies) < 2 or len(pids) == 0
    ):
        proxy_id, body = _post_retry(port, payload)
        proxies.add(proxy_id)
        pids.add(body["result"]["pid"])
    assert proxies == {"http#0", "http#1"}, proxies
    assert len(pids) == 1, pids

    # (c) proxies push metric snapshots on a ~1s cadence; poll the rollup
    deadline = time.time() + 15
    ingress = {}
    while time.time() < deadline:
        ingress = rt_state.metrics_summary()["ingress"]
        # each proxy pushes on its own clock: wait for both snapshots
        if ingress.get("num_proxies", 0) >= 2 and all(
            ingress["proxies"].get(p, {}).get("requests", {}).get("ok", 0) > 0
            for p in proxies
        ):
            break
        time.sleep(0.5)
    assert ingress["num_proxies"] >= 2, ingress
    assert ingress["requests_total"] > 0
    for proxy_id in proxies:
        row = ingress["proxies"][proxy_id]
        assert row["requests"].get("ok", 0) > 0
        assert row["latency_ms"]["count"] > 0

    # (d) drain one proxy: deregisters, survivor keeps serving
    assert ray_tpu.get(
        controller.drain_proxy.remote("http#1"), timeout=30
    )
    assert [r["proxy_id"] for r in rt_state.list_proxies()] == ["http#0"]
    for _ in range(5):
        _post_retry(port, {"token_ids": [1]})


def test_proxy_kill_failover(cluster):
    """SIGKILL one of two proxies (ingress chaos): clients retrying
    connection errors keep succeeding on the survivor, and the
    controller's health poll deregisters the corpse."""
    from ray_tpu import testing
    from ray_tpu.util import state as rt_state

    port = 18206
    _fresh_serve(port, num_proxies=2)
    assert len(rt_state.list_proxies()) == 2
    killed_id, pid = testing.kill_serve_proxy("http#0")
    assert killed_id == "http#0" and pid
    # the survivor owns the port: retried traffic must keep flowing
    for _ in range(10):
        proxy_id, _ = _post_retry(port, {"token_ids": [2]})
        assert proxy_id in ("http#0", "http#1")
    deadline = time.time() + 30
    while time.time() < deadline:
        rows = rt_state.list_proxies()
        if [r["proxy_id"] for r in rows] == ["http#1"]:
            break
        time.sleep(0.5)
    assert [r["proxy_id"] for r in rt_state.list_proxies()] == ["http#1"]
    # post-mortem: the registry lifecycle is on the flight recorder
    # (event rings stream to the GCS on a ~1s cadence — poll, bounded)
    deadline = time.time() + 15
    events = set()
    while time.time() < deadline:
        events = {
            e.get("name") for e in rt_state.list_events(limit=2000)
        }
        if {"proxy_start", "proxy_stop"} <= events:
            break
        time.sleep(0.5)
    assert "proxy_start" in events, events
    assert "proxy_stop" in events, events
