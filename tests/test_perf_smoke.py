"""Microbenchmark suite smoke (reference: _private/ray_perf.py metrics run
in release/microbenchmark) — correctness of the harness, not speed."""

import pytest

import ray_tpu
from ray_tpu._internal.perf import run_microbenchmarks


def test_microbenchmarks_produce_all_metrics(shutdown_only):
    results = run_microbenchmarks(small=True)
    expected = {
        "single_client_put_1kb",
        "single_client_get_1kb",
        "single_client_put_get_gb_s",
        "single_client_tasks_sync",
        "single_client_tasks_async",
        "one_to_one_actor_calls_sync",
        "one_to_one_actor_calls_async",
        "single_client_wait_100_refs_s",
        "rpcs_per_task_sync",
        "lease_rpcs_per_task_sync",
        "weights_publish_mb_s",
        "weights_subscribe_x1_mb_s",
        "weights_subscribe_x2_mb_s",
    }
    assert expected <= set(results)
    for metric, value in results.items():
        if "per_task" in metric:
            # ratios where 0 is the optimum (warm lease cache -> 0 lease
            # RPCs); the push itself keeps rpcs_per_task >= 1
            assert value >= 0, (metric, value)
        else:
            assert value > 0, (metric, value)
    assert results["rpcs_per_task_sync"] >= 1
    assert not ray_tpu.is_initialized()  # the suite cleans up after itself


def test_microbenchmark_json_output(shutdown_only):
    """The CLI's machine-readable mode: every metric
    carries a unit, and the per-method RPC latency histograms ride along."""
    import json

    from ray_tpu._internal.perf import json_results, metric_unit

    results = run_microbenchmarks(small=True)
    doc = json.loads(json_results(results))
    assert set(doc["metrics"]) == set(results)
    for name, entry in doc["metrics"].items():
        assert entry["unit"] == metric_unit(name)
    lat = doc["rpc_latency_ms"]
    assert "push_task" in lat and lat["push_task"]["count"] > 0
    assert "buckets" in lat["push_task"]


def test_warm_stream_lease_rpcs_regression_guard(shutdown_only):
    """Regression guard for lease reuse (counter-based, stable on a 1-core
    box): a warm same-class task stream must issue at most one lease RPC
    total — NOT one per task."""
    from ray_tpu.util import metrics

    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote
    def noop(i):
        return i

    ray_tpu.get(noop.remote(0))  # warm: acquire + cache the lease
    before = metrics.rpc_calls_by_method()
    n = 25
    for i in range(n):
        assert ray_tpu.get(noop.remote(i)) == i
    after = metrics.rpc_calls_by_method()
    lease_delta = after.get("request_worker_lease", 0.0) - before.get(
        "request_worker_lease", 0.0
    )
    push_delta = after.get("push_task", 0.0) - before.get("push_task", 0.0)
    assert lease_delta <= 1, f"{lease_delta} lease RPCs for {n} warm tasks"
    assert push_delta == n


def test_tracing_disabled_overhead_guard(shutdown_only, monkeypatch):
    """The tracing plane must never silently tax the hot path: with
    RAY_TPU_TRACE unset a stream of tasks_sync calls injects no context
    and records zero spans anywhere. Counts only: what the dormant plane
    costs in time is a chip run's to say."""
    monkeypatch.delenv("RAY_TPU_TRACE", raising=False)
    from ray_tpu.util import tracing

    tracing._enabled = False
    assert not tracing.is_tracing_enabled()
    tracing.clear_spans()
    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote
    def noop(i):
        return i

    injected = []
    real_inject = tracing.inject_context

    def recording_inject():
        injected.append(real_inject())
        return injected[-1]

    monkeypatch.setattr(tracing, "inject_context", recording_inject)
    for i in range(150):
        assert ray_tpu.get(noop.remote(i)) == i
    assert injected == [None] * 150  # one call a task, nothing to carry
    assert tracing.get_spans() == []  # plane fully dormant when disabled


def test_serve_tracing_disabled_overhead_guard(shutdown_only, monkeypatch):
    """The serve request path carries the same guarantee as tasks_sync:
    with tracing off, the whole request (handle -> replica) emits zero
    spans anywhere."""
    import time as _time

    monkeypatch.delenv("RAY_TPU_TRACE", raising=False)
    from ray_tpu import serve
    from ray_tpu.util import tracing

    tracing._enabled = False
    assert not tracing.is_tracing_enabled()
    tracing.clear_spans()
    ray_tpu.init(num_cpus=4)

    @serve.deployment
    class Echo:
        def __call__(self, x):
            return x

    handle = serve.run(Echo.bind(), name="perfguard", _proxy=False)
    try:
        for i in range(55):
            assert handle.remote(i).result(timeout_s=30) == i
        # zero spans: none recorded driver-side, none flushed from the
        # replica to the GCS span store (its pusher runs on a 1s cadence)
        assert tracing.get_spans() == []
        _time.sleep(1.5)
        cluster_spans = [
            s for s in tracing.timeline() if s.get("span_id")
        ]
        assert cluster_spans == [], cluster_spans
    finally:
        serve.shutdown()


def test_router_pick_fast_allocates_no_dicts():
    """The per-request routing pick runs tens of thousands of times a
    second per proxy at saturation; it must stay index arithmetic over the
    precomputed view — building a dict per request is the regression this
    guards against. dis-based so it fails on the allocation being
    *reintroduced*, not on a timing artifact of a noisy box."""
    import dis

    from ray_tpu.serve.handle import Router

    banned = {"BUILD_MAP", "MAP_ADD", "DICT_MERGE", "DICT_UPDATE",
              "BUILD_CONST_KEY_MAP"}
    ops = {ins.opname for ins in dis.get_instructions(Router._pick_fast)}
    assert not (ops & banned), ops & banned


@pytest.mark.slow
def test_multiproxy_tracing_disabled_overhead_guard(shutdown_only,
                                                    monkeypatch):
    """The multi-proxy data plane serves the single-proxy request path:
    with tracing off, every request over a persistent connection through a
    1-proxy and then a 2-proxy SO_REUSEPORT ingress on the same port is
    answered 200 with its own payload, and records no span."""
    import http.client
    import json as _json

    monkeypatch.delenv("RAY_TPU_TRACE", raising=False)
    from ray_tpu import serve
    from ray_tpu.util import tracing

    tracing._enabled = False
    assert not tracing.is_tracing_enabled()
    tracing.clear_spans()
    ray_tpu.init(num_cpus=4)
    port = 18290

    def start(n):
        serve.shutdown()
        serve.start(http_port=port, num_proxies=n)

        @serve.deployment
        class Echo:
            def __call__(self, x):
                return x

        serve.run(Echo.bind(), name="mpguard", route_prefix="/")

    def answered(n_requests=45):
        headers = {"Content-Type": "application/json"}
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        try:
            for i in range(n_requests):
                conn.request(
                    "POST", "/", _json.dumps({"x": i}).encode(), headers)
                resp = conn.getresponse()
                assert resp.status == 200
                assert _json.loads(resp.read()) == {"result": {"x": i}}
        finally:
            conn.close()

    try:
        for proxies in (1, 2):
            start(proxies)
            answered()
        assert tracing.get_spans() == []
    finally:
        serve.shutdown()


def test_prefix_cache_prefill_computes_only_suffix():
    """Perf guard for the KV-cache plane (CPU-safe, counter-based): a
    repeated prompt must prefill ONLY the tokens past its cached prefix —
    the counters are what a prefix hit's TTFT win rests on,
    and a silent full-prefill regression would keep outputs correct while
    erasing the speedup."""
    import jax

    from ray_tpu.kvcache import KVCacheManager
    from ray_tpu.llm.engine import ContinuousBatchingEngine, GenerationRequest
    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.parallel.sharding import unbox_params

    cfg = LlamaConfig.tiny(max_seq_len=128)
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
    kv = KVCacheManager(num_blocks=16, block_size=16)
    eng = ContinuousBatchingEngine(cfg, params, num_slots=2, kv_cache=kv)
    prompt = list(range(7, 7 + 56))  # 3 full blocks + 8-token tail

    eng.generate([GenerationRequest(token_ids=prompt, max_new_tokens=2,
                                    temperature=0.0)])
    s0 = kv.stats()
    assert s0["prefill_tokens_computed"] == len(prompt)  # cold: everything

    eng.generate([GenerationRequest(token_ids=prompt, max_new_tokens=2,
                                    temperature=0.0)])
    s1 = kv.stats()
    computed = s1["prefill_tokens_computed"] - s0["prefill_tokens_computed"]
    hit = s1["prefix_hit_tokens"] - s0["prefix_hit_tokens"]
    assert hit == 48, f"expected 3 cached blocks (48 tokens), hit {hit}"
    assert computed == len(prompt) - 48, (
        f"fully-cached prefix recomputed {computed} tokens, "
        f"expected only the {len(prompt) - 48}-token suffix"
    )


def test_chunked_prefill_respects_step_budget():
    """Perf guard for the chunked-prefill scheduler (CPU-safe,
    counter-based): with prefill_chunk_tokens set, NO engine step may
    compute more prefill tokens than the budget — the whole point is
    bounding the per-step stall a long prompt can impose on in-flight
    decodes. Also pins the floor: the prompt must take at least
    ceil(plen / budget) steps to admit (no silent budget bypass)."""
    import math

    import jax

    from ray_tpu.kvcache import KVCacheManager
    from ray_tpu.llm.engine import ContinuousBatchingEngine, GenerationRequest
    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.parallel.sharding import unbox_params

    budget = 16
    cfg = LlamaConfig.tiny(max_seq_len=256)
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
    kv = KVCacheManager(num_blocks=32, block_size=16)
    eng = ContinuousBatchingEngine(
        cfg, params, num_slots=2, kv_cache=kv,
        prefill_chunk_tokens=budget,
    )
    plen = 100
    eng.add_request(GenerationRequest(
        token_ids=list(range(plen)), max_new_tokens=2, temperature=0.0,
    ))
    steps = 0
    while eng.num_active:
        eng.step()
        steps += 1
        assert eng.last_step_prefill_tokens <= budget, (
            f"step computed {eng.last_step_prefill_tokens} prefill "
            f"tokens, budget is {budget}"
        )
        assert steps < 100
    assert steps >= math.ceil(plen / budget)


def test_scale_smoke_queued_tasks(shutdown_only):
    """Queue-depth envelope smoke (BASELINE.md 'tasks queued on a single
    node'): hundreds of queued no-op tasks on 2 workers all complete
    correctly. (Sized for the 1-core CI box; the envelope itself is
    documented in BASELINE.md.)"""
    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote
    def f(i):
        return i

    refs = [f.remote(i) for i in range(400)]
    out = ray_tpu.get(refs, timeout=600)
    assert out == list(range(400))


@pytest.mark.slow
def test_scale_smoke_many_actors(shutdown_only):
    """Actor-count envelope smoke: 16 concurrently alive zero-cpu actors
    (sized for the 1-core CI box; the reference envelope is BASELINE.md's)."""
    ray_tpu.init(num_cpus=4)

    @ray_tpu.remote(num_cpus=0)
    class A:
        def __init__(self, i):
            self.i = i

        def who(self):
            return self.i

    actors = [A.remote(i) for i in range(16)]
    assert ray_tpu.get([a.who.remote() for a in actors], timeout=600) == list(
        range(16)
    )
    for a in actors:
        ray_tpu.kill(a)


def test_scale_100_virtual_nodes(shutdown_only):
    """Scalability quantification (BASELINE.md's 2,000-node envelope,
    scaled to a 1-core CI box): a 100-raylet in-process cluster must
    register quickly, serve O(n) cluster views fast, and dispatch work
    across the full node set. Prints its timings."""
    import time

    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(head_node_args=dict(num_cpus=1))
    t0 = time.perf_counter()
    for i in range(99):
        cluster.add_node(num_cpus=1, resources={f"node{i}": 1.0})
    register_s = time.perf_counter() - t0
    cluster.connect()
    try:
        import ray_tpu as rt

        deadline = time.time() + 60
        while time.time() < deadline:
            if len(rt.nodes()) >= 100:
                break
            time.sleep(0.2)
        nodes = rt.nodes()
        assert len(nodes) == 100, len(nodes)

        t0 = time.perf_counter()
        for _ in range(20):
            res = rt.cluster_resources()
        view_ms = (time.perf_counter() - t0) / 20 * 1000
        assert res.get("CPU", 0) == 100.0

        # dispatch across distinct far nodes via custom-resource pinning
        @rt.remote(num_cpus=0)
        def where():
            import os
            return os.getpid()

        t0 = time.perf_counter()
        refs = [
            where.options(resources={f"node{i * 12}": 1.0}).remote()
            for i in range(8)
        ]
        pids = rt.get(refs, timeout=300)
        dispatch_s = time.perf_counter() - t0
        assert len(set(pids)) == 8  # eight distinct nodes executed

        print(
            f"scale100: register_99_nodes={register_s:.2f}s "
            f"cluster_view={view_ms:.2f}ms "
            f"8_cross_node_dispatch={dispatch_s:.2f}s"
        )
        assert register_s < 120
        assert view_ms < 200
    finally:
        import ray_tpu

        ray_tpu.shutdown()
        cluster.shutdown()
