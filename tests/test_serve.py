"""Tests for ray_tpu.serve (reference model: python/ray/serve/tests/)."""

import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture(scope="module")
def cluster(http_port):
    """Yields the base URL of the cluster's HTTP proxy, on a port of this
    module's own (``conftest.http_port``)."""
    ray_tpu.init(num_cpus=6, resources={"TPU": 4})
    serve.start(http_port=http_port)
    yield f"http://127.0.0.1:{http_port}"
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.fixture(autouse=True)
def _cleanup_apps():
    yield
    # delete apps between tests but keep controller/proxy warm
    try:
        for app in list(serve.status().keys()):
            serve.delete(app)
    except Exception:
        pass


def test_basic_deployment_and_handle(cluster):
    @serve.deployment
    class Greeter:
        def __call__(self, name):
            return f"hello {name}"

    handle = serve.run(Greeter.bind(), name="greet", _proxy=False)
    assert handle.remote("tpu").result(timeout_s=30) == "hello tpu"

    st = serve.status()["greet"]
    assert st.status == "RUNNING"
    assert st.deployments["Greeter"].status == "HEALTHY"


def test_function_deployment(cluster):
    @serve.deployment
    def square(x):
        return x * x

    handle = serve.run(square.bind(), name="sq", _proxy=False)
    assert handle.remote(7).result(timeout_s=30) == 49


def test_multi_replica_load_balancing(cluster):
    @serve.deployment(num_replicas=2)
    class WhoAmI:
        def __init__(self):
            import os

            self.pid = os.getpid()

        def __call__(self, _):
            return self.pid

    handle = serve.run(WhoAmI.bind(), name="who", _proxy=False)
    pids = {handle.remote(None).result(timeout_s=30) for _ in range(20)}
    assert len(pids) == 2  # both replicas served traffic


def test_composition_nested_handles(cluster):
    @serve.deployment
    class Adder:
        def __init__(self, increment):
            self.increment = increment

        def __call__(self, x):
            return x + self.increment

    @serve.deployment
    class Chain:
        def __init__(self, adder):
            self.adder = adder

        def __call__(self, x):
            partial = self.adder.remote(x).result(timeout_s=30)
            return partial * 10

    app = Chain.bind(Adder.bind(3))
    handle = serve.run(app, name="chain", _proxy=False)
    assert handle.remote(4).result(timeout_s=30) == 70


def test_method_routing(cluster):
    @serve.deployment
    class Multi:
        def __call__(self, x):
            return ("call", x)

        def other(self, x):
            return ("other", x)

    handle = serve.run(Multi.bind(), name="multi", _proxy=False)
    assert handle.remote(1).result(timeout_s=30) == ("call", 1)
    assert handle.other.remote(2).result(timeout_s=30) == ("other", 2)


def test_user_config_reconfigure(cluster):
    @serve.deployment(user_config={"threshold": 1})
    class Configurable:
        def __init__(self):
            self.threshold = None

        def reconfigure(self, config):
            self.threshold = config["threshold"]

        def __call__(self, _):
            return self.threshold

    handle = serve.run(Configurable.bind(), name="cfg", _proxy=False)
    assert handle.remote(None).result(timeout_s=30) == 1

    @serve.deployment(user_config={"threshold": 5})
    class Configurable2:
        def __init__(self):
            self.threshold = None

        def reconfigure(self, config):
            self.threshold = config["threshold"]

        def __call__(self, _):
            return self.threshold

    Configurable2._config.name = "Configurable"
    serve.run(Configurable2.bind(), name="cfg", _proxy=False)
    deadline = time.time() + 20
    while time.time() < deadline:
        if handle.remote(None).result(timeout_s=30) == 5:
            break
        time.sleep(0.3)
    assert handle.remote(None).result(timeout_s=30) == 5


def test_replica_failure_recovery(cluster):
    @serve.deployment
    class Fragile:
        def __call__(self, x):
            if x == "die":
                import os

                os._exit(1)
            return "alive"

    handle = serve.run(Fragile.bind(), name="fragile", _proxy=False)
    assert handle.remote("ok").result(timeout_s=30) == "alive"
    try:
        handle.remote("die").result(timeout_s=10)
    except Exception:
        pass
    # controller should replace the dead replica
    deadline = time.time() + 60
    ok = False
    while time.time() < deadline:
        try:
            if handle.remote("ok").result(timeout_s=10) == "alive":
                ok = True
                break
        except Exception:
            time.sleep(0.5)
    assert ok, "replica was not replaced after crash"


def test_http_proxy_end_to_end(cluster):
    @serve.deployment
    class Echo:
        def __call__(self, body):
            return {"echo": body}

    serve.run(Echo.bind(), name="echo_app", route_prefix="/echo")
    deadline = time.time() + 30
    result = None
    while time.time() < deadline:
        try:
            req = urllib.request.Request(
                f"{cluster}/echo",
                data=json.dumps({"msg": "hi"}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=10) as resp:
                result = json.loads(resp.read())
            break
        except Exception:
            time.sleep(0.5)
    assert result == {"result": {"echo": {"msg": "hi"}}}, result

    with urllib.request.urlopen(
        f"{cluster}/-/healthz", timeout=10
    ) as resp:
        assert json.loads(resp.read())["status"] == "ok"


def test_a_proxy_that_cannot_bind_its_port_fails_to_start(cluster):
    """A second cluster asking for a port that is taken must hear of it: its
    requests would reach whoever holds the port (here: this module's)."""
    from ray_tpu.serve.proxy import HTTPProxy

    port = int(cluster.rsplit(":", 1)[1])
    with pytest.raises(RuntimeError, match="failed to start.*in use"):
        HTTPProxy(None, "127.0.0.1", port, "http#late")


def test_autoscaling_up_and_down(cluster):
    @serve.deployment(
        autoscaling_config=dict(
            min_replicas=1,
            max_replicas=3,
            target_ongoing_requests=1,
            upscale_delay_s=0.5,
            downscale_delay_s=2.0,
        ),
        max_ongoing_requests=10,
    )
    class Slow:
        def __call__(self, _):
            time.sleep(1.5)
            return "done"

    handle = serve.run(Slow.bind(), name="auto", _proxy=False)

    def n_running():
        st = serve.status()["auto"].deployments["Slow"]
        return sum(1 for r in st.replicas if r.state == "RUNNING")

    assert n_running() == 1
    # flood with concurrent requests to drive queue length up
    responses = [handle.remote(None) for _ in range(12)]
    deadline = time.time() + 45
    scaled = False
    while time.time() < deadline:
        if n_running() >= 2:
            scaled = True
            break
        responses.extend(handle.remote(None) for _ in range(3))
        time.sleep(0.5)
    assert scaled, "deployment did not scale up under load"
    for r in responses:
        try:
            r.result(timeout_s=60)
        except Exception:
            pass
    # idle: should scale back toward min_replicas
    deadline = time.time() + 60
    downscaled = False
    while time.time() < deadline:
        if n_running() <= 2:
            downscaled = True
            break
        time.sleep(0.5)
    assert downscaled, "deployment did not scale down when idle"


def test_delete_application(cluster):
    @serve.deployment
    class Temp:
        def __call__(self, _):
            return 1

    serve.run(Temp.bind(), name="temp", _proxy=False)
    assert "temp" in serve.status()
    serve.delete("temp")
    assert "temp" not in serve.status()


def test_serve_batch_accumulates(cluster):
    from ray_tpu import serve

    @serve.deployment
    class Batcher:
        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.2)
        async def handle(self, items):
            # one result per item, tagged with the batch size it rode in
            return [{"v": i * 2, "batch": len(items)} for i in items]

        async def __call__(self, x):
            return await self.handle(x)

    handle = serve.run(Batcher.bind(), name="batch-app", _proxy=False)
    responses = [handle.remote(i) for i in range(8)]
    results = [r.result(timeout_s=60) for r in responses]
    assert [r["v"] for r in results] == [2 * i for i in range(8)]
    # at least one call actually rode in a multi-item batch
    assert max(r["batch"] for r in results) >= 2
    serve.delete("batch-app")


def test_serve_batch_error_propagates(cluster):
    from ray_tpu import serve

    @serve.deployment
    class Bad:
        @serve.batch(max_batch_size=2, batch_wait_timeout_s=0.05)
        async def handle(self, items):
            raise RuntimeError("batch exploded")

        async def __call__(self, x):
            return await self.handle(x)

    handle = serve.run(Bad.bind(), name="badbatch-app", _proxy=False)
    with pytest.raises(Exception, match="batch exploded"):
        handle.remote(1).result(timeout_s=60)
    serve.delete("badbatch-app")


def test_serve_multiplexed_lru(cluster):
    from ray_tpu import serve

    @serve.deployment
    class MultiModel:
        def __init__(self):
            self.loads = []

        @serve.multiplexed(max_num_models_per_replica=2)
        async def get_model(self, model_id):
            self.loads.append(model_id)
            return {"id": model_id}

        async def __call__(self, _x):
            model = await self.get_model()
            return {
                "served_by": model["id"],
                "ctx": serve.get_multiplexed_model_id(),
                "loads": list(self.loads),
            }

    handle = serve.run(MultiModel.bind(), name="mux-app", _proxy=False)
    r1 = handle.options(multiplexed_model_id="m1").remote(0).result(timeout_s=60)
    assert r1["served_by"] == "m1" and r1["ctx"] == "m1"
    r2 = handle.options(multiplexed_model_id="m2").remote(0).result(timeout_s=60)
    # m1 cached: no reload
    r3 = handle.options(multiplexed_model_id="m1").remote(0).result(timeout_s=60)
    assert r3["loads"].count("m1") == 1
    # third model evicts LRU (m2); asking for m2 again reloads it
    handle.options(multiplexed_model_id="m3").remote(0).result(timeout_s=60)
    r5 = handle.options(multiplexed_model_id="m2").remote(0).result(timeout_s=60)
    assert r5["loads"].count("m2") == 2
    serve.delete("mux-app")


def test_prefix_affinity_key_stability():
    """The affinity key must be stable across processes (crc32, not
    hash()) and derived from the leading tokens only."""
    from ray_tpu.serve.handle import _prefix_affinity_key

    req = {"token_ids": list(range(40)), "max_new_tokens": 4}
    k1 = _prefix_affinity_key((req,), {}, 16)
    k2 = _prefix_affinity_key((), {"request": dict(req)}, 16)
    assert k1 is not None and k1 == k2
    # same head, different tail -> same key (that's the cache-reuse signal)
    other = {"token_ids": list(range(16)) + [999]}
    assert _prefix_affinity_key((other,), {}, 16) == k1
    # different head -> (almost surely) different key
    assert _prefix_affinity_key(({"token_ids": [7] * 16},), {}, 16) != k1
    # prompt-string fallback, and None when there is nothing to hash
    assert _prefix_affinity_key(({"prompt": "hello world"},), {}, 8) is not None
    assert _prefix_affinity_key((42, "x"), {}, 8) is None


def test_prefix_affinity_routes_same_prompt_to_same_replica(cluster):
    """handle.options(prefix_affinity_tokens=N): requests sharing a prompt
    prefix keep landing on one replica (where its KV blocks live) instead
    of spraying across the fleet pow2-style."""
    import os

    @serve.deployment(num_replicas=2)
    class Which:
        def __call__(self, request):
            return os.getpid()

    handle = serve.run(Which.bind(), name="affinity-app", _proxy=False)
    affine = handle.options(prefix_affinity_tokens=8)
    prompt = {"token_ids": [5, 6, 7, 8, 9, 10, 11, 12], "max_new_tokens": 2}
    pids = {
        affine.remote(dict(prompt)).result(timeout_s=60) for _ in range(6)
    }
    assert len(pids) == 1, f"shared prefix spread across replicas: {pids}"
    # a longer prompt with the same head co-locates with it
    longer = {"token_ids": prompt["token_ids"] + [99, 98], "max_new_tokens": 2}
    assert affine.remote(longer).result(timeout_s=60) in pids
    serve.delete("affinity-app")


def test_serve_batch_composes_with_multiplex(cluster):
    """@serve.batch under @serve.multiplexed: pending queues are
    partitioned by model id, so one flush never mixes models, and the
    batch task re-enters the model-id context — the handler's
    get_multiplexed_model_id() returns the batch's model, not ""
    (regression: a single shared queue interleaved m1/m2 items and the
    handler ran with an empty model id)."""
    from ray_tpu import serve

    @serve.deployment
    class MuxBatcher:
        @serve.multiplexed(max_num_models_per_replica=2)
        async def get_model(self, model_id):
            return {"id": model_id}

        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.2)
        async def handle(self, items):
            # the whole point: the batch task must know its model id
            model = await self.get_model()
            ctx = serve.get_multiplexed_model_id()
            return [
                {"v": i, "model": model["id"], "ctx": ctx,
                 "batch": len(items)}
                for i in items
            ]

        async def __call__(self, x):
            return await self.handle(x)

    handle = serve.run(MuxBatcher.bind(), name="muxbatch-app", _proxy=False)
    responses = [
        (f"m{1 + i % 2}",
         handle.options(multiplexed_model_id=f"m{1 + i % 2}").remote(i))
        for i in range(8)
    ]
    results = [(m, r.result(timeout_s=60)) for m, r in responses]
    for i, (model_id, out) in enumerate(results):
        assert out["v"] == i
        assert out["model"] == model_id, "batch mixed models"
        assert out["ctx"] == model_id, "model-id context lost in batch task"
    # same-model requests still actually batch together
    assert max(out["batch"] for _m, out in results) >= 2
    serve.delete("muxbatch-app")


def test_local_testing_mode_no_cluster():
    """serve.run(_local_testing_mode=True) needs no cluster at all
    (reference: serve/_private/local_testing_mode.py)."""
    from ray_tpu import serve

    @serve.deployment
    class Doubler:
        def __call__(self, x):
            return x * 2

    @serve.deployment
    class Gateway:
        def __init__(self, doubler):
            self.doubler = doubler

        def __call__(self, x):
            return self.doubler.remote(x).result() + 1

        async def aecho(self, x):
            return x

    app = Gateway.bind(Doubler.bind())
    handle = serve.run(app, _local_testing_mode=True)
    assert handle.remote(10).result() == 21
    # method routing + async methods work locally
    assert handle.options(method_name="aecho").remote("hi").result() == "hi"


def test_local_testing_mode_batching_and_multiplex():
    from ray_tpu import serve

    @serve.deployment
    class Batched:
        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.02)
        async def __call__(self, items):
            return [i + 100 for i in items]

        @serve.multiplexed(max_num_models_per_replica=2)
        async def get_model(self, model_id: str):
            return {"id": model_id}

        async def which_model(self):
            model = await self.get_model()
            return model["id"]

    handle = serve.run(Batched.bind(), _local_testing_mode=True)
    rs = [handle.remote(i) for i in range(4)]
    assert [r.result(5) for r in rs] == [100, 101, 102, 103]
    out = (
        handle.options(multiplexed_model_id="m7", method_name="which_model")
        .remote()
        .result(5)
    )
    assert out == "m7"


def test_grpc_ingress(cluster):
    """gRPC proxy routes to deployments (reference: serve gRPC proxy path,
    proxy.py:533) via the generic bytes service."""
    from ray_tpu import serve

    @serve.deployment
    class Echo:
        def __call__(self, payload):
            return {"echo": payload}

        def shout(self, payload):
            return str(payload).upper()

    serve.start(proxy=False, grpc_port=0)
    serve.run(Echo.bind(), _proxy=False)
    try:
        addr = serve.grpc_proxy_address()
        assert addr is not None
        out = serve.grpc_call(addr, {"x": 1})
        assert out == {"echo": {"x": 1}}
        out2 = serve.grpc_call(addr, "hi", method="shout")
        assert out2 == "HI"
    finally:
        serve.shutdown()


def test_response_chaining(cluster):
    """A DeploymentResponse passed into another handle call resolves to its
    VALUE before the downstream method runs (reference: model composition by
    passing responses between deployments)."""
    from ray_tpu import serve

    @serve.deployment
    class Doubler:
        def __call__(self, x):
            return x * 2

    @serve.deployment
    class Adder:
        def __call__(self, x):
            assert isinstance(x, int), f"chained arg not resolved: {x!r}"
            return x + 1

    serve.run(Doubler.bind(), name="chain_doubler", _proxy=False)
    serve.run(Adder.bind(), name="chain_adder", _proxy=False)
    try:
        doubler = serve.get_app_handle("chain_doubler")
        adder = serve.get_app_handle("chain_adder")
        resp = doubler.remote(20)          # -> 40 (not awaited)
        out = adder.remote(resp).result(timeout_s=60)
        assert out == 41
    finally:
        serve.delete("chain_doubler")
        serve.delete("chain_adder")


def test_controller_crash_recovery(cluster):
    """Kill the controller worker under traffic: routers keep serving from
    their cached tables, the restarted controller recovers goal state from
    its GCS-KV checkpoint and re-adopts the SAME replicas — no churn
    (reference: controller.py:98-148 checkpoint/recover)."""
    import os
    import signal
    import time as _time

    from ray_tpu import _worker_api

    node = _worker_api.get_node()
    serve.start(proxy=False)

    @serve.deployment(num_replicas=2)
    class Echo:
        def __call__(self, x):
            return ("pid", os.getpid(), x)

    handle = serve.run(Echo.bind(), name="crashapp", _proxy=False)
    assert handle.remote(1).result(timeout_s=60)[2] == 1

    def replica_ids():
        st = serve.status()["crashapp"]
        return sorted(
            r.replica_id
            for dep in st.deployments.values()
            for r in dep.replicas
            if r.state == "RUNNING"
        )

    before = replica_ids()
    assert len(before) == 2

    # SIGKILL the controller's worker process
    ctrl_pids = [
        lease.worker.pid
        for lease in node.raylet._leases.values()
        if getattr(lease.spec, "actor_name", None) == "SERVE_CONTROLLER"
    ]
    assert len(ctrl_pids) == 1
    os.kill(ctrl_pids[0], signal.SIGKILL)

    # traffic keeps flowing through the handle's cached routing table while
    # the controller is down/restarting
    for i in range(10):
        assert handle.remote(i).result(timeout_s=60)[2] == i

    # the restarted controller converges to the SAME replica set
    deadline = _time.time() + 120
    after = None
    while _time.time() < deadline:
        try:
            after = replica_ids()
            if after == before:
                break
        except Exception:
            pass
        _time.sleep(0.5)
    assert after == before, (before, after)
    # and keeps managing: scale the app up through the recovered controller
    serve.delete("crashapp")
