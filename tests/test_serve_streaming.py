"""Serve streaming + ASGI ingress tests (reference: serve/handle.py:557
DeploymentResponseGenerator; serve/_private/proxy.py:805 ASGI protocol;
serve/api.py:181 @serve.ingress)."""

import json
import time
import urllib.request

import pytest
from conftest import wait_for

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve import DeploymentResponseGenerator


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
    try:
        for app in list(serve.status().keys()):
            serve.delete(app)
    except Exception:
        pass


def test_handle_streaming_first_item_before_completion(cluster):
    """The defining property of streaming: the first chunk is consumable
    while the replica is still generating."""

    @serve.deployment
    class Streamer:
        def __call__(self, n):
            for i in range(n):
                if i > 0:
                    time.sleep(1.5)
                yield {"chunk": i}

    handle = serve.run(Streamer.bind(), name="stream1", _proxy=False)
    gen = handle.options(stream=True).remote(3)
    assert isinstance(gen, DeploymentResponseGenerator)
    t0 = time.time()
    first = next(gen)
    first_latency = time.time() - t0
    assert first == {"chunk": 0}
    # producer sleeps 1.5s before chunk 1 and again before chunk 2; getting
    # chunk 0 in well under that proves item-level delivery
    assert first_latency < 1.4, f"first chunk took {first_latency:.2f}s"
    assert list(gen) == [{"chunk": 1}, {"chunk": 2}]


def test_handle_streaming_async_generator(cluster):
    @serve.deployment
    class AsyncStreamer:
        async def __call__(self, n):
            import asyncio

            for i in range(n):
                await asyncio.sleep(0.01)
                yield i * 10

    handle = serve.run(AsyncStreamer.bind(), name="stream2", _proxy=False)
    out = list(handle.options(stream=True).remote(4))
    assert out == [0, 10, 20, 30]


def test_handle_streaming_non_generator_errors(cluster):
    @serve.deployment
    class NotAGen:
        def __call__(self, x):
            return x

    handle = serve.run(NotAGen.bind(), name="stream3", _proxy=False)
    gen = handle.options(stream=True).remote(1)
    with pytest.raises(Exception, match="generator"):
        list(gen)


def test_http_streaming_ndjson(cluster):
    """Generator ingress streams chunked NDJSON through the proxy; the first
    chunk arrives before the generator finishes."""

    @serve.deployment
    class SlowTokens:
        def __call__(self, body):
            for i in range(3):
                if i > 0:
                    time.sleep(1.5)
                yield {"token": i}

    serve.run(SlowTokens.bind(), name="htstream")
    # streaming flag must have reached the controller via auto-detection
    url = f"{cluster}/htstream"
    req = urllib.request.Request(
        url, data=json.dumps({}).encode(), method="POST"
    )
    t0 = time.time()
    with urllib.request.urlopen(req, timeout=30) as resp:
        assert resp.headers.get("Content-Type", "").startswith(
            "application/x-ndjson"
        )
        first_line = resp.readline()
        first_latency = time.time() - t0
        rest = [ln for ln in resp.read().splitlines() if ln.strip()]
    assert json.loads(first_line) == {"token": 0}
    assert first_latency < 1.4, f"first chunk took {first_latency:.2f}s"
    assert [json.loads(ln) for ln in rest] == [{"token": 1}, {"token": 2}]


def test_http_streaming_sse(cluster):
    @serve.deployment
    class SSEGen:
        def __call__(self, body):
            yield {"a": 1}
            yield {"a": 2}

    serve.run(SSEGen.bind(), name="ssestream")
    req = urllib.request.Request(
        f"{cluster}/ssestream",
        data=b"{}",
        method="POST",
        headers={"Accept": "text/event-stream"},
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        assert resp.headers.get("Content-Type", "").startswith(
            "text/event-stream"
        )
        payload = resp.read().decode()
    events = [
        json.loads(line[len("data: "):])
        for line in payload.splitlines()
        if line.startswith("data: ")
    ]
    assert events == [{"a": 1}, {"a": 2}]


# -- ASGI ingress -------------------------------------------------------------


async def _toy_asgi_app(scope, receive, send):
    """Hand-written ASGI-3 app (no fastapi in the image): routes /hello and
    a /stream endpoint that sends body chunks incrementally."""
    assert scope["type"] == "http"
    path = scope["path"]
    if path == "/hello":
        msg = await receive()
        body = msg.get("body", b"")
        replica = scope.get("ray_tpu.replica")
        await send({
            "type": "http.response.start",
            "status": 200,
            "headers": [(b"content-type", b"application/json"),
                        (b"x-served-by", b"asgi")],
        })
        await send({
            "type": "http.response.body",
            "body": json.dumps({
                "echo": body.decode() if body else "",
                "method": scope["method"],
                "has_replica": replica is not None,
            }).encode(),
        })
    elif path == "/stream":
        import asyncio

        await send({
            "type": "http.response.start",
            "status": 200,
            "headers": [(b"content-type", b"text/plain")],
        })
        for i in range(3):
            await send({
                "type": "http.response.body",
                "body": f"part{i};".encode(),
                "more_body": True,
            })
            await asyncio.sleep(0.01)
        await send({"type": "http.response.body", "body": b"done"})
    else:
        await send({"type": "http.response.start", "status": 404,
                    "headers": []})
        await send({"type": "http.response.body", "body": b"nope"})


def test_asgi_ingress_end_to_end(cluster):
    @serve.deployment
    @serve.ingress(_toy_asgi_app)
    class ASGIApp:
        pass

    serve.run(ASGIApp.bind(), name="asgiapp")
    req = urllib.request.Request(
        f"{cluster}/asgiapp/hello",
        data=b"ping",
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        assert resp.status == 200
        assert resp.headers["x-served-by"] == "asgi"
        data = json.loads(resp.read())
    assert data == {"echo": "ping", "method": "POST", "has_replica": True}

    with urllib.request.urlopen(
        f"{cluster}/asgiapp/stream", timeout=30
    ) as resp:
        body = resp.read().decode()
    assert body == "part0;part1;part2;done"

    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(
            f"{cluster}/asgiapp/missing", timeout=30
        )
    assert err.value.code == 404


def test_local_mode_streaming():
    @serve.deployment
    class LocalGen:
        def __call__(self, n):
            for i in range(n):
                yield i + 100

    handle = serve.run(LocalGen.bind(), name="lm", _local_testing_mode=True)
    out = list(handle.options(stream=True).remote(3))
    assert out == [100, 101, 102]


# -- a stream's items reach the handle's consumer as values ------------------
# (serve/handle.py: one hop onto the owner's loop an item, no ObjectRef.)
# Counts, never times.


def _owner():
    from ray_tpu import _worker_api

    return _worker_api.get_core_worker()


def _left_with_owner(task_id):
    w = _owner()
    return [
        oid
        for oid in list(w.memory_store._objects) + list(w._owned)
        if oid.task_id() == task_id
    ]


def test_stream_items_travel_as_values(cluster):
    """After a serving stream the owner's counter reads: values taken =
    items yielded, refs made = 0, takes <= items; nothing is left behind."""

    @serve.deployment
    class Tokens:
        async def __call__(self, n):
            import asyncio

            for i in range(n):
                await asyncio.sleep(0.005)
                yield {"token_id": i, "text": f"t{i}", "finished": i == n - 1}

    handle = serve.run(Tokens.bind(), name="valstream", _proxy=False)
    before = dict(_owner().stream_counts)
    gen = handle.options(stream=True).remote(25)
    task_id = gen._to_object_ref_gen()._task_id
    out = list(gen)
    assert [o["token_id"] for o in out] == list(range(25))
    assert out[-1]["finished"] and gen._consumed == 25
    after = _owner().stream_counts
    assert after["values"] - before["values"] == 25
    assert after["refs"] == before["refs"]
    assert 1 <= after["takes"] - before["takes"] <= 25
    assert task_id not in _owner()._streams
    wait_for(lambda: _left_with_owner(task_id) == [])


def test_stream_consumer_behind_catches_up_in_one_hop(cluster):
    @serve.deployment
    class Burst:
        def __call__(self, n):
            for i in range(n):
                yield i

    handle = serve.run(Burst.bind(), name="burststream", _proxy=False)
    gen = handle.options(stream=True).remote(30)
    task_id = gen._to_object_ref_gen()._task_id

    def all_produced():
        state = _owner()._streams.get(task_id)
        return state is not None and state.total == 30 and len(state.reported) == 30

    wait_for(all_produced)  # the consumer was away while all 30 were yielded
    before = dict(_owner().stream_counts)
    assert next(gen) == 0
    assert len(gen._taken) == 29  # one hop brought them all
    assert list(gen) == list(range(1, 30))
    after = _owner().stream_counts
    assert after["takes"] - before["takes"] == 1
    assert after["values"] - before["values"] == 30
    assert after["refs"] == before["refs"]


def test_stream_close_drops_what_was_taken_and_stops_the_replica(cluster):
    @serve.deployment
    class Endless:
        def __init__(self):
            self.closed = False

        async def __call__(self, _):
            import asyncio

            try:
                i = 0
                while True:
                    yield i
                    i += 1
                    await asyncio.sleep(0.01)
            finally:
                self.closed = True

        async def was_closed(self):
            return self.closed

    handle = serve.run(Endless.bind(), name="closestream", _proxy=False)
    gen = handle.options(stream=True).remote(None)
    task_id = gen._to_object_ref_gen()._task_id
    assert next(gen) == 0
    wait_for(lambda: len(_owner()._streams[task_id].reported) >= 5)
    assert next(gen) == 1 and len(gen._taken) >= 2  # taken, not handed out
    gen.close()
    assert not gen._taken
    with pytest.raises(StopIteration):
        next(gen)
    assert gen._consumed == 2
    wait_for(lambda: handle.was_closed.remote().result(timeout_s=30))
    assert task_id not in _owner()._streams
    wait_for(lambda: _left_with_owner(task_id) == [])


def test_stream_timeout_bounds_the_wait_for_the_next_item(cluster):
    from ray_tpu.exceptions import GetTimeoutError

    @serve.deployment
    class Stalls:
        async def __call__(self, _):
            import asyncio

            yield "first"
            await asyncio.sleep(30)
            yield "never"

    handle = serve.run(Stalls.bind(), name="stallstream", _proxy=False)
    gen = handle.options(stream=True, timeout_s=1.0).remote(None)
    assert next(gen) == "first"
    with pytest.raises(GetTimeoutError):
        next(gen)
    with pytest.raises(StopIteration):  # the timed-out stream was closed
        next(gen)


class _ScriptedStream:
    """Stands in for an ObjectRefGenerator: each take is the next step of a
    script, a list of values, None (the end) or an exception to raise."""

    def __init__(self, *steps):
        from ray_tpu._internal import serialization

        self._steps = [
            [serialization.pack(v) for v in step] if isinstance(step, list) else step
            for step in steps
        ]
        self.closed = False

    def take_values(self, timeout=None):
        step = self._steps.pop(0)
        if isinstance(step, BaseException):
            raise step
        return step

    def close(self):
        self.closed = True


def _scripted_context(resubmission):
    """A real _RequestContext whose router hands back one fake replica that
    answers a resubmission with ``resubmission``."""
    from types import SimpleNamespace

    from ray_tpu.serve.handle import _RequestContext

    submitted = []

    def remote(*call):
        submitted.append(call)
        return resubmission

    replica = SimpleNamespace(
        handle_request_stream=SimpleNamespace(
            options=lambda **_: SimpleNamespace(remote=remote)
        )
    )
    router = SimpleNamespace(pick=lambda *a, **k: ("replica-2", replica))
    ctx = _RequestContext(
        router, "dep", "__call__", (), {}, None, None, True, None,
        {"max_attempts": 3, "backoff_s": 0.0}, "replica-1",
    )
    return ctx, submitted


@pytest.mark.parametrize("consumed_before_death", [0, 1, 3])
def test_stream_fails_over_only_with_nothing_consumed(consumed_before_death):
    """The idempotency guard counts items handed to the caller, not items
    taken from the owner: a death before the first item resubmits, one
    after any item surfaces, and the stream is closed either way."""
    from ray_tpu.exceptions import ActorDiedError

    died = ActorDiedError("a1", "node lost")
    delivered = [f"tok{i}" for i in range(consumed_before_death)]
    # every delivered item arrives in one take, then the death
    first = _ScriptedStream(*([delivered] if delivered else []), died)
    second = _ScriptedStream(["again0", "again1"], None)
    ctx, submitted = _scripted_context(second)
    gen = DeploymentResponseGenerator(first, timeout_s=5.0, ctx=ctx)
    if consumed_before_death == 0:
        assert list(gen) == ["again0", "again1"]
        assert len(submitted) == 1 and first.closed
        assert gen.replica_id() == "replica-2" and gen._consumed == 2
    else:
        got = []
        with pytest.raises(ActorDiedError):
            for item in gen:
                got.append(item)
        assert got == delivered and gen._consumed == consumed_before_death
        assert submitted == [] and first.closed
        with pytest.raises(StopIteration):
            next(gen)
