"""Serve streaming + ASGI ingress tests (reference: serve/handle.py:557
DeploymentResponseGenerator; serve/_private/proxy.py:805 ASGI protocol;
serve/api.py:181 @serve.ingress)."""

import json
import time
import urllib.request

import pytest

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
