"""HTTP proxy actor: the cluster's ingress.

Role-equivalent of the reference's ProxyActor (python/ray/serve/_private/
proxy.py:1153; HTTP handling :709): terminates HTTP, resolves the route
prefix to an application, forwards the request body to the app's ingress
deployment through a DeploymentHandle, and streams the response back.
aiohttp replaces uvicorn; JSON in/out is the default content type.
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import threading
import time
from typing import Dict, Optional

from .._internal.rpc import RPC_OOB_THRESHOLD as _RPC_OOB_THRESHOLD

logger = logging.getLogger(__name__)


class HTTPProxy:
    """Actor: runs an aiohttp server in a dedicated thread+loop.

    Multi-proxy data plane: N HTTPProxy actors share ONE host:port via
    SO_REUSEPORT (``reuse_port=True``) — the kernel spreads accepted
    connections across the listeners, so ingress scales with proxy count
    with no front-end balancer. Each proxy registers with the controller
    under its ``proxy_id`` (GCS ``proxy:`` prefix) so drains, chaos kills
    and the dashboard address individual proxies."""

    def __init__(self, controller, host: str = "127.0.0.1", port: int = 8000,
                 proxy_id: str = "http#0", reuse_port: bool = False):
        self._controller = controller
        self._host = host
        self._port = port
        self._proxy_id = proxy_id
        self._reuse_port = reuse_port
        self._routes: Dict[str, str] = {}
        self._handles: Dict[str, object] = {}
        self._ingress: Dict[str, dict] = {}
        self._ready = threading.Event()
        self._error: Optional[str] = None
        self._started_at = time.time()
        self._draining = False
        self._inflight = 0
        # pre-bound metric handles + pre-built hot response headers: the
        # request loop must not build tag dicts or header dicts per request
        from ..util.metrics import ingress_handles

        self._m = ingress_handles(proxy_id)
        self._hot_headers = {"X-Proxy-Id": proxy_id}
        self._thread = threading.Thread(
            target=self._serve_forever, daemon=True, name="http-proxy"
        )
        self._thread.start()
        # _ready is set on the error path too: a proxy that could not bind
        # its port is no proxy, and a request meant for it would be answered
        # by whoever holds the port
        if not self._ready.wait(timeout=15) or self._error:
            raise RuntimeError(f"HTTP proxy failed to start: {self._error}")

    # -- server --------------------------------------------------------------

    def _serve_forever(self):
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        try:
            loop.run_until_complete(self._start_server())
            loop.run_forever()
        except Exception as e:  # noqa: BLE001
            self._error = repr(e)
            self._ready.set()

    async def _start_server(self):
        from aiohttp import web

        app = web.Application()
        app.router.add_route("*", "/-/routes", self._handle_routes)
        app.router.add_route("*", "/-/healthz", self._handle_health)
        app.router.add_route("*", "/{tail:.*}", self._handle_request)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(
            runner, self._host, self._port, reuse_port=self._reuse_port
        )
        await site.start()
        self._ready.set()

    async def _handle_health(self, request):
        from aiohttp import web

        return web.json_response({"status": "ok"})

    async def _handle_routes(self, request):
        from aiohttp import web

        await self._refresh_routes_async()
        return web.json_response(self._routes)

    async def _refresh_routes_async(self):
        # the blocking handle API must stay off the aiohttp loop, or one
        # slow controller call freezes every in-flight HTTP request
        await asyncio.get_event_loop().run_in_executor(
            None, self._refresh_routes
        )

    def _refresh_routes(self):
        from .. import api

        try:
            self._routes = api.get(
                self._controller.get_app_route_prefixes.remote(), timeout=10
            )
            # re-deploys may flip an app's ingress mode (stream/asgi)
            self._ingress.clear()
        except Exception:
            logger.exception("route refresh failed")

    def _resolve(self, path: str):
        """Longest-prefix route match -> (app_name, remaining path)."""
        best = None
        for prefix, app_name in self._routes.items():
            if path == prefix or path.startswith(prefix.rstrip("/") + "/") or (
                prefix == "/" and best is None
            ):
                if best is None or len(prefix) > len(best[0]):
                    best = (prefix, app_name)
        return best

    @staticmethod
    def _request_timeout_s(request) -> Optional[float]:
        """Per-request deadline from the ``X-Request-Timeout-S`` header
        (reference: serve's RAY_SERVE_REQUEST_PROCESSING_TIMEOUT_S header
        override); None defers to the deployment's
        RequestRouterConfig.default_timeout_s (60 s out of the box)."""
        raw = request.headers.get("X-Request-Timeout-S")
        if not raw:
            return None
        try:
            timeout_s = float(raw)
        except ValueError:
            return None
        return timeout_s if timeout_s > 0 else None

    @staticmethod
    def _trace_context(request) -> Optional[dict]:
        """Mint the request's trace at the ingress: honor an inbound
        ``X-Trace-Id`` (caller-chosen id — loadgen/bench join their
        records to server spans with it), else start a fresh trace when
        this process traces. None on the untraced path — requests with no
        header and tracing off cost nothing."""
        from ..util import tracing

        raw = request.headers.get("X-Trace-Id")
        if raw:
            return tracing.new_trace_context(raw.strip()[:64])
        if tracing.is_tracing_enabled():
            return tracing.new_trace_context()
        return None

    @staticmethod
    def _error_response(exc: Exception):
        """Map typed serve errors onto HTTP semantics: backpressure sheds
        are 503 + Retry-After (the client should back off and retry),
        deadline expiry is 504, everything else stays a 500."""
        from aiohttp import web

        from ..exceptions import (
            BackPressureError,
            DeadlineExceededError,
            GetTimeoutError,
        )

        cause = getattr(exc, "cause", None) or exc
        if isinstance(cause, BackPressureError):
            return web.json_response(
                {"error": repr(cause), "retry_after_s": cause.retry_after_s},
                status=503,
                headers={
                    "Retry-After": str(max(1, int(cause.retry_after_s + 0.5)))
                },
            )
        if isinstance(cause, (DeadlineExceededError, GetTimeoutError)):
            return web.json_response({"error": repr(cause)}, status=504)
        return web.json_response({"error": repr(exc)}, status=500)

    async def _handle_request(self, request):
        from aiohttp import web

        if self._draining:
            self._m["drain"].inc()
            return web.json_response(
                {"error": "proxy draining", "retry_after_s": 1.0},
                status=503,
                headers={"Retry-After": "1", "X-Proxy-Id": self._proxy_id},
            )
        t0 = time.perf_counter()
        self._inflight += 1
        self._m["inflight"].set(self._inflight)
        try:
            resp = await self._dispatch(request)
        except Exception as e:  # noqa: BLE001
            resp = self._error_response(e)
        finally:
            self._inflight -= 1
            self._m["inflight"].set(self._inflight)
            self._m["latency"].observe((time.perf_counter() - t0) * 1000.0)
        status = resp.status
        if status < 400:
            self._m["ok"].inc()
        elif status == 503:
            self._m["shed"].inc()
        elif status == 504:
            self._m["timeout"].inc()
        else:
            self._m["error"].inc()
        if not resp.prepared:
            # streaming/ASGI responses stamp the header pre-prepare
            resp.headers.setdefault("X-Proxy-Id", self._proxy_id)
        return resp

    async def _dispatch(self, request):
        from aiohttp import web

        path = "/" + request.match_info["tail"]
        match = self._resolve(path)
        if match is None:
            await self._refresh_routes_async()
            match = self._resolve(path)
        if match is None:
            return web.json_response(
                {"error": f"no app for path {path}"}, status=404
            )
        prefix, app_name = match
        info = await self._ingress_info(app_name)
        if info.get("asgi"):
            return await self._handle_asgi(request, app_name, path, prefix)
        body: object = None
        raw = b""
        if request.body_exists:
            raw = await request.read()
            if raw:
                if request.content_type == "application/octet-stream":
                    # binary fast path: no JSON decode, and large bodies are
                    # wrapped in bytearray so the proxy→replica hop ships
                    # them through the v2 framing's zero-copy out-of-band
                    # buffer path instead of re-pickling the payload inline
                    body = (
                        bytearray(raw)
                        if len(raw) >= _RPC_OOB_THRESHOLD else raw
                    )
                else:
                    try:
                        body = json.loads(raw)
                    except json.JSONDecodeError:
                        body = raw.decode("utf-8", "replace")
        timeout_s = self._request_timeout_s(request)
        trace_ctx = self._trace_context(request)
        if info.get("stream"):
            return await self._handle_stream(request, app_name, body,
                                             timeout_s, trace_ctx)
        # forward to the app's ingress deployment off-loop (the handle API
        # is blocking); one thread per in-flight request keeps the proxy
        # loop responsive
        result = await asyncio.get_event_loop().run_in_executor(
            None, self._call_ingress, app_name, path, prefix, body, timeout_s,
            trace_ctx,
        )
        # untraced hot path reuses ONE prebuilt header dict (aiohttp copies
        # it into the response's CIMultiDict); traced requests echo the
        # trace id so callers can join their latency record with the
        # server-side spans (`ray_tpu timeline`)
        if trace_ctx is None:
            headers = self._hot_headers
        else:
            headers = {"X-Proxy-Id": self._proxy_id,
                       "X-Trace-Id": trace_ctx["trace_id"]}
        if isinstance(result, Exception):
            resp = self._error_response(result)
            resp.headers.update(headers)
            return resp
        if isinstance(result, (dict, list, int, float, str, bool)) or result is None:
            return web.json_response({"result": result}, headers=headers)
        return web.Response(
            body=bytes(result), headers=headers,
            content_type="application/octet-stream",
        )

    _INGRESS_TTL_S = 5.0

    async def _ingress_info(self, app_name: str) -> dict:
        import time

        cached = self._ingress.get(app_name)
        if cached is not None and time.time() - cached[0] < self._INGRESS_TTL_S:
            return cached[1]
        from .. import api

        def fetch():
            try:
                return api.get(
                    self._controller.get_ingress_info.remote(app_name),
                    timeout=10,
                )
            except Exception:
                logger.exception("ingress info fetch failed")
                return {}

        info = await asyncio.get_event_loop().run_in_executor(None, fetch)
        self._ingress[app_name] = (time.time(), info)
        return info

    def _get_handle(self, app_name: str):
        from .api import get_app_handle

        handle = self._handles.get(app_name)
        if handle is None:
            handle = get_app_handle(app_name, _controller=self._controller)
            self._handles[app_name] = handle
        return handle

    def _call_ingress(self, app_name: str, path: str, prefix: str, body,
                      timeout_s: Optional[float] = None,
                      trace_ctx: Optional[dict] = None):
        # the deadline rides through the handle into the replica; the
        # result() wait is bounded by it (default 60 s — no more hardcoded
        # proxy timeout disagreeing with the request's actual budget). The
        # handle absorbs replica deaths/drains (and sheds, per the
        # deployment's RequestRouterConfig); what still escapes maps to
        # typed HTTP statuses in _error_response.
        from ..util import tracing

        try:
            handle = self._get_handle(app_name).options(
                timeout_s=timeout_s
            ) if timeout_s is not None else self._get_handle(app_name)
            if trace_ctx is None and not tracing.is_tracing_enabled():
                # untraced fast path: skip the span contextmanager entirely
                # (even a no-op span allocates the generator + frame; the
                # perf-smoke 5% guard fences this)
                return handle.remote(body).result()
            # the proxy span is the trace's top: route/attempt/replica
            # spans parent under it (this runs on an executor thread, so
            # the task-context install inside is thread-safe)
            with tracing.request_span(
                "serve.proxy", trace_ctx, app=app_name, path=path
            ):
                return handle.remote(body).result()
        except Exception as e:  # noqa: BLE001
            return e

    # -- streaming -----------------------------------------------------------

    async def _iter_stream(self, make_gen):
        """Drive a blocking DeploymentResponseGenerator on a pool thread,
        relaying items onto the aiohttp loop as they arrive — the proxy
        event loop never blocks on the next item. Closing this generator
        (client disconnect, early break) stops the pump so the pool thread
        is released instead of draining the rest of the replica's stream
        into the queue."""
        loop = asyncio.get_event_loop()
        queue: asyncio.Queue = asyncio.Queue()
        _DONE = object()
        stop = threading.Event()

        def pump():
            gen = None
            try:
                gen = make_gen()
                for item in gen:
                    if stop.is_set():
                        break
                    loop.call_soon_threadsafe(queue.put_nowait, item)
            except Exception as e:  # noqa: BLE001 — relayed to the consumer
                loop.call_soon_threadsafe(queue.put_nowait, e)
            finally:
                close = getattr(gen, "close", None)
                if close is not None:
                    try:
                        close()
                    except Exception:  # noqa: BLE001
                        pass
                loop.call_soon_threadsafe(queue.put_nowait, _DONE)

        loop.run_in_executor(None, pump)
        try:
            while True:
                item = await queue.get()
                if item is _DONE:
                    return
                if isinstance(item, Exception):
                    raise item
                yield item
        finally:
            stop.set()

    async def _handle_stream(self, request, app_name: str, body,
                             timeout_s: Optional[float] = None,
                             trace_ctx: Optional[dict] = None):
        """Generator ingress -> chunked HTTP: newline-delimited JSON, or SSE
        when the client asks for text/event-stream (reference: proxy
        streaming of DeploymentResponseGenerator outputs). Teardown (client
        disconnect, early close) closes the DeploymentResponseGenerator,
        which cancels the replica-side generator — the replica stops
        producing tokens nobody will read."""
        from aiohttp import web

        sse = "text/event-stream" in request.headers.get("Accept", "")
        resp = web.StreamResponse()
        resp.content_type = "text/event-stream" if sse else "application/x-ndjson"
        resp.headers["X-Proxy-Id"] = self._proxy_id
        if trace_ctx:
            resp.headers["X-Trace-Id"] = trace_ctx["trace_id"]
        await resp.prepare(request)

        def make_gen():
            from ..util import tracing

            opts = {"stream": True}
            if timeout_s is not None:
                opts["timeout_s"] = timeout_s
            handle = self._get_handle(app_name).options(**opts)
            if trace_ctx is None:
                return handle.remote(body)
            # covers submission only (items stream on after it closes);
            # the replica-side stream span covers the generation itself
            with tracing.request_span(
                "serve.proxy", trace_ctx, app=app_name, stream=True
            ):
                return handle.remote(body)

        from contextlib import aclosing

        try:
            async with aclosing(self._iter_stream(make_gen)) as stream:
                async for item in stream:
                    if isinstance(item, (bytes, bytearray)):
                        chunk = bytes(item)
                    elif sse:
                        chunk = f"data: {json.dumps(item)}\n\n".encode()
                    else:
                        chunk = (json.dumps(item) + "\n").encode()
                    await resp.write(chunk)
        except Exception as e:  # noqa: BLE001 — stream already started
            err = json.dumps({"error": repr(e)})
            # keep the error inside the negotiated framing or SSE parsers
            # silently drop it
            await resp.write(
                f"data: {err}\n\n".encode() if sse else (err + "\n").encode()
            )
        await resp.write_eof()
        return resp

    async def _handle_asgi(self, request, app_name: str, path: str,
                           prefix: str):
        """ASGI ingress: build an ASGI-3 HTTP scope from the aiohttp
        request, stream it through the replica's __asgi__ method, and relay
        response-start/body events back as they arrive (reference: the
        proxy's ASGI protocol with ingress replicas, proxy.py:805)."""
        from aiohttp import web

        root = prefix.rstrip("/")
        scope = {
            "type": "http",
            "asgi": {"version": "3.0", "spec_version": "2.3"},
            "http_version": "1.1",
            "method": request.method,
            "scheme": "http",
            "path": path[len(root):] or "/" if path.startswith(root) else path,
            "raw_path": path.encode(),
            "root_path": root,
            "query_string": request.query_string.encode(),
            "headers": [
                (k.lower().encode(), v.encode())
                for k, v in request.headers.items()
            ],
            "client": None,
            "server": (self._host, self._port),
        }
        body = await request.read() if request.body_exists else b""

        def make_gen():
            return (
                self._get_handle(app_name)
                .options(stream=True, method_name="__asgi__")
                .remote(scope, body)
            )

        from contextlib import aclosing

        resp = None

        async def relay():
            nonlocal resp
            async with aclosing(self._iter_stream(make_gen)) as stream:
                async for event in stream:
                    etype = event.get("type")
                    if etype == "http.response.start":
                        resp = web.StreamResponse(
                            status=event.get("status", 200)
                        )
                        for k, v in event.get("headers", []):
                            name = k.decode() if isinstance(k, bytes) else k
                            val = v.decode() if isinstance(v, bytes) else v
                            # aiohttp computes framing itself
                            if name.lower() not in ("content-length",
                                                    "transfer-encoding"):
                                resp.headers[name] = val
                        await resp.prepare(request)
                    elif etype == "http.response.body":
                        if resp is None:
                            raise RuntimeError(
                                "ASGI app sent body before response start"
                            )
                        await resp.write(event.get("body", b""))
                        if not event.get("more_body"):
                            return
                    elif etype == "asgi.error":
                        raise RuntimeError(
                            event.get("error", "ASGI app failed")
                        )

        try:
            await relay()
        except Exception as e:  # noqa: BLE001
            if resp is None:
                return web.json_response({"error": repr(e)}, status=500)
            await resp.write(json.dumps({"error": repr(e)}).encode())
        if resp is None:
            return web.json_response(
                {"error": "ASGI app sent no response"}, status=500
            )
        await resp.write_eof()
        return resp

    # -- control -------------------------------------------------------------

    def address(self):
        return (self._host, self._port)

    def ping(self):
        return True

    def describe(self) -> dict:
        """Identity record the controller writes under the GCS ``proxy:``
        prefix — what `ray_tpu proxies`, the dashboard and chaos kill-proxy
        see."""
        from ..util.metrics import _node_hex

        return {
            "kind": "http",
            "proxy_id": self._proxy_id,
            "host": self._host,
            "port": self._port,
            "pid": os.getpid(),
            "node": _node_hex(),
            "started_at": self._started_at,
        }

    def stats(self) -> dict:
        return {"proxy_id": self._proxy_id, "inflight": self._inflight,
                "draining": self._draining}

    def drain(self, timeout_s: float = 5.0) -> bool:
        """Stop accepting (new requests get 503 + Retry-After so clients
        move to a surviving proxy), then wait — bounded — for in-flight
        requests to finish. Returns True when the proxy drained clean."""
        from ..util import events as _events

        self._draining = True
        deadline = time.time() + timeout_s
        while self._inflight > 0 and time.time() < deadline:
            time.sleep(0.02)
        _events.record_event(
            _events.PROXY_DRAIN, proxy_id=self._proxy_id, kind="http",
            inflight=self._inflight,
        )
        return self._inflight == 0
