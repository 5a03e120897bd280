"""Client server: the cluster-side half of ray:// connections.

Role-equivalent of the reference's client server
(python/ray/util/client/server/server.py, proxier.py): hosts one driver
CoreWorker per server inside the cluster network and exposes three RPCs —
``client_connect`` (handshake metadata), ``worker_op`` (invoke a CoreWorker
method by name: submit_task/put/get_objects/...), and ``proxy_rpc`` (relay
an arbitrary control-plane call, e.g. to the GCS, through the server's
client pool). Ownership of every client-created object rests with the
server's worker, exactly as the reference parks ownership in the proxied
driver.
"""

from __future__ import annotations

import asyncio
import logging
from typing import Optional, Tuple

from .._internal.config import Config
from .._internal.event_loop import LoopThread
from .._internal.rpc import RpcClient, RpcServer
from ..runtime.gcs import keys as gcs_keys
from ..runtime.worker.core_worker import CoreWorker, WorkerMode

logger = logging.getLogger(__name__)


class ClientServer:
    # CoreWorker ops clients may invoke; everything else (shutdown, start,
    # handler registration...) would let one client break the shared worker
    ALLOWED_OPS = frozenset({
        "put", "get_objects", "wait", "submit_task", "create_actor",
        "submit_actor_task", "kill_actor", "attach_actor",
        "next_stream_item", "take_stream_values", "drop_stream",
    })

    def __init__(self, gcs_address: Tuple[str, int], config: Optional[Config] = None):
        self.gcs_address = gcs_address
        self.config = config or Config()
        self.server = RpcServer("client-server")
        self.worker: Optional[CoreWorker] = None
        self.address: Optional[Tuple[str, int]] = None
        # ids pinned on behalf of each client session (reference: Ray Client
        # server-side per-session pinning); a session's pins release when its
        # connection drops (or at stop for sessions that never disconnect)
        self._pins_by_client: dict = {}  # client_id -> set[ObjectID]
        self._activity: dict = {}  # client_id -> op counter (reconnect detection)
        self._exported_fns: set = set()

    async def _find_raylet(self):
        from .._internal.node_lookup import find_raylet_address

        client = RpcClient(*self.gcs_address, name="client-server-lookup")
        try:
            return await find_raylet_address(client)
        finally:
            await client.close()

    async def start(self, host: str = "127.0.0.1", port: int = 0):
        raylet_address = await self._find_raylet()
        self.worker = CoreWorker(
            WorkerMode.DRIVER, self.config, self.gcs_address, raylet_address,
            asyncio.get_event_loop(),
        )
        await self.worker.start()
        await self.worker.register_driver_job({"namespace": "_client_server"})
        self.server.register("client_connect", self._handle_connect)
        self.server.register("worker_op", self._handle_worker_op)
        self.server.register("proxy_rpc", self._handle_proxy_rpc)
        self.server.register("xlang_task", self._handle_xlang_task)
        self.server.on_connection_lost(self._on_client_disconnect)
        # a transparent reconnect (same client_id) counts as activity so the
        # disconnect-grace timer never frees a live session's pins
        self.server.on_connection_registered(self._on_client_register)
        bound = await self.server.start(host, port)
        self.address = (host, bound)
        logger.info("client server on %s", self.address)
        return self.address

    async def stop(self):
        await self.server.stop()
        if self.worker is not None:
            for client_id in list(self._pins_by_client):
                self._release_client(client_id)
            await self.worker.shutdown()

    def _release_client(self, client_id: str):
        pinned = self._pins_by_client.pop(client_id, None)
        if not pinned or self.worker is None:
            return
        with self.worker._ref_lock:
            for oid in pinned:
                self.worker._local_refs[oid] -= 1
        for oid in pinned:
            self.worker._maybe_free(oid)

    #: seconds a disconnected session's pins survive — RpcClient reconnects
    #: transparently with the same client_id after a TCP blip, and freeing
    #: immediately would invalidate refs the continuing session still holds
    RELEASE_GRACE_S = 60.0

    def _on_client_register(self, peer_meta: dict):
        client_id = peer_meta.get("client_id")
        if client_id:
            self._activity[client_id] = self._activity.get(client_id, 0) + 1

    def _on_client_disconnect(self, peer_meta: dict):
        client_id = peer_meta.get("client_id")
        if not client_id:
            return
        seen = self._activity.get(client_id, 0)
        asyncio.get_event_loop().call_later(
            self.RELEASE_GRACE_S, self._release_if_inactive, client_id, seen
        )

    def _release_if_inactive(self, client_id: str, activity_at_disconnect: int):
        if self._activity.get(client_id, 0) != activity_at_disconnect:
            return  # the session reconnected and kept working
        logger.info("client %s gone; releasing its pins", client_id)
        self._activity.pop(client_id, None)
        self._release_client(client_id)

    # -- handlers -----------------------------------------------------------

    async def _handle_connect(self):
        return {
            "worker_address": self.worker.address,
            "worker_id": self.worker.worker_id,
            "gcs_address": self.gcs_address,
        }

    def _pin(self, object_ids, client_id: str):
        """Hold a local ref on behalf of a client session so the owner worker
        doesn't free objects the client still references (clients have no
        in-cluster refcount presence). Released on that client's disconnect."""
        pins = self._pins_by_client.setdefault(client_id, set())
        with self.worker._ref_lock:
            for oid in object_ids:
                if oid not in pins:
                    pins.add(oid)
                    self.worker._local_refs[oid] += 1

    async def _handle_worker_op(self, client_id: str, op: str, *args):
        if op not in self.ALLOWED_OPS:
            raise ValueError(f"worker_op {op!r} not allowed")
        self._activity[client_id] = self._activity.get(client_id, 0) + 1
        fn = getattr(self.worker, op)
        result = fn(*args)
        if asyncio.iscoroutine(result):
            result = await result
        if op == "put":
            self._pin([result], client_id)
        elif op in ("submit_task", "submit_actor_task"):
            self._pin(result, client_id)
        elif op == "next_stream_item" and result is not None:
            # stream items the client read: pin like any other client-held
            # ref (the item ObjectRef lives on the client with no in-cluster
            # refcount presence)
            self._pin([result.id], client_id)
        return result

    # control-plane calls a client may relay — GCS reads, KV, jobs, and
    # placement groups. Mirrors ALLOWED_OPS: an open relay would let one
    # client call exit_worker/free_objects on raylets and other workers,
    # breaking sessions it doesn't own.
    ALLOWED_PROXY_METHODS = frozenset({
        "register_job", "finish_job", "list_jobs",
        "get_all_nodes", "cluster_resources", "cluster_available_resources",
        "get_cluster_resource_state", "get_autoscaling_state",
        "get_actor", "get_actor_by_name", "list_actors",
        "create_placement_group", "remove_placement_group",
        "get_placement_group", "get_placement_group_by_name",
        "pg_wait_ready", "list_placement_groups",
        "kv_put", "kv_get", "kv_del", "kv_multi_get", "kv_exists", "kv_keys",
        "list_task_events",
    })

    # device-object resolution must reach the OWNING WORKER, not the GCS
    # (experimental/device_objects.py fetches by owner address); these two
    # read/free handlers are the only worker-addressed relays permitted
    ALLOWED_WORKER_PROXY_METHODS = frozenset({
        "fetch_device_object", "free_device_object",
    })

    async def _handle_proxy_rpc(self, address, method: str, *args):
        if method in self.ALLOWED_WORKER_PROXY_METHODS:
            pass  # any worker address
        elif tuple(address) != tuple(self.gcs_address):
            raise ValueError("proxy_rpc may only target the GCS")
        elif method not in self.ALLOWED_PROXY_METHODS:
            raise ValueError(f"proxy_rpc method {method!r} not allowed")
        return await self.worker.client_pool.get(*tuple(address)).call(
            method, *args
        )

    # -- cross-language entry (reference: ray.cross_language P28 + the C++
    # frontend N25): non-Python clients submit named Python functions with
    # JSON args; the reply is ALWAYS a JSON string so a minimal non-Python
    # pickle reader can parse the response frame -----------------------------

    async def _handle_xlang_task(
        self, module: str, qualname: str, args_json: str,
        num_cpus: float = 1.0, timeout: float = 120.0,
    ) -> str:
        import hashlib
        import json

        from .._internal import args as arglib
        from .._internal import serialization
        from .._internal.protocol import (
            FunctionDescriptor,
            TaskArg,
            TaskSpec,
            TaskType,
        )
        from ..object_ref import ObjectRef

        try:
            worker = self.worker
            pickled = serialization.dumps(_xlang_exec)
            fn_hash = hashlib.sha1(pickled).hexdigest()
            if fn_hash not in self._exported_fns:
                await worker.client_pool.get(*self.gcs_address).call(
                    "kv_put", gcs_keys.FUNCTION.key(fn_hash), pickled, True
                )
                self._exported_fns.add(fn_hash)
            structure, _refs = arglib.flatten((module, qualname, args_json), {})
            spec = TaskSpec(
                task_id=worker.next_task_id(),
                job_id=worker.job_id,
                task_type=TaskType.NORMAL_TASK,
                function=FunctionDescriptor(
                    module=_xlang_exec.__module__,
                    qualname="_xlang_exec",
                    function_hash=fn_hash,
                ),
                args=[TaskArg(value=serialization.pack(structure))],
                num_returns=1,
                resources={"CPU": float(num_cpus)},
                owner_worker_id=worker.worker_id,
                owner_address=worker.address,
            )
            return_ids = await worker.submit_task(spec)
            ref = ObjectRef(return_ids[0], worker.address, _register=False)
            try:
                values = await worker.get_objects([ref], timeout)
            except Exception:
                # task still running: freeing now would strip ownership and
                # orphan the eventual result — reap it in the background
                # once it materializes
                async def _reap():
                    try:
                        await worker.get_objects([ref], 3600.0)
                    except Exception:
                        pass
                    worker._maybe_free(ref.id)

                asyncio.ensure_future(_reap())
                raise
            # result handed to the caller; drop the owner-side entry
            worker._maybe_free(ref.id)
            return values[0]  # _xlang_exec already returns a JSON envelope
        except Exception as e:  # noqa: BLE001 — JSON-encodable error reply
            return json.dumps({"ok": False, "error": repr(e)})


def _xlang_exec(module: str, qualname: str, args_json: str) -> str:
    """Runs in a worker: import + call the named function with JSON args."""
    import importlib
    import json

    try:
        obj = importlib.import_module(module)
        for part in qualname.split("."):
            obj = getattr(obj, part)
        args = json.loads(args_json) if args_json else []
        out = obj(**args) if isinstance(args, dict) else obj(*args)
        return json.dumps({"ok": True, "value": out})
    except Exception as e:  # noqa: BLE001
        return json.dumps({"ok": False, "error": repr(e)})


def start_client_server(
    gcs_address: Tuple[str, int],
    loop_thread: LoopThread,
    host: str = "127.0.0.1",
    port: int = 0,
) -> ClientServer:
    """Start a ClientServer on an existing loop thread (used by Node when
    ``client_server_port`` is configured, and by tests)."""
    server = ClientServer(gcs_address)
    loop_thread.run(server.start(host, port), timeout=30)
    return server
