"""ClientWorker: the client-side half of ray:// connections.

Role-equivalent of the reference's client-mode Worker
(python/ray/util/client/worker.py): presents the same surface the API
layer uses on a real CoreWorker (submit_task/put/get_objects/wait/actor
ops, plus the owner-identity attributes), but every operation is an RPC to
the ClientServer, whose driver CoreWorker is the true owner. Task specs
built on the client carry the *server worker's* identity in their owner
fields, so the cluster never needs a route back to the client machine.
"""

from __future__ import annotations

import logging
from typing import Any, List, Optional, Tuple

from .._internal.config import Config
from .._internal.event_loop import LoopThread
from .._internal.ids import ActorID, JobID, ObjectID, TaskID, WorkerID
from .._internal.rpc import RpcClient

logger = logging.getLogger(__name__)


class _ProxyClient:
    """Stand-in for one RpcClient: relays calls through the client server."""

    def __init__(self, client_worker: "ClientWorker", address):
        self._cw = client_worker
        self._address = tuple(address)

    async def call(self, method: str, *args, timeout: Optional[float] = None):
        import asyncio

        coro = self._cw._server.call("proxy_rpc", self._address, method, *args)
        if timeout is not None:
            return await asyncio.wait_for(coro, timeout)
        return await coro

    async def call_oneway(self, method: str, *args):
        return await self.call(method, *args)


class _ProxyClientPool:
    """Stand-in for the worker's ClientPool (api.py and the function
    exporter reach the GCS through it)."""

    def __init__(self, client_worker: "ClientWorker"):
        self._cw = client_worker

    def get(self, host, port) -> _ProxyClient:
        return _ProxyClient(self._cw, (host, port))

    async def close_all(self):
        pass


class ClientWorker:
    """Implements the CoreWorker surface used by the api/actor/task layers,
    delegating to a ClientServer."""

    def __init__(
        self,
        host: str,
        port: int,
        config: Optional[Config] = None,
        *,
        namespace: str = "",
        runtime_env: Optional[dict] = None,
    ):
        import uuid

        self.config = config or Config()
        self.loop_thread = LoopThread("ray_tpu-client")
        self.loop = self.loop_thread.loop
        # session identity: the server releases this session's object pins
        # when the connection carrying this id drops
        self._client_id = uuid.uuid4().hex
        self._server = RpcClient(
            host, port, name="ray-client",
            register_meta={"client_id": self._client_id},
        )
        meta = self.loop_thread.run(
            self._server.call("client_connect"), timeout=30
        )
        # owner identity = the server's driver worker: specs built here must
        # name an owner the cluster can reach
        self.address: Tuple[str, int] = tuple(meta["worker_address"])
        self.worker_id: WorkerID = meta["worker_id"]
        self.gcs_address: Tuple[str, int] = tuple(meta["gcs_address"])
        self.client_pool = _ProxyClientPool(self)
        # a job of our own for task-id scoping and dashboard attribution
        self.job_id: JobID = self.loop_thread.run(
            self._server.call(
                "proxy_rpc", self.gcs_address, "register_job",
                {"namespace": namespace, "client": True},
            ),
            timeout=30,
        )
        self.namespace = namespace
        self.job_runtime_env = dict(runtime_env) if runtime_env else None
        self._task_index = 0
        # api.cancel pokes at this on real workers; nothing pends client-side
        self._pending_tasks: dict = {}
        self._background_tasks: set = set()

    # -- identity / bookkeeping the API layer touches -----------------------

    def next_task_id(self) -> TaskID:
        self._task_index += 1
        return TaskID.of(self.job_id)

    def register_ref(self, ref) -> None:
        """Client-held refs pin their objects on the server driver for the
        lifetime of this session (reference: Ray Client server-side
        per-session pinning); the whole session's pins release when this
        client's connection drops."""

    def unregister_ref(self, ref) -> None:
        pass

    # -- delegated operations ----------------------------------------------

    async def put(self, value: Any, object_id: Optional[ObjectID] = None):
        return await self._server.call("worker_op", self._client_id, "put", value, object_id)

    async def get_objects(self, refs: List[Any], timeout: Optional[float] = None):
        return await self._server.call("worker_op", self._client_id, "get_objects", refs, timeout)

    async def wait(self, refs, num_returns: int, timeout, fetch_local: bool = True):
        return await self._server.call(
            "worker_op", self._client_id, "wait", refs, num_returns, timeout,
            fetch_local,
        )

    async def submit_task(self, spec) -> List[ObjectID]:
        return await self._server.call("worker_op", self._client_id, "submit_task", spec)

    async def create_actor(self, spec, detached: bool) -> ActorID:
        return await self._server.call("worker_op", self._client_id, "create_actor", spec, detached)

    async def submit_actor_task(self, spec) -> List[ObjectID]:
        return await self._server.call("worker_op", self._client_id, "submit_actor_task", spec)

    async def kill_actor(self, actor_id: ActorID, no_restart: bool = True):
        return await self._server.call(
            "worker_op", self._client_id, "kill_actor", actor_id, no_restart
        )

    async def next_stream_item(self, task_id: TaskID):
        """Streaming-generator reads proxy to the owning server worker; the
        returned item refs are pinned server-side for this session like any
        other client-held ref."""
        return await self._server.call(
            "worker_op", self._client_id, "next_stream_item", task_id
        )

    async def take_stream_values(
        self, task_id: TaskID, timeout: Optional[float] = None
    ):
        """The value-reading half of the same stream: the server's worker
        takes the items and they travel here packed, as a get's values do;
        nothing is pinned, since no ref is made."""
        return await self._server.call(
            "worker_op", self._client_id, "take_stream_values", task_id,
            timeout,
        )

    def drop_stream(self, task_id: TaskID):
        """Sync fire-and-forget like CoreWorker.drop_stream — invoked from
        ObjectRefGenerator.__del__ via call_soon_threadsafe on this loop."""
        import asyncio

        task = asyncio.ensure_future(
            self._server.call(
                "worker_op", self._client_id, "drop_stream", task_id
            )
        )
        self._background_tasks.add(task)
        task.add_done_callback(self._background_tasks.discard)

    def attach_actor(self, actor_id, info=None):
        """Synchronous and non-blocking on CoreWorker — and it MUST stay
        non-blocking here: handle unpickling invokes it from a callback ON
        the client loop (actor.py _rebuild_handle via call_soon_threadsafe),
        where a blocking wait on the same loop would deadlock. Fire the
        relay and let it complete in the background."""
        import asyncio

        coro = self._server.call("worker_op", self._client_id, "attach_actor", actor_id, info)
        try:
            running = asyncio.get_running_loop()
        except RuntimeError:
            running = None
        if running is self.loop:
            task = asyncio.ensure_future(coro)
            self._background_tasks.add(task)
            task.add_done_callback(self._background_tasks.discard)
        else:
            asyncio.run_coroutine_threadsafe(coro, self.loop)

    def as_future(self, ref):
        import asyncio

        async def _one():
            return (await self.get_objects([ref], None))[0]

        return asyncio.run_coroutine_threadsafe(_one(), self.loop)

    # -- lifecycle ----------------------------------------------------------

    async def shutdown(self):
        try:
            await self._server.call(
                "proxy_rpc", self.gcs_address, "finish_job", self.job_id
            )
        except Exception:
            pass
        await self._server.close()


def connect(
    address: str,
    config: Optional[Config] = None,
    *,
    namespace: str = "",
    runtime_env: Optional[dict] = None,
) -> ClientWorker:
    """Parse 'ray://host:port' and build a connected ClientWorker."""
    assert address.startswith("ray://"), address
    hostport = address[len("ray://"):]
    host, port = hostport.rsplit(":", 1)
    return ClientWorker(
        host, int(port), config, namespace=namespace, runtime_env=runtime_env
    )
