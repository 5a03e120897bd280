"""Unit tests for IDs, serialization, and the RPC substrate."""

import asyncio

import numpy as np
import pytest

from ray_tpu._internal import serialization
from ray_tpu._internal.event_loop import LoopThread
from ray_tpu._internal.ids import ActorID, JobID, ObjectID, TaskID
from ray_tpu._internal.rpc import RpcClient, RpcServer, set_rpc_chaos
from ray_tpu.exceptions import RpcError


def test_object_id_derivation():
    job = JobID.from_int(7)
    task = TaskID.of(job)
    assert task.job_id() == job
    oid = ObjectID.for_task_return(task, 2)
    assert oid.task_id() == task
    assert oid.return_index() == 2
    assert not oid.is_put()
    put = ObjectID.for_put(task, 5)
    assert put.is_put() and put.return_index() == 5
    assert ActorID.of(job).job_id() == job


def test_id_equality_and_pickle():
    import pickle

    t = TaskID.of(JobID.from_int(1))
    assert pickle.loads(pickle.dumps(t)) == t
    assert TaskID.nil().is_nil()


def test_serialization_roundtrip_zero_copy():
    arr = np.arange(10000, dtype=np.float32)
    packed = serialization.pack({"x": arr, "y": "hello"})
    out = serialization.unpack(packed)
    assert out["y"] == "hello"
    np.testing.assert_array_equal(out["x"], arr)


def test_pack_into_matches_pack():
    value = {"a": np.ones((64, 64)), "b": list(range(100))}
    meta, bufs = serialization.serialize(value)
    size = serialization.packed_size(meta, bufs)
    dest = bytearray(size)
    written = serialization.pack_into(meta, bufs, memoryview(dest))
    assert written == size
    out = serialization.unpack(memoryview(dest))
    np.testing.assert_array_equal(out["a"], value["a"])
    assert out["b"] == value["b"]


class _EchoService:
    async def handle_echo(self, x):
        return x

    async def handle_boom(self):
        raise ValueError("boom")


def test_rpc_roundtrip():
    loop = LoopThread("test-rpc")

    async def scenario():
        server = RpcServer("echo")
        server.register_service(_EchoService())
        port = await server.start()
        client = RpcClient("127.0.0.1", port)
        out = await client.call("echo", {"a": 1})
        assert out == {"a": 1}
        with pytest.raises(ValueError, match="boom"):
            await client.call("boom")
        # concurrent calls multiplex on one connection
        outs = await asyncio.gather(*[client.call("echo", i) for i in range(50)])
        assert outs == list(range(50))
        await client.close()
        await server.stop()

    loop.run(scenario(), timeout=30)
    loop.stop()


def test_frame_v2_zero_copy_buffers():
    """v2 framing: large buffers travel out-of-band and are reconstructed
    as views over the received body — no copy."""
    from ray_tpu._internal import rpc

    arr = np.arange(1 << 16, dtype=np.uint8)
    parts = rpc._encode_frame((1, "m", (arr,), {}))
    assert len(parts) >= 3  # header, meta, at least one oob buffer
    blob = b"".join(bytes(p) for p in parts)
    body = memoryview(blob)[4:]  # strip the u32 length prefix
    req_id, method, args, kwargs = rpc._decode_body(body)
    assert (req_id, method) == (1, "m")
    out = args[0]
    np.testing.assert_array_equal(out, arr)
    # buffer identity: the decoded array aliases the received frame body
    assert np.shares_memory(out, np.frombuffer(blob, np.uint8))


def test_frame_v2_no_header_body_concat():
    """The multi-MB payload must appear in the parts list as a raw buffer
    view, not be copied into a concatenated header+body bytes object."""
    from ray_tpu._internal import rpc

    arr = np.zeros(4 << 20, dtype=np.uint8)
    parts = rpc._encode_frame((0, "m", (arr,), {}))
    assert any(
        isinstance(p, memoryview) and p.nbytes == arr.nbytes for p in parts
    )
    assert all(
        len(bytes(p)) < 1 << 20 for p in parts[:2]
    )  # header + meta stay small


def test_frame_v1_interop():
    """A legacy v1 body (raw pickle) still decodes — v2 readers accept v1
    senders."""
    import pickle

    from ray_tpu._internal import rpc

    body = pickle.dumps((7, True, {"x": 1}))
    assert rpc._decode_body(body) == (7, True, {"x": 1})


def test_v1_peer_gets_v1_replies():
    """A legacy peer sending raw-pickle (v1) frames must get raw-pickle
    replies — the C++ xlang client's minimal pickle reader cannot parse the
    v2 header (first reply body byte must be the 0x80 PROTO opcode)."""
    import pickle
    import struct

    loop = LoopThread("test-v1peer")

    async def scenario():
        server = RpcServer("echo")
        server.register_service(_EchoService())
        port = await server.start()
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        body = pickle.dumps((1, "echo", ("hi",), {}))
        writer.write(struct.pack("<I", len(body)) + body)
        await writer.drain()
        (length,) = struct.unpack("<I", await reader.readexactly(4))
        reply = await reader.readexactly(length)
        assert reply[0] == 0x80, hex(reply[0])  # v1 raw pickle, no v2 header
        assert pickle.loads(reply) == (1, True, "hi")
        writer.close()
        await server.stop()

    loop.run(scenario(), timeout=30)
    loop.stop()


def test_rpc_oob_roundtrip_over_socket():
    """Socket-level v2 round trip: arrays cross client->server->client with
    the out-of-band counters advancing on both directions."""
    from ray_tpu._internal import rpc

    loop = LoopThread("test-v2")

    async def scenario():
        server = RpcServer("echo")
        server.register_service(_EchoService())
        port = await server.start()
        client = RpcClient("127.0.0.1", port)
        before = rpc.frame_stats()
        arr = np.arange(1 << 18, dtype=np.float32)
        out = await client.call("echo", arr)
        np.testing.assert_array_equal(out, arr)
        after = rpc.frame_stats()
        assert after["oob_buffers_sent"] - before["oob_buffers_sent"] >= 2
        assert (
            after["oob_buffers_received"] - before["oob_buffers_received"] >= 2
        )
        # closures still work via the cloudpickle fallback
        out = await client.call("echo", lambda: 41)
        assert out() == 41
        await client.close()
        await server.stop()

    loop.run(scenario(), timeout=30)
    loop.stop()


def test_recv_loop_survives_non_exception_error_payload():
    """A hostile/malformed server sending a non-exception error payload must
    surface as RpcError on that call — not TypeError killing the recv loop."""
    loop = LoopThread("test-baderr")

    async def scenario():
        from ray_tpu._internal.rpc import _write_frame

        async def on_client(reader, writer):
            # speak just enough protocol: echo an error for every request
            from ray_tpu._internal.rpc import _read_frame

            while True:
                try:
                    req_id, method, args, kwargs = await _read_frame(reader)
                except Exception:
                    # Python 3.12's Server.wait_closed() waits for every
                    # connection: leave none open behind the handler
                    writer.close()
                    return
                if req_id == -1:
                    continue
                if method == "bad":
                    _write_frame(writer, (req_id, False, "not an exception"))
                else:
                    _write_frame(writer, (req_id, True, "fine"))
                await writer.drain()

        server = await asyncio.start_server(on_client, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        client = RpcClient("127.0.0.1", port)
        with pytest.raises(RpcError, match="non-exception"):
            await client.call("bad")
        # the recv loop survived: the connection still serves calls
        assert await client.call("ok") == "fine"
        await client.close()
        server.close()
        await server.wait_closed()

    loop.run(scenario(), timeout=30)
    loop.stop()


def test_auth_preamble_gates_v2_frames():
    """With a token set, a v2 frame from a client that skipped the auth
    preamble is dropped before any parsing."""
    from ray_tpu._internal import rpc

    loop = LoopThread("test-v2auth")

    async def scenario():
        rpc.set_auth_token("secret")
        try:
            server = RpcServer("echo")
            server.register_service(_EchoService())
            port = await server.start()
            # raw connection, no preamble: write a valid v2 frame
            rpc.set_auth_token(None)  # encode/connect without the token
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            rpc.set_auth_token("secret")
            writer.writelines(rpc._encode_frame((1, "echo", (1,), {})))
            await writer.drain()
            # server drops the connection without answering
            assert await reader.read(1) == b""
            writer.close()
            await server.stop()
        finally:
            rpc.set_auth_token(None)

    loop.run(scenario(), timeout=30)
    loop.stop()


def test_rpc_chaos_injection():
    loop = LoopThread("test-chaos")

    async def scenario():
        set_rpc_chaos({"echo": 1.0})
        try:
            server = RpcServer("echo")
            server.register_service(_EchoService())
            port = await server.start()
            client = RpcClient("127.0.0.1", port)
            with pytest.raises(RpcError, match="injected"):
                await client.call("echo", 1)
            await client.close()
            await server.stop()
        finally:
            set_rpc_chaos({})

    loop.run(scenario(), timeout=30)
    loop.stop()
