"""Streaming generators (reference: num_returns="streaming" ->
ObjectRefGenerator backed by ObjectRefStream, task_manager.h:67 and
ReportGeneratorItemReturns, core_worker.proto:507)."""

import time

import pytest
from conftest import wait_for

import ray_tpu
from ray_tpu.object_ref import ObjectRefGenerator


def test_basic_stream(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i * i

    g = gen.remote(6)
    assert isinstance(g, ObjectRefGenerator)
    out = [ray_tpu.get(ref, timeout=60) for ref in g]
    assert out == [i * i for i in range(6)]


def test_items_stream_before_task_finishes(ray_start_regular):
    """The first item is consumable while the producer still runs."""
    @ray_tpu.remote
    def warm():
        return True

    ray_tpu.get(warm.remote(), timeout=60)  # absorb worker-spawn latency

    @ray_tpu.remote(num_returns="streaming")
    def slow_gen():
        yield "first"
        time.sleep(3.0)
        yield "second"

    g = slow_gen.remote()
    t0 = time.time()
    first = ray_tpu.get(next(g), timeout=60)
    first_latency = time.time() - t0
    assert first == "first"
    assert first_latency < 2.5  # did not wait for the full 3s producer
    assert ray_tpu.get(next(g), timeout=60) == "second"
    with pytest.raises(StopIteration):
        next(g)


def test_large_items_via_plasma(ray_start_regular):
    import numpy as np

    @ray_tpu.remote(num_returns="streaming")
    def big_gen():
        for i in range(3):
            yield np.full((300_000,), i, np.float32)  # > inline threshold

    vals = [ray_tpu.get(r, timeout=120) for r in big_gen.remote()]
    assert [float(v[0]) for v in vals] == [0.0, 1.0, 2.0]
    assert all(v.shape == (300_000,) for v in vals)


def test_mid_stream_error_after_yields(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming", max_retries=0)
    def bad_gen():
        yield 1
        yield 2
        raise RuntimeError("stream broke")

    g = bad_gen.remote()
    assert ray_tpu.get(next(g), timeout=60) == 1
    assert ray_tpu.get(next(g), timeout=60) == 2
    with pytest.raises(Exception, match="stream broke"):
        next(g)


def test_non_generator_function_errors(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming", max_retries=0)
    def not_a_gen():
        return 42

    g = not_a_gen.remote()
    with pytest.raises(Exception, match="generator"):
        next(g)


# -- actor streaming generators (reference: python/ray/actor.py:516-548) ----


def test_actor_basic_stream(ray_start_regular):
    @ray_tpu.remote
    class A:
        def gen(self, n):
            for i in range(n):
                yield i * i

    a = A.remote()
    g = a.gen.options(num_returns="streaming").remote(6)
    assert isinstance(g, ObjectRefGenerator)
    out = [ray_tpu.get(ref, timeout=60) for ref in g]
    assert out == [i * i for i in range(6)]


def test_actor_items_stream_before_method_finishes(ray_start_regular):
    @ray_tpu.remote
    class A:
        def ping(self):
            return True

        def slow_gen(self):
            yield "first"
            time.sleep(3.0)
            yield "second"

    a = A.remote()
    ray_tpu.get(a.ping.remote(), timeout=60)  # absorb worker-spawn latency
    g = a.slow_gen.options(num_returns="streaming").remote()
    t0 = time.time()
    first = ray_tpu.get(next(g), timeout=60)
    first_latency = time.time() - t0
    assert first == "first"
    assert first_latency < 2.5
    assert ray_tpu.get(next(g), timeout=60) == "second"
    with pytest.raises(StopIteration):
        next(g)


def test_actor_stream_interleaves_with_state(ray_start_regular):
    """Streams run in the actor's seq order and see its mutable state;
    ordinary calls after a stream observe the generator's effects."""
    @ray_tpu.remote
    class Accum:
        def __init__(self):
            self.total = 0

        def add_stream(self, n):
            for i in range(n):
                self.total += i
                yield self.total

        def get_total(self):
            return self.total

    a = Accum.remote()
    g = a.add_stream.options(num_returns="streaming").remote(4)
    later = a.get_total.remote()
    assert [ray_tpu.get(r, timeout=60) for r in g] == [0, 1, 3, 6]
    assert ray_tpu.get(later, timeout=60) == 6


def test_actor_large_items_via_plasma(ray_start_regular):
    import numpy as np

    @ray_tpu.remote
    class A:
        def big_gen(self):
            for i in range(3):
                yield np.full((300_000,), i, np.float32)

    a = A.remote()
    g = a.big_gen.options(num_returns="streaming").remote()
    vals = [ray_tpu.get(r, timeout=120) for r in g]
    assert [float(v[0]) for v in vals] == [0.0, 1.0, 2.0]


def test_actor_mid_stream_error_after_yields(ray_start_regular):
    @ray_tpu.remote
    class A:
        def bad_gen(self):
            yield 1
            yield 2
            raise RuntimeError("stream broke")

    a = A.remote()
    g = a.bad_gen.options(num_returns="streaming").remote()
    assert ray_tpu.get(next(g), timeout=60) == 1
    assert ray_tpu.get(next(g), timeout=60) == 2
    with pytest.raises(Exception, match="stream broke"):
        next(g)


def test_actor_non_generator_method_errors(ray_start_regular):
    @ray_tpu.remote
    class A:
        def not_a_gen(self):
            return 42

    a = A.remote()
    g = a.not_a_gen.options(num_returns="streaming").remote()
    with pytest.raises(Exception, match="generator"):
        next(g)


def test_actor_stream_survives_actor_death(shutdown_only):
    """Mid-stream actor death surfaces as an error on the NEXT read; items
    already delivered stay readable (task-side parity), and with retries the
    resent call re-runs the generator on the restarted incarnation."""
    import os
    import signal

    node = ray_tpu.init(num_cpus=2)

    @ray_tpu.remote(max_restarts=2, max_task_retries=2)
    class A:
        def gen(self, n):
            for i in range(n):
                yield i

    a = A.remote()
    # a completed stream first, so the actor is warm
    g1 = a.gen.options(num_returns="streaming").remote(3)
    assert [ray_tpu.get(r, timeout=60) for r in g1] == [0, 1, 2]
    # SIGKILL the actor's worker from outside (an in-actor os._exit would be
    # re-executed by the retry, burning every restart — at-least-once): one
    # kill, one restart; the next streaming call rides the restart path
    pids = [lease.worker.pid for lease in node.raylet._leases.values()]
    assert pids
    for pid in pids:
        os.kill(pid, signal.SIGKILL)
    time.sleep(0.5)
    g2 = a.gen.options(num_returns="streaming").remote(4)
    assert [ray_tpu.get(r, timeout=120) for r in g2] == [0, 1, 2, 3]


# -- reading a stream as values (ObjectRefGenerator.take_values) -------------
# Counts, never times: how many hops an item cost its owner's loop and how
# many refs were registered for it (1 and 0), and what is left behind.


def _worker():
    from ray_tpu import _worker_api

    return _worker_api.get_core_worker()


def _produced(g, n):
    """Block until the owner holds the stream's end and all ``n`` items."""
    streams = _worker()._streams

    def done():
        state = streams.get(g._task_id)
        return state is not None and state.total == n and len(state.reported) == n

    wait_for(done)


def _entries_of(g):
    """What the owner still holds for the stream: store entries, owned ids."""
    w = _worker()
    mine = lambda oid: oid.task_id() == g._task_id  # noqa: E731
    return (
        [oid for oid in list(w.memory_store._objects) if mine(oid)],
        [oid for oid in list(w._owned) if mine(oid)],
    )


def _drain_values(g):
    from ray_tpu.object_ref import unpack_stream_value

    out = []
    while (taken := g.take_values(60.0)) is not None:
        assert taken, "a take that returns holds at least one item"
        out.extend(unpack_stream_value(item) for item in taken)
    return out


def _item(i, big):
    import numpy as np

    # 300_000 float32 is over max_direct_call_object_size: it goes to plasma
    return np.full((300_000,), i, np.float32) if big else {"i": i, "sq": i * i}


def _same(a, b):
    import numpy as np

    if isinstance(a, np.ndarray):
        return isinstance(b, np.ndarray) and a.shape == b.shape and bool((a == b).all())
    return a == b


@pytest.mark.parametrize("big_at", [None, 2], ids=["inline", "one_in_plasma"])
@pytest.mark.parametrize("kind", ["task", "actor"])
def test_values_match_refs(ray_start_regular, kind, big_at):
    """The value path returns what the ref path returns, in the same order,
    for a task's and an actor's generator, inline and through plasma."""

    def items(n, big_at):
        for i in range(n):
            yield _item(i, i == big_at)

    if kind == "task":
        start = ray_tpu.remote(num_returns="streaming")(items).remote
    else:
        @ray_tpu.remote
        class A:
            def gen(self, n, big_at):
                yield from items(n, big_at)

        a = A.remote()  # held: a dropped handle kills its actor
        start = a.gen.options(num_returns="streaming").remote
    by_ref = [ray_tpu.get(r, timeout=120) for r in start(5, big_at)]
    before = dict(_worker().stream_counts)
    g = start(5, big_at)
    by_value = _drain_values(g)
    assert len(by_value) == len(by_ref) == 5
    assert all(_same(a, b) for a, b in zip(by_value, by_ref))
    after = _worker().stream_counts
    assert after["values"] - before["values"] == 5
    assert after["refs"] == before["refs"]  # not one ObjectRef was made
    assert 1 <= after["takes"] - before["takes"] <= 5
    # drained: nothing of the stream is left with its owner
    assert g._task_id not in _worker()._streams
    wait_for(lambda: _entries_of(g) == ([], []))


def test_refs_and_values_share_the_cursor(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i

    from ray_tpu.object_ref import unpack_stream_value

    g = gen.remote(6)
    _produced(g, 6)
    first = next(g)  # index 0, as a ref
    state = _worker()._streams[g._task_id]
    assert state.next_read == 1
    # a take would bring 1..5 at once; stop the stream short to mix readers
    state.reported.discard(3)
    assert [unpack_stream_value(v) for v in g.take_values(60.0)] == [1, 2]
    state.reported.add(3)
    third = next(g)  # index 3, a ref again
    assert [unpack_stream_value(v) for v in g.take_values(60.0)] == [4, 5]
    assert g.take_values(60.0) is None
    with pytest.raises(StopIteration):
        next(g)
    # the refs are refs still: fetchable, more than once
    assert ray_tpu.get([first, third, first], timeout=60) == [0, 3, 0]
    # the values are gone from the owner; the refs' entries are not
    stored, owned = _entries_of(g)
    assert sorted(stored) == sorted(owned) == sorted([first.id, third.id])


@pytest.mark.parametrize("kind", ["task", "actor"])
def test_values_then_the_tasks_error(ray_start_regular, kind):
    """A generator that raises after k items delivers k values, then its
    error; the failed stream leaves nothing behind."""

    def items():
        yield "a"
        yield "b"
        yield "c"
        raise RuntimeError("stream broke")

    if kind == "task":
        g = ray_tpu.remote(num_returns="streaming", max_retries=0)(items).remote()
    else:
        @ray_tpu.remote
        class A:
            def gen(self):
                yield from items()

        a = A.remote()  # held: a dropped handle kills its actor
        g = a.gen.options(num_returns="streaming").remote()
    from ray_tpu.object_ref import unpack_stream_value

    got = []
    with pytest.raises(Exception, match="stream broke"):
        while True:
            got.extend(unpack_stream_value(v) for v in g.take_values(60.0))
    assert got == ["a", "b", "c"]
    assert g._task_id not in _worker()._streams
    assert g.take_values(60.0) is None  # a terminated stream reads as ended
    wait_for(lambda: _entries_of(g) == ([], []))


def test_close_mid_stream_frees_unread_and_tells_the_producer(
    ray_start_regular, tmp_path
):
    closed_marker = tmp_path / "generator_closed"

    @ray_tpu.remote(num_returns="streaming")
    def endless(marker):
        try:
            i = 0
            while True:
                yield i
                i += 1
                time.sleep(0.02)
        finally:
            open(marker, "w").close()

    from ray_tpu.object_ref import unpack_stream_value

    g = endless.remote(str(closed_marker))
    assert unpack_stream_value(g.take_values(60.0)[0]) == 0
    # let unread items pile up at the owner, then abandon the stream
    wait_for(lambda: len(_worker()._streams[g._task_id].reported) >= 4)
    g.close()
    # the next report learns nobody listens: the user generator is closed
    wait_for(closed_marker.exists)
    assert g._task_id not in _worker()._streams
    wait_for(lambda: _entries_of(g) == ([], []))


def test_rereport_under_the_cursor_is_freed(ray_start_regular):
    """An actor restarted mid-stream yields again from 0: an index already
    taken as a value has no reader, so it is not stored again; one read as a
    ref keeps its value while the ref lives."""
    from ray_tpu import _worker_api
    from ray_tpu._internal import serialization
    from ray_tpu._internal.ids import ObjectID
    from ray_tpu.object_ref import unpack_stream_value

    @ray_tpu.remote(num_returns="streaming")
    def gen():
        yield "r"
        yield "v"
        yield "rest"

    g = gen.remote()
    _produced(g, 3)
    w = _worker()
    held = next(g)  # index 0 read as a ref
    w._streams[g._task_id].reported.discard(2)
    assert [unpack_stream_value(v) for v in g.take_values(60.0)] == ["v"]
    w._streams[g._task_id].reported.add(2)

    def report(index, value):
        packed = serialization.pack(value)
        return _worker_api.run_on_worker_loop(
            w._handle_report_generator_item(
                g._task_id, index, packed, len(packed)
            )
        )

    taken_id = ObjectID.for_task_return(g._task_id, 1)
    assert report(1, "v") is True  # the consumer is alive, the item unwanted
    assert w.memory_store.get_if_exists(taken_id) is None
    assert taken_id not in w._owned
    assert report(0, "r") is True
    assert ray_tpu.get(held, timeout=60) == "r"
    # neither re-report moved the cursor or re-queued an item
    assert [unpack_stream_value(v) for v in g.take_values(60.0)] == ["rest"]
    assert g.take_values(60.0) is None
    # once the stream is gone, a late report is told so and leaves nothing
    late_id = ObjectID.for_task_return(g._task_id, 7)
    assert report(7, "late") is False
    assert w.memory_store.get_if_exists(late_id) is None


def test_consumer_that_fell_behind_catches_up_in_one_hop(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield {"token": i}

    n = 40
    g = gen.remote(n)
    _produced(g, n)  # the consumer "slept" while all n were yielded
    before = dict(_worker().stream_counts)
    from ray_tpu.object_ref import unpack_stream_value

    taken = g.take_values(60.0)
    assert [unpack_stream_value(v) for v in taken] == [{"token": i} for i in range(n)]
    assert g.take_values(60.0) is None
    after = _worker().stream_counts
    assert after["takes"] - before["takes"] == 1  # the end is not a take
    assert after["values"] - before["values"] == n
    assert after["refs"] == before["refs"]
    assert after["max_take"] >= n


def test_take_timeout_bounds_the_wait_for_the_next_item(ray_start_regular):
    from ray_tpu.exceptions import GetTimeoutError
    from ray_tpu.object_ref import unpack_stream_value

    @ray_tpu.remote(max_concurrency=2)  # open() runs while gen() waits
    class Gated:
        def __init__(self):
            import asyncio

            self.gate = asyncio.Event()

        async def open(self):
            self.gate.set()

        async def gen(self):
            yield "before"
            await self.gate.wait()
            yield "after"

    a = Gated.remote()
    g = a.gen.options(num_returns="streaming").remote()
    assert unpack_stream_value(g.take_values(60.0)[0]) == "before"
    with pytest.raises(GetTimeoutError):
        g.take_values(0.2)
    # a timed-out wait took nothing and the stream is still there
    ray_tpu.get(a.open.remote(), timeout=60)
    assert _drain_values(g) == ["after"]
