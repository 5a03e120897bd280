"""One thread steps the engine (ROADMAP S12).

``ContinuousBatchingEngine`` owns a stepping thread: ``generate``,
``generate_one``, ``generate_stream`` and ``stream_to`` enqueue and wait on a
sink for what that thread makes, and ``_LLMReplica.stream`` is an ``async
def`` generator that awaits its tokens on the caller's event loop. These
cases hold the mechanism to its rules on a toy model on the CPU: the same
tokens as a caller's own ``run_until_complete``, rows that stay full under
a closed loop, a lock that outsiders get between two steps, a submission
that waits for no step, streams that may be closed, steps that may fail,
and a thread that parks, stops and dies with its engine.

One replica serves most of the file. A temperature sample depends on the
request's id, its slot and the number of the step that made it, so the
equality cases set the idle engine's counters back (``_rewind``) and run
the same requests twice; prompts and answers stay under one KV block, so
the second run finds no cached prefix the first did not.
"""

import asyncio
import contextlib
import gc
import sys
import threading
import time
import weakref

import numpy as np
import pytest

from ray_tpu.llm import GenerationRequest, LLMConfig
from ray_tpu.llm import engine as engine_module
from ray_tpu.llm import serving
from ray_tpu.llm.engine import ContinuousBatchingEngine
from ray_tpu.llm.serving import _LLMReplica

SLOTS = 4
STEP_S = 0.02  # what a slowed pool step sleeps


def _llm_config(**over):
    return LLMConfig(**{**dict(
        model_id="llama-tiny", max_seq_len=64, max_batch_size=SLOTS,
        kv_cache_blocks=32, kv_block_size=32, seed=0), **over})


@pytest.fixture(scope="module")
def replica():
    rep = _LLMReplica(_llm_config())
    yield rep
    rep.shutdown()
    assert not rep._engine._stepper.thread.is_alive()


@pytest.fixture()
def eng(replica):
    engine = replica._engine
    _wait_idle(engine)
    yield engine
    _wait_idle(engine)
    assert not engine._sinks and not engine._enqueue_ts


def _wait_idle(engine, timeout=60.0):
    deadline = time.monotonic() + timeout
    while engine._has_work():
        assert time.monotonic() < deadline, "the engine never went idle"
        time.sleep(0.005)


def _rewind(engine):
    """An idle engine that numbers its next request and step as a new one
    does: what it samples then is what it sampled the first time."""
    _wait_idle(engine)
    with engine._lock:
        engine._next_id = 0
        engine._step_count = 0


def _prompt(seed, n):
    return [int(t) for t in np.random.RandomState(seed).randint(1, 250, n)]


def _requests(base, temp, new=(7, 10, 5)):
    return [
        GenerationRequest(token_ids=_prompt(base + i, 6 + 2 * i),
                          max_new_tokens=n, temperature=temp)
        for i, n in enumerate(new)
    ]


def _as_dict(req):
    return {"token_ids": req.token_ids, "max_new_tokens": req.max_new_tokens,
            "temperature": req.temperature}


def _pair(result):
    return result.token_ids, result.finished_reason


@contextlib.contextmanager
def _slowed(engine, step_s=STEP_S):
    """Every pool step sleeps ``step_s`` first; ``running`` is set while one
    sleeps."""
    decode = engine._decode
    running = threading.Event()

    def slow(params, cache, last, *args, **kwargs):
        if "active" in kwargs:  # a pool step, not a prefill chunk
            running.set()
            time.sleep(step_s)
            running.clear()
        return decode(params, cache, last, *args, **kwargs)

    engine._decode = slow
    try:
        yield running
    finally:
        engine._decode = decode


@contextlib.contextmanager
def _recorded(monkeypatch):
    """The engine's step spans as (monotonic at entry, name, counts, thread,
    [seconds inside])."""
    spans = []

    @contextlib.contextmanager
    def span(name, **counts):
        entry = [time.monotonic(), name, counts, threading.get_ident(), None]
        spans.append(entry)
        try:
            yield
        finally:
            entry[4] = time.monotonic() - entry[0]

    with monkeypatch.context() as patch:
        patch.setattr(engine_module, "_span", span)
        yield spans


@contextlib.contextmanager
def _closed_loop(engine, clients, new=24):
    """``clients`` threads, each streaming one request after another."""
    stop = threading.Event()
    errors = []

    def client(i):
        n = 0
        try:
            while not stop.is_set():
                req = GenerationRequest(
                    token_ids=_prompt(1000 * i + n, 5), max_new_tokens=new)
                *tokens, final = engine.generate_stream(req)
                assert tokens == final.token_ids and len(tokens) == new
                n += 1
        except BaseException as exc:  # noqa: BLE001 - reported below
            errors.append(exc)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(clients)]
    for t in threads:
        t.start()
    try:
        yield
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors


# -- the same tokens ---------------------------------------------------------


def _by_generate(rep, reqs):
    return [_pair(r) for r in rep._engine.generate(reqs)]


def _by_generate_one(rep, reqs):
    return [_pair(rep._engine.generate_one(r)) for r in reqs]


def _by_generate_stream(rep, reqs):
    out = []
    for r in reqs:
        *tokens, final = rep._engine.generate_stream(r)
        assert tokens == final.token_ids
        out.append(_pair(final))
    return out


def _by_replica_stream(rep, reqs):
    async def one(req):
        *tokens, summary = [item async for item in rep.stream(_as_dict(req))]
        assert [t["index"] for t in tokens] == list(range(len(tokens)))
        assert [t["token_id"] for t in tokens] == summary["token_ids"]
        assert summary["finished"] is True
        assert summary["num_prompt_tokens"] == len(req.token_ids)
        return summary["token_ids"], summary["finished_reason"]

    return [asyncio.run(one(r)) for r in reqs]


# entry -> (how it is driven, whether its requests are in the engine together)
ENTRIES = {
    "generate": (_by_generate, True),
    "generate_one": (_by_generate_one, False),
    "generate_stream": (_by_generate_stream, False),
    "replica_stream": (_by_replica_stream, False),
}


@pytest.mark.parametrize("entry", list(ENTRIES))
@pytest.mark.parametrize("temp", [0.0, 0.8], ids=["greedy", "temp0.8"])
def test_a_waiting_entry_gives_what_the_callers_own_drive_gives(
        replica, eng, temp, entry):
    drive, together = ENTRIES[entry]
    reqs = _requests(10 * list(ENTRIES).index(entry) + 100 * bool(temp), temp)
    _rewind(eng)
    want = []
    if together:
        rids = [eng.add_request(r) for r in reqs]
        done = eng.run_until_complete()
        want = [_pair(done[rid]) for rid in rids]
    else:
        for r in reqs:
            rid = eng.add_request(r)
            want.append(_pair(eng.run_until_complete()[rid]))
    assert [len(t) for t, _ in want] == [r.max_new_tokens for r in reqs]
    _rewind(eng)
    steps = eng.stepper_stats()["steps"]
    assert drive(replica, reqs) == want
    # and it was the engine's thread that stepped for it
    assert eng.stepper_stats()["steps"] > steps


def test_streams_on_one_loop_are_woken_once_a_step(replica, eng, monkeypatch):
    posted = []
    post = serving._LoopStreams.post
    monkeypatch.setattr(
        serving._LoopStreams, "post",
        lambda self, batch: (posted.append(len(batch)), post(self, batch))[1])
    reqs = _requests(300, 0.0, new=(12, 12, 12))

    async def all_of_them():
        async def one(req):
            return [item async for item in replica.stream(_as_dict(req))]

        return await asyncio.gather(*[one(r) for r in reqs])

    steps = eng.stepper_stats()["steps"]
    with _slowed(eng):
        streams = asyncio.run(all_of_them())
    steps = eng.stepper_stats()["steps"] - steps
    assert [len(s) for s in streams] == [13, 13, 13]
    # one call_soon_threadsafe a step at most, carrying all three streams'
    # tokens where all three made one: not one a token
    assert len(posted) <= steps and max(posted) == 3
    assert sum(posted) < 3 * 13
    assert not replica._loop_streams


# -- the rows fill -----------------------------------------------------------


def test_a_closed_loop_keeps_the_rows_full_and_no_slot_waits(eng, monkeypatch):
    # the clients' prompt length compiles here, not in the first admission
    eng.generate([GenerationRequest(token_ids=_prompt(0, 5), max_new_tokens=2)])
    with _recorded(monkeypatch) as spans, _slowed(eng):
        with _closed_loop(eng, clients=4 * SLOTS):
            time.sleep(2.5)
            ended = time.monotonic()
    # past the first round of admissions, which found the slots as the
    # cases before left them
    lo = [t for t, name, *_ in spans if name == "engine.admit"][SLOTS - 1] + 0.1
    batches = [c["batch"] for t, name, c, *_ in spans
               if name == "engine.decode_dispatch" and lo <= t <= ended]
    assert len(batches) > 50
    assert sum(batches) / len(batches) >= 0.9 * SLOTS
    step_s = [d for t, name, _, _, d in spans
              if name == "engine.step" and lo <= t <= ended]
    taken = [(t, c["slot_free_us"] / 1e6) for t, name, c, *_ in spans
             if name == "engine.admit" and lo <= t <= ended]
    free_s = [free for _, free in taken]
    assert len(free_s) >= 2 * SLOTS and min(free_s) > 0
    # a slot its request left is taken under two steps later: by the very
    # next step, counted in steps begun since (however the host was loaded)
    # and in seconds against the longest step
    began = [t for t, name, *_ in spans if name == "engine.step"]
    for at, free in taken:
        assert sum(at - free < t <= at for t in began) <= 2, (at, free)
    assert max(free_s) < 2 * max(step_s)
    # every step on one thread, which is none of the clients'
    assert len({th for _, name, _, th, _ in spans if name == "engine.step"}) == 1


def test_every_token_once_and_in_order_under_a_short_switch_interval(eng):
    """More clients than cores, threads switched every 10 us, nothing slowed:
    submissions, deliveries and retirements interleave as they like, and
    every stream still gets each of its tokens once, in order, and its
    result (``_closed_loop`` asserts it of every request)."""
    steps = eng.stepper_stats()["steps"]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with _closed_loop(eng, clients=24, new=9):
            time.sleep(2.0)
    finally:
        sys.setswitchinterval(interval)
    _wait_idle(eng)
    assert eng.stepper_stats()["steps"] - steps > 24
    assert not eng._sinks and not eng._slots and not eng._pending


def test_a_slot_never_used_reads_zero(monkeypatch):
    rep = _LLMReplica(_llm_config(max_batch_size=2))
    with _recorded(monkeypatch) as spans:
        rep._engine.generate(_requests(400, 0.0))
    free = [c["slot_free_us"] for _, name, c, *_ in spans if name == "engine.admit"]
    # two slots for three requests: the third takes one that had been used
    assert free[:2] == [0, 0] and free[2] > 0
    rep.shutdown()


# -- outsiders get in ----------------------------------------------------------


def test_a_submission_waits_for_no_step(eng):
    with _slowed(eng, step_s=0.5) as running:
        with _closed_loop(eng, clients=2, new=4):
            took = []
            for i in range(4):
                assert running.wait(timeout=30)
                asked = time.monotonic()
                eng.add_request(GenerationRequest(
                    token_ids=_prompt(500 + i, 4), max_new_tokens=2))
                took.append(time.monotonic() - asked)
                time.sleep(0.55)
    # under a tenth of the step it arrived in (it took the engine lock once)
    assert max(took) < 0.05, took


def _take_lock(replica):
    with replica._engine._lock:
        pass


def _read_state_under_lock(replica):
    assert replica._engine.cache_bytes_per_token() > 0


def _kvcache_stats(replica):
    assert replica.kvcache_stats()["capacity"] > 0


@pytest.mark.parametrize(
    "outsider", [_take_lock, _read_state_under_lock, _kvcache_stats],
    ids=["with_lock", "cache_bytes_per_token", "kvcache_stats"])
def test_an_outsider_is_in_within_two_steps(replica, eng, monkeypatch, outsider):
    waited = []
    with _recorded(monkeypatch) as spans, _slowed(eng):
        with _closed_loop(eng, clients=2 * SLOTS, new=12):
            time.sleep(0.3)
            for _ in range(20):
                asked = time.monotonic()
                outsider(replica)
                waited.append(time.monotonic() - asked)
                time.sleep(0.013)
    step_s = [d for _, name, _, _, d in spans if name == "engine.step"]
    assert len(step_s) > 20
    # the thread stepped back to back meanwhile and gave way every time
    assert max(waited) < 2 * max(step_s), (waited, max(step_s))


# -- streams that are closed, steps that fail ---------------------------------


def test_a_closed_stream_leaves_no_sink_and_no_result(eng):
    stream = eng.generate_stream(GenerationRequest(
        token_ids=_prompt(600, 5), max_new_tokens=20))
    with _slowed(eng):
        assert isinstance(next(stream), int) and len(eng._sinks) == 1
        stream.close()
        assert not eng._sinks
        assert eng._slots  # the row runs on
        _wait_idle(eng)
    assert not eng._slots and eng._inflight is None and not eng._sinks


def test_a_closed_replica_stream_leaves_nothing_on_the_loop(replica, eng):
    async def two_tokens_then_close():
        stream = replica.stream(_as_dict(GenerationRequest(
            token_ids=_prompt(601, 5), max_new_tokens=20)))
        first = await stream.__anext__()
        assert first["index"] == 0 and len(replica._loop_streams) == 1
        await stream.aclose()
        assert not replica._loop_streams and not eng._sinks

    with _slowed(eng):
        asyncio.run(two_tokens_then_close())
        _wait_idle(eng)
    assert not eng._slots and not eng._sinks


def test_a_step_that_raises_reaches_every_waiter_and_the_next_is_served(
        replica, eng):
    sample = eng._sample_rows
    armed = threading.Event()

    def failing(*args):
        if armed.is_set():
            armed.clear()
            raise RuntimeError("the step failed")
        return sample(*args)

    caught = {}

    def waiter(name, call):
        try:
            call()
        except RuntimeError as exc:
            caught[name] = str(exc)

    async def streamed():
        return [item async for item in replica.stream(_as_dict(
            GenerationRequest(token_ids=_prompt(702, 5), max_new_tokens=30)))]

    long = [GenerationRequest(token_ids=_prompt(700 + i, 5), max_new_tokens=30)
            for i in range(2)]
    eng._sample_rows = failing
    try:
        with _slowed(eng):
            threads = [
                threading.Thread(target=waiter, args=(
                    "generate", lambda: eng.generate(long[:1]))),
                threading.Thread(target=waiter, args=(
                    "generate_stream", lambda: list(eng.generate_stream(long[1])))),
                threading.Thread(target=waiter, args=(
                    "replica_stream", lambda: asyncio.run(streamed()))),
            ]
            for t in threads:
                t.start()
            deadline = time.monotonic() + 30
            while len(eng._slots) < 3:
                assert time.monotonic() < deadline
                time.sleep(0.005)
            armed.set()
            for t in threads:
                t.join(timeout=60)
    finally:
        del eng._sample_rows
    assert caught == dict.fromkeys(
        ["generate", "generate_stream", "replica_stream"], "the step failed")
    # the engine is empty, its thread alive, and the next request is served
    assert not eng._has_work() and not eng._sinks
    assert eng._stepper.thread.is_alive()
    req = _requests(710, 0.0)[0]
    _rewind(eng)
    rid = eng.add_request(req)
    want = _pair(eng.run_until_complete()[rid])
    assert _pair(eng.generate([req])[0]) == want
    assert not replica._loop_streams


# -- the thread itself ---------------------------------------------------------


def _cpu_s(thread):
    return time.clock_gettime(time.pthread_getcpuclockid(thread.ident))


def test_an_idle_engines_thread_is_parked(replica, eng):
    parked = eng.stepper_stats()["parked"]
    eng.generate(_requests(800, 0.0))
    deadline = time.monotonic() + 10
    while eng.stepper_stats()["parked"] == parked:
        assert time.monotonic() < deadline
        time.sleep(0.005)
    stats = eng.stepper_stats()
    thread = eng._stepper.thread
    burnt = _cpu_s(thread)
    time.sleep(0.5)
    # parked on its condition, without the lock: no step, no spin
    assert _cpu_s(thread) - burnt < 0.005
    assert eng.stepper_stats() == stats
    assert eng._lock._lock.acquire(blocking=False)
    eng._lock._lock.release()
    assert replica.runtime_info()["engine"]["stepper"] == stats


def test_close_joins_and_an_unreferenced_engine_takes_its_thread_with_it():
    rep = _LLMReplica(_llm_config(max_batch_size=2))
    engine = rep._engine
    reqs = _requests(900, 0.0)
    want = [_pair(r) for r in engine.generate(reqs)]
    thread = engine._stepper.thread
    assert thread.is_alive() and thread.daemon
    rep.shutdown()
    assert not thread.is_alive()
    with pytest.raises(RuntimeError, match="closed"):
        engine.generate(reqs[:1])
    assert not engine._sinks
    # a caller's own drive needs no thread
    assert not engine._pending
    _rewind(engine)
    rids = [engine.add_request(r) for r in reqs]
    done = engine.run_until_complete()
    assert [_pair(done[rid]) for rid in rids] == want

    other = ContinuousBatchingEngine(
        engine._cfg, engine._params, num_slots=2, seed=0)
    assert [_pair(r) for r in other.generate(reqs)] == want
    thread, gone = other._stepper.thread, weakref.ref(other)
    assert thread.is_alive()
    del other
    gc.collect()
    thread.join(timeout=10)
    assert gone() is None and not thread.is_alive()


def test_a_replica_without_a_pool_streams_through_the_same_method():
    rep = _LLMReplica(_llm_config(kv_cache_blocks=None))
    req = _requests(950, 0.0)[0]

    async def streamed():
        return [item async for item in rep.stream(_as_dict(req))]

    *tokens, summary = asyncio.run(streamed())
    (want,) = rep._engine.generate([req])
    assert [t["token_id"] for t in tokens] == want.token_ids == summary["token_ids"]
    assert rep({**_as_dict(req), "stream": True}) == summary
    assert not rep._engine._sinks and not rep._loop_streams
    rep.shutdown()
