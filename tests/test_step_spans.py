"""Step spans: the engine's and the replica's regions in the profiler's own
trace (``tracing.annotate_device_trace`` / ``tracing.step_span``), read back
with ``jax.profiler.ProfileData`` under the benchmark harness's profiler
options (host tracer level 1, Python tracer off).

One tiny engine and one profile session serve the whole file: a stepped
scenario whose counts the test arranges, a scenario held back by a full
block pool, and three threads that each wait on a ``generate_stream`` while
the engine's own thread steps. A second tiny engine, built by an
``_LLMReplica`` inside a serve ``Replica``, streams two answers to consumers
that take 31 ms an item against steps of a few ms (the way out's spans, on
the loop's thread), in the same session.
"""

import asyncio
import threading
import time
from collections import defaultdict

import jax
import pytest

from ray_tpu.util import tracing

BLOCK = 16
SLOTS = 3
NEW = 14  # 20 prompt tokens + 13 fed back: the tail crosses a block boundary


def _prompt(i, n=20):
    return [(97 * i + 13 * j + 5) % 250 + 3 for j in range(n)]


def _request(i, n=20, new=NEW):
    from ray_tpu.llm.engine import GenerationRequest

    return GenerationRequest(
        token_ids=_prompt(i, n), max_new_tokens=new, temperature=0.0)


def _engine():
    from ray_tpu.kvcache import KVCacheManager
    from ray_tpu.llm.engine import ContinuousBatchingEngine
    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.parallel.sharding import unbox_params

    cfg = LlamaConfig.tiny(max_seq_len=128)
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
    return ContinuousBatchingEngine(
        cfg, params, num_slots=SLOTS, seed=0,
        kv_cache=KVCacheManager(num_blocks=8, block_size=BLOCK))


def _drain(eng):
    out = {}
    while eng.num_active:
        out.update(eng.step())
    return out


def _stepped(eng, base):
    """Four requests on three slots, then the first prompt again."""
    rids = [eng.add_request(_request(base + i)) for i in range(4)]
    out = _drain(eng)
    again = eng.add_request(_request(base))  # prefix hit: one cached block
    out.update(_drain(eng))
    return [out[r].token_ids for r in rids + [again]]


def _blocked(eng, base, hold_s=0.05):
    """Two 64-token prompts pin all 8 blocks; a third has a free slot and
    no blocks until one of them retires."""
    for i in range(2):
        eng.add_request(_request(base + i, n=64, new=4))
    eng.step()
    held = eng.add_request(_request(base + 2, n=64, new=2))
    asked = time.time()
    eng.step()
    time.sleep(hold_s)
    eng.step()
    time.sleep(hold_s)
    waited_s = time.time() - asked
    _drain(eng)
    return held, waited_s


def _threaded(eng, base, step_s=0.0):
    """Three threads, a stream each (a ``test.stream`` region, so that the
    trace shows which threads they were). ``step_s`` lengthens every decode
    step by a sleep, and each consumer then pauses a tenth of that over a
    token, as one that writes to a socket would."""
    out = [None] * 3
    sample = eng._sample_rows

    def slow_sample(*args):
        time.sleep(step_s)
        return sample(*args)

    def stream(i):
        with tracing.annotate_device_trace("test.stream"):
            for item in eng.generate_stream(_request(base + i)):
                time.sleep(step_s / 10)
        out[i] = item.token_ids

    eng._sample_rows = slow_sample
    try:
        threads = [threading.Thread(target=stream, args=(i,)) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        del eng._sample_rows
    return out


ITEM_S = 0.031  # what a way-out consumer takes over an item
WAYOUT_NEW = 10


def _wayout_replica():
    """A serve replica around ``_LLMReplica``, as the controller builds it."""
    from ray_tpu._internal import serialization
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.serving import _LLMReplica
    from ray_tpu.serve.replica import Replica

    config = LLMConfig(
        model_id="llama-tiny", max_seq_len=128, max_batch_size=SLOTS,
        kv_cache_blocks=8, kv_block_size=BLOCK, seed=0)
    return Replica("d", "r0", serialization.dumps(_LLMReplica), (config,), {}, None)


def _wayout(replica, base, item_s=0.0):
    """Two streams through ``handle_request_stream``, each consumer pausing
    ``item_s`` over an item before it asks for the next; returns what each
    got and how long its stream lived."""
    async def consume(i):
        request = {"token_ids": _prompt(base + i), "max_new_tokens": WAYOUT_NEW,
                   "temperature": 0.0}
        began = time.perf_counter()
        items = []
        async for item in replica.handle_request_stream("stream", (request,), {}, None):
            items.append(item)
            await asyncio.sleep(item_s)
        return items, time.perf_counter() - began

    async def both():
        return await asyncio.gather(consume(0), consume(1))

    return asyncio.run(both())


def _read(logdir):
    """[{name, thread, start, end, stats}] of the host plane's step spans,
    nanoseconds, in start order (outer before inner)."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")
    (plane,) = [p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU"]
    spans = []
    for thread, line in enumerate(plane.lines):
        for ev in line.events:
            if ev.name.split(".")[0] in ("engine", "kv", "replica", "test"):
                spans.append({
                    "name": ev.name, "thread": thread, "start": ev.start_ns,
                    "end": ev.start_ns + ev.duration_ns,
                    "stats": dict(ev.stats)})
    return sorted(spans, key=lambda s: (s["start"], -s["end"]))


@pytest.fixture(scope="module", autouse=True)
def no_request_tracing():
    """A file that ran earlier in this process may have left request
    tracing on (``enable_tracing()`` has no undo); these cases are about a
    process that traces no request."""
    was, tracing._enabled = tracing._enabled, False
    yield
    tracing._enabled = was


@pytest.fixture(scope="module")
def recorded(tmp_path_factory, no_request_tracing):
    eng = _engine()
    # compile every program the scenarios use, outside the session
    _stepped(eng, 100)
    _blocked(eng, 110, hold_s=0.0)
    replica = _wayout_replica()
    _wayout(replica, 130)
    logdir = str(tmp_path_factory.mktemp("step_spans"))
    tracing.clear_spans()
    with tracing.device_profile(logdir):
        with tracing.annotate_device_trace("test.stepped"):
            stepped = _stepped(eng, 0)
        with tracing.annotate_device_trace("test.blocked"):
            held, waited_s = _blocked(eng, 10)
        with tracing.annotate_device_trace("test.threaded"):
            threaded = _threaded(eng, 20, step_s=0.02)
        with tracing.annotate_device_trace("test.wayout"):
            wayout = _wayout(replica, 30, item_s=ITEM_S)
    spans = _read(logdir)

    def scenario(name):
        (mark,) = [s for s in spans if s["name"] == f"test.{name}"]
        return [s for s in spans if mark["start"] <= s["start"]
                and s["end"] <= mark["end"] and s is not mark]

    yield {
        "engine": eng, "stepped": scenario("stepped"),
        "blocked": scenario("blocked"), "threaded": scenario("threaded"),
        "wayout": scenario("wayout"), "replica": replica,
        "streamed": wayout,
        "tokens": {"stepped": stepped, "threaded": threaded,
                   "wayout": [items for items, _ in wayout]},
        "held": held, "waited_s": waited_s,
        "request_spans": tracing.get_spans(),
    }
    replica._callable.shutdown()


def _parents(spans):
    """id(span) -> the innermost span of its thread that contains it; also
    checks that spans of one thread nest and never straddle."""
    parent, stacks = {}, defaultdict(list)
    for s in spans:
        stack = stacks[s["thread"]]
        while stack and stack[-1]["end"] <= s["start"]:
            stack.pop()
        if stack:
            assert s["end"] <= stack[-1]["end"], (s, stack[-1])
            parent[id(s)] = stack[-1]
        stack.append(s)
    return parent


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def test_every_span_of_the_table_with_its_counts(recorded):
    spans = recorded["stepped"]
    parent = _parents(spans)
    inside = {
        "engine.admit": "engine.step", "engine.decode_dispatch": "engine.step",
        "engine.sample_sync": "engine.step", "engine.emit": "engine.step",
        "kv.acquire": "engine.admit", "engine.prefill": "engine.admit",
        "kv.insert_row": "engine.admit", "kv.assemble": "engine.prefill",
        "kv.extract_row": "kv.commit",
    }
    for name, outer in inside.items():
        found = _named(spans, name)
        assert found, f"no {name} span"
        for s in found:
            assert parent[id(s)]["name"] == outer, (s, parent[id(s)])
    for s in _named(spans, "kv.commit"):
        outer = parent[id(s)]["name"]
        if s["stats"].get("tail"):
            # a decoded tail is committed as its slot retires, inside emit
            assert outer == "engine.emit" and s["stats"]["blocks"] == 1
        else:
            assert outer == "engine.admit" and "blocks" in s["stats"]
    assert any(s["stats"].get("tail") == 1 for s in _named(spans, "kv.commit"))
    steps = _named(spans, "engine.step")
    assert all({"step", "pending", "prefilling", "wall_us"} <= set(s["stats"])
               for s in steps)
    # wall_us is the wall clock at entry: it advances as the trace's does
    a, b = steps[0], steps[-1]
    assert (b["stats"]["wall_us"] - a["stats"]["wall_us"]) * 1e3 == pytest.approx(
        b["start"] - a["start"], abs=5e6)
    admits = _named(spans, "engine.admit")
    assert [s["stats"]["prompt_tokens"] for s in admits] == [20] * 5
    assert len({s["stats"]["request_id"] for s in admits}) == 5
    prefills = _named(spans, "engine.prefill")
    assert [(s["stats"]["computed_tokens"], s["stats"]["cached_tokens"])
            for s in prefills] == [(20, 0)] * 4 + [(4, BLOCK)]
    # ... and which program took the prompt: the whole one, or chunks
    # against the cache behind the hit
    assert [s["stats"]["path"] for s in prefills] == ["whole"] * 4 + ["suffix"]
    assert len(_named(spans, "kv.assemble")) == 1
    # the first committed prompt block of the repeated prompt is cached
    assert [s["stats"]["blocks"] for s in _named(spans, "kv.commit")
            if not s["stats"].get("tail")] == [1, 1, 1, 1, 0]
    # no span a token or a slot: a step reads one decode step, whatever it
    # carries, after dispatching the one that follows it. ``ahead`` counts
    # the steps the device had been given and the host had not read when
    # this one was dispatched: 1, but for the first of an engine found idle,
    # which is dispatched with its successor in one call
    for step in steps:
        own = [s for s in spans if parent.get(id(s)) is step]
        for name in ("engine.sample_sync", "engine.emit"):
            assert len(_named(own, name)) <= 1
        dispatched = _named(own, "engine.decode_dispatch")
        ahead = [d["stats"]["ahead"] for d in dispatched]
        assert ahead in ([], [1], [0, 1], [0]), ahead
        for d, sync in zip(dispatched[-1:], _named(own, "engine.sample_sync")):
            assert d["end"] <= sync["start"]  # dispatched before the read
    # three runs of NEW - 1 decode steps, each begun on an idle engine; a
    # run's last step is dispatched alone, and read in a call that
    # dispatches nothing: no row could want another token
    aheads = [s["stats"]["ahead"] for s in _named(spans, "engine.decode_dispatch")]
    assert aheads == ([0] + [1] * (NEW - 2)) * 3
    owned = [[s["name"] for s in spans if parent.get(id(s)) is step] for step in steps]
    reads = [own.count("engine.decode_dispatch") for own in owned
             if "engine.sample_sync" in own]
    assert reads == ([2] + [1] * (NEW - 3) + [0]) * 3


def test_kv_commit_counts_its_dispatches_and_evictions(recorded):
    """A commit is one program whatever it writes, a tail's row read one
    more, a commit with nothing missing none; and the blocks a call had to
    evict are counted on it (the 8-block pool is full before the session)."""
    commits = _named(recorded["stepped"], "kv.commit")
    assert [(s["stats"]["blocks"], s["stats"]["dispatches"]) for s in commits
            if not s["stats"].get("tail")] == [(1, 1)] * 4 + [(0, 0)]
    # each tail's block takes an evicted one's place; the repeated prompt's
    # greedy tail is the first's, already there: its row is read and
    # nothing is written
    assert [(s["stats"]["dispatches"], s["stats"]["evictions"]) for s in commits
            if s["stats"].get("tail")] == [(2, 1)] * 4 + [(1, 0)]
    for name in ("stepped", "blocked", "threaded"):
        for s in _named(recorded[name], "kv.commit"):
            assert 0 <= s["stats"]["dispatches"] <= 2, s
            assert s["stats"]["evictions"] >= 0, s


def test_batch_and_pending_are_what_was_arranged(recorded):
    spans = recorded["stepped"]
    steps = _named(spans, "engine.step")
    # four requests queued on three slots, then one, then the repeat alone
    assert [s["stats"]["pending"] for s in steps[:3]] == [4, 1, 1]
    # ``step`` counts decode steps dispatched: two in the call that found
    # the engine idle, one a call after that
    assert [s["stats"]["step"] for s in steps[:3]] == [
        steps[0]["stats"]["step"] + n for n in (0, 2, 3)]
    batches = [s["stats"]["batch"] for s in _named(spans, "engine.decode_dispatch")]
    # NEW - 1 decode steps a request: three together, the fourth alone (it
    # is admitted in the step after the three retire), then the repeat
    assert batches == [3] * (NEW - 1) + [1] * (NEW - 1) * 2
    assert all(s["stats"]["prefilling"] == 0 for s in steps)


def test_live_tokens_is_the_sum_of_the_live_rows_lengths(recorded):
    live = [s["stats"]["live_tokens"]
            for s in _named(recorded["stepped"], "engine.decode_dispatch")]
    # a row's keys at a decode step: its 20 prompt tokens, the token its
    # prefill sampled, and one more with every step since
    row = [20 + 1 + i for i in range(NEW - 1)]
    assert live == [3 * n for n in row] + row * 2


def test_queue_wait_of_a_request_the_pool_held_back(recorded):
    spans = recorded["blocked"]
    parent = _parents(spans)
    mine = [s for s in _named(spans, "engine.admit")
            if s["stats"]["request_id"] == recorded["held"]]
    # every attempt opens engine.admit; only the last one got its blocks
    assert len(mine) >= 3
    for attempt in mine[:-1]:
        children = [s["name"] for s in spans if parent.get(id(s)) is attempt]
        assert children == ["kv.acquire"]
    admitted = mine[-1]
    assert "engine.prefill" in [
        s["name"] for s in spans if parent.get(id(s)) is admitted]
    assert admitted["stats"]["queue_wait_us"] >= recorded["waited_s"] * 1e6
    waits = [s["stats"]["queue_wait_us"] for s in mine]
    assert waits == sorted(waits) and waits[0] < 50_000 <= waits[-1]
    assert recorded["engine"]._kv.stats()["admission_blocked"] >= 2


def test_one_thread_steps_and_no_stream_waits_for_the_lock(recorded):
    spans = recorded["threaded"]
    parent = _parents(spans)  # nesting holds on every thread
    waits = _named(spans, "engine.lock_wait")
    steps = _named(spans, "engine.step")
    streams = _named(spans, "test.stream")
    # every step of the three streams ran on one thread, and none of theirs
    (stepper,) = {s["thread"] for s in steps}
    assert len({s["thread"] for s in streams}) == 3
    assert stepper not in {s["thread"] for s in streams}
    # a stream's thread opens nothing of the engine's: it does not step,
    # does not ask for the lock, and its submission does not wait for one
    assert {s["thread"] for s in spans if s["name"].startswith(("engine.", "kv."))
            } == {stepper}
    # the stepping thread takes the lock anew for every step, unopposed
    assert len(waits) == len(steps)
    ordered = sorted(steps, key=lambda s: s["start"])
    for a, b in zip(ordered, ordered[1:]):
        assert a["end"] <= b["start"]
    step_s = sum(s["end"] - s["start"] for s in steps)
    assert sum(w["end"] - w["start"] for w in waits) < 0.05 * step_s
    # the three stepped together for most of their length
    full = [parent[id(d)] for d in _named(spans, "engine.decode_dispatch")
            if d["stats"]["batch"] == 3]
    assert len(full) >= 5
    # each step hands its tokens over once, inside the step
    delivers = _named(spans, "engine.deliver")
    assert delivers and all(parent[id(d)]["name"] == "engine.step" for d in delivers)
    assert all(len([d for d in delivers if parent[id(d)] is s]) <= 1 for s in steps)


def _way_out_fan_out(spans, recorded):
    """A step's deliveries reach the loop in one callback, a real region on
    the loop's thread, which is not the stepping one."""
    fans = _named(spans, "replica.fan_out")
    assert fans and all(f["stats"]["post_lag_us"] >= 0 for f in fans)
    assert all(1 <= f["stats"]["streams"] <= 2 for f in fans)
    # a row gets a token a step: ten deliveries a stream or fewer
    assert WAYOUT_NEW <= sum(f["stats"]["streams"] for f in fans) <= 2 * WAYOUT_NEW
    (loop,) = {f["thread"] for f in fans}
    assert loop not in {s["thread"] for s in _named(spans, "engine.step")}
    assert {s["thread"] for s in _named(spans, "replica.stream_end")} == {loop}


def _way_out_stream_end(spans, recorded):
    """Steps of a few ms against 31 ms an item: the engine is done with an
    answer while most of its tokens still wait for the acknowledgement the
    coroutine awaits, and the result waits the arranged delay for every item
    that was ahead of it. Once a request: nothing a token."""
    ends = _named(spans, "replica.stream_end")
    assert len(ends) == 2 and len({e["stats"]["request_id"] for e in ends}) == 2
    assert all(1 <= e["stats"]["tokens"] <= WAYOUT_NEW for e in ends)
    assert not _named(spans, "replica.stream_take")
    posted = _named(spans, "replica.fan_out")[-1]["start"]  # the results' post
    by_stream = defaultdict(list)
    for s in _named(spans, "replica.stream_item"):
        by_stream[s["stats"]["stream"]].append(s["start"])
    # a stream's items acknowledged between that post and the earlier end's
    # take are 31 ms apart or more, and each end is behind its own
    ahead = min(sum(posted <= at <= ends[0]["start"] for at in acks)
                for acks in by_stream.values())
    assert ahead >= 3
    assert min(e["stats"]["inbox_wait_us"] for e in ends) >= (ahead - 1) * ITEM_S * 1e6


def _way_out_stream_item(spans, recorded):
    """An item's round trip ends when the consumer asks for the next one."""
    by_stream = defaultdict(list)
    for s in _named(spans, "replica.stream_item"):
        by_stream[s["stats"]["stream"]].append(s["stats"]["rtt_us"])
    assert len(by_stream) == 2
    # the consumer that began first was let in first
    for (items, lived_s), (_, rtts) in zip(recorded["streamed"], sorted(by_stream.items())):
        assert len(rtts) == len(items) == WAYOUT_NEW + 1  # tokens and the summary
        assert min(rtts) >= 30_000
        assert sum(rtts) <= lived_s * 1e6


def _way_out_stream_open(spans, recorded):
    """Once a request, when the replica's admission let it in."""
    opened = [s["stats"] for s in _named(spans, "replica.stream_open")]
    assert len(opened) == 2 and len({o["stream"] for o in opened}) == 2
    assert {o["stream"] for o in opened} == {
        s["stats"]["stream"] for s in _named(spans, "replica.stream_item")}
    assert all(0 <= o["admit_wait_us"] < 1_000_000 for o in opened)


@pytest.mark.parametrize("holds", [
    _way_out_fan_out, _way_out_stream_end, _way_out_stream_item,
    _way_out_stream_open], ids=lambda f: f.__name__[len("_way_out_"):])
def test_the_way_out_has_its_spans(recorded, holds):
    spans = recorded["wayout"]
    _parents(spans)  # the loop's regions nest in nothing falsely
    holds(spans, recorded)


def test_without_a_session_the_same_tokens_and_no_request_span(recorded):
    # nothing recorded a wall-clock request span while the profiler ran
    assert recorded["request_spans"] == []
    eng = recorded["engine"]
    tracing.clear_spans()
    assert _stepped(eng, 0) == recorded["tokens"]["stepped"]
    assert _threaded(eng, 20) == recorded["tokens"]["threaded"]
    # the way out's regions are no-ops: the same items, nothing recorded
    assert [items for items, _ in _wayout(recorded["replica"], 30)] == (
        recorded["tokens"]["wayout"])
    assert tracing.get_spans() == []
    # a fresh engine stepped alone agrees token for token
    assert _stepped(_engine(), 0)[:1] == recorded["tokens"]["stepped"][:1]


def test_step_span_records_the_request_span_only_for_a_traced_request(monkeypatch):
    monkeypatch.setattr(tracing, "flush_spans", lambda: None)
    tracing.clear_spans()
    with tracing.step_span("kv.acquire", None, request_span="kvcache.acquire",
                           category="kvcache", attrs={"request_id": 1}) as s:
        s.set(cached_tokens=16)
    assert tracing.get_spans() == []
    ctx = tracing.new_trace_context()
    parent = {"trace_id": ctx["trace_id"], "span_id": "abcd"}
    trace = {"ctx": parent, "wall": time.time()}
    with tracing.step_span("kv.acquire", trace, request_span="kvcache.acquire",
                           category="kvcache", attrs={"request_id": 1}) as s:
        s.set(cached_tokens=16)
        time.sleep(0.01)
    with tracing.step_span("kv.commit", trace, request_span="kvcache.commit",
                           blocks=2) as s:
        s.cancel()
    with pytest.raises(RuntimeError):
        with tracing.step_span("engine.prefill", trace, computed_tokens=4):
            raise RuntimeError("prefill failed")
    (span,) = tracing.get_spans()
    assert span["name"] == "kvcache.acquire" and span["cat"] == "kvcache"
    assert span["trace_id"] == ctx["trace_id"] and span["parent_id"] == "abcd"
    assert span["args"]["request_id"] == 1 and span["args"]["cached_tokens"] == 16
    assert span["dur"] >= 10_000
    tracing.clear_spans()


def test_replica_stream_next_counts_the_wait_for_a_pool_thread(tmp_path):
    from ray_tpu._internal import serialization
    from ray_tpu.serve.replica import Replica

    class Slow:
        def gen(self, n):
            for i in range(n):
                time.sleep(0.03)
                yield i

    async def streams(replica, count):
        async def one():
            return [item async for item in replica.handle_request_stream(
                "gen", (3,), {}, None)]

        return await asyncio.gather(*[one() for _ in range(count)])

    replica = Replica("d", "r0", serialization.dumps(Slow), (), {}, None)
    logdir = str(tmp_path / "prof")
    with tracing.device_profile(logdir):
        with tracing.annotate_device_trace("test.alone"):
            assert asyncio.run(streams(replica, 1)) == [[0, 1, 2]]
        with tracing.annotate_device_trace("test.crowded"):
            # 12 streams on the pool's 8 threads: four wait a whole next()
            assert asyncio.run(streams(replica, 12)) == [[0, 1, 2]] * 12
    spans = _read(logdir)
    (alone,) = _named(spans, "test.alone")
    nexts = _named(spans, "replica.stream_next")
    first = [s for s in nexts if s["end"] <= alone["end"]]
    later = [s for s in nexts if s["start"] >= alone["end"]]
    # three items and the end of the stream, each one next()
    assert len(first) == 4 and len(later) == 12 * 4
    assert min(s["stats"]["executor_wait_us"] for s in first) < 20_000
    assert max(s["stats"]["executor_wait_us"] for s in later) >= 20_000
    assert len({s["thread"] for s in later}) == 8
    # the pool-driven branch counts an item's way out too, on the loop's
    # thread: three a stream, none for the end of a stream
    items = _named(spans, "replica.stream_item")
    assert len(items) == 13 * 3
    assert len({s["stats"]["stream"] for s in items}) == 13
    assert all(s["stats"]["rtt_us"] >= 0 for s in items)
    assert not {s["thread"] for s in items} & {s["thread"] for s in nexts}
    assert len(_named(spans, "replica.stream_open")) == 13


def test_a_process_without_jax_is_not_made_to_import_it():
    """A replica of a plain Python deployment opens ``replica.stream_next``
    too. No jax there means no profiler session to write to: the region is
    a no-op, and the process stays as light as it was."""
    import subprocess
    import sys

    done = subprocess.run(
        [sys.executable, "-c", (
            "import sys\n"
            "from ray_tpu.util import tracing\n"
            "with tracing.annotate_device_trace('replica.stream_next', executor_wait_us=3):\n"
            "    pass\n"
            "with tracing.step_span('kv.acquire', None, request_span='kvcache.acquire'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n"
            "assert tracing.get_spans() == []\n")],
        capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr
