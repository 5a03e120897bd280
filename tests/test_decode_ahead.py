"""The decode step runs one ahead of the host (ROADMAP S5).

``ContinuousBatchingEngine._dense_step`` dispatches step N+1 before it has
read step N: the sampled ids feed the next step on the device
(``_merge_last``), a token reaches its caller one host read late, and a row
leaves when the host has *seen* its last token, so it may ride one step
more. These cases hold that engine, token for token, to a plain loop over
*undonating* ``jax.jit`` of the same two functions with the sampling done
beside it, one request at a time: nothing is dropped, shortened, emitted
twice or sampled under another key.

A temperature sample depends on the row's slot and on the number of the
step that made it (``fold_in(rng, 10_000 + step)`` over the whole pool), so
a recorder notes where each request was admitted and the number of every
pool step; the reference is given those, nothing else of the engine's.
"""

import itertools
import os
import subprocess
import sys
import tempfile
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.kvcache import KVCacheManager
from ray_tpu.llm import GenerationRequest
from ray_tpu.llm import engine as engine_module
from ray_tpu.llm.engine import ContinuousBatchingEngine, _sample_impl
from ray_tpu.models.llama import LlamaConfig, init_params
from ray_tpu.parallel.sharding import unbox_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 8  # KV block size of the paged engines
SLOTS = 2  # three requests on two slots: the third waits for a retirement
SEED = 0
# (prompt length, max_new_tokens): one prompt of several chunks, one whose
# decoded tail crosses two block boundaries
SHAPES = [(19, 7), (11, 16), (21, 10)]


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig.tiny(max_seq_len=64)
    return cfg, unbox_params(init_params(cfg, jax.random.PRNGKey(0)))


def _prompt(seed, n):
    return [int(t) for t in np.random.RandomState(seed).randint(1, 250, n)]


class Recorder:
    """What the reference may know of a run: in which slot and under which
    id each request was admitted, and the number of every pool step, in
    the order the engine made them."""

    def __init__(self, eng):
        self.log, self.admitted = [], {}
        decode, finish = eng._decode, eng._finish_admission

        def spy_decode(params, cache, last_tokens, *args, **kwargs):
            if "active" in kwargs:  # a pool step, not a prefill chunk
                assert last_tokens.shape == (eng._num_slots, 1)
                assert not isinstance(last_tokens, np.ndarray)
                self.log.append(eng._step_count + 1)
            return decode(params, cache, last_tokens, *args, **kwargs)

        def spy_finish(si, rid, req, *args):
            self.admitted[id(req)] = (rid, si, len(self.log))
            return finish(si, rid, req, *args)

        eng._decode, eng._finish_admission = spy_decode, spy_finish

    def of(self, req):
        """(request id, slot, numbers of the pool steps since admission)."""
        rid, si, at = self.admitted[id(req)]
        return rid, si, self.log[at:]


def _engine(tiny, paged, chunk, slots=SLOTS):
    cfg, params = tiny
    kv = KVCacheManager(num_blocks=48, block_size=BS) if paged else None
    eng = ContinuousBatchingEngine(
        cfg, params, num_slots=slots, kv_cache=kv, seed=SEED,
        prefill_chunk_tokens=chunk)
    return eng, Recorder(eng)


@pytest.fixture(scope="module")
def engines(tiny):
    """One engine a (pool, chunk): its programs compile once, and every
    case that follows finds it as the case before left it."""
    made = {}

    def get(paged, chunk):
        if (paged, chunk) not in made:
            made[paged, chunk] = _engine(tiny, paged, chunk)
        return made[paged, chunk]

    return get


@pytest.fixture(scope="module")
def plain(tiny):
    """The reference: prefill, then one undonated decode a token, batch of
    one, sampled beside the loop with the engine's rule on a pool-shaped
    array that is zero but for the row's slot."""
    cfg, params = tiny
    model = ContinuousBatchingEngine(cfg, params, num_slots=1, seed=SEED)
    prefill = jax.jit(model._prefill_impl)
    decode = jax.jit(model._decode_impl)
    rng = jax.random.PRNGKey(SEED)

    def sample(logits_row, temp, key, si, slots):
        if not temp:
            return int(jnp.argmax(logits_row))
        logits = jnp.zeros((slots, logits_row.shape[0])).at[si].set(logits_row)
        temps = jnp.zeros((slots,)).at[si].set(temp)
        return int(_sample_impl(logits, temps, key)[si])

    def tokens(req, rid=0, si=0, steps=None, slots=SLOTS):
        temp = max(req.temperature, 0.0)
        steps = list(range(1, req.max_new_tokens)) if steps is None else steps
        logits, cache = prefill(params, jnp.asarray([req.token_ids], jnp.int32))
        out = [sample(logits[0], temp, jax.random.fold_in(rng, rid), 0, 1)]
        for step in steps:
            if len(out) >= req.max_new_tokens or out[-1] == req.eos_token_id:
                break
            logits, cache = decode(
                params, cache, jnp.asarray([[out[-1]]], jnp.int32))
            out.append(sample(
                logits[0], temp, jax.random.fold_in(rng, 10_000 + step),
                si, slots))
        assert len(out) == req.max_new_tokens or out[-1] == req.eos_token_id
        return out, "eos" if out[-1] == req.eos_token_id else "length"

    return tokens


def _first_new(stream, lo):
    """The first index from ``lo`` whose token the stream had not shown:
    as an ``eos`` it ends the stream there and nowhere before."""
    return next(i for i in range(lo, len(stream)) if stream[i] not in stream[:i])


def _requests(base, temp, eos, plain):
    reqs = [
        GenerationRequest(token_ids=_prompt(base + i, n), max_new_tokens=new,
                          temperature=temp)
        for i, (n, new) in enumerate(SHAPES)
    ]
    if eos and not temp:
        # each ends by the third token it would have gone on from; greedy
        # tokens depend on nothing but the prompt
        for r in reqs:
            stream = plain(r)[0]
            r.eos_token_id = stream[_first_new(stream, 2)]
    elif eos:
        for r in reqs:  # a sample cannot be known beforehand: any id
            r.eos_token_id = 17
    return reqs


def _expect(reqs, rec, plain):
    out = []
    for r in reqs:
        rid, si, steps = rec.of(r)
        out.append(plain(r, rid, si, steps))
    return out


def _by_generate(eng, reqs):
    return [(r.token_ids, r.finished_reason) for r in eng.generate(reqs)]


def _by_stream(eng, reqs):
    out = []
    for r in reqs:
        *streamed, final = eng.generate_stream(r)
        assert streamed == final.token_ids
        out.append((final.token_ids, final.finished_reason))
    return out


def _by_run_until_complete(eng, reqs):
    rids = [eng.add_request(r) for r in reqs]
    done = eng.run_until_complete()
    assert sorted(done) == sorted(rids)
    return [(done[rid].token_ids, done[rid].finished_reason) for rid in rids]


def _by_three_threads(eng, reqs):
    out = [None] * len(reqs)

    def stream(i):
        *streamed, final = eng.generate_stream(reqs[i])
        assert streamed == final.token_ids
        out[i] = (final.token_ids, final.finished_reason)

    threads = [threading.Thread(target=stream, args=(i,)) for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    return out


ENTRIES = {
    "generate": _by_generate, "generate_stream": _by_stream,
    "run_until_complete": _by_run_until_complete,
    "three_threads": _by_three_threads,
}


@pytest.mark.parametrize("entry", list(ENTRIES))
@pytest.mark.parametrize("temp", [0.0, 0.8], ids=["greedy", "temp0.8"])
@pytest.mark.parametrize("eos", [False, True], ids=["no_eos", "eos"])
@pytest.mark.parametrize("chunk", [0, BS], ids=["whole_prefill", "chunked"])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_tokens_are_the_plain_loops(engines, plain, paged, chunk, eos, temp, entry):
    eng, rec = engines(paged, chunk)
    base = 100 * list(ENTRIES).index(entry) + 10 * int(eos) + 50 * int(bool(temp))
    reqs = _requests(base, temp, eos, plain)
    got = ENTRIES[entry](eng, reqs)
    assert got == _expect(reqs, rec, plain)
    if eos and not temp:
        assert [reason for _, reason in got] == ["eos"] * 3
        assert all(3 <= len(t) < r.max_new_tokens for (t, _), r in zip(got, reqs))
    if not eos:
        assert [len(t) for t, _ in got] == [new for _, new in SHAPES]
    # nothing is left behind: no row, no step the host has not read
    assert not eng.num_active and eng._inflight is None


@pytest.mark.parametrize("temp", [0.0, 0.8], ids=["greedy", "temp0.8"])
def test_a_row_that_ended_rides_one_step_and_returns_its_tokens(tiny, plain, temp):
    """Two rows; ``a`` ends by ``eos`` in step N, which the host reads
    after it has dispatched step N+1 with ``a`` still live. ``a`` returns
    its tokens up to the ``eos`` and not the one step N+1 made for it; the
    blocks its retirement committed are what the reference computes, for
    a prompt that continues it decodes as the reference does."""
    eng, rec = _engine(tiny, paged=True, chunk=0)
    kv = eng._kv
    a = GenerationRequest(token_ids=_prompt(1, 2 * BS - 3), max_new_tokens=24,
                          temperature=temp)
    b = GenerationRequest(token_ids=_prompt(2, 13), max_new_tokens=24,
                          temperature=temp)
    if temp:
        # samples cannot be known beforehand: a dry run without the eos,
        # then a fresh engine, which draws the same keys up to it
        eng.generate([a, b])
        first = _expect([a, b], rec, plain)[0][0]
        eng, rec = _engine(tiny, paged=True, chunk=0)
        kv = eng._kv
    else:
        first = plain(a)[0]
    # end on a token the stream had not shown before: 2 * BS + 2 tokens of
    # (prompt + generated[:-1]) or more, so the tail commit has a block
    at = _first_new(first, 6)
    a.eos_token_id = first[at]
    rid_a, rid_b = eng.add_request(a), eng.add_request(b)
    done = {}
    rode = False
    while eng.num_active:
        before = dict(eng._slots)
        inflight = eng._inflight
        done.update(eng.step())
        if rid_a in done and not rode:
            rode = True
            # a's last token was in the step read by this call, and the
            # step dispatched before that read carried a as a live row
            assert inflight is not None and eng._inflight is not None
            assert any(s.request is a for s in eng._inflight.rows.values())
            assert any(s.request is a for s in before.values())
            assert not any(s.request is a for s in eng._slots.values())
    assert rode
    want_a, want_b = _expect([a, b], rec, plain)
    assert (done[rid_a].token_ids, done[rid_a].finished_reason) == want_a
    assert want_a == (first[: at + 1], "eos")
    assert (done[rid_b].token_ids, done[rid_b].finished_reason) == want_b
    assert len(done[rid_b].token_ids) == 24
    # the retirement committed full blocks of prompt + generated[:-1] only
    seq = a.token_ids + want_a[0][:-1]
    assert kv.cached_blocks(seq + [1] * BS) == len(seq) // BS >= 2
    # a prompt over those blocks: a prefix hit on what the ridden row left
    hits = kv.stats()["prefix_hit_tokens"]
    c = GenerationRequest(
        token_ids=seq[: (len(seq) // BS) * BS] + _prompt(3, 5), max_new_tokens=6)
    (res,) = eng.generate([c])
    assert kv.stats()["prefix_hit_tokens"] - hits == (len(seq) // BS) * BS
    assert (res.token_ids, res.finished_reason) == plain(c)


def test_the_retirement_commit_never_takes_a_position_a_ridden_step_wrote(tiny, plain):
    """``a`` ends by count with prompt + generated[:-1] exactly two blocks
    long while ``b`` goes on, so ``a`` rides a step that writes position
    2 * BS, the first of a third block: two blocks are committed, and they
    hold what a request that never rode holds."""
    eng, _ = _engine(tiny, paged=True, chunk=0)
    a = GenerationRequest(token_ids=_prompt(4, BS + 3), max_new_tokens=BS - 2)
    b = GenerationRequest(token_ids=_prompt(5, 9), max_new_tokens=20)
    ra, rb = eng.generate([a, b])
    assert ra.token_ids == plain(a)[0] and rb.token_ids == plain(b)[0]
    seq = a.token_ids + ra.token_ids[:-1]
    assert len(seq) == 2 * BS
    assert eng._kv.cached_blocks(seq + ra.token_ids[-1:] + [1] * BS) == 2
    alone, _ = _engine(tiny, paged=True, chunk=0)
    assert alone.generate([a])[0].token_ids == ra.token_ids

    def blocks(e):
        lease = e._kv.acquire(seq + [1])
        row = e._kv.assemble(lease)
        e._kv.release(lease)
        return [np.asarray(leaf)[:, ..., : 2 * BS, :] if leaf.ndim > 1 else None
                for leaf in jax.tree.leaves(row)]

    for x, y in zip(blocks(eng), blocks(alone)):
        if x is not None:
            np.testing.assert_array_equal(x, y)


def test_a_second_decode_is_dispatched_before_the_first_is_read(tiny, plain, monkeypatch):
    """With rows live the device always holds a step the host has not read:
    ``_decode`` is called a second time before the first ``host_sync`` of a
    step's ids, and from then on dispatch and read alternate."""
    eng, _ = _engine(tiny, paged=False, chunk=0)
    warm = GenerationRequest(token_ids=_prompt(6, 7), max_new_tokens=3)
    eng.generate([warm])
    order = []
    sync, decode = engine_module.host_sync, eng._decode

    def spy_sync(x):
        order.append("sync")
        return sync(x)

    def spy_decode(*args, **kwargs):
        order.append("decode")
        return decode(*args, **kwargs)

    spans = []
    span = engine_module._span

    def spy_span(name, **counts):
        if name == "engine.decode_dispatch":
            spans.append(counts)
        return span(name, **counts)

    monkeypatch.setattr(engine_module, "host_sync", spy_sync)
    monkeypatch.setattr(engine_module, "_span", spy_span)
    eng._decode = spy_decode
    r = GenerationRequest(token_ids=_prompt(6, 7), max_new_tokens=6)
    rid = eng.add_request(r)
    assert eng.step() == [] and len(eng._slots[0].generated) == 2
    # two steps, then the admission's first token, then the first step
    assert order == ["decode", "decode", "sync", "sync"]
    assert [c["ahead"] for c in spans] == [0, 1]
    assert eng._inflight is not None and eng._step_count - 2 == 2
    order.clear()
    assert eng.step() == [] and order == ["decode", "sync"]
    assert spans[-1] == {"batch": 1, "live_tokens": 7 + 3, "ahead": 1}
    done = dict(eng.run_until_complete())
    # five decode steps for six tokens, the last read without a dispatch
    assert order == ["decode", "sync"] * 3 + ["sync"]
    assert [c["ahead"] for c in spans] == [0, 1, 1, 1, 1]
    assert done[rid].token_ids == plain(r)[0]
    assert eng._inflight is None


def test_an_engine_left_with_a_step_in_flight_admits_the_next_request(tiny, plain):
    """The last row ends by ``eos`` with a step queued behind it: the
    device runs that step for no one. The next request is inserted behind
    it, is fed its own first token (not what that step sampled for the
    slot), and the step nobody read gives its number to the next."""
    eng, rec = _engine(tiny, paged=True, chunk=0, slots=1)
    a = GenerationRequest(token_ids=_prompt(7, 9), max_new_tokens=12)
    stream = plain(a)[0]
    n = _first_new(stream, 4)  # n decode steps make the token that ends it
    a.eos_token_id = stream[n]
    (ra,) = eng.generate([a])
    assert ra.finished_reason == "eos" and ra.token_ids == stream[: n + 1]
    # n steps were read; one more was dispatched, and forgotten
    assert rec.log == list(range(1, n + 2)) and eng._step_count == n
    assert eng._inflight is None and not eng._slots
    b = GenerationRequest(token_ids=_prompt(8, 12), max_new_tokens=8,
                          temperature=0.8)
    (rb,) = eng.generate([b])
    assert rec.of(b) == (1, 0, list(range(n + 1, n + 8)))
    assert (rb.token_ids, rb.finished_reason) == _expect([b], rec, plain)[0]
    assert len(rb.token_ids) == 8 and eng._step_count == n + 7


def test_a_concurrent_batch_reaches_no_program_a_lone_request_did_not():
    """The benchmark's ``correct``: after a warm-up of single requests sent
    one at a time (``check_and_warm``), nothing may compile, nor be read
    from the compile cache. So every program of a step runs in every step:
    with or without fresh rows, riding rows, a step in flight, and a
    step's admissions, one or six, go through programs whose shapes know
    nothing of their number. Counted by ``compile_cache.stats()`` in a
    process of its own, as a replica does."""
    script = r"""
import threading
import jax
import numpy as np
from ray_tpu._internal import compile_cache
compile_cache.configure()
from ray_tpu.kvcache import KVCacheManager
from ray_tpu.llm import GenerationRequest
from ray_tpu.llm.engine import ContinuousBatchingEngine
from ray_tpu.models.llama import LlamaConfig, init_params
from ray_tpu.parallel.sharding import unbox_params

BS = 8
cfg = LlamaConfig.tiny(max_seq_len=128)
params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
eng = ContinuousBatchingEngine(
    cfg, params, num_slots=6, seed=0,
    kv_cache=KVCacheManager(num_blocks=64, block_size=BS))

def request(seed, n, new):
    ids = [int(t) for t in np.random.RandomState(seed).randint(1, 250, n)]
    return GenerationRequest(token_ids=ids, max_new_tokens=new)

def stream(req):
    *tokens, final = eng.generate_stream(req)
    assert tokens == final.token_ids and len(tokens) == req.max_new_tokens

lengths = (12, 20, 28)
# the shape of check_and_warm: one request of each prompt length, one at a
# time, the first long enough to commit a decoded tail at retirement
for i, n in enumerate(lengths):
    stream(request(i, n, 8 if i else BS + 2))
before = compile_cache.stats()
assert before["programs"] > 0 and before["cache_requests"] > 0, before
threads = [
    threading.Thread(target=stream, args=(request(10 + i, lengths[i % 3], 6 + 5 * (i % 4)),))
    for i in range(10)
]
for t in threads:
    t.start()
for t in threads:
    t.join()
# six admissions in one step: the bound's early reads, the pool's read of two
rids = [eng.add_request(request(30 + i, lengths[i % 3], 9 + i)) for i in range(6)]
assert len(eng.run_until_complete()) == 6
# and one token a request, which is never inserted
rids = [eng.add_request(request(40 + i, lengths[i % 3], 1)) for i in range(5)]
assert len(eng.run_until_complete()) == 5
after = compile_cache.stats()
same = {k: (before[k], after[k]) for k in ("programs", "cache_requests")}
assert all(a == b for a, b in same.values()), same
print("steps", eng._step_count, same)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    with tempfile.TemporaryDirectory() as cache:
        env["JAX_COMPILATION_CACHE_DIR"] = cache
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.startswith("steps ")


# -- an admission's first token stays on the device (ROADMAP S14) ------------
#
# ``_admit_one`` dispatches the prefill and its sampler and goes on; the host
# reads the token behind the next step's dispatch (``_read_firsts``), and at
# most two admissions are ever dispatched and unread (``_hold_to_bound``).
# The reference is the same engine made to read every first token at once,
# which is what the engine did before: ``_reads_first_at_once`` -> True.

WIDE = 8  # slots of the engines below: five admissions fit beside a live row
_prompt_seeds = itertools.count(10_000)


def _new_prompt(n):
    """A prompt no case before it used: no prefix of it is in any pool."""
    return _prompt(next(_prompt_seeds), n)


class Watch:
    """What an engine did, in order: "prefill" (a whole-prompt prefill
    dispatched, with the admissions unread at that moment), "decode" (a pool
    step dispatched), "sync" (a read by the host), and its
    ``engine.first_sync`` / ``engine.prefill`` spans' counts."""

    def __init__(self, eng, monkeypatch):
        self.order, self.unread, self.first_syncs, self.prefills = [], [], [], []
        prefill, decode = eng._prefill, eng._decode
        sync, span = engine_module.host_sync, engine_module._span
        step_span = engine_module._tracing.step_span

        def spy_prefill(*args, **kwargs):
            self.order.append("prefill")
            self.unread.append(len(eng._unread))
            return prefill(*args, **kwargs)

        def spy_decode(*args, **kwargs):
            if "active" in kwargs:
                self.order.append("decode")
            return decode(*args, **kwargs)

        def spy_sync(x):
            self.order.append("sync")
            return sync(x)

        def spy_span(name, **counts):
            if name == "engine.first_sync":
                self.first_syncs.append(counts)
            return span(name, **counts)

        def spy_step_span(name, *args, **kwargs):
            if name == "engine.prefill":
                self.prefills.append(kwargs["first"])
            return step_span(name, *args, **kwargs)

        eng._prefill, eng._decode = spy_prefill, spy_decode
        monkeypatch.setattr(engine_module, "host_sync", spy_sync)
        monkeypatch.setattr(engine_module, "_span", spy_span)
        monkeypatch.setattr(engine_module._tracing, "step_span", spy_step_span)
        self._eng, self._own = eng, (prefill, decode)

    def close(self):
        self._eng._prefill, self._eng._decode = self._own


def _wide(tiny, at_once):
    cfg, params = tiny
    eng = ContinuousBatchingEngine(
        cfg, params, num_slots=WIDE, seed=SEED,
        kv_cache=KVCacheManager(num_blocks=96, block_size=BS))
    if at_once:
        eng._reads_first_at_once = lambda export: True
    return eng


@pytest.fixture(scope="module")
def builds(tiny):
    """(the engine, the same engine reading every first token at once): a
    case drives both through the same requests, so their request ids, slots
    and step numbers stay side by side from case to case."""
    pair = _wide(tiny, False), _wide(tiny, True)

    def get():
        now, then = pair
        assert (now._next_id, now._step_count) == (then._next_id, then._step_count)
        return pair

    return get


def _idle(eng):
    return (not eng._slots and eng._inflight is None and not eng._unread
            and not eng._pending and not eng._has_work())


def _drive(eng, reqs, busy=None):
    """``reqs`` admitted in one step, beside a row that has been decoding
    ``busy`` (a prompt) for three steps, if given; returns every request's
    (tokens, reason), the live row's first."""
    rids, done = [], {}
    if busy:
        bg = GenerationRequest(token_ids=busy, max_new_tokens=14,
                               temperature=reqs[0].temperature)
        rids.append(eng.add_request(bg))
        for _ in range(3):
            done.update(eng.step())
    rids += [eng.add_request(r) for r in reqs]
    free = WIDE - len(eng._slots)
    done.update(eng.step())
    assert free >= len(reqs) and not eng._pending  # one step took them all
    done.update(eng.run_until_complete())
    assert sorted(done) == sorted(rids) and _idle(eng)
    return [(done[r].token_ids, done[r].finished_reason) for r in rids]


@pytest.mark.parametrize("temp", [0.0, 0.8], ids=["greedy", "temp0.8"])
@pytest.mark.parametrize("busy", [False, True], ids=["idle", "beside_a_live_row"])
@pytest.mark.parametrize("n", [1, 2, 3, 5])
def test_deferred_first_tokens_are_those_read_at_once(
        builds, plain, monkeypatch, n, busy, temp):
    """``n`` admissions in one step: every stream is bit for bit what the
    build that reads each first token at once returns; the host reads
    nothing before the step's dispatch but what the bound makes it read,
    and never has more than two admissions dispatched and unread."""
    now, then = builds()
    busy = _new_prompt(10) if busy else None
    reqs = [
        GenerationRequest(token_ids=_new_prompt(9 + 3 * i),
                          max_new_tokens=5 + 2 * i, temperature=temp)
        for i in range(n)
    ]
    want = _drive(then, reqs, busy)
    watch = Watch(now, monkeypatch)
    try:
        got = _drive(now, reqs, busy)
    finally:
        watch.close()
    assert got == want
    assert [len(t) for t, _ in got[bool(busy):]] == [r.max_new_tokens for r in reqs]
    if not temp:
        assert [t for t, _ in got[bool(busy):]] == [plain(r)[0] for r in reqs]
    # the admitting step: prefills, early reads only where two are out,
    # then the step's dispatch, then the read of what is left
    assert watch.prefills[-n:] == ["deferred"] * n
    assert max(watch.unread) <= 1 and watch.unread[-n:] == [0, 1, 1, 1, 1][:n]
    at = len(watch.order) - 1 - watch.order[::-1].index("prefill")  # last prefill
    first_decode = watch.order.index("decode", at)
    head = watch.order[watch.order.index("prefill", 3 * bool(busy)):first_decode]
    assert head.count("prefill") == n and head.count("sync") == max(n - 2, 0)
    assert head[:2] == ["prefill", "prefill"][:n]
    waited = [c for c in watch.first_syncs if c["waited"]]
    behind = [c for c in watch.first_syncs if not c["waited"]]
    assert waited == [{"rows": 1, "waited": 1}] * max(n - 2, 0)
    assert behind[bool(busy):] == [{"rows": min(n, 2), "waited": 0}]


def test_the_build_that_reads_at_once_reads_before_it_goes_on(builds, monkeypatch):
    """The reference of the cases above is the behaviour it stands for:
    one read an admission, before the next prefill and before any step."""
    now, then = builds()
    reqs = [GenerationRequest(token_ids=_new_prompt(9 + i), max_new_tokens=4)
            for i in range(3)]
    got = _drive(now, reqs)
    watch = Watch(then, monkeypatch)
    try:
        assert _drive(then, reqs) == got
    finally:
        watch.close()
    assert watch.order[:7] == ["prefill", "sync"] * 3 + ["decode"]
    assert watch.prefills == ["read"] * 3 and not watch.first_syncs


@pytest.mark.parametrize("temp", [0.0, 0.8], ids=["greedy", "temp0.8"])
@pytest.mark.parametrize("busy", [False, True], ids=["idle", "beside_a_live_row"])
@pytest.mark.parametrize("new", [1, 2])
def test_a_request_of_one_or_two_tokens(builds, plain, busy, new, temp):
    """``max_new_tokens`` 1: known without the token, so the request is
    never inserted, its token is read with the others and its slot is free
    at once (the next request of the same step takes it). 2: one step."""
    now, then = builds()
    busy = _new_prompt(10) if busy else None
    reqs = [
        GenerationRequest(token_ids=_new_prompt(12), max_new_tokens=new,
                          temperature=temp),
        GenerationRequest(token_ids=_new_prompt(10), max_new_tokens=6,
                          temperature=temp),
    ]
    inserted = []
    insert = now._insert_row
    now._insert_row = lambda *a: inserted.append(int(a[2])) or insert(*a)
    try:
        got = _drive(now, reqs, busy)
    finally:
        now._insert_row = insert
    assert got == _drive(then, reqs, busy)
    short = got[bool(busy)]
    assert len(short[0]) == new and short[1] == "length"
    if not temp:
        assert short[0] == plain(reqs[0])[0]
    # beside a live row in slot 0 the two take slot 1: the first left it
    first_free = int(bool(busy))
    assert inserted[-2 + (new == 1):] == (
        [first_free] if new == 1 else [first_free, first_free + 1])


@pytest.mark.parametrize("busy", [False, True], ids=["idle", "beside_a_live_row"])
def test_eos_as_the_first_token_is_found_one_read_late(builds, plain, busy):
    """The request ends with ``generated == [first]``; the steps an idle
    engine dispatched for it alone never happened, so a request sampled at
    a temperature afterwards draws the keys it draws in the build that
    finds the eos at admission."""
    now, then = builds()
    busy = _new_prompt(10) if busy else None
    a = GenerationRequest(token_ids=_new_prompt(11), max_new_tokens=9)
    a.eos_token_id = plain(a)[0][0]
    after = GenerationRequest(token_ids=_new_prompt(13), max_new_tokens=7,
                              temperature=0.8)
    got, want = [], []
    for eng, out in ((now, got), (then, want)):
        steps = eng._step_count
        out += _drive(eng, [a], busy)
        out.append(eng._step_count - steps)
        out += _drive(eng, [after])
    assert got == want
    assert got[bool(busy)] == ([a.eos_token_id], "eos")
    assert got[-2] == 0 if not busy else got[-2] > 0  # steps that happened
    assert len(got[-1][0]) == 7


def test_a_request_cancelled_between_its_prefill_and_its_read(builds, plain):
    """``drop_sink`` after the prefill was dispatched and before the host
    read its token: nothing is delivered for it, its row runs to its end
    and leaves, and the stream beside it is untouched."""
    now, then = builds()
    kept = GenerationRequest(token_ids=_new_prompt(12), max_new_tokens=6)
    gone = GenerationRequest(token_ids=_new_prompt(9), max_new_tokens=5)
    posted = []
    rid_kept = now.stream_to(kept, posted.extend)
    rid_gone = now.stream_to(gone, posted.extend)
    dispatch = now._dispatch_decode

    def cancel_then_dispatch(unread):
        if any(a.rid == rid_gone for a in now._unread):
            now.drop_sink(rid_gone)
        return dispatch(unread)

    now._dispatch_decode = cancel_then_dispatch
    try:
        deadline = 600
        while not any(end is not None for rid, _, end in posted if rid == rid_kept):
            deadline -= 1
            assert deadline, posted
            threading.Event().wait(0.05)
        while now._has_work():
            threading.Event().wait(0.05)
    finally:
        now._dispatch_decode = dispatch
    assert {rid for rid, _, _ in posted} == {rid_kept}
    tokens = [t for _, new, _ in posted for t in new]
    assert tokens == plain(kept)[0]
    with now._lock:
        assert _idle(now)
    then.generate([kept, gone])  # the pair stays side by side


def test_prefill_only_and_a_tier_export_read_at_once(tiny, monkeypatch):
    """Told apart by what the engine is doing, not by a switch: a
    disaggregated prefill ships the token, as does the first export of a
    prefix to the tier. Each reads behind its own prefill; nothing is ever
    unread."""
    from ray_tpu.kvtier import KVTierClient, LocalTierBackend

    cfg, params = tiny
    tier = KVTierClient(model="LlamaConfig", backend=LocalTierBackend(),
                        block_size=BS, codec="raw", holder_id="prefill")
    pre = ContinuousBatchingEngine(
        cfg, params, num_slots=4, seed=SEED, kv_tier=tier,
        kv_cache=KVCacheManager(num_blocks=48, block_size=BS))
    watch = Watch(pre, monkeypatch)
    prompt = _new_prompt(2 * BS + 3)
    shipment = pre.prefill_only(
        GenerationRequest(token_ids=prompt, max_new_tokens=4))
    assert watch.order[:2] == ["prefill", "sync"] and not pre._unread
    # the first computation of another prefix here is exported with its token
    other = GenerationRequest(token_ids=_new_prompt(2 * BS + 1), max_new_tokens=4)
    (res,) = pre.generate([other])
    watch.close()
    assert watch.prefills == ["read"] and not watch.first_syncs
    assert shipment.first_token is not None and len(res.token_ids) == 4
    # the same prompt again: nothing to export, so nothing to read at once
    watch = Watch(pre, monkeypatch)
    again = GenerationRequest(token_ids=_new_prompt(BS - 1), max_new_tokens=4)
    pre.generate([again])
    watch.close()
    assert watch.prefills == ["deferred"]
    assert watch.first_syncs == [{"rows": 1, "waited": 0}]
    assert _idle(pre)
    pre.close()
