"""ops/decode_attention.py: the decode step's attention kernel, interpreted,
against the einsum it replaced (models/llama.py's ``s > 1`` lines kept here
as a plain function), and the engine's part of the bargain: a free row of
the pool is one key long, whatever ran in it before.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import greedy_reference

from ray_tpu.ops import decode_attention as da


def _einsum_attention(q, k_cache, v_cache, lengths):
    """The decode branch's own lines for one query token a row."""
    b, h, d = q.shape
    hk, max_seq_len = k_cache.shape[1], k_cache.shape[2]
    q = q[:, :, None]
    idx = lengths - 1
    k_all = jnp.repeat(k_cache, h // hk, axis=1)
    v_all = jnp.repeat(v_cache, h // hk, axis=1)
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k_all.astype(jnp.float32),
    ) / math.sqrt(d)
    q_pos = idx[:, None, None] + jnp.arange(1)[None, :, None]
    k_pos = jnp.arange(max_seq_len)[None, None, :]
    mask = k_pos <= q_pos
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhqk,bhkd->bhqd", probs, v_all.astype(jnp.float32)
    ).astype(q.dtype)
    return out[:, :, 0]


def _case(group, d, dtype, lengths, max_seq_len, hk=2, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    b = len(lengths)
    q = jax.random.normal(keys[0], (b, hk * group, d), dtype)
    k = jax.random.normal(keys[1], (b, hk, max_seq_len, d), dtype)
    v = jax.random.normal(keys[2], (b, hk, max_seq_len, d), dtype)
    lengths = jnp.asarray(lengths, jnp.int32)
    live = jnp.arange(max_seq_len)[None, None, :, None] < lengths[
        :, None, None, None]
    # the reference masks what lies past a row's length; the kernel is
    # given NaN there and must not let one through
    want = _einsum_attention(
        q, jnp.where(live, k, 0), jnp.where(live, v, 0), lengths)
    got = jax.jit(da.decode_attention)(
        q, jnp.where(live, k, jnp.nan), jnp.where(live, v, jnp.nan), lengths)
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


def _one_step(want, dtype):
    """The spacing of ``dtype``'s values at the largest output."""
    bits = 7 if dtype == jnp.bfloat16 else 23
    return float(2.0 ** (math.floor(math.log2(np.abs(want).max())) - bits))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("kept", ["ring", "row"])
def test_a_group_of_seven_over_a_ring_and_a_full_row(kept, dtype, monkeypatch):
    """28 query heads on 4 K/V heads (SmallThinker's: a group that is
    neither a power of two nor a multiple of the 8 sublanes) over a window
    layer's ring, rows younger than it and rows that have wrapped it (every
    slot live), and over a full layer's ragged rows."""
    monkeypatch.setattr(da, "_BLOCK_BYTES", 0)
    hk, d = 4, 128
    block = da.block_k(1 << 20, hk, d, dtype)
    if kept == "ring":
        ring = 2 * block
        # min(index + 1, ring): young rows, the edge, and wrapped ones
        lengths = [min(p + 1, ring) for p in
                   (0, 6, block - 1, block, ring - 2, ring - 1, ring, 5 * ring)]
        max_seq_len = ring
    else:
        max_seq_len = 3 * block
        lengths = [1, 7, block + 1, max_seq_len, block // 3, 2 * block + 5]
    got, want = _case(7, d, dtype, lengths, max_seq_len, hk, seed=7)
    assert got.shape == (len(lengths), 28, d)
    assert not np.isnan(got).any()
    steps = 1 if dtype == jnp.bfloat16 else 8
    assert np.abs(got - want).max() <= steps * _one_step(want, dtype)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("d", [32, 128])
@pytest.mark.parametrize("group", [1, 4, 8])
def test_matches_the_einsum_on_ragged_rows(group, d, dtype, monkeypatch):
    # the smallest block there is (128 keys), so three of them stay small
    monkeypatch.setattr(da, "_BLOCK_BYTES", 0)
    hk = 2
    block = da.block_k(1 << 20, hk, d, dtype)
    max_seq_len = 3 * block
    lengths = [1, block, block + 1, max_seq_len, block // 3, 2 * block + 5]
    got, want = _case(group, d, dtype, lengths, max_seq_len, hk)
    assert got.shape == (len(lengths), hk * group, d)
    assert not np.isnan(got).any()
    # f32 sums in another order: a few of f32's steps, one of bf16's
    steps = 1 if dtype == jnp.bfloat16 else 8
    assert np.abs(got - want).max() <= steps * _one_step(want, dtype)


def test_a_cache_shorter_than_a_block_and_one_that_ends_inside_one(monkeypatch):
    # tiny models: the whole cache is one chunk of its own length
    assert da.block_k(64, 4, 32, jnp.bfloat16) == 64
    got, want = _case(2, 32, jnp.bfloat16, [1, 17, 64], 64)
    assert np.abs(got - want).max() <= _one_step(want, jnp.bfloat16)
    # 320 positions are no whole chunks of 128, and a copy cannot hang over
    # the cache's end as a block could: five chunks of 64
    monkeypatch.setattr(da, "_BLOCK_BYTES", 0)
    assert da.block_k(320, 2, 128, jnp.bfloat16) == 64
    got, want = _case(4, 128, jnp.bfloat16, [320, 257, 256, 3], 320)
    assert not np.isnan(got).any()
    assert np.abs(got - want).max() <= _one_step(want, jnp.bfloat16)


def test_block_size_follows_the_cache_not_an_option():
    # every serving shape (8, 16, 4 KV heads of 128, bf16): 128 keys a
    # visit, the lane width of the scores, 256 KB to 1 MB of K and V
    assert da.block_k(4096, 8, 128, jnp.bfloat16) == 128
    assert da.block_k(4096, 16, 128, jnp.bfloat16) == 128
    assert da.block_k(1024, 4, 128, jnp.bfloat16) == 128
    # Llama-2 widths, 32 KV heads: never under the 128 lanes
    assert da.block_k(2048, 32, 128, jnp.bfloat16) == 128
    # a tp=4 shard of the chat cells' cache holds 2 KV heads: a visit
    # wants bytes, 128 KB of K
    assert da.block_k(4096, 2, 128, jnp.bfloat16) == 256
    # a latent row: 1024 positions, keys and values in one
    assert da.latent_block_k(8192, 576, jnp.bfloat16) == 1024


def test_a_row_of_length_zero_attends_nothing():
    got, _ = _case(4, 32, jnp.bfloat16, [0, 5], 64)
    assert (got[0] == 0).all() and np.abs(got[1]).max() > 0


def test_heads_sharded_over_tp_give_the_same_rows():
    from ray_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(4, tp=2, fsdp=2)
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(keys[0], (3, 8, 32), jnp.bfloat16)
    k = jax.random.normal(keys[1], (3, 2, 64, 32), jnp.bfloat16)
    v = jax.random.normal(keys[2], (3, 2, 64, 32), jnp.bfloat16)
    lengths = jnp.asarray([64, 1, 33], jnp.int32)
    one = da.decode_attention(q, k, v, lengths)
    with mesh:
        sharded = jax.jit(
            lambda *a: da.decode_attention(*a, mesh=mesh))(q, k, v, lengths)
    assert (np.asarray(one, np.float32) == np.asarray(sharded, np.float32)).all()


# ---------------------------------------------------------------------------
# the schedule: a step visits its live chunks, and nothing else
# ---------------------------------------------------------------------------

CHUNK = 128
MAX_SEQ_LEN = 4 * CHUNK
# lengths a step can hold, in chunks of 128 of a 512-position cache
STEPS = {
    "ragged": [1, CHUNK, CHUNK + 1, 3 * CHUNK, CHUNK // 3, 2 * CHUNK + 5],
    # the steady cell's shape: fifteen free rows, one key each, beside one
    # long row
    "fifteen_free_rows": [1] * 15 + [2 * CHUNK + 17],
    "every_row_full": [MAX_SEQ_LEN] * 4,
    "whole_chunks": [2 * CHUNK, CHUNK, 3 * CHUNK],
    "at_max_seq_len": [MAX_SEQ_LEN, 1, MAX_SEQ_LEN - 1],
}


def _latent_einsum(q, qr, c, r, lengths, scale):
    """models/deepseek.py's own lines for a chunk behind a cached prefix
    (the ``s > 1`` path), at one query a row."""
    rows_c, rows_r = c[:, 0], r[:, 0]
    scores = (
        jnp.einsum("bhsr,bkr->bhsk", q[:, :, None], rows_c,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bhsd,bkd->bhsk", qr[:, :, None], rows_r,
                     preferred_element_type=jnp.float32)
    ) * scale
    q_pos = (lengths - 1)[:, None, None] + jnp.arange(1)[None, :, None]
    k_pos = jnp.arange(c.shape[2])[None, None, :]
    scores = jnp.where((k_pos <= q_pos)[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum(
        "bhsk,bkr->bhsr", probs.astype(q.dtype), rows_c,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)[:, :, 0]


def _latent_case(lengths, max_seq_len, heads=4, rank=128, rope=64, seed=0):
    dtype = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    b = len(lengths)
    q = jax.random.normal(keys[0], (b, heads, rank), dtype)
    qr = jax.random.normal(keys[1], (b, heads, rope), dtype)
    c = jax.random.normal(keys[2], (b, 1, max_seq_len, rank), dtype)
    r = jax.random.normal(keys[3], (b, 1, max_seq_len, rope), dtype)
    lengths = jnp.asarray(lengths, jnp.int32)
    live = jnp.arange(max_seq_len)[None, None, :, None] < lengths[
        :, None, None, None]
    scale = 1.0 / math.sqrt(rank // 4 + rope)
    want = _latent_einsum(
        q, qr, jnp.where(live, c, 0), jnp.where(live, r, 0), lengths, scale)
    got = jax.jit(
        lambda *a: da.latent_decode_attention(*a, sm_scale=scale)
    )(q, qr, jnp.where(live, c, jnp.nan), jnp.where(live, r, jnp.nan), lengths)
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


@pytest.fixture
def chunks_of_128(monkeypatch):
    """The smallest chunk there is in both kernels, so that a cache of 512
    positions holds four."""
    monkeypatch.setattr(da, "_BLOCK_BYTES", 0)
    monkeypatch.setattr(da, "_LATENT_BLOCK_BYTES", 0)
    assert da.block_k(MAX_SEQ_LEN, 2, 128, jnp.bfloat16) == CHUNK
    assert da.latent_block_k(MAX_SEQ_LEN, 192, jnp.bfloat16) == CHUNK


def _walked(lengths, chunk):
    """The schedule ``_walk`` runs over rows ``lengths`` long, as plain
    Python: every copy's start and wait and every visit, in order."""
    events = []

    class Copy:
        def __init__(self, row, ci, slot):
            self.at = (int(row), int(ci), int(slot))

        def start(self):
            events.append(("start",) + self.at)

        def wait(self):
            events.append(("wait",) + self.at)

    def visit(row, ci, slot, ends):
        events.append(("visit", int(row), int(ci), int(slot), bool(ends)))

    with jax.disable_jit():
        da._walk(
            jnp.asarray(lengths, jnp.int32), chunk,
            lambda *at: (Copy(*at),), visit)
    return events


@pytest.mark.parametrize("step", STEPS)
def test_the_walk_visits_a_steps_live_chunks_and_nothing_else(step):
    """``sum(cdiv(max(length, 1), chunk))`` visits, rows in order, each
    row's chunks in order and its last one marked; every visit's copy is
    started once, ahead of it, into a buffer nobody is still using."""
    lengths = STEPS[step]
    events = _walked(lengths, CHUNK)
    chunks = [-(-max(n, 1) // CHUNK) for n in lengths]
    visited = [e[1:] for e in events if e[0] == "visit"]
    assert len(visited) == sum(chunks) == int(
        da.visits(np.asarray(lengths), CHUNK))
    assert [(row, ci, ends) for row, ci, _, ends in visited] == [
        (row, ci, ci == n - 1) for row, n in enumerate(chunks)
        for ci in range(n)]
    assert [slot for _, _, slot, _ in visited] == [
        nth % da._SLOTS for nth in range(len(visited))]
    holds = {}  # slot -> the visit whose chunk it holds, not yet worked on
    for kind, row, ci, slot, *_ in events:
        if kind == "start":
            assert slot not in holds
            holds[slot] = (row, ci)
            assert len(holds) <= da._SLOTS
        elif kind == "wait":
            assert holds[slot] == (row, ci)
        else:
            assert holds.pop(slot) == (row, ci)
    assert not holds  # nothing copied that no visit took


@pytest.mark.parametrize("step", STEPS)
@pytest.mark.parametrize("kernel", ["gqa", "latent"])
def test_a_steps_rows_match_the_einsum(kernel, step, chunks_of_128):
    """Each kernel against its model's own einsum over the same step; what
    lies past a row's length, NaN here, is masked."""
    lengths = STEPS[step]
    if kernel == "gqa":
        got, want = _case(4, 128, jnp.bfloat16, lengths, MAX_SEQ_LEN)
        steps = 1
    else:
        got, want = _latent_case(lengths, MAX_SEQ_LEN)
        steps = 2  # the model's einsum rounds its probabilities to bf16
    assert not np.isnan(got).any()
    assert np.abs(got - want).max() <= steps * _one_step(want, jnp.bfloat16)


def _grids(fn, *args):
    """The grid of every pallas_call in ``fn``'s trace."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["grid_mapping"].grid)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("kernel", ["gqa", "latent"])
def test_no_grid_is_a_function_of_max_seq_len(kernel):
    """One call of the kernel walks the step: there is no grid whose
    extent the cache's length could set."""
    b, lengths = 3, jnp.ones((3,), jnp.int32)
    for s in (256, 2048):
        if kernel == "gqa":
            kv = jnp.zeros((b, 2, s, 128), jnp.bfloat16)
            grids = _grids(
                da.decode_attention, jnp.zeros((b, 8, 128), jnp.bfloat16),
                kv, kv, lengths)
        else:
            grids = _grids(
                lambda *a: da.latent_decode_attention(*a, sm_scale=1.0),
                jnp.zeros((b, 4, 128), jnp.bfloat16),
                jnp.zeros((b, 4, 64), jnp.bfloat16),
                jnp.zeros((b, 1, s, 128), jnp.bfloat16),
                jnp.zeros((b, 1, s, 64), jnp.bfloat16), lengths)
        assert grids == [()]


@pytest.mark.parametrize("kernel", ["gqa", "latent"])
def test_a_free_rows_stale_keys_never_reach_the_result(kernel, chunks_of_128):
    """Fifteen rows of one key whose caches are NaN from position 1 on
    (what a free row keeps of the request before) beside one long row: a
    one-key row's output is that key's value, to the bit."""
    lengths = jnp.asarray([1] * 15 + [3 * CHUNK + 9], jnp.int32)
    b = len(lengths)
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    live = jnp.arange(MAX_SEQ_LEN)[None, None, :, None] < lengths[
        :, None, None, None]
    if kernel == "gqa":
        q = jax.random.normal(keys[0], (b, 8, 128), jnp.bfloat16)
        k = jax.random.normal(keys[1], (b, 2, MAX_SEQ_LEN, 128), jnp.bfloat16)
        v = jax.random.normal(keys[2], (b, 2, MAX_SEQ_LEN, 128), jnp.bfloat16)
        got = jax.jit(da.decode_attention)(
            q, jnp.where(live, k, jnp.nan), jnp.where(live, v, jnp.nan),
            lengths)
        first = jnp.repeat(v[:, :, 0], 4, axis=1)  # (b, h, d)
    else:
        q = jax.random.normal(keys[0], (b, 4, 128), jnp.bfloat16)
        qr = jax.random.normal(keys[1], (b, 4, 64), jnp.bfloat16)
        c = jax.random.normal(keys[2], (b, 1, MAX_SEQ_LEN, 128), jnp.bfloat16)
        r = jax.random.normal(keys[3], (b, 1, MAX_SEQ_LEN, 64), jnp.bfloat16)
        got = jax.jit(
            lambda *a: da.latent_decode_attention(*a, sm_scale=0.1)
        )(q, qr, jnp.where(live, c, jnp.nan), jnp.where(live, r, jnp.nan),
          lengths)
        first = jnp.broadcast_to(c[:, 0, 0][:, None], (b, 4, 128))
    got, first = np.asarray(got, np.float32), np.asarray(first, np.float32)
    assert np.isfinite(got).all()
    assert (got[:15] == first[:15]).all()
    assert (got[15] != first[15]).any()


@pytest.mark.parametrize("tp", [2, 4])
def test_ragged_rows_sharded_over_tp_walk_their_own_chunks(tp, chunks_of_128):
    """Each shard runs the schedule over its own heads of the same rows:
    several chunks a row, and the rows of the unsharded call, to the bit."""
    from ray_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(4, tp=tp, fsdp=4 // tp)
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    lengths = jnp.asarray(STEPS["ragged"], jnp.int32)
    b = len(lengths)
    q = jax.random.normal(keys[0], (b, 8, 128), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, 4, MAX_SEQ_LEN, 128), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, 4, MAX_SEQ_LEN, 128), jnp.bfloat16)
    one = da.decode_attention(q, k, v, lengths)
    with mesh:
        sharded = jax.jit(
            lambda *a: da.decode_attention(*a, mesh=mesh))(q, k, v, lengths)
    assert (np.asarray(one, np.float32) == np.asarray(sharded, np.float32)).all()


# ---------------------------------------------------------------------------
# the engine: grouped-query decode, and free rows
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gqa():
    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.parallel.sharding import unbox_params

    cfg = LlamaConfig.tiny(n_layers=1, n_heads=4, n_kv_heads=2, max_seq_len=64)
    return cfg, unbox_params(init_params(cfg, jax.random.PRNGKey(0)))


def _cache_indexes(engine):
    return [np.asarray(leaf) for leaf in jax.tree.leaves(engine._cache)
            if leaf.ndim == 1]


def test_one_live_row_of_eight_after_the_others_ran_long(gqa):
    """Seven rows decode 40 tokens and retire; the eighth then decodes 58
    alone. A free row's position used to run on with every step (past the
    cache's 64 here); now it stays one key long, and the live row's tokens
    are those of an engine that never held the others."""
    from ray_tpu.llm.engine import ContinuousBatchingEngine, GenerationRequest

    cfg, params = gqa
    lone = GenerationRequest(token_ids=[7, 8, 9, 10, 11], max_new_tokens=58)
    fresh = ContinuousBatchingEngine(cfg, params, num_slots=8)
    rid = fresh.add_request(lone)
    want = fresh.run_until_complete()[rid].token_ids
    assert want[:6] == greedy_reference(cfg, params, lone.token_ids, 6)

    engine = ContinuousBatchingEngine(cfg, params, num_slots=8)
    for i in range(7):
        engine.add_request(GenerationRequest(
            token_ids=[3 + i, 14, 15, 92 - i], max_new_tokens=40))
    engine.run_until_complete()
    rid = engine.add_request(lone)
    got, steps = None, 0
    while engine.num_active:
        for done, result in engine.step():
            got = result.token_ids if done == rid else got
        steps += 1
        for idx in _cache_indexes(engine):
            assert idx.max() <= cfg.max_seq_len
            if engine._slots:  # a row that retires is reset a step later
                (live,) = engine._slots
                assert all(idx[si] <= 1 for si in range(8) if si != live)
    assert steps >= 57 and got == want


def test_the_engine_counts_the_chunks_its_steps_visit(monkeypatch):
    """``attention_chunks()`` from the host's own row positions: a lone
    request on eight slots of 256 positions in chunks of 128 visits one
    chunk a row until its row passes 128 keys and nine a step from there,
    where a grid of rows x the whole cache has sixteen a step."""
    from ray_tpu.llm.engine import ContinuousBatchingEngine, GenerationRequest
    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.parallel.sharding import unbox_params

    monkeypatch.setattr(da, "_BLOCK_BYTES", 0)
    cfg = LlamaConfig.tiny(n_layers=1, n_heads=4, n_kv_heads=2, max_seq_len=256)
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
    engine = ContinuousBatchingEngine(cfg, params, num_slots=8)
    assert engine.attention_chunks() == {
        "attention_chunks_visited": 0, "attention_chunks_dense": 0}
    engine.add_request(GenerationRequest(
        token_ids=[7, 8, 9, 10, 11], max_new_tokens=140))
    engine.run_until_complete()
    counted = engine.attention_chunks()
    steps, rest = divmod(counted["attention_chunks_dense"], 8 * 2)
    assert rest == 0 and steps == engine._step_count >= 139
    # the step that feeds token n attends 5 + n keys: past 128 from n = 124
    long_steps = counted["attention_chunks_visited"] - 8 * steps
    assert long_steps == steps - 123
