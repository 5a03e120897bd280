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
    # (a new function object a call: a second ``jax.jit`` of the same one
    # would answer from the first's trace, whatever the rule is patched to)
    got = jax.jit(lambda *a: da.decode_attention(*a))(
        q, jnp.where(live, k, jnp.nan), jnp.where(live, v, jnp.nan), lengths)
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


PIECE = 128  # key positions the kernel's arithmetic takes at a time
# 128-key pieces a visit holds: one (8 K/V heads and more, and every cache
# until PR 62) and four (a bf16 cache of 4 K/V heads of 128: 512 KB a visit)
SPANS = [1, 4]


def _visits_of(monkeypatch, span):
    """Visits of ``span`` pieces of 128 keys, whatever a position holds."""
    monkeypatch.setattr(da, "_VISIT_BYTES", 1 << 40)
    monkeypatch.setattr(da, "_LONE_PIECE_BYTES", 1 << 40)
    monkeypatch.setattr(da, "_MAX_PIECES", span)
    return span * PIECE


def _close(got, want, lengths, dtype, steps=1):
    """Rows that hold a key match the einsum to ``steps`` of ``dtype``; a
    row of length 0 (the reference's softmax over nothing is NaN) is zeros."""
    held = np.asarray(lengths) > 0
    assert not np.isnan(got).any()
    assert (got[~held] == 0).all()
    return np.abs(got[held] - want[held]).max() <= steps * _one_step(
        want[held], dtype)


def _one_step(want, dtype):
    """The spacing of ``dtype``'s values at the largest output."""
    bits = 7 if dtype == jnp.bfloat16 else 23
    return float(2.0 ** (math.floor(math.log2(np.abs(want).max())) - bits))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("kept", ["ring", "row"])
def test_a_group_of_seven_over_a_ring_and_a_full_row(kept, dtype):
    """28 query heads on 4 K/V heads (SmallThinker's: a group that is
    neither a power of two nor a multiple of the 8 sublanes) over a window
    layer's ring, rows younger than it and rows that have wrapped it (every
    slot live), and over a full layer's ragged rows; under the rule's own
    visit (512 keys of the bf16 cache, 128 of the f32 one), ring and row
    several whole visits long."""
    hk, d = 4, 128
    block = da.block_k(1 << 20, hk, d, dtype)
    assert block == (4 * PIECE if dtype == jnp.bfloat16 else PIECE)
    if kept == "ring":
        ring = 2 * block
        # min(index + 1, ring): young rows, a piece's and a visit's edge,
        # and wrapped ones
        lengths = [min(p + 1, ring) for p in
                   (0, 6, PIECE - 1, PIECE, block - 1, block, block + PIECE,
                    ring - 2, ring - 1, ring, 5 * ring)]
        max_seq_len = ring
    else:
        max_seq_len = 3 * block
        lengths = [1, 7, PIECE + 1, block + 1, max_seq_len, block // 3,
                   2 * block + 5, 2 * block + PIECE]
    got, want = _case(7, d, dtype, lengths, max_seq_len, hk, seed=7)
    assert da.traced_chunk((len(lengths), hk, max_seq_len, d)) == block
    assert got.shape == (len(lengths), 28, d)
    assert not np.isnan(got).any()
    steps = 1 if dtype == jnp.bfloat16 else 8
    assert np.abs(got - want).max() <= steps * _one_step(want, dtype)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("d", [32, 128])
@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("span", SPANS)
def test_matches_the_einsum_on_ragged_rows(span, group, d, dtype, monkeypatch):
    # three visits of ``span`` pieces of 128 keys (the smallest there is)
    block = _visits_of(monkeypatch, span)
    hk = 2
    assert da.block_k(1 << 20, hk, d, dtype) == block
    max_seq_len = 3 * block
    # ... rows that end inside a visit's first piece, on a piece's edge
    # inside a visit and one past it, on a visit's edge and one past it
    lengths = [1, block, block + 1, max_seq_len, block // 3, 2 * block + 5,
               block + PIECE, 2 * block - PIECE + 1, 0]
    got, want = _case(group, d, dtype, lengths, max_seq_len, hk)
    assert da.traced_chunk((len(lengths), hk, max_seq_len, d)) == block
    assert got.shape == (len(lengths), hk * group, d)
    # f32 sums in another order: a few of f32's steps, one of bf16's
    assert _close(got, want, lengths, dtype, 1 if dtype == jnp.bfloat16 else 8)


def test_a_cache_shorter_than_a_block_and_one_that_ends_inside_one(monkeypatch):
    # tiny models: the whole cache is one chunk of its own length
    assert da.block_k(64, 4, 32, jnp.bfloat16) == 64
    got, want = _case(2, 32, jnp.bfloat16, [1, 17, 64], 64)
    assert np.abs(got - want).max() <= _one_step(want, jnp.bfloat16)
    # 320 positions are no whole chunks of 128, and a copy cannot hang over
    # the cache's end as a block could: five chunks of 64
    monkeypatch.setattr(da, "_VISIT_BYTES", 0)
    assert da.block_k(320, 2, 128, jnp.bfloat16) == 64
    got, want = _case(4, 128, jnp.bfloat16, [320, 257, 256, 3], 320)
    assert not np.isnan(got).any()
    assert np.abs(got - want).max() <= _one_step(want, jnp.bfloat16)


SERVING_SHAPES = {
    # cell's cache (positions, K/V heads of 128, bf16) -> keys a visit
    "mistral": ((4096, 8), 128),
    "olmoe": ((4096, 16), 128),
    "solar_open2": ((2048, 8), 128),
    "command_a_plus_ring": ((4096, 8), 128),
    "command_a_plus_row": ((10240, 8), 128),
    "falcon_h1": ((1024, 4), 512),
    "smallthinker_ring": ((4096, 4), 512),
    "smallthinker_row": ((5120, 4), 512),
    "nemotron_h": ((4096, 2), 1024),
}


@pytest.mark.parametrize("cell", SERVING_SHAPES)
def test_a_visit_follows_the_bytes_a_position_holds(cell):
    """The seven serving shapes: 128 keys where they are 256 KB a cache or
    more (8 and 16 K/V heads: the parent's visit, the parent's program),
    512 KB a cache where they are less (4 heads: 512 keys, 2: 1024), in
    whole 128-key pieces that divide the cache."""
    (max_seq_len, kv_heads), keys = SERVING_SHAPES[cell]
    assert da.block_k(max_seq_len, kv_heads, 128, jnp.bfloat16) == keys
    assert max_seq_len % keys == 0 and keys % PIECE == 0
    # (three buffers a cache of that: 3 MB of the default scoped VMEM)
    assert keys == PIECE or keys * kv_heads * 128 * 2 == 512 * 1024


def test_block_size_follows_the_cache_not_an_option():
    # Llama-2 widths, 32 KV heads: never under the 128 lanes
    assert da.block_k(2048, 32, 128, jnp.bfloat16) == 128
    # a tp=4 shard of the chat cells' cache holds 2 KV heads, one of
    # Falcon-H1's 1: a visit wants bytes, 512 KB of K, in 8 pieces at most
    assert da.block_k(4096, 2, 128, jnp.bfloat16) == 1024
    assert da.block_k(4096, 1, 128, jnp.bfloat16) == 1024
    # an f32 cache of 4 heads holds 8 heads' bytes a position
    assert da.block_k(4096, 4, 128, jnp.float32) == 128
    # a visit never hangs over the cache's end: 5120 = 10 x 512, 640 = 5 x 128
    assert da.block_k(5120, 4, 128, jnp.bfloat16) == 512
    assert da.block_k(640, 4, 128, jnp.bfloat16) == 128
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
MAX_SEQ_LEN = 8 * CHUNK
# lengths a step can hold, in chunks of 128 of a 1024-position cache: eight
# visits of one piece, or two of four
STEPS = {
    "ragged": [1, CHUNK, CHUNK + 1, 3 * CHUNK, CHUNK // 3, 2 * CHUNK + 5],
    # the steady cell's shape: fifteen free rows, one key each, beside one
    # long row
    "fifteen_free_rows": [1] * 15 + [2 * CHUNK + 17],
    "every_row_full": [MAX_SEQ_LEN] * 4,
    "whole_chunks": [2 * CHUNK, CHUNK, 3 * CHUNK],
    "at_max_seq_len": [MAX_SEQ_LEN, 1, MAX_SEQ_LEN - 1],
    # every 128-key boundary inside a visit of four pieces, on it, one short
    # of it and one past it; a row that holds nothing
    "every_piece_edge": [0] + [
        n + by for n in range(CHUNK, MAX_SEQ_LEN + 1, CHUNK) for by in (-1, 0, 1)
        if n + by <= MAX_SEQ_LEN],
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


@pytest.fixture(params=SPANS, ids=lambda span: f"span{span}")
def visit(request, monkeypatch):
    """Key positions a visit of both kernels holds: one piece of 128 (the
    smallest there is: a cache of 1024 positions holds eight) or four."""
    keys = _visits_of(monkeypatch, request.param)
    monkeypatch.setattr(da, "_LATENT_BLOCK_BYTES", keys * 192 * 2)
    assert da.block_k(MAX_SEQ_LEN, 2, 128, jnp.bfloat16) == keys
    assert da.latent_block_k(MAX_SEQ_LEN, 192, jnp.bfloat16) == keys
    return keys


@pytest.fixture
def chunks_of_128(monkeypatch):
    """One piece a visit in both kernels."""
    _visits_of(monkeypatch, 1)
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
@pytest.mark.parametrize("span", SPANS)
def test_the_walk_visits_a_steps_live_chunks_and_nothing_else(span, step):
    """``sum(cdiv(max(length, 1), chunk))`` visits of ``span`` pieces, rows
    in order, each row's chunks in order and its last one marked; every
    visit's copy is started once, ahead of it, into a buffer nobody is
    still using."""
    lengths = STEPS[step]
    keys = span * CHUNK
    events = _walked(lengths, keys)
    chunks = [-(-max(n, 1) // keys) for n in lengths]
    visited = [e[1:] for e in events if e[0] == "visit"]
    assert len(visited) == sum(chunks) == int(
        da.visits(np.asarray(lengths), keys))
    # a row's last visit is copied whole: a piece at most past a row of one
    # piece a visit, ``span`` pieces less one key at most past any
    over = [n * keys - max(length, 1) for n, length in zip(chunks, lengths)]
    assert all(0 <= o < keys for o in over)
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
def test_a_steps_rows_match_the_einsum(kernel, step, visit):
    """Each kernel against its model's own einsum over the same step, at
    one piece a visit and at four; what lies past a row's length, NaN here,
    is masked, in the pieces of a visit that hold no key too."""
    lengths = STEPS[step]
    if kernel == "gqa":
        got, want = _case(4, 128, jnp.bfloat16, lengths, MAX_SEQ_LEN)
        assert da.traced_chunk((len(lengths), 2, MAX_SEQ_LEN, 128)) == visit
        steps = 1
    else:
        got, want = _latent_case(lengths, MAX_SEQ_LEN)
        assert da.traced_chunk((len(lengths), 1, MAX_SEQ_LEN, 128)) == visit
        steps = 2  # the model's einsum rounds its probabilities to bf16
    assert _close(got, want, lengths, jnp.bfloat16, steps)


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


def _kernel_dots(jaxpr, found):
    """Operand shapes of every ``dot_general`` under ``jaxpr``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(tuple(v.aval.shape for v in eqn.invars))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_dots(sub, found)
    return found


@pytest.mark.parametrize("kv_heads, keys", [(8, 128), (4, 512), (2, 1024)])
def test_a_visit_copies_its_bytes_and_multiplies_a_piece_at_a_time(kv_heads, keys):
    """What a visit copies and what a matmul contracts are two things: the
    buffers hold the visit's ``keys`` positions of every K/V head, and every
    q.k and p.v of the kernel is over one 128-key piece of one head (a
    contraction over 256 keys was slower than either neighbour on the chip:
    PERF.md, PR 48 and PR 62)."""
    b, group, d, s = 4, 4, 128, 2048
    kv = jnp.zeros((b, kv_heads, s, d), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda *a: da.decode_attention(*a))(
        jnp.zeros((b, kv_heads * group, d), jnp.bfloat16), kv, kv,
        jnp.ones((b,), jnp.int32)).jaxpr
    assert da.traced_chunk(kv.shape) == keys

    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    (call,) = calls(jaxpr)
    buffers = [v.aval.shape for v in call.params["jaxpr"].invars
               if v.aval.shape[:1] == (da._SLOTS,)]
    assert buffers == [(da._SLOTS, kv_heads, keys, d)] * 2
    dots = _kernel_dots(call.params["jaxpr"], [])
    rows = da._GROUP_ROWS
    assert sorted(dots) == sorted(
        [((rows, d), (PIECE, d)), ((rows, PIECE), (PIECE, d))]
        * (kv_heads * keys // PIECE))


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


@pytest.mark.parametrize("span", [1, 2])
def test_the_engine_counts_the_chunks_its_steps_visit(span, monkeypatch):
    """``attention_chunks()`` from the host's own row positions: a lone
    request on eight slots of 256 positions in visits of 128 keys visits
    one chunk a row until its row passes 128 keys and nine a step from
    there, where a grid of rows x the whole cache has sixteen a step; in
    visits of two pieces every row is one visit, and a step copies all
    2048 positions of the cache for the 12 to 152 that hold a key."""
    from ray_tpu.llm.engine import ContinuousBatchingEngine, GenerationRequest
    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.parallel.sharding import unbox_params

    keys = _visits_of(monkeypatch, span)
    cfg = LlamaConfig.tiny(n_layers=1, n_heads=4, n_kv_heads=2, max_seq_len=256)
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
    engine = ContinuousBatchingEngine(cfg, params, num_slots=8)
    assert engine.attention_chunks() == {
        "attention_chunks_visited": 0, "attention_chunks_dense": 0,
        "attention_positions_copied": 0}
    engine.add_request(GenerationRequest(
        token_ids=[7, 8, 9, 10, 11], max_new_tokens=140))
    engine.run_until_complete()
    counted = engine.attention_chunks()
    steps, rest = divmod(counted["attention_chunks_dense"], 8 * 256 // keys)
    assert rest == 0 and steps == engine._step_count >= 139
    # the step that feeds token n attends 5 + n keys: past 128 from n = 124
    long_steps = counted["attention_chunks_visited"] - 8 * steps
    assert long_steps == (steps - 123 if span == 1 else 0)
    # a visit copies whole: 128 (256) positions for a free row's one key
    assert counted["attention_positions_copied"] == keys * counted[
        "attention_chunks_visited"]
    live = sum(7 + 5 + n for n in range(steps))  # seven free rows, one live
    assert live < counted["attention_positions_copied"] <= steps * 8 * 256
