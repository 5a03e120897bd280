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
    # tiny models: the whole cache is one block of its own length
    assert da.block_k(64, 4, 32, jnp.bfloat16) == 64
    got, want = _case(2, 32, jnp.bfloat16, [1, 17, 64], 64)
    assert np.abs(got - want).max() <= _one_step(want, jnp.bfloat16)
    # 320 positions in blocks of 128: the last block hangs over the end
    monkeypatch.setattr(da, "_BLOCK_BYTES", 0)
    assert da.block_k(320, 2, 128, jnp.bfloat16) == 128
    got, want = _case(4, 128, jnp.bfloat16, [320, 257, 256, 3], 320)
    assert not np.isnan(got).any()
    assert np.abs(got - want).max() <= _one_step(want, jnp.bfloat16)


def test_block_size_follows_the_cache_not_an_option():
    # the chat cells (8 KV heads of 128, bf16): 512 keys, 1 MB of K a step
    assert da.block_k(4096, 8, 128, jnp.bfloat16) == 512
    # Llama-2 widths, 32 KV heads: never under the 128 lanes of the scores
    assert da.block_k(2048, 32, 128, jnp.bfloat16) == 128
    # a tp=4 shard of the chat cells' cache holds 2 KV heads
    assert da.block_k(4096, 2, 128, jnp.bfloat16) == 2048


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
# the engine: grouped-query decode, and free rows
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gqa():
    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.parallel.sharding import unbox_params

    cfg = LlamaConfig.tiny(n_layers=1, n_heads=4, n_kv_heads=2, max_seq_len=64)
    return cfg, unbox_params(init_params(cfg, jax.random.PRNGKey(0)))


def _greedy_reference(cfg, params, prompt, n_new):
    """Greedy decoding by whole forward passes of the training model."""
    from ray_tpu.models.llama import Llama

    model, toks = Llama(cfg, None), list(prompt)
    for _ in range(n_new):
        logits = model.apply({"params": params}, jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


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
    assert want[:6] == _greedy_reference(cfg, params, lone.token_ids, 6)

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
