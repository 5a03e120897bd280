"""The decode programs donate the cache they advance (ROADMAP S1).

``_decode`` takes a cache and returns its successor in the same buffers
(``_insert_row`` the pool's). A use of a donated buffer raises ("Array
has been deleted"), so every path that hands a row or a pool to it
must hold no second reference that outlives the call. These cases
walk each such path on the CPU (which donates like the chip does) and
hold its tokens to a greedy loop over *undonating* programs built here:
they fail if a donated buffer is ever reused, and if a result changes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.kvcache import KVCacheManager
from ray_tpu.llm import GenerationRequest
from ray_tpu.llm.engine import ContinuousBatchingEngine, _DecodeModelBase
from ray_tpu.models.llama import LlamaConfig, init_params
from ray_tpu.parallel.sharding import unbox_params

BS = 8  # KV block size of every paged engine here
N_NEW = 6


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig.tiny(max_seq_len=64)
    return cfg, unbox_params(init_params(cfg, jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def reference(tiny):
    """Greedy tokens from the engine's own two functions under plain
    ``jax.jit``: no donation, one request at a time."""
    cfg, params = tiny
    model = _DecodeModelBase(cfg, params)
    prefill = jax.jit(model._prefill_impl)
    decode = jax.jit(model._decode_impl)
    memo = {}

    def tokens(prompt, n=N_NEW):
        key = (tuple(prompt), n)
        if key not in memo:
            logits, cache = prefill(params, jnp.asarray([prompt], jnp.int32))
            out, kept = [], []
            for _ in range(n):
                out.append(int(jnp.argmax(logits[0])))
                kept.append(cache)  # every cache stays usable: no donation
                logits, cache = decode(
                    params, cache, jnp.asarray([[out[-1]]], jnp.int32)
                )
            assert not any(
                leaf.is_deleted() for c in kept for leaf in jax.tree.leaves(c)
            )
            memo[key] = out
        return memo[key]

    return tokens


def _prompt(seed, n):
    return [int(t) for t in np.random.RandomState(seed).randint(1, 250, n)]


def _engine(tiny, *, paged=True, chunk=0, num_slots=2):
    cfg, params = tiny
    kv = KVCacheManager(num_blocks=48, block_size=BS) if paged else None
    eng = ContinuousBatchingEngine(
        cfg, params, num_slots=num_slots, kv_cache=kv, seed=0,
        prefill_chunk_tokens=chunk,
    )
    return eng, kv


def _run(eng, prompt, n=N_NEW):
    return eng.generate(
        [GenerationRequest(token_ids=prompt, max_new_tokens=n)]
    )[0].token_ids


def _two_chunked_prefills_without_a_prefix(tiny, reference, paged):
    # each starts from _empty_row(): the row the first one's _decode
    # consumed must not be the row the second one is handed
    eng, _ = _engine(tiny, paged=paged, chunk=8)
    a, b = _prompt(1, 21), _prompt(2, 19)
    assert _run(eng, a) == reference(a)
    assert _run(eng, b) == reference(b)
    # and two in flight at once, in one step's budget loop
    c, d = _prompt(3, 18), _prompt(4, 23)
    out = eng.generate([
        GenerationRequest(token_ids=p, max_new_tokens=N_NEW) for p in (c, d)
    ])
    assert [r.token_ids for r in out] == [reference(c), reference(d)]


def _chunked_prefill_with_partial_commits(tiny, reference, paged=True):
    # budget = one block a step: after every chunk but the last the row's
    # new full block is committed from st["row"], and the next step's
    # _decode donates that same row
    eng, kv = _engine(tiny, chunk=BS)
    a = _prompt(5, 4 * BS + 3)
    rid = eng.add_request(GenerationRequest(token_ids=a, max_new_tokens=N_NEW))
    eng.step()
    eng.step()
    assert eng._prefilling and kv.stats()["blocks_in_use"] >= 2
    assert eng.run_until_complete()[rid].token_ids == reference(a)
    # a second prompt over the first's blocks: assemble() seeds the
    # chunked row, which is then donated chunk by chunk
    b = a[: 2 * BS] + _prompt(6, 2 * BS + 1)
    assert _run(eng, b) == reference(b)
    assert kv.stats()["prefix_hit_tokens"] >= 2 * BS


def _prefix_hit_then_retirement_commit(tiny, reference, paged=True):
    eng, kv = _engine(tiny)
    a = _prompt(7, 2 * BS + 4)
    # retiring commits the decode tail from _extract_row(self._cache)
    assert _run(eng, a, 2 * BS) == reference(a, 2 * BS)
    # _prefill_leased: assemble the cached blocks, decode the suffix into
    # the assembled row; then the retirement commit reads the pool again
    b = a[: 2 * BS] + _prompt(8, BS + 5)
    assert _run(eng, b, 2 * BS) == reference(b, 2 * BS)
    assert kv.stats()["prefix_hit_tokens"] >= 2 * BS
    # the whole of a's sequence is cached now (prompt and tail)
    c = a + reference(a, 2 * BS)[:BS]
    assert _run(eng, c) == reference(c)
    assert kv.stats()["prefix_hit_tokens"] >= 2 * BS + 3 * BS


def _generate_and_stream_match_the_undonated_loop(tiny, reference, paged):
    if paged is None:  # a pool of one dense row: the batch is the request
        eng, _ = _engine(tiny, paged=False, num_slots=1)
    else:
        eng, _ = _engine(tiny, paged=paged)
    a = _prompt(9, 11)
    req = GenerationRequest(token_ids=a, max_new_tokens=N_NEW)
    batch = eng.generate([req])[0].token_ids
    *streamed, final = eng.generate_stream(req)
    assert batch == streamed == final.token_ids == reference(a)


@pytest.mark.parametrize(
    "scenario,paged",
    [
        (_two_chunked_prefills_without_a_prefix, False),
        (_two_chunked_prefills_without_a_prefix, True),
        (_chunked_prefill_with_partial_commits, True),
        (_prefix_hit_then_retirement_commit, True),
        (_generate_and_stream_match_the_undonated_loop, None),
        (_generate_and_stream_match_the_undonated_loop, False),
        (_generate_and_stream_match_the_undonated_loop, True),
    ],
    ids=lambda v: v.__name__.strip("_") if callable(v)
    else {None: "one_slot", False: "dense", True: "paged"}[v],
)
def test_no_donated_buffer_is_reused(tiny, reference, scenario, paged):
    scenario(tiny, reference, paged)


def test_a_step_consumes_the_cache_it_was_given(tiny):
    """Donation happened: what was ``engine._cache`` before a step is
    deleted after it, and ``engine._cache`` is a new, live tree (so is a
    chunked prefill's row)."""
    eng, _ = _engine(tiny, chunk=BS)
    eng.add_request(GenerationRequest(
        token_ids=_prompt(13, BS + 2), max_new_tokens=4
    ))
    eng.add_request(GenerationRequest(
        token_ids=_prompt(14, 3 * BS), max_new_tokens=4
    ))
    eng.step()  # a chunk each: both rows parked
    rows = [st["row"] for st in eng._prefilling.values()]
    assert len(rows) == 2
    eng.step()  # the short one is admitted and decodes; the long one chunks
    assert all(
        leaf.is_deleted() for r in rows for leaf in jax.tree.leaves(r)
    )
    assert eng._slots and eng._prefilling
    before = eng._cache
    eng.step()
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(before))
    after = jax.tree.leaves(eng._cache)
    assert not any(leaf.is_deleted() for leaf in after)
    assert all(np.isfinite(np.asarray(leaf, np.float32)).all()
               for leaf in after)
    assert all(len(r.token_ids) == 4
               for r in eng.run_until_complete().values())


def test_empty_row_is_fresh_each_call(tiny):
    """The chunked-prefill seed: shapes memoised, buffers not."""
    eng, _ = _engine(tiny, paged=False, chunk=8)
    a, b = eng._empty_row(), eng._empty_row()
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x is not y
        assert x.unsafe_buffer_pointer() != y.unsafe_buffer_pointer()
        assert not np.asarray(x, np.float32).any()
