"""Chunked prefill (PR 19).

It must be invisible in the tokens: temperature-0 parity pins the chunked
prefill scheduler against the model's greedy trajectory token-for-token;
the no-stall test pins the actual scheduling claim — in-flight decodes keep
emitting while a long prompt prefills in chunks; block accounting pins
leak-freedom (what a batch, chunked or not, or a ``prefill_only`` leases is
back in the pool once it is through).

Kept OUT of @pytest.mark.slow deliberately: temp-0 parity is a tier-1
gate. Engines are module-scoped fixtures — jit programs compile once per
engine instance, so sharing the instance across tests is what keeps this
file tier-1-affordable.
"""

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.kvcache import KVCacheManager
from ray_tpu.llm import GenerationRequest, LLMConfig
from ray_tpu.llm.engine import ContinuousBatchingEngine
from ray_tpu.models.llama import Llama, LlamaConfig, init_params
from ray_tpu.parallel.sharding import unbox_params


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig.tiny(max_seq_len=128)
    return cfg, unbox_params(init_params(cfg, jax.random.PRNGKey(0)))


def _engine(cfg, params, *, chunk=0, tier=None):
    kv = KVCacheManager(num_blocks=64, block_size=8)
    eng = ContinuousBatchingEngine(
        cfg, params, num_slots=4, kv_cache=kv, seed=0,
        prefill_chunk_tokens=chunk, kv_tier=tier,
    )
    return eng, kv


@pytest.fixture(scope="module")
def chunked(tiny):
    return _engine(*tiny, chunk=8)


def _assert_greedy_trajectory(cfg, params, prompt, generated):
    """Assert ``generated`` is the model's greedy continuation of
    ``prompt``: ONE teacher-forced apply over prompt+generated, then
    check each generated token is the argmax at its predecessor
    position. Equivalent to regenerating the greedy trajectory (by
    induction on the matching prefix) at 1/n the eager-apply cost."""
    model = Llama(cfg, None)
    seq = list(prompt) + list(generated)
    logits = model.apply({"params": params}, jnp.asarray([seq], jnp.int32))
    preds = [int(t) for t in jnp.argmax(logits[0], axis=-1)]
    for i, tok in enumerate(generated):
        assert tok == preds[len(prompt) - 1 + i], f"diverged at {i}"


class TestChunkedPrefill:
    def test_chunked_matches_unchunked(self, tiny, chunked):
        cfg, params = tiny
        prompt = list(range(1, 41))  # 40 tokens, budget 8/step
        eng, _ = chunked
        rid = eng.add_request(
            GenerationRequest(token_ids=prompt, max_new_tokens=8)
        )
        out = eng.run_until_complete()
        assert len(out[rid].token_ids) == 8
        _assert_greedy_trajectory(cfg, params, prompt, out[rid].token_ids)

    def test_chunked_prefill_with_prefix_hit(self, chunked):
        """A second request sharing a cached prefix still prefills only
        the suffix under a chunk budget — and stays token-identical."""
        from ray_tpu.util.metrics import kvcache_counters

        eng, kv = chunked
        prompt = [2] * 24
        r1 = eng.add_request(
            GenerationRequest(token_ids=prompt, max_new_tokens=4)
        )
        out1 = eng.run_until_complete()
        before = kvcache_counters()["prefix_hit_tokens"]
        r2 = eng.add_request(
            GenerationRequest(token_ids=prompt, max_new_tokens=4)
        )
        out2 = eng.run_until_complete()
        assert out2[r2].token_ids == out1[r1].token_ids
        assert kvcache_counters()["prefix_hit_tokens"] > before

    def test_decodes_do_not_stall_behind_long_prompt(self, chunked):
        """The scheduling claim itself: while a long prompt advances
        chunk-by-chunk, the in-flight short request emits one token EVERY
        step — no step gaps. Reuses the module engine (a fresh one would
        recompile every decode width this file already paid for)."""
        eng, _ = chunked
        short = eng.add_request(
            GenerationRequest(token_ids=[1] * 8, max_new_tokens=30)
        )
        eng.step()  # short admitted + first token
        long_prompt = list(range(80))
        eng.add_request(
            GenerationRequest(token_ids=long_prompt, max_new_tokens=4)
        )
        slot = next(iter(eng._slots.values()))
        assert slot.request_id == short
        prefilling_steps = 0
        for _ in range(60):
            before = len(slot.generated)
            eng.step()
            if eng._prefilling:
                # a long prefill is mid-flight AND the decode advanced
                prefilling_steps += 1
                assert len(slot.generated) == before + 1
                assert eng.last_step_prefill_tokens <= 8
            if eng.num_active == 0:
                break
        # 80 tokens / budget 8 => the long prompt was parked ~10 steps
        assert prefilling_steps >= 9
        assert eng.num_active == 0


def _no_lease_open(kv):
    """Every block in use is the index's alone: one reference each."""
    held = [b for b in range(kv.capacity) if kv._alloc.refcount(b)]
    assert len(held) == kv.blocks_in_use == kv.stats()["index_nodes"]
    assert all(kv._alloc.refcount(b) == 1 for b in held)


@pytest.mark.parametrize("chunk", [0, 8], ids=["unchunked", "chunked"])
def test_the_pool_is_whole_again_after_a_shared_prefix_batch(tiny, chunk):
    """What a batch leased is back when it has retired: free + cached
    blocks are the pool, every cached block is held by the index alone,
    and a second batch over the same prefix (hits, whose leases pin what
    the first one committed) leaves the same count behind."""
    eng, kv = _engine(*tiny, chunk=chunk)
    shared = list(range(1, 25))  # three full blocks
    batch = [
        GenerationRequest(token_ids=shared + [40 + i, 50 + i],
                          max_new_tokens=7 + i)
        for i in range(5)  # one more than the slots
    ]
    free_before = kv.stats()["blocks_free"]
    assert free_before == kv.capacity and kv.blocks_in_use == 0
    for round_ in range(2):
        for req in batch:
            eng.add_request(req)
        out = eng.run_until_complete()
        assert len(out) == len(batch) and eng.num_active == 0
        _no_lease_open(kv)
        assert kv.stats()["blocks_free"] + kv.blocks_in_use == kv.capacity
        if round_ == 0:
            cached = kv.blocks_in_use
            assert cached >= 3  # the shared prefix, at the least
    assert kv.blocks_in_use == cached
    assert kv.stats()["prefix_hit_tokens"] > 0


@pytest.mark.parametrize("exported", [False, True],
                         ids=["cold", "behind_an_export"])
def test_prefill_only_leaves_no_lease_open(tiny, exported):
    """The prefill role's lease ends with the call, whether it computed the
    whole prompt or sat behind a prefix that a fused admission had already
    exported to the tier (a hit: the lease pins committed blocks)."""
    from ray_tpu.kvtier import KVTierClient, LocalTierBackend

    tier = KVTierClient(
        model="tiny", backend=LocalTierBackend(), block_size=8,
        holder_id="prefill",
    )
    eng, kv = _engine(*tiny, tier=tier)
    prompt = list(range(60, 87))  # three full blocks and a tail
    if exported:
        assert tier.should_export(prompt, 3)
        eng.add_request(GenerationRequest(token_ids=prompt, max_new_tokens=3))
        eng.run_until_complete()
        assert not tier.should_export(prompt, 3)  # the admission's export
        _no_lease_open(kv)
    before = kv.stats()
    shipment = eng.prefill_only(
        GenerationRequest(token_ids=prompt + [90, 91], max_new_tokens=4))
    assert shipment is not None and shipment.ntokens == len(prompt) + 2
    after = kv.stats()
    hit = after["prefix_hit_tokens"] - before["prefix_hit_tokens"]
    assert hit == (24 if exported else 0)
    _no_lease_open(kv)
    assert after["blocks_free"] + after["blocks_in_use"] == kv.capacity
    assert eng.num_active == 0 and not eng._unread


def test_chunking_needs_no_pool():
    cfg = LLMConfig(prefill_chunk_tokens=256)
    assert cfg.kv_cache_blocks is None
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        LLMConfig(prefill_chunk_tokens=-1)


class TestLongPrefillMixWorkload:
    def test_trace_classes_and_summary_itl(self):
        from ray_tpu.loadgen import (
            CallableTarget,
            LoadGenerator,
            long_prefill_mix,
        )

        trace = long_prefill_mix(
            40, rps=400.0, long_prompt_tokens=256,
            short_prompt_tokens=16, seed=3,
        )
        names = {r.cls for r in trace.requests}
        assert names == {"short_decode", "long_prefill"}
        longs = [r for r in trace.requests if r.cls == "long_prefill"]
        assert longs and all(len(r.token_ids) == 256 for r in longs)

        def fake_stream(payload):
            for _ in range(3):
                yield 0

        gen = LoadGenerator(CallableTarget(fake_stream), max_inflight=8)
        result = gen.run(trace, time_scale=0.01)
        summary = result.summary()
        assert set(summary["classes"]) == names
        sd = summary["classes"]["short_decode"]
        assert "itl_p99_ms" in sd  # streamed gaps landed per class
        assert all(len(r.itl_s) == 2 for r in result.ok)
