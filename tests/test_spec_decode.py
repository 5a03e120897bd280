"""Speculative decoding + chunked prefill (PR 19).

The two engine-loop optimizations share one correctness bar: they must be
invisible in the tokens. Temperature-0 parity pins the speculative verify
pass (accept = argmax match) and the chunked prefill scheduler against
the dense engine's greedy trajectory token-for-token; block accounting
pins rollback leak-freedom (a rejected proposal must not strand COW
blocks); the no-stall test pins the actual scheduling claim — in-flight
decodes keep emitting while a long prompt prefills in chunks.

Kept OUT of @pytest.mark.slow deliberately: temp-0 parity is the tier-1
gate the ISSUE names. Engines are module-scoped fixtures — jit programs
compile once per engine instance, so sharing the instance across tests
is what keeps this file tier-1-affordable.
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
def tiny_pair():
    """Target + two 1-layer drafts over the same vocab. The target's
    second layer is zeroed to an exact identity (wo / w_down kernels = 0
    leave the residual stream untouched), so ``dsame`` — the surviving
    layer packaged as a 1-layer model — is mathematically the target:
    acceptance 1.0 by construction. ``drand`` is a different random
    model: acceptance ~0, every step exercises rejection/rollback. One
    engine + ``swap_params`` serves both regimes, halving this file's
    dominant cost (jit compiles are per engine instance)."""
    cfg = LlamaConfig.tiny(max_seq_len=128)
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
    z = jnp.zeros_like
    l1 = params["layer_1"]
    l1["attn"]["wo"]["base"]["kernel"] = z(l1["attn"]["wo"]["base"]["kernel"])
    l1["mlp"]["w_down"]["kernel"] = z(l1["mlp"]["w_down"]["kernel"])
    dcfg = LlamaConfig.tiny(max_seq_len=128, n_layers=1)
    drand = unbox_params(init_params(dcfg, jax.random.PRNGKey(1)))
    dsame = {k: params[k] for k in ("embed", "final_norm", "layer_0",
                                    "lm_head")}
    return cfg, params, dcfg, drand, dsame


def _engine(cfg, params, *, draft=None, k=0, chunk=0, num_blocks=64,
            num_slots=4):
    kv = KVCacheManager(num_blocks=num_blocks, block_size=8)
    eng = ContinuousBatchingEngine(
        cfg, params, num_slots=num_slots, kv_cache=kv, seed=0,
        draft=draft, spec_tokens=k, prefill_chunk_tokens=chunk,
    )
    return eng, kv


@pytest.fixture(scope="module")
def spec(tiny_pair):
    """The one speculative engine; tests swap the draft's params between
    ``drand`` (rejection-heavy) and ``dsame`` (acceptance 1.0)."""
    cfg, params, dcfg, drand, _ = tiny_pair
    return _engine(cfg, params, draft=(dcfg, drand), k=4)


@pytest.fixture(scope="module")
def chunked(tiny_pair):
    cfg, params, _, _, _ = tiny_pair
    return _engine(cfg, params, chunk=8)


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


# ONE prompt length (each distinct length costs a prefill compile for
# target AND draft — the dominant cost of this file); decode tails long
# enough to cross block boundaries cover the block-crossing paths
PROMPTS = [[1, 2, 3, 4, 5, 6, 7]]


class TestSpecParity:
    def test_spec_matches_dense_low_acceptance(self, tiny_pair, spec):
        """Random draft: ~every proposal rejected, so the emitted stream
        is built almost entirely from correction tokens + rollbacks — and
        must still equal the dense greedy trajectory exactly."""
        cfg, params, _, drand, _ = tiny_pair
        eng, _ = spec
        eng._draft.swap_params(drand)
        rids = [
            eng.add_request(
                GenerationRequest(token_ids=p, max_new_tokens=10)
            )
            for p in PROMPTS
        ]
        out = eng.run_until_complete()
        for rid, p in zip(rids, PROMPTS):
            assert len(out[rid].token_ids) == 10
            _assert_greedy_trajectory(cfg, params, p, out[rid].token_ids)

    def test_spec_matches_dense_full_acceptance(self, tiny_pair, spec):
        """Draft == target (the identity-layer construction): every
        proposal accepted — acceptance 1.0, the k+1-tokens-per-step fast
        path — and the same parity bar."""
        cfg, params, _, _, dsame = tiny_pair
        eng, _ = spec
        eng._draft.swap_params(dsame)
        prompt = [5, 4, 3, 2, 1, 6, 7]
        rid = eng.add_request(
            GenerationRequest(token_ids=prompt, max_new_tokens=12)
        )
        out = eng.run_until_complete()
        assert len(out[rid].token_ids) == 12
        _assert_greedy_trajectory(cfg, params, prompt, out[rid].token_ids)

    def test_spec_acceptance_metrics_move(self, tiny_pair, spec):
        from ray_tpu.util.metrics import llm_counters

        _, _, _, _, dsame = tiny_pair
        eng, _ = spec
        eng._draft.swap_params(dsame)
        before = llm_counters()
        # 7-token prompt reuses the fixture's already-compiled prefill
        eng.add_request(
            GenerationRequest(token_ids=[2, 5, 2, 5, 2, 5, 2],
                              max_new_tokens=8)
        )
        eng.run_until_complete()
        after = llm_counters()
        proposed = (
            after["spec_proposed_tokens"] - before["spec_proposed_tokens"]
        )
        accepted = (
            after["spec_accepted_tokens"] - before["spec_accepted_tokens"]
        )
        assert proposed > 0
        # identical draft: (almost) everything proposed is accepted
        assert accepted / proposed > 0.8
        assert after["itl_observations"] > before["itl_observations"]

    def test_spec_temperature_smoke(self, tiny_pair, spec):
        """temp>0 rides the rejection-sampling branch: emitted ids must be
        in-vocab and the request must complete (distribution equality is
        a statistical property; the deterministic bar is temp-0 parity)."""
        cfg, _, _, drand, _ = tiny_pair
        eng, _ = spec
        eng._draft.swap_params(drand)
        rid = eng.add_request(
            GenerationRequest(
                token_ids=[7, 6, 5, 4, 3, 2, 1], max_new_tokens=10,
                temperature=0.9,
            )
        )
        out = eng.run_until_complete()
        assert len(out[rid].token_ids) == 10
        assert all(0 <= t < cfg.vocab_size for t in out[rid].token_ids)

    def test_spec_headroom_guard(self, spec):
        eng, _ = spec
        with pytest.raises(ValueError, match="spec_tokens"):
            eng.add_request(
                GenerationRequest(token_ids=[1] * 100, max_new_tokens=26)
            )


class TestRollbackLeakFreedom:
    def test_blocks_return_to_baseline_after_rejections(self, tiny_pair,
                                                         spec):
        """Every block the radix index holds is accounted for after a
        rejection-heavy run retires all requests: in_use == index nodes
        (no stranded lease refs from speculative lease extension)."""
        _, _, _, drand, _ = tiny_pair
        eng, kv = spec
        eng._draft.swap_params(drand)
        for p in PROMPTS:
            eng.add_request(
                GenerationRequest(token_ids=p, max_new_tokens=16)
            )
        eng.run_until_complete()
        assert eng.num_active == 0
        assert kv.blocks_in_use == kv.stats()["index_nodes"]

    def test_extend_release_accounting(self):
        kv = KVCacheManager(num_blocks=16, block_size=8)
        lease = kv.acquire([1] * 17)  # 2 full blocks reserved
        base = kv.blocks_in_use
        got = kv.extend(lease, 3)
        assert got == 3
        assert kv.blocks_in_use == base + 3
        kv.release(lease)
        assert kv.blocks_in_use == 0
        # closed lease: extension refuses instead of leaking
        assert kv.extend(lease, 2) == 0


class TestChunkedPrefill:
    def test_chunked_matches_unchunked(self, tiny_pair, chunked):
        cfg, params, _, _, _ = tiny_pair
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


class TestConfigKnobs:
    def test_spec_needs_draft(self):
        with pytest.raises(ValueError, match="draft_model"):
            LLMConfig(spec_tokens=4, kv_cache_blocks=32)

    def test_draft_defaults_spec_tokens(self):
        cfg = LLMConfig(draft_model="llama-tiny", kv_cache_blocks=32)
        assert cfg.spec_tokens == 4
        assert cfg.build_draft_model_config().max_seq_len == cfg.max_seq_len

    def test_spec_and_chunking_need_no_pool(self):
        cfg = LLMConfig(draft_model="llama-tiny", prefill_chunk_tokens=256)
        assert cfg.kv_cache_blocks is None and cfg.spec_tokens == 4
        with pytest.raises(ValueError, match="prefill_chunk_tokens"):
            LLMConfig(prefill_chunk_tokens=-1)

    def test_draft_max_seq_len_must_cover_target(self, tiny_pair):
        cfg, params, _, _, _ = tiny_pair
        dcfg = LlamaConfig.tiny(max_seq_len=64, n_layers=1)
        dparams = unbox_params(init_params(dcfg, jax.random.PRNGKey(1)))
        with pytest.raises(ValueError, match="max_seq_len"):
            _engine(cfg, params, draft=(dcfg, dparams), k=4)


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
