"""ray_tpu.llm tests.

Models the reference's llm test surface (python/ray/llm/tests/): engine
generation correctness (the KV-cache decode path must match the full
forward pass token-for-token under greedy decoding), serve deployment
round trip, and the batch-inference stage.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import greedy_reference

from ray_tpu.kvcache import KVCacheManager
from ray_tpu.llm import (
    ContinuousBatchingEngine,
    GenerationRequest,
    LLMConfig,
    LLMPredictor,
    build_llm_deployment,
)
from ray_tpu.models.llama import LlamaConfig, init_params
from ray_tpu.parallel.sharding import unbox_params


def _with_engine(**model_kwargs):
    """(cfg, params, a four-slot engine with no block pool)."""
    cfg = LlamaConfig.tiny(max_seq_len=64, **model_kwargs)
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
    engine = ContinuousBatchingEngine(cfg, params, num_slots=4)
    yield cfg, params, engine
    engine.close()


@pytest.fixture(scope="module")
def tiny_engine():
    yield from _with_engine()


@pytest.fixture(scope="module")
def gqa_engine():
    """Two query heads a KV head: the decode kernel's grouped path."""
    yield from _with_engine(n_heads=4, n_kv_heads=2)


@pytest.mark.parametrize(
    "num_slots,block,prompt",
    [
        (1, None, [3, 14, 15, 92, 65, 35]),
        # a pool of 4-token blocks: the prompt is two blocks and a tail,
        # and the decode crosses into a fourth
        (4, 4, [3, 14, 15, 92, 65, 35, 89, 79, 32]),
    ],
    ids=["one_dense_slot", "four_paged_slots_prompt_over_a_block"],
)
@pytest.mark.parametrize("engine_fixture", ["tiny_engine", "gqa_engine"])
def test_cache_decode_matches_full_forward(
    engine_fixture, num_slots, block, prompt, request
):
    """The KV-cache decode path against whole forward passes, token for
    token: a lone dense row through the stepping thread, and a row over
    pool blocks stepped by the caller."""
    cfg, params, _ = request.getfixturevalue(engine_fixture)
    n_new = 8
    ref = greedy_reference(cfg, params, prompt, n_new)
    kv = KVCacheManager(num_blocks=16, block_size=block) if block else None
    engine = ContinuousBatchingEngine(
        cfg, params, num_slots=num_slots, kv_cache=kv
    )
    req = GenerationRequest(token_ids=prompt, max_new_tokens=n_new)
    if kv is None:
        out = engine.generate([req])[0]
        engine.close()
    else:
        rid = engine.add_request(req)
        out = engine.run_until_complete()[rid]
    assert out.token_ids == ref
    assert out.num_prompt_tokens == len(prompt)
    assert out.finished_reason == "length"


def test_batched_same_length_prompts(tiny_engine):
    cfg, params, engine = tiny_engine
    prompts = [[1, 2, 3, 4], [9, 8, 7, 6], [5, 5, 5, 5]]
    outs = engine.generate(
        [GenerationRequest(token_ids=p, max_new_tokens=5) for p in prompts]
    )
    for p, o in zip(prompts, outs):
        assert o.token_ids == greedy_reference(cfg, params, p, 5)


def test_mixed_length_prompts_in_one_batch(tiny_engine):
    cfg, params, engine = tiny_engine
    prompts = [[1, 2], [3, 4, 5, 6], [7, 8], [9, 10, 11, 12]]
    outs = engine.generate(
        [GenerationRequest(token_ids=p, max_new_tokens=4) for p in prompts]
    )
    for p, o in zip(prompts, outs):
        assert o.token_ids == greedy_reference(cfg, params, p, 4)


def test_eos_stops_generation(tiny_engine):
    """A row that meets its EOS leaves the batch; its neighbour goes on."""
    cfg, params, engine = tiny_engine
    prompt, other = [3, 14, 15, 92], [9, 8, 7, 6, 5]
    ref = greedy_reference(cfg, params, prompt, 8)
    eos = ref[1]  # the second greedy token acts as EOS
    out, beside = engine.generate([
        GenerationRequest(token_ids=prompt, max_new_tokens=8,
                          eos_token_id=eos),
        GenerationRequest(token_ids=other, max_new_tokens=8),
    ])
    assert out.finished_reason == "eos"
    assert out.token_ids == ref[:ref.index(eos) + 1]
    assert beside.finished_reason == "length"
    assert beside.token_ids == greedy_reference(cfg, params, other, 8)


def test_temperature_sampling_changes_output(tiny_engine):
    _cfg, _params, engine = tiny_engine
    req = GenerationRequest(
        token_ids=[1, 2, 3, 4], max_new_tokens=16, temperature=5.0
    )
    a = engine.generate([req])[0].token_ids
    greedy = engine.generate(
        [GenerationRequest(token_ids=[1, 2, 3, 4], max_new_tokens=16)]
    )[0].token_ids
    # with very high temperature the trajectory should diverge from greedy
    assert a != greedy


def test_seq_len_guard(tiny_engine):
    _cfg, _params, engine = tiny_engine
    with pytest.raises(ValueError, match="max_seq_len"):
        engine.generate(
            [GenerationRequest(token_ids=[1] * 60, max_new_tokens=10)]
        )


@pytest.mark.slow
def test_llm_serve_deployment(ray_start_regular):
    from ray_tpu import serve

    llm_config = LLMConfig(
        model_id="llama-tiny",
        max_seq_len=64,
        max_new_tokens=4,
        resources_per_replica={"CPU": 1.0},
    )
    app = build_llm_deployment(llm_config)
    serve.start(proxy=False)
    handle = serve.run(app, name="llm-app", route_prefix=None, _proxy=False)
    try:
        resp = handle.remote({"token_ids": [1, 2, 3, 4], "max_new_tokens": 3})
        out = resp.result(timeout_s=120)
        assert len(out["token_ids"]) == 3
        assert out["finished_reason"] in ("length", "eos")
    finally:
        serve.shutdown()


@pytest.mark.slow
def test_llm_batch_stage(ray_start_regular):
    from ray_tpu import data as rd

    llm_config = LLMConfig(
        model_id="llama-tiny", max_seq_len=64, max_new_tokens=3
    )
    ds = rd.from_items(
        [{"token_ids": [i + 1, i + 2, i + 3]} for i in range(8)]
    )
    out = ds.map_batches(
        LLMPredictor,
        fn_constructor_args=(llm_config,),
        compute=rd.ActorPoolStrategy(size=1),
        batch_size=4,
    ).take_all()
    assert len(out) == 8
    assert all(len(r["generated"]) == 3 for r in out)


def test_batch_predictor_returns_the_reference_greedy_tokens():
    """``LLMPredictor`` steps the engine the replicas serve with: at
    temperature 0 a batch of mixed lengths, more rows than slots, gets the
    whole-forward greedy tokens, and closing it stops the thread."""
    llm_config = LLMConfig(
        model_id="llama-tiny", max_seq_len=64, max_new_tokens=5,
        max_batch_size=2,
    )
    predictor = LLMPredictor(llm_config)
    assert isinstance(predictor._engine, ContinuousBatchingEngine)
    assert predictor._engine._kv is None
    cfg = llm_config.build_model_config()
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
    prompts = [[1, 2, 3], [9, 8, 7, 6, 5, 4], [5], [31, 41, 59, 26]]
    out = predictor({"token_ids": prompts})
    assert out["generated"] == [
        greedy_reference(cfg, params, p, 5) for p in prompts
    ]
    thread = predictor._engine._stepper.thread
    assert thread.is_alive()
    predictor.close()
    assert not thread.is_alive()


class TestContinuousBatching:
    def test_interleaved_mixed_lengths(self, tiny_engine):
        """Different prompt lengths decode TOGETHER in one pool (the whole
        point of continuous batching)."""
        cfg, params, _ = tiny_engine
        engine = ContinuousBatchingEngine(cfg, params, num_slots=4)
        prompts = [[3, 14, 15], [92, 65, 35, 89, 79], [4], [31, 41]]
        refs = {
            engine.add_request(
                GenerationRequest(token_ids=p, max_new_tokens=6)
            ): greedy_reference(cfg, params, p, 6)
            for p in prompts
        }
        results = engine.run_until_complete()
        for rid, ref in refs.items():
            assert results[rid].token_ids == ref, rid

    def test_late_admission_into_freed_slot(self, tiny_engine):
        """More requests than slots: later requests admit as slots free."""
        cfg, params, _ = tiny_engine
        engine = ContinuousBatchingEngine(cfg, params, num_slots=2)
        prompts = [[3, 14], [92, 65, 35], [4, 5, 6, 7], [31]]
        refs = {
            engine.add_request(
                GenerationRequest(token_ids=p, max_new_tokens=4)
            ): greedy_reference(cfg, params, p, 4)
            for p in prompts
        }
        # step manually: at most 2 slots busy at once
        results = {}
        while engine.num_active:
            for rid, res in engine.step():
                results[rid] = res
            assert len(engine._slots) <= 2
        for rid, ref in refs.items():
            assert results[rid].token_ids == ref, rid

    def test_eos_frees_slot(self, tiny_engine):
        cfg, params, _ = tiny_engine
        engine = ContinuousBatchingEngine(cfg, params, num_slots=2)
        prompt = [3, 14, 15]
        ref = greedy_reference(cfg, params, prompt, 8)
        eos = ref[2]  # force eos at the 3rd generated token
        rid = engine.add_request(
            GenerationRequest(
                token_ids=prompt, max_new_tokens=8, eos_token_id=eos
            )
        )
        results = engine.run_until_complete()
        assert results[rid].finished_reason == "eos"
        assert results[rid].token_ids == ref[:3]


class TestAdmission:
    """Regression tests for the CB admission path (slot bookkeeping and
    queue discipline, with and without the memory gate)."""

    def test_pending_fifo_under_full_slots(self, tiny_engine):
        """More requests than slots: admission order == arrival order."""
        cfg, params, _ = tiny_engine
        engine = ContinuousBatchingEngine(cfg, params, num_slots=2)
        rids = [
            engine.add_request(
                GenerationRequest(token_ids=[i + 1, i + 2], max_new_tokens=6)
            )
            for i in range(5)
        ]
        admitted_order = []
        while engine.num_active:
            engine.step()
            for slot in engine._slots.values():
                if slot.request_id not in admitted_order:
                    admitted_order.append(slot.request_id)
        assert admitted_order == rids

    def test_slot_reuse_after_finish_at_admission(self, tiny_engine):
        """max_new_tokens=1 finishes AT admission: its slot must be handed
        to the next pending request in the same step, not leaked."""
        cfg, params, _ = tiny_engine
        engine = ContinuousBatchingEngine(cfg, params, num_slots=1)
        r1 = engine.add_request(
            GenerationRequest(token_ids=[3, 14], max_new_tokens=1)
        )
        r2 = engine.add_request(
            GenerationRequest(token_ids=[15, 92], max_new_tokens=3)
        )
        finished = dict(engine.step())
        assert r1 in finished and len(finished[r1].token_ids) == 1
        # r2 took the freed slot within the same admission pass
        assert {s.request_id for s in engine._slots.values()} == {r2}
        results = engine.run_until_complete()
        assert len(results[r2].token_ids) == 3

    def test_finish_at_admission_via_eos(self, tiny_engine):
        cfg, params, _ = tiny_engine
        engine = ContinuousBatchingEngine(cfg, params, num_slots=2)
        prompt = [3, 14, 15, 92]
        ref = greedy_reference(cfg, params, prompt, 1)
        rid = engine.add_request(
            GenerationRequest(
                token_ids=prompt, max_new_tokens=8, eos_token_id=ref[0]
            )
        )
        results = engine.run_until_complete()
        assert results[rid].finished_reason == "eos"
        assert results[rid].token_ids == ref[:1]
        assert not engine._slots

    def test_run_until_complete_leaks_nothing(self, tiny_engine):
        """After draining, every per-request structure must be empty (a
        serving loop runs forever; any residue is a leak)."""
        cfg, params, _ = tiny_engine
        engine = ContinuousBatchingEngine(cfg, params, num_slots=2)
        for i in range(6):
            engine.add_request(
                GenerationRequest(
                    token_ids=[i + 1, i + 2, i + 3],
                    max_new_tokens=1 + i % 3,
                )
            )
        results = engine.run_until_complete()
        assert len(results) == 6
        assert engine.num_active == 0
        assert not engine._slots
        assert not engine._pending
        assert not engine._sinks
        assert not engine._enqueue_ts

    def test_memory_gated_admission_preserves_fifo(self, tiny_engine):
        """With a KV pool too small for two prompts, the blocked request
        waits at the HEAD of the queue (no reordering, no crash) and
        admits after the holder retires."""
        cfg, params, _ = tiny_engine
        kv = KVCacheManager(num_blocks=2, block_size=16)
        engine = ContinuousBatchingEngine(
            cfg, params, num_slots=4, kv_cache=kv
        )
        rids = [
            engine.add_request(
                GenerationRequest(
                    token_ids=list(range(b, b + 33)), max_new_tokens=4
                )
            )
            for b in (1, 100, 200)
        ]
        engine.step()
        assert len(engine._slots) == 1  # only the first fit
        assert [entry[0] for entry in engine._pending] == rids[1:]
        results = engine.run_until_complete()
        assert set(results) == set(rids)
        assert kv.stats()["admission_blocked"] >= 1
        assert engine.num_active == 0


@pytest.mark.parametrize(
    "kwargs,refused",
    [
        (dict(prefill_chunk_tokens=4), False),
        (dict(adapters={"max_live": 2}), False),
        (dict(roles={"prefill": 1, "decode": 1}), True),
        (dict(kv_tier=True), True),
    ],
    ids=["chunked_prefill", "adapters", "roles", "kv_tier"],
)
def test_what_a_config_without_kv_cache_blocks_may_ask(kwargs, refused):
    """``kv_cache_blocks`` says how many blocks, not which engine: chunked
    prefill and adapters serve over dense rows, from a replica
    whose greedy tokens are the whole-forward ones; what ships KV blocks is
    refused, and the message names the pool."""
    from ray_tpu.llm.serving import _LLMReplica

    base = dict(model_id="llama-tiny", max_seq_len=64, max_batch_size=2, seed=0)
    if refused:
        with pytest.raises(ValueError, match="block pool: set kv_cache_blocks"):
            LLMConfig(**base, **kwargs)
        LLMConfig(**base, kv_cache_blocks=8, **kwargs)
        return
    config = LLMConfig(**base, **kwargs)
    assert config.kv_cache_blocks is None
    replica = _LLMReplica(config)
    try:
        assert replica._kv_cache is None and replica.kvcache_stats() is None
        prompt = [3, 14, 15, 92, 65, 35, 89, 79, 32]
        reply = replica({"token_ids": prompt, "max_new_tokens": 6})
        cfg = config.build_model_config()
        params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
        assert reply["token_ids"] == greedy_reference(cfg, params, prompt, 6)
    finally:
        replica.shutdown()


def test_engine_seed_reproducible_and_per_instance():
    """Sampling seed control: an explicit seed reproduces the sampled
    stream exactly; different seeds diverge at high temperature (the old
    hardcoded PRNGKey(0) made every replica emit identical samples)."""
    cfg = LlamaConfig.tiny(max_seq_len=64)
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
    req = lambda: GenerationRequest(  # noqa: E731
        token_ids=[1, 2, 3, 4], max_new_tokens=16, temperature=5.0
    )
    def sampled(seed):
        engine = ContinuousBatchingEngine(cfg, params, num_slots=2, seed=seed)
        try:
            return engine.generate([req()])
        finally:
            engine.close()

    a, b, c = sampled(11), sampled(11), sampled(12)
    assert a[0].token_ids == b[0].token_ids
    assert a[0].token_ids != c[0].token_ids


@pytest.mark.slow
def test_tp_sharded_decode_matches_single_device():
    """Serving tensor parallelism: an engine over GSPMD-sharded params on a
    tp x fsdp mesh decodes the whole-forward greedy tokens of the unsharded
    model (the role vLLM's tensor_parallel_size plays behind ray.llm), on
    the stepping thread as under a caller's own drive."""
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.parallel.sharding import param_shardings

    cfg = LlamaConfig.tiny(max_seq_len=64)
    boxed = init_params(cfg, jax.random.PRNGKey(0))
    params = unbox_params(boxed)
    prompt = [3, 14, 15, 92, 65]

    ref_out = greedy_reference(cfg, params, prompt, 8)

    mesh = make_mesh(8, tp=4, fsdp=2)
    sharded = jax.device_put(params, param_shardings(mesh, boxed))
    with mesh:
        cb = ContinuousBatchingEngine(cfg, sharded, mesh=mesh, num_slots=2)
        rid = cb.add_request(GenerationRequest(prompt, max_new_tokens=8))
        cb_out = cb.run_until_complete()[rid].token_ids
        tp_out = cb.generate(
            [GenerationRequest(prompt, max_new_tokens=8)]
        )[0].token_ids
        cb.close()
    assert tp_out == ref_out
    assert cb_out == ref_out


def test_engine_generate_stream_matches_batch(tiny_engine):
    """generate_stream yields the same greedy tokens generate() produces,
    one at a time, ending with the summary GenerationResult."""
    cfg, params, engine = tiny_engine
    prompt = [3, 14, 15, 92, 65, 35]
    req = GenerationRequest(token_ids=prompt, max_new_tokens=6)
    ref = engine.generate([GenerationRequest(token_ids=prompt,
                                             max_new_tokens=6)])[0]
    items = list(engine.generate_stream(req))
    tokens, summary = items[:-1], items[-1]
    assert tokens == ref.token_ids
    assert summary.token_ids == ref.token_ids
    assert summary.finished_reason == ref.finished_reason
    assert summary.num_prompt_tokens == len(prompt)


@pytest.mark.slow
def test_llm_serve_token_streaming_e2e(ray_start_regular):
    """Token-streaming end-to-end through serve (the reference's
    DeploymentResponseGenerator path for ray.llm): the first token arrives
    before the full completion exists, and the streamed tokens equal the
    buffered result."""
    import time as _time

    from ray_tpu import serve

    llm_config = LLMConfig(
        model_id="llama-stream-tiny",
        max_seq_len=64,
        max_new_tokens=8,
        resources_per_replica={"CPU": 1.0},
    )
    app = build_llm_deployment(llm_config)
    serve.start(proxy=False)
    handle = serve.run(app, name="llm-stream", route_prefix=None, _proxy=False)
    try:
        request = {"token_ids": [1, 2, 3, 4], "max_new_tokens": 6}
        buffered = handle.remote(dict(request)).result(timeout_s=120)

        gen = handle.options(stream=True, method_name="stream").remote(
            dict(request)
        )
        t0 = _time.time()
        first = next(gen)
        first_latency = _time.time() - t0
        rest = list(gen)
        assert first["index"] == 0
        streamed_tokens = [first["token_id"]] + [
            d["token_id"] for d in rest if "token_id" in d
        ]
        summary = rest[-1]
        assert summary.get("finished") is True
        assert streamed_tokens == buffered["token_ids"]
        assert summary["token_ids"] == buffered["token_ids"]
        # TTFT sanity: the first token must not wait for the whole stream
        # (tiny model decodes fast; just assert it beat the full wall time)
        assert first_latency < 60
    finally:
        serve.shutdown()


@pytest.mark.slow
def test_llm_deployment_with_replica_autoscaling(ray_start_regular):
    """BASELINE configs[4]: LLM serving with replica autoscaling — the
    builder wires LLMConfig.autoscaling_config into the serve deployment
    and the controller scales engine replicas under request pressure."""
    import time

    from ray_tpu import serve

    llm_config = LLMConfig(
        model_id="llama-tiny",
        max_seq_len=64,
        max_new_tokens=8,
        resources_per_replica={"CPU": 0.5},
        autoscaling_config=dict(
            min_replicas=2,
            max_replicas=3,
            target_ongoing_requests=2,
        ),
    )
    app = build_llm_deployment(llm_config, name="llm-auto")
    serve.start(proxy=False)
    handle = serve.run(app, name="llm-auto-app", route_prefix=None, _proxy=False)
    try:
        def n_running():
            st = serve.status()["llm-auto-app"].deployments["llm-auto"]
            return sum(1 for r in st.replicas if r.state == "RUNNING")

        # the controller owns the replica count now: it must bring the
        # deployment up to the autoscaling floor (2 engine replicas), not
        # LLMConfig.num_replicas (1) — proves the config reached serve
        deadline = time.time() + 60
        while time.time() < deadline and n_running() < 2:
            time.sleep(0.5)
        assert n_running() >= 2, "autoscaler never reached min_replicas=2"
        out = handle.remote(
            {"token_ids": [1, 2, 3], "max_new_tokens": 8}
        ).result(timeout_s=120)
        assert out["finished_reason"] in ("length", "eos")
    finally:
        serve.shutdown()


# ---------------------------------------------------------------------------
# a whole-prompt prefill attends to its own keys, not to all of the cache
# ---------------------------------------------------------------------------

# a softmax over s keys against one over max_seq_len of which all but s are
# masked sums the same terms in another order: float32's rounding, and in
# bfloat16 a step of the result's at most (0.0039 at these logits, 0.031
# at a cache row's largest values)
WIDTH_TOL = {"f32": 1e-5, "bf16": 2e-2}
PREFILL_VARIANTS = {
    "gqa_4_to_1": dict(n_heads=4, n_kv_heads=1),
    "mha": dict(n_heads=4, n_kv_heads=4),
    "qk_norm": dict(n_heads=4, n_kv_heads=2, qk_norm=True),
    "attn_gate_no_rope": dict(
        n_heads=4, n_kv_heads=1, attn_gate=True, rope=False
    ),
}


def _decode_model(dtype=jnp.bfloat16, max_seq_len=64, **model_kwargs):
    """(the engine's two functions for a tiny model, its params)."""
    from ray_tpu.llm.engine import _DecodeModelBase

    cfg = LlamaConfig.tiny(
        max_seq_len=max_seq_len, dtype=dtype, **model_kwargs
    )
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
    return _DecodeModelBase(cfg, params), params


def _tokens(n, seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randint(1, 250, (1, n)), jnp.int32
    )


def _f32(x):
    return np.asarray(x, np.float32)


def _assert_rows_close(got, want, tol):
    """Two cache leaves within ``tol`` of the leaf's largest value (a rotated
    key is a difference of products, so a small one carries the rounding of
    large ones); ``tol`` 0 is bit for bit."""
    got, want = _f32(got), _f32(want)
    assert np.abs(want).max() > 0.5
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


def _score_widths(fn, *args, queries):
    """The last extent of every float32 (.., .., queries, n) in ``fn``'s
    jaxpr: among them (beside a head's width and its halves) the number of
    keys the prompt's queries were scored against."""
    import re

    return {
        int(keys) for keys in re.findall(
            rf"f32\[\d+,\d+,{queries},(\d+)\]", str(jax.make_jaxpr(fn)(*args))
        )
    }


@pytest.mark.parametrize(
    "length", [128, 137, 1100], ids=["128", "odd_137", "long_1100"]
)
@pytest.mark.parametrize("variant", list(PREFILL_VARIANTS))
def test_whole_prompt_prefill_is_the_full_width_prefill(variant, length):
    """The fresh-cache branch against the einsum over every position of the
    cache, which is what the same prompt runs into when a zeroed cache is
    handed in (the program a whole prefill was): the last position's logits
    and the second layer's row within the reduction's rounding, the first
    layer's row (written before any attention) bit for bit, and no score
    of the prompt against more keys than it has."""
    max_seq_len = 1152
    model, params = _decode_model(
        max_seq_len=max_seq_len, **PREFILL_VARIANTS[variant]
    )
    tokens = _tokens(length)
    logits, cache = jax.jit(model._prefill_impl)(params, tokens)
    empty = jax.tree.map(jnp.zeros_like, cache)
    want_logits, want_cache = jax.jit(model._decode_impl)(
        params, empty, tokens
    )
    widths = _score_widths(model._prefill_impl, params, tokens, queries=length)
    assert length in widths and max_seq_len not in widths
    assert max_seq_len in _score_widths(
        model._decode_impl, params, empty, tokens, queries=length
    )
    tol = WIDTH_TOL["bf16"]
    assert np.abs(_f32(want_logits)).max() > 10 * tol
    assert np.abs(_f32(logits) - _f32(want_logits)).max() < tol
    assert int(jnp.argmax(logits)) == int(jnp.argmax(want_logits))
    for layer, row_tol in (("layer_0", 0.0), ("layer_1", tol)):
        got, want = cache[layer]["attn"], want_cache[layer]["attn"]
        assert int(got["cache_index"][0]) == length
        for leaf in ("cached_key", "cached_value"):
            _assert_rows_close(got[leaf], want[leaf], row_tol)
            assert not _f32(got[leaf])[:, :, length:].any()


@pytest.mark.parametrize(
    "dtype,tol", [(jnp.float32, 1e-4), (jnp.bfloat16, 2e-2)],
    ids=["f32", "bf16"],
)
def test_prefill_then_decode_is_the_prompt_stepped_a_token_at_a_time(
    dtype, tol
):
    """A whole prefill and decode steps behind it, against the same tokens
    fed one at a time into an empty row (the decode kernel at every
    position): each step's logits, and in float32 the greedy tokens."""
    model, params = _decode_model(dtype, n_heads=4, n_kv_heads=2)
    prefill, decode = jax.jit(model._prefill_impl), jax.jit(model._decode_impl)
    prompt, n_new = _tokens(21, seed=3), 6

    logits, cache = prefill(params, prompt)
    fed, stepped_after = [int(t) for t in prompt[0]], []
    for _ in range(n_new):
        stepped_after.append(logits[0])
        fed.append(int(jnp.argmax(logits[0])))
        logits, cache = decode(params, cache, jnp.asarray([[fed[-1]]]))

    _, row = prefill(params, prompt[:, :1])
    row = jax.tree.map(jnp.zeros_like, row)
    one_at_a_time = []
    for token in fed[:-1]:
        logits, row = decode(params, row, jnp.asarray([[token]]))
        one_at_a_time.append(logits[0])
    for got, want in zip(stepped_after, one_at_a_time[prompt.shape[1] - 1:]):
        assert np.abs(_f32(got) - _f32(want)).max() < tol
        if dtype == jnp.float32:
            assert int(jnp.argmax(got)) == int(jnp.argmax(want))


@pytest.mark.parametrize(
    "heads,held",
    [(dict(n_heads=16, n_kv_heads=1, attn_head_dim=32), True),
     (dict(n_heads=4, n_kv_heads=1), False)],
    ids=["query_four_times_dim_held", "query_of_dim_left_alone"],
)
def test_a_decode_step_that_holds_its_query_projection_is_the_step_that_does_not(
    heads, held, monkeypatch
):
    """``llama.holds_projection`` decides from ``W_q``'s bytes whether a
    decode step keeps the projection's output as the matmul made it (what
    keeps the chip's compiler from re-laying the weight every step: PERF 6,
    PR 60). With the limit put between two toys' weights (float32, dim 128:
    256 KiB at 16 heads of 32 over one KV head, 64 KiB at 4), the wide one's
    step carries the hold in every layer and the narrow one's none; either
    way the step's logits and rows are bit for bit those of the same module
    with the rule off, and the prompt's last position as a whole prefill
    has it."""
    from ray_tpu.models import llama

    def step():  # a function of its own: traced anew under each patch
        return lambda *a: model._decode_impl(*a)

    def holds(*args):
        return str(jax.make_jaxpr(step())(*args)).count("optimization_barrier")

    monkeypatch.setattr(llama, "_UNSTAGED_WEIGHT_BYTES", 128 * 1024)
    model, params = _decode_model(**heads)
    prompt = _tokens(22, seed=7)
    prefill = jax.jit(model._prefill_impl)
    _, cache = prefill(params, prompt[:, :-1])
    args = (params, cache, prompt[:, -1:])
    assert holds(*args) == (2 if held else 0)  # a layer each
    # a prefill is no decode step: its projection is never held
    assert "optimization_barrier" not in str(
        jax.make_jaxpr(model._prefill_impl)(params, prompt))
    logits, row = jax.jit(step())(*args)

    monkeypatch.setattr(llama, "holds_projection", lambda weight_bytes: False)
    assert holds(*args) == 0
    free_logits, free_row = jax.jit(step())(*args)
    np.testing.assert_array_equal(_f32(logits), _f32(free_logits))
    for got, want in zip(jax.tree.leaves(row), jax.tree.leaves(free_row)):
        np.testing.assert_array_equal(_f32(got), _f32(want))

    whole_logits, _ = prefill(params, prompt)
    assert np.abs(_f32(whole_logits)).max() > 10 * WIDTH_TOL["bf16"]
    assert np.abs(_f32(logits) - _f32(whole_logits)).max() < WIDTH_TOL["bf16"]


@pytest.mark.parametrize(
    "dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"],
)
def test_suffix_prefill_scores_the_cache_and_equals_a_whole_one(dtype):
    """A suffix behind cached keys (a prefix hit, a chunk, a verify) is a
    cache that came in: it scores every position of it, in the arithmetic
    of the whole prompt's prefill, and ends where that does: the same
    greedy token, in bfloat16 too, which is what lets a prefix hit answer
    as the miss before it did (tests/test_step_spans.py replays one)."""
    model, params = _decode_model(dtype, n_heads=4, n_kv_heads=1)
    prefill, decode = jax.jit(model._prefill_impl), jax.jit(model._decode_impl)
    prompt = _tokens(29, seed=5)
    whole_logits, whole = prefill(params, prompt)
    _, row = prefill(params, prompt[:, :16])
    assert 64 in _score_widths(
        model._decode_impl, params, row, prompt[:, 16:], queries=13
    )
    logits, row = decode(params, row, prompt[:, 16:])
    tol = WIDTH_TOL["f32" if dtype == jnp.float32 else "bf16"]
    assert np.abs(_f32(logits) - _f32(whole_logits)).max() < tol
    assert int(jnp.argmax(logits)) == int(jnp.argmax(whole_logits))
    for got, want in zip(jax.tree.leaves(row), jax.tree.leaves(whole)):
        _assert_rows_close(got, want, tol)
