"""Multi-tenant LoRA serving plane (PR 20).

The correctness bar mirrors the other engine-loop features: adapters must
be invisible except in the math. Temperature-0 parity pins the
batched-gather path — a mixed-adapter batch (several tenants + base rows
in ONE jitted step) must emit token-for-token what each tenant gets when
served alone, with the SAME prompt across tenants so the adapter-salted
KV prefix keys are exercised (an unsalted trie would reuse tenant A's
K/V for tenant B). Store tests pin the lease lifecycle (refcount, LRU
evict, backpressure-as-None, rollback); the weight-plane test pins the
publish -> evict -> refill round-trip; the no-stall test pins the
threading claim — a cold attach on a request thread never gaps an
in-flight decode.

Engines are module-scoped where possible: jit programs compile once per
engine instance and per decode width, the dominant cost of this file.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.kvcache import KVCacheManager
from ray_tpu.llm import GenerationRequest, LLMConfig
from ray_tpu.llm.config import AdapterConfig
from ray_tpu.llm.engine import ContinuousBatchingEngine
from ray_tpu.lora import AdapterStore, adapter_target_paths, publish_adapter
from ray_tpu.models.llama import Llama, LlamaConfig, init_params
from ray_tpu.parallel.sharding import unbox_params

RANK = 4


def _adapter_tree(cfg, seed, rank=RANK, scale=0.5):
    """A random nonzero adapter in train/lora.py leaf naming. ``scale``
    is large on purpose: the delta must actually move tiny-model argmaxes
    so per-tenant trajectories diverge from base."""
    rng = np.random.RandomState(seed)
    tree = {}
    for path, in_dim, out_dim in adapter_target_paths(cfg):
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = {
            "lora_a": jnp.asarray(
                rng.normal(0.0, scale, (in_dim, rank)), jnp.float32
            ),
            "lora_b": jnp.asarray(
                rng.normal(0.0, scale, (rank, out_dim)), jnp.float32
            ),
        }
    return tree


@pytest.fixture(scope="module")
def tiny():
    """f32 compute end to end: gather-vs-per-weight parity is then exact,
    not epsilon-close."""
    cfg = LlamaConfig.tiny(max_seq_len=128, dtype=jnp.float32)
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, params


# -- batched-gather math -----------------------------------------------------


class TestGatherParity:
    def test_gather_matches_per_weight_lora(self, tiny):
        """The same adapter through both code paths — per-weight LoRADense
        params (the train-time path, scales alpha/rank at compute) vs the
        slot bank gather (pre-scaled at attach) — must agree on logits."""
        from flax import traverse_util

        cfg, _ = tiny
        tree = _adapter_tree(cfg, seed=42)
        cfg_l = LlamaConfig.tiny(
            max_seq_len=128, dtype=jnp.float32,
            lora_rank=RANK, lora_alpha=16.0,
        )
        flat = traverse_util.flatten_dict(
            unbox_params(init_params(cfg_l, jax.random.PRNGKey(0)))
        )
        tree_flat = traverse_util.flatten_dict(tree)
        for k in list(flat):
            if k[-1] in ("lora_a", "lora_b"):
                flat[k] = tree_flat[k]
        params_l = traverse_util.unflatten_dict(flat)
        base_params = traverse_util.unflatten_dict({
            k: v for k, v in flat.items()
            if k[-1] not in ("lora_a", "lora_b")
        })

        store = AdapterStore(
            cfg, max_live=2, rank=RANK, alpha=16.0,
            param_dtype=jnp.float32,
        )
        lease = store.acquire("t", tree=tree)
        tokens = jnp.asarray([[1, 2, 3, 4, 5]], jnp.int32)
        ref = Llama(cfg_l, None).apply({"params": params_l}, tokens)
        got = Llama(cfg, None).apply(
            {"params": base_params}, tokens,
            store.bank(), jnp.asarray([lease.slot], jnp.int32),
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5
        )

    def test_slot_minus_one_is_base_path(self, tiny):
        """Row slot = -1 (no adapter) must equal the base model exactly
        even with a live bank passed in: the mask zeroes the delta, it
        does not gather garbage."""
        cfg, params = tiny
        store = AdapterStore(cfg, max_live=2, rank=RANK,
                             param_dtype=jnp.float32)
        lease = store.acquire("t", tree=_adapter_tree(cfg, seed=7))
        tokens = jnp.asarray([[9, 8, 7, 6]], jnp.int32)
        model = Llama(cfg, None)
        base = model.apply({"params": params}, tokens)
        masked = model.apply(
            {"params": params}, tokens,
            store.bank(), jnp.asarray([-1], jnp.int32),
        )
        tinted = model.apply(
            {"params": params}, tokens,
            store.bank(), jnp.asarray([lease.slot], jnp.int32),
        )
        np.testing.assert_allclose(np.asarray(masked), np.asarray(base))
        assert not np.allclose(np.asarray(tinted), np.asarray(base))


# -- store lifecycle ---------------------------------------------------------


class TestStoreLifecycle:
    def test_lru_evict_backpressure_and_refill_counts(self, tiny):
        cfg, _ = tiny
        calls = []
        trees = {f"t{i}": _adapter_tree(cfg, i) for i in range(3)}

        def source(aid):
            calls.append(aid)
            return trees[aid]

        store = AdapterStore(cfg, max_live=2, rank=RANK, source=source,
                             param_dtype=jnp.float32)
        l0 = store.acquire("t0")
        l1 = store.acquire("t1")
        # every slot pinned -> None (backpressure), never an eviction of
        # an in-flight adapter
        assert store.acquire("t2") is None
        store.release(l0)
        l2 = store.acquire("t2")  # evicts idle t0, keeps pinned t1
        assert store.evictions == 1
        assert sorted(store.stats()["resident"]) == ["t1", "t2"]
        # resident hit: no refetch, same slot
        l1b = store.acquire("t1")
        assert store.hits == 1 and l1b.slot == l1.slot
        store.release(l1)
        store.release(l1b)
        store.release(l2)
        # t0 was evicted: acquiring it again is a second cold attach
        l0b = store.acquire("t0")
        assert calls == ["t0", "t1", "t2", "t0"]
        assert store.cold_attaches == 4
        # release is idempotent
        store.release(l0b)
        store.release(l0b)
        assert store.stats()["slots_pinned"] == 0

    def test_failed_refill_rolls_back_slot(self, tiny):
        cfg, _ = tiny

        def boom(aid):
            raise RuntimeError("registry down")

        store = AdapterStore(cfg, max_live=1, rank=RANK, source=boom,
                             param_dtype=jnp.float32)
        with pytest.raises(RuntimeError, match="registry down"):
            store.acquire("x")
        # the slot returned to the free list: the store is not leaked empty
        assert store.stats()["slots_free"] == 1
        store.prewarm("y", _adapter_tree(cfg, 5))
        assert store.stats()["resident"] == ["y"]

    def test_rank_mismatch_rejected(self, tiny):
        cfg, _ = tiny
        store = AdapterStore(cfg, max_live=1, rank=8,
                             param_dtype=jnp.float32)
        with pytest.raises(ValueError, match="slot_rank"):
            store.acquire("t", tree=_adapter_tree(cfg, 0, rank=4))

    def test_publish_requires_lora_leaves(self):
        with pytest.raises(ValueError, match="lora_a"):
            publish_adapter("t/x", "bad", {"w": jnp.zeros((2, 2))})


# -- mixed-adapter batches, over a block pool and over dense rows ------------


PROMPT = [3, 14, 15, 9, 2, 6, 5]  # ONE length: prefill compiles are per length
TENANTS = ["tenant_a", "tenant_b", "tenant_c"]


@pytest.fixture(scope="module", params=[True, False], ids=["paged", "dense"])
def lora_engine(tiny, request):
    cfg, params = tiny
    trees = {t: _adapter_tree(cfg, 10 + i) for i, t in enumerate(TENANTS)}
    store = AdapterStore(
        cfg, max_live=4, rank=RANK, source=trees.__getitem__,
        param_dtype=jnp.float32,
    )
    kv = KVCacheManager(num_blocks=64, block_size=8) if request.param else None
    eng = ContinuousBatchingEngine(
        cfg, params, num_slots=4, kv_cache=kv, seed=0,
        adapter_store=store,
    )
    return eng, store


def _run_one(eng, store, aid, n=8):
    lease = store.acquire(aid) if aid else None
    try:
        rid = eng.add_request(GenerationRequest(
            token_ids=PROMPT, max_new_tokens=n, temperature=0.0,
            adapter_id=aid, adapter_slot=lease.slot if lease else -1,
        ))
        return eng.run_until_complete()[rid].token_ids
    finally:
        store.release(lease)


class TestMixedBatch:
    def test_mixed_batch_matches_sequential(self, lora_engine):
        """3 tenants + 1 base row decode CONCURRENTLY as one gather batch,
        all on the SAME prompt (so only the adapter-salted KV keys keep
        their prefixes apart) — and each row must equal its solo run."""
        eng, store = lora_engine
        leases = {t: store.acquire(t) for t in TENANTS}
        rids = {}
        for t in TENANTS:
            rids[t] = eng.add_request(GenerationRequest(
                token_ids=PROMPT, max_new_tokens=8, temperature=0.0,
                adapter_id=t, adapter_slot=leases[t].slot,
            ))
        rids[None] = eng.add_request(GenerationRequest(
            token_ids=PROMPT, max_new_tokens=8, temperature=0.0,
        ))
        mixed = eng.run_until_complete()
        for lease in leases.values():
            store.release(lease)

        solo = {aid: _run_one(eng, store, aid) for aid in TENANTS + [None]}
        for aid, rid in rids.items():
            assert mixed[rid].token_ids == solo[aid], f"row {aid} diverged"
        # the adapters actually did something: tenants differ from base
        # (random deltas at scale 0.5 move tiny-model argmaxes)
        assert any(solo[t] != solo[None] for t in TENANTS)

    def test_resident_tenant_is_a_hit(self, lora_engine):
        eng, store = lora_engine
        before = store.stats()
        out1 = _run_one(eng, store, TENANTS[0])
        out2 = _run_one(eng, store, TENANTS[0])
        after = store.stats()
        assert out1 == out2  # temp-0 determinism across runs
        assert after["cold_attaches"] == before["cold_attaches"]
        assert after["hits"] >= before["hits"] + 2


def test_cold_attach_does_not_stall_decodes(tiny):
    """The threading claim: a cold adapter's pull + slot write run on the
    caller's thread (serve: the replica request thread) — while it is in
    flight, an engine stepping on another thread emits one token EVERY
    step, no gaps."""
    import time

    cfg, params = tiny

    def slow_source(aid):
        time.sleep(0.3)  # a weight-plane pull's worth of latency
        return _adapter_tree(cfg, 99)

    store = AdapterStore(cfg, max_live=2, rank=RANK, source=slow_source,
                         param_dtype=jnp.float32)
    eng = ContinuousBatchingEngine(
        cfg, params, num_slots=2,
        kv_cache=KVCacheManager(num_blocks=64, block_size=8), seed=0,
        adapter_store=store,
    )
    rid = eng.add_request(GenerationRequest(
        token_ids=PROMPT, max_new_tokens=100, temperature=0.0,
    ))
    eng.step()  # admit + first token (pays the compiles up front)
    slot = next(iter(eng._slots.values()))
    assert slot.request_id == rid

    got = []
    t = threading.Thread(target=lambda: got.append(store.acquire("cold")))
    t.start()
    overlapped = 0
    while t.is_alive() and len(slot.generated) < 95:
        before = len(slot.generated)
        eng.step()
        assert len(slot.generated) == before + 1, "decode gapped"
        overlapped += 1
    t.join()
    assert overlapped >= 2  # the attach window really overlapped stepping
    assert got and got[0] is not None
    store.release(got[0])
    eng.run_until_complete()


# -- tp=2 sharded slot bank --------------------------------------------------


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >=2 (host) devices")
def test_tp2_bank_shards_alongside_base_weights(tiny):
    """Under a PartitionPlan the bank is born sharded: lora_b rows of
    wq/wk/wv split on the output (head) dim like their base kernels, wo's
    lora_a splits on the input dim, and the slot axis stays replicated.
    A slot write must preserve the layout and the row values."""
    from ray_tpu.parallel.plan import PartitionPlan

    cfg, _ = tiny
    plan = PartitionPlan.for_model(cfg, 2)
    store = AdapterStore(cfg, max_live=2, rank=RANK, alpha=16.0,
                         plan=plan, param_dtype=jnp.float32)
    tree = _adapter_tree(cfg, 3)
    lease = store.acquire("t", tree=tree)
    bank = store.bank()
    h = cfg.n_heads * cfg.head_dim
    wq = bank["layer_0"]["attn"]["wq"]
    wo = bank["layer_0"]["attn"]["wo"]
    assert wq["lora_b"].addressable_shards[0].data.shape == \
        (store.num_slots, RANK, h // 2)
    assert wq["lora_a"].addressable_shards[0].data.shape == \
        (store.num_slots, cfg.dim, RANK)  # replicated
    assert wo["lora_a"].addressable_shards[0].data.shape == \
        (store.num_slots, h // 2, RANK)
    np.testing.assert_allclose(
        np.asarray(wq["lora_a"][lease.slot]),
        np.asarray(tree["layer_0"]["attn"]["wq"]["lora_a"]),
        rtol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(wq["lora_b"][lease.slot]),
        np.asarray(tree["layer_0"]["attn"]["wq"]["lora_b"]) * (16.0 / RANK),
        rtol=1e-6,
    )
    store.release(lease)


# -- weight-plane refill round trip ------------------------------------------


def test_weight_plane_publish_evict_refill(ray_start_regular, tiny):
    """publish_adapter -> cold attach -> LRU evict -> re-attach pulls the
    SAME bytes back off the weight plane (raw codec: exact; int8 codec:
    within quantization error)."""
    cfg, _ = tiny
    t0 = _adapter_tree(cfg, 0)
    t1 = _adapter_tree(cfg, 1)
    publish_adapter("t/adapters", "a0", t0, quantized=False)
    publish_adapter("t/adapters", "a1", t1, quantized=False)
    store = AdapterStore(
        cfg, max_live=1, rank=RANK, alpha=16.0,
        source="weights:t/adapters", param_dtype=jnp.float32,
    )

    def row(leaf, slot):
        node = store.bank()["layer_0"]["attn"]["wq"][leaf]
        return np.asarray(node[slot])

    expect_a0 = np.asarray(t0["layer_0"]["attn"]["wq"]["lora_a"])
    l0 = store.acquire("a0")
    np.testing.assert_allclose(row("lora_a", l0.slot), expect_a0, rtol=1e-6)
    store.release(l0)

    l1 = store.acquire("a1")  # max_live=1: evicts a0
    assert store.evictions == 1
    np.testing.assert_allclose(
        row("lora_b", l1.slot),
        np.asarray(t1["layer_0"]["attn"]["wq"]["lora_b"]) * (16.0 / RANK),
        rtol=1e-6,
    )
    store.release(l1)

    l0b = store.acquire("a0")  # the refill round trip
    assert store.cold_attaches == 3
    np.testing.assert_allclose(row("lora_a", l0b.slot), expect_a0, rtol=1e-6)
    store.release(l0b)

    # int8 publish (the default): quarter the bytes, still attaches close
    publish_adapter("t/adapters", "q0", t0)
    lq = store.acquire("q0")
    np.testing.assert_allclose(
        row("lora_a", lq.slot), expect_a0, rtol=0.05, atol=0.05
    )
    store.release(lq)


# -- serving + batch integration ---------------------------------------------


def test_serve_multiplexed_adapters_on_paged_engine(ray_start_regular):
    """The full plane through serve: AdapterConfig on a paged deployment,
    tenants named via multiplexed model-id AND the explicit adapter_id
    field, concurrent mixed-tenant requests, per-tenant determinism, and
    adapter stats off the replica."""
    from ray_tpu import serve
    from ray_tpu.llm.serving import build_llm_deployment

    llm_config = LLMConfig(
        model_id="llama-tiny",
        max_seq_len=64,
        max_new_tokens=4,
        kv_cache_blocks=32,
        kv_block_size=8,
        resources_per_replica={"CPU": 1.0},
        adapters=AdapterConfig(
            max_live=2, slot_rank=RANK, source="weights:t/lora"
        ),
    )
    mcfg = llm_config.build_model_config()
    publish_adapter("t/lora", "m1", _adapter_tree(mcfg, 1), quantized=False)
    publish_adapter("t/lora", "m2", _adapter_tree(mcfg, 2), quantized=False)

    app = build_llm_deployment(llm_config)
    serve.start(proxy=False)
    handle = serve.run(app, name="llm-lora", route_prefix=None, _proxy=False)
    try:
        body = {"token_ids": [1, 2, 3, 4], "max_new_tokens": 3,
                "temperature": 0.0}
        base = handle.remote(dict(body)).result(timeout_s=180)
        assert len(base["token_ids"]) == 3

        # concurrent mixed-tenant requests: 2 tenants x 2 requests in
        # flight at once against ONE replica's gather batch
        futs = [
            handle.options(
                multiplexed_model_id=f"m{1 + i % 2}"
            ).remote(dict(body))
            for i in range(4)
        ]
        outs = [f.result(timeout_s=180) for f in futs]
        assert outs[0]["token_ids"] == outs[2]["token_ids"]  # m1 == m1
        assert outs[1]["token_ids"] == outs[3]["token_ids"]  # m2 == m2

        # explicit adapter_id field is the same tenant identity
        explicit = handle.remote(
            dict(body, adapter_id="m1")
        ).result(timeout_s=180)
        assert explicit["token_ids"] == outs[0]["token_ids"]

        stats = handle.adapters_stats.remote().result(timeout_s=60)
        assert stats["cold_attaches"] == 2  # m1 + m2, once each
        assert stats["hits"] >= 3
        assert sorted(stats["resident"]) == ["m1", "m2"]
        assert stats["slots_pinned"] == 0  # every lease released
    finally:
        serve.shutdown()


def test_batch_predictor_per_row_adapters(tiny):
    """LLMPredictor multiplexes per-row adapter_id columns through one
    engine: rows for different tenants (and None rows on the base path)
    share a batch, and leases release after the batch."""
    from ray_tpu.llm.batch import LLMPredictor

    cfg, _ = tiny
    trees = {"u1": _adapter_tree(cfg, 21), "u2": _adapter_tree(cfg, 22)}
    llm_config = LLMConfig(
        model_id="llama-tiny",
        max_seq_len=64,
        max_new_tokens=3,
        adapters=AdapterConfig(
            max_live=2, slot_rank=RANK, source=trees.__getitem__
        ),
    )
    pred = LLMPredictor(llm_config)
    out = pred({
        "token_ids": [[1, 2, 3], [1, 2, 3], [1, 2, 3], [1, 2, 3]],
        "adapter_id": ["u1", "u2", None, "u1"],
    })
    assert all(len(g) == 3 for g in out["generated"])
    assert out["generated"][0] == out["generated"][3]  # same tenant
    stats = pred._adapter_store.stats()
    assert stats["slots_pinned"] == 0
    assert stats["cold_attaches"] == 2

    # a second batch for resident tenants is all hits
    pred({"token_ids": [[1, 2, 3]], "adapter_id": ["u2"]})
    assert pred._adapter_store.stats()["cold_attaches"] == 2
