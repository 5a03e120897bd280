"""Tensor-parallel serving plane (PR 13): partition-plan validation,
tp=2 paged-vs-dense temperature-0 parity (cold + shared-prefix warm),
sharded KV pool accounting, mesh-tagged spans, and the weight plane's
pull-each-shard-once guarantee.

Runs entirely on host devices — conftest forces
``--xla_force_host_platform_device_count=8`` so a 2-way mesh exists on
any CPU box."""

import pickle

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu import exceptions
from ray_tpu.kvcache import KVCacheManager
from ray_tpu.llm.config import LLMConfig
from ray_tpu.llm.engine import (
    ContinuousBatchingEngine,
    GenerationRequest,
)
from ray_tpu.models.llama import LlamaConfig, init_params
from ray_tpu.parallel.plan import (
    DEFAULT_LLM_RULES,
    PartitionPlan,
    match_partition_rules,
    validate_mesh_for_model,
)
from ray_tpu.parallel.sharding import unbox_params
from ray_tpu.util import tracing

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >=2 (host) devices"
)


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = LlamaConfig.tiny(max_seq_len=128)
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, params


@pytest.fixture(scope="module")
def tp2(tiny_setup):
    """ONE shared tp=2 paged engine: jit compiles dominate this file's
    wall-clock, so the parity/accounting/span tests reuse the same sharded
    programs (tests that need fresh KV state measure stats() deltas)."""
    cfg, params = tiny_setup
    plan = PartitionPlan.for_model(cfg, 2)
    kv = KVCacheManager(num_blocks=32, block_size=16, plan=plan)
    eng = ContinuousBatchingEngine(
        cfg, params, plan.mesh, num_slots=4, kv_cache=kv, seed=7, plan=plan,
    )
    return eng, kv, plan


# -- partition plan ----------------------------------------------------------


def test_partition_rules_cover_llama_params(tiny_setup):
    cfg, params = tiny_setup
    plan = PartitionPlan.for_model(cfg, 2)
    specs = match_partition_rules(DEFAULT_LLM_RULES, params)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
    )
    assert len(spec_leaves) == len(leaves)
    # at least the attention/MLP kernels actually shard (not all-replicated)
    assert any("tp" in tuple(s) for s in spec_leaves)
    # every matched spec maps onto the mesh: shard_params must not raise
    sharded = plan.shard_params(params)
    flat = jax.tree_util.tree_leaves(sharded)
    assert all(isinstance(leaf, jax.Array) for leaf in flat)


def test_mesh_validation_typed_errors():
    with pytest.raises(exceptions.MeshValidationError):
        validate_mesh_for_model(3, 8)  # tp does not divide devices
    with pytest.raises(exceptions.MeshValidationError):
        validate_mesh_for_model(0, 8)  # non-positive tp
    with pytest.raises(exceptions.MeshValidationError):
        # tp divides devices but not the head counts
        validate_mesh_for_model(8, 8, n_heads=4, n_kv_heads=4)
    cfg = LlamaConfig.tiny()
    with pytest.raises(exceptions.MeshValidationError) as ei:
        PartitionPlan.for_model(cfg, 3)
    # typed + picklable: serve deployment errors cross process boundaries
    err = ei.value
    clone = pickle.loads(pickle.dumps(err))
    assert isinstance(clone, exceptions.MeshValidationError)
    assert isinstance(clone, ValueError)
    assert str(clone) == str(err)


def test_llmconfig_mesh_field_wins_and_validates():
    lc = LLMConfig(model_id="m", mesh={"tp": 4})
    assert lc.effective_parallelism() == (4, 1)
    lc2 = LLMConfig(model_id="m", tensor_parallel_size=2)
    assert lc2.effective_parallelism() == (2, 1)
    # mesh dict wins over the scalar fields
    lc3 = LLMConfig(model_id="m", tensor_parallel_size=2, mesh={"tp": 8})
    assert lc3.effective_parallelism() == (8, 1)
    with pytest.raises(exceptions.MeshValidationError):
        LLMConfig(model_id="m", mesh={"pp": 2})  # unknown axis
    with pytest.raises(exceptions.MeshValidationError):
        LLMConfig(model_id="m", mesh={"tp": 0})  # non-positive size


# -- parity ------------------------------------------------------------------


def test_tp2_paged_matches_dense_temperature0(tiny_setup, tp2):
    """The acceptance bar: a tp=2 sharded paged replica is token-identical
    to the dense single-device engine at temperature 0, for cold prompts
    AND a warm request that rides the shared-prefix cache."""
    cfg, params = tiny_setup
    dense = ContinuousBatchingEngine(cfg, params, num_slots=4, seed=7)
    paged, kv, _ = tp2

    rng = np.random.RandomState(0)
    prompts = [list(map(int, rng.randint(0, 256, size=n)))
               for n in (17, 33, 21)]
    d = dense.generate([GenerationRequest(list(p), max_new_tokens=8)
                        for p in prompts])
    p = paged.generate([GenerationRequest(list(p), max_new_tokens=8)
                        for p in prompts])
    for i, (a, b) in enumerate(zip(d, p)):
        assert a.token_ids == b.token_ids, (i, a.token_ids, b.token_ids)

    # warm request: first 32 tokens (2 blocks) shared with prompts[1]
    warm = prompts[1][:32] + list(map(int, rng.randint(0, 256, size=5)))
    s0 = kv.stats()
    wd = dense.generate([GenerationRequest(list(warm), max_new_tokens=8)])[0]
    wp = paged.generate([GenerationRequest(list(warm), max_new_tokens=8)])[0]
    s1 = kv.stats()
    assert wd.token_ids == wp.token_ids
    # the warm request really hit the cache: 32 cached, 5 computed
    assert s1["prefix_hit_tokens"] - s0["prefix_hit_tokens"] == 32
    assert (s1["prefill_tokens_computed"]
            - s0["prefill_tokens_computed"]) == len(warm) - 32


# -- sharded KV pools --------------------------------------------------------


def test_kv_pools_sharded_with_per_device_accounting(tiny_setup, tp2):
    cfg, params = tiny_setup
    paged, kv, plan = tp2
    # force pool creation + a resident sequence
    paged.generate([GenerationRequest(list(range(40)), max_new_tokens=2)])

    pool = kv._pools[0]
    # head axis (axis 1) is split across the mesh: each device holds half
    # the kv heads for every block
    shard_shapes = {tuple(s.data.shape) for s in pool.addressable_shards}
    assert shard_shapes == {(32, cfg.n_kv_heads // 2, 16, cfg.head_dim)}

    stats = kv.stats()
    assert stats["mesh"] == "tp=2"
    assert stats["num_devices"] == 2
    assert stats["heads_per_device"] == cfg.n_kv_heads // 2
    assert stats["kv_pool_bytes_total"] == sum(p.nbytes for p in kv._pools)
    assert (stats["kv_pool_bytes_per_device"] * 2
            == stats["kv_pool_bytes_total"])

    acct = kv.pool_accounting()
    assert acct["kv_pool_bytes_per_device"] == stats["kv_pool_bytes_per_device"]


def test_unsharded_manager_accounting_still_works():
    kv = KVCacheManager(num_blocks=4, block_size=8)
    acct = kv.pool_accounting()
    assert acct == {
        "kv_pool_bytes_total": 0,
        "kv_pool_bytes_per_device": 0,
        "heads_per_device": 0,
    }
    assert kv.stats()["mesh"] == "tp=1"


# -- observability -----------------------------------------------------------


def test_engine_spans_carry_mesh_tag(tp2, monkeypatch):
    monkeypatch.setattr(tracing, "flush_spans", lambda: None)
    paged, _, _ = tp2

    tracing.enable_tracing()
    tracing.clear_spans()
    try:
        ctx = tracing.new_trace_context()
        with tracing.request_span("test.request", ctx):
            paged.generate([GenerationRequest(list(range(3, 40)),
                                              max_new_tokens=2,
                                              temperature=0.0)])
        spans = [s for s in tracing.get_spans()
                 if s["trace_id"] == ctx["trace_id"]]
        tagged = [s for s in spans
                  if s["name"] in ("engine.prefill", "engine.decode")]
        assert tagged, "no engine spans recorded"
        assert all(s["args"]["mesh"] == "tp=2" for s in tagged)
    finally:
        tracing._enabled = False
        tracing.clear_spans()


# -- weight plane: each shard's bytes pulled once ----------------------------


def test_weight_chunks_pulled_once_into_sharded_layout(cluster):
    """A subscriber resolving a manifest into a sharded layout pulls every
    chunk exactly once (counter-asserted) — no second fetch, no replicated
    staging pull — and the pinned tree is served from cache afterwards."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.util.state import list_weights
    from ray_tpu.weights import WeightPublisher, WeightSubscriber

    mesh = make_mesh(2, tp=2, fsdp=1)

    def shardings(tree):
        return jax.tree_util.tree_map(
            lambda x: NamedSharding(
                mesh, P("tp") if x.ndim == 1 and x.shape[0] % 2 == 0 else P()
            ),
            tree,
        )

    pub = WeightPublisher("t/tp-shards", chunk_size=128 * 1024)
    params = {f"layer{i}": np.full(50_000, i, np.float32) for i in range(4)}
    pub.publish(params)
    n_chunks = {r["name"]: r for r in list_weights()}["t/tp-shards"][
        "num_chunks"
    ]
    assert n_chunks >= 2

    sub = WeightSubscriber("t/tp-shards")
    assert sub.chunk_pulls == 0
    _, got = sub.get(sharding=shardings)
    assert sub.chunk_pulls == n_chunks
    assert sub.bytes_pulled > 0
    for i in range(4):
        leaf = got[f"layer{i}"]
        assert isinstance(leaf, jax.Array)
        assert leaf.sharding.spec == P("tp")
        # each device holds half the leaf — the shard, not a replica
        assert {s.data.shape for s in leaf.addressable_shards} == {(25_000,)}
        np.testing.assert_array_equal(np.asarray(leaf), params[f"layer{i}"])

    # cached path: a second get() pulls zero additional chunks
    _, again = sub.get(sharding=shardings)
    assert sub.chunk_pulls == n_chunks
    assert jax.tree_util.tree_leaves(again)[0] is not None
    sub.release()
