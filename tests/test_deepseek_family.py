"""A third architecture through the serving stack: a Moonlight-shaped model
(latent attention with one cached row for all heads, a dense first layer,
then routed experts beside shared ones under a sigmoid router with a
selection bias) built by ``ray_tpu.models`` for the engines, against the
benchmark's plain reference (``benchmarks/reference/deepseek_v3_arch.py``),
which imports none of the program's model code and attends in the published
form everywhere.

Tolerance, float32 on the CPU: 1e-4 on logits of magnitude ~1. Both sides
multiply exactly here; they differ in the order of their float32 sums, and
the decode step besides in its *form*: it absorbs ``W_kvb`` into the query
and the output and never up-projects the cached row. Measured: 3e-6 or
less. Every fault the benchmark's check has to see is 1e-3 and more here
(asserted below), the same cache rounded to fp8 among them.
"""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import deepseek_v3_arch as arch  # noqa: E402
from ray_tpu import models  # noqa: E402
from ray_tpu.kvcache import KVCacheManager  # noqa: E402
from ray_tpu.llm import LLMConfig  # noqa: E402
from ray_tpu.llm.engine import (  # noqa: E402
    ContinuousBatchingEngine, GenerationRequest,
)
from ray_tpu.models import ROUTING  # noqa: E402
from ray_tpu.models.deepseek import DeepseekConfig  # noqa: E402
from ray_tpu.ops import decode_attention as da  # noqa: E402
from ray_tpu.ops import moe_experts  # noqa: E402
from ray_tpu.parallel import expert as ep  # noqa: E402
from ray_tpu.parallel.sharding import unbox_params  # noqa: E402

TOL = 1e-4
VOCAB = 96
KWARGS = dict(
    vocab_size=VOCAB, dim=64, n_layers=3, n_heads=4, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate=96,
    moe_intermediate=32, n_experts=8, experts_per_token=3, n_shared_experts=2,
    first_dense_layers=1, norm_topk_prob=True, routed_scale=2.446,
    max_seq_len=64, rope_theta=50000.0, dtype=jnp.float32,
    param_dtype=jnp.float32,
)


def _config(**kw):
    return DeepseekConfig(**dict(KWARGS, **kw))


def _sizes(cfg, **kw):
    return dict(dict(
        n_layers=cfg.n_layers, n_heads=cfg.n_heads, rank=cfg.kv_lora_rank,
        nope=cfg.qk_nope_head_dim, rope_dim=cfg.qk_rope_head_dim,
        theta=cfg.rope_theta, eps=cfg.norm_eps, top_k=cfg.experts_per_token,
        norm_topk_prob=cfg.norm_topk_prob, scale=cfg.routed_scale,
    ), **kw)


def _params(cfg, seed=0):
    """Seeded weights with the norms away from one and the selection bias
    away from zero (it is zero-initialised), so that a norm or a bias left
    out or misplaced shows."""
    params = unbox_params(models.init_params(cfg, jax.random.PRNGKey(seed)))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def shake(path, leaf):
        if path[-1].key == "router_bias":
            return 0.2 * jax.random.normal(next(keys), leaf.shape)
        if leaf.ndim == 1:
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    return jax.tree_util.tree_map_with_path(shake, params)


def _tokens(shape, seed=2):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 3, VOCAB - 1)


def _diff(a, b):
    return float(jnp.max(jnp.abs(a - b)))


@pytest.fixture(scope="module")
def tiny():
    cfg = _config()
    return cfg, _params(cfg)


# -- the model against the reference -----------------------------------------


def test_full_forward_matches_the_reference(tiny):
    cfg, params = tiny
    tokens = _tokens((2, 13))
    got, sown = models.build(cfg).apply(
        {"params": params}, tokens, mutable=[ROUTING])
    routing = []
    want = arch.logits(params, tokens, routing=routing, **_sizes(cfg))
    assert _diff(got, want) < TOL
    # routed layers only: the dense first layer sows and counts nothing
    assert sorted(sown[ROUTING]) == ["layer_1", "layer_2"]
    mine = arch.program_routing(sown[ROUTING], cfg.n_layers)
    assert len(mine) == len(routing) == 2
    for a, b in zip(mine, routing):
        assert (np.sort(np.asarray(a), -1) == np.sort(np.asarray(b), -1)).all()
    # following the program's own choice is no other function, and fair
    slack = []
    followed = arch.logits(params, tokens, follow=mine, slack=slack, **_sizes(cfg))
    assert _diff(followed, want) < TOL
    assert float(jnp.max(jnp.stack(slack))) == 0.0


def test_prefill_then_decode_through_the_cache_matches_the_reference(tiny):
    """The prefill attends in the published form, the steps in the absorbed
    one against the cached latent row: logits, every position."""
    cfg, params = tiny
    tokens = _tokens((2, 17))
    want = arch.logits(params, tokens, **_sizes(cfg))
    model = models.build(cfg, None, decode=True)
    got, state = model.apply({"params": params}, tokens[:, :11], mutable=["cache"])
    assert _diff(got, want[:, :11]) < TOL
    # the cache row the KV manager and the latent kernel know: the latent
    # and the rotary columns a layer, and the row's position
    for layer in range(cfg.n_layers):
        leaves = jax.tree.leaves(state["cache"][f"layer_{layer}"])
        assert sorted(leaf.shape for leaf in leaves) == [
            (2,), (2, 1, 64, 8), (2, 1, 64, 32)]
    step = jax.jit(lambda cache, token: model.apply(
        {"params": params, "cache": cache}, token, mutable=["cache"]))
    for i in range(11, 17):
        got, state = step(state["cache"], tokens[:, i:i + 1])
        assert _diff(got[:, 0], want[:, i]) < TOL


def test_absorbed_form_is_the_published_form(tiny):
    """A chunk behind a cached prefix (suffix and chunked prefill) runs the
    absorbed einsum over the whole cache: the same logits as the sequence
    attended at once in the published form."""
    cfg, params = tiny
    tokens = _tokens((2, 16), seed=4)
    model = models.build(cfg, None, decode=True)
    whole, _ = model.apply({"params": params}, tokens, mutable=["cache"])
    _, state = model.apply({"params": params}, tokens[:, :5], mutable=["cache"])
    for start, end in ((5, 12), (12, 16)):
        got, state = _step(model)(params, state["cache"], tokens[:, start:end])
        assert _diff(got, whole[:, start:end]) < TOL
    assert _diff(whole, models.build(cfg).apply({"params": params}, tokens)) == 0.0


def _through_the_cache(cfg, params, tokens, spoil):
    """Last-position logits of prefill(8) + decode steps, the cache passed
    through ``spoil`` after the prefill."""
    model = models.build(cfg, None, decode=True)
    _, state = model.apply({"params": params}, tokens[:, :8], mutable=["cache"])
    cache = jax.tree.map(
        lambda leaf: spoil(leaf) if leaf.ndim == 4 else leaf, state["cache"])
    for i in range(8, tokens.shape[1]):
        got, state = _step(model)(params, cache, tokens[:, i:i + 1])
        cache = state["cache"]
    return got[:, 0]


_STEPS = {}


def _step(model):
    """``model``'s jitted application to a cache and the tokens behind it."""
    if model not in _STEPS:
        _STEPS[model] = jax.jit(lambda params, cache, tokens: model.apply(
            {"params": params, "cache": cache}, tokens, mutable=["cache"]))
    return _STEPS[model]


def test_the_tolerance_fails_an_fp8_latent_row(tiny):
    """The guarantee is bf16 or better in the cache. A row rounded to fp8
    (e4m3: 3 mantissa bits) moves the logits by 100 x the tolerance; the
    same row rounded to bf16 (7 bits) by 16 x less than fp8 does."""
    cfg, params = tiny
    tokens = _tokens((1, 12), seed=6)
    want = arch.logits(params, tokens, last=1, **_sizes(cfg))[:, 0]
    exact = _through_the_cache(cfg, params, tokens, lambda leaf: leaf)
    assert _diff(exact, want) < TOL
    fp8 = _through_the_cache(
        cfg, params, tokens,
        lambda leaf: leaf.astype(jnp.float8_e4m3fn).astype(leaf.dtype))
    bf16 = _through_the_cache(
        cfg, params, tokens,
        lambda leaf: leaf.astype(jnp.bfloat16).astype(leaf.dtype))
    assert _diff(fp8, want) > 100 * TOL
    assert _diff(bf16, want) < _diff(fp8, want) / 8


def _spoiled(params, cfg, fault):
    """``params`` or the reference's sizes with one fault of the list the
    benchmark's check has to see."""
    sizes = _sizes(cfg)
    if fault == "scale_left_out":
        return params, dict(sizes, scale=1.0)
    if fault == "not_normalised":
        return params, dict(sizes, norm_topk_prob=False)
    if fault == "sixth_expert_lost":
        return params, dict(sizes, top_k=cfg.experts_per_token - 1)

    def edit(path, leaf):
        names = [p.key for p in path]
        if fault == "shared_left_out" and names[-3:-1] == ["shared", "w_down"]:
            return leaf * 0
        if fault == "bias_left_out" and names[-1] == "router_bias":
            return leaf * 0
        if fault == "latent_norm_left_out" and names[-1] == "kv_norm":
            return jnp.ones_like(leaf)
        return leaf

    return jax.tree_util.tree_map_with_path(edit, params), sizes


@pytest.mark.parametrize("fault", [
    "scale_left_out", "not_normalised", "sixth_expert_lost", "shared_left_out",
    "bias_left_out", "latent_norm_left_out"])
def test_the_parity_is_not_blind_to(tiny, fault):
    """Each is another model: the program's logits against a reference with
    the fault differ by far more than the tolerance."""
    cfg, params = tiny
    tokens = _tokens((2, 12), seed=3)
    got = models.build(cfg).apply({"params": params}, tokens)
    other, sizes = _spoiled(params, cfg, fault)
    assert _diff(got, arch.logits(other, tokens, **sizes)) > 10 * TOL


def test_the_bias_enters_the_choice_and_not_the_weights():
    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0], [0.1, 0.2, 0.3, 0.4]])
    bias = jnp.asarray([-5.0, 0.0, 0.0, 3.0])
    weights, experts, _ = ep.top_k_routing(
        logits, 2, True, "sigmoid", bias, 2.5)
    scores = jax.nn.sigmoid(logits)
    # expert 0 is pushed out and expert 3 in, in both rows
    assert np.asarray(experts).tolist() == [[3, 1], [3, 2]]
    kept = jnp.take_along_axis(scores, experts, axis=-1)
    want = kept / (kept.sum(-1, keepdims=True) + 1e-20) * 2.5
    assert _diff(weights, want) < 1e-7
    assert abs(float(weights.sum(-1)[0]) - 2.5) < 1e-6


def _softmax_routing_as_it_was(router_logits, k, normalize):
    """``top_k_routing`` before it had a second scoring, line for line."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(probs, k)
    if normalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts.astype(jnp.int32)


@pytest.mark.parametrize("normalize", [False, True])
@pytest.mark.parametrize("rows,k", [(1, 2), (7, 3), (64, 8)])
def test_softmax_routing_is_bit_equal_to_what_it_was(rows, k, normalize):
    logits = jax.random.normal(jax.random.PRNGKey(rows), (rows, 16)) * 3
    weights, experts, _ = jax.jit(
        ep.top_k_routing, static_argnums=(1, 2))(logits, k, normalize)
    was_w, was_e = jax.jit(
        _softmax_routing_as_it_was, static_argnums=(1, 2))(logits, k, normalize)
    assert (np.asarray(experts) == np.asarray(was_e)).all()
    assert (np.asarray(weights) == np.asarray(was_w)).all()


def test_unknown_scoring_is_refused():
    with pytest.raises(ValueError, match="scoring"):
        ep.top_k_routing(jnp.zeros((2, 4)), 2, True, "tanh")
    from ray_tpu.models.moe import MoEConfig

    with pytest.raises(ValueError, match="dropless"):
        MoEConfig(router_scoring="sigmoid")


# -- the latent kernel ---------------------------------------------------------


def _latent_einsum(q, qr, c, r, lengths, scale):
    """The absorbed form's own lines for one query token a row."""
    c, r = c[:, 0].astype(jnp.float32), r[:, 0].astype(jnp.float32)
    scores = (jnp.einsum("bhw,bkw->bhk", q.astype(jnp.float32), c)
              + jnp.einsum("bhw,bkw->bhk", qr.astype(jnp.float32), r)) * scale
    k_pos = jnp.arange(c.shape[1])[None, None, :]
    scores = jnp.where(k_pos < lengths[:, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhk,bkr->bhr", probs, c).astype(q.dtype)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("heads,rank,rope", [(16, 512, 64), (4, 32, 8), (3, 128, 64)])
def test_latent_kernel_matches_the_einsum_on_ragged_rows(
        heads, rank, rope, dtype, monkeypatch):
    """Moonlight's own shape (16 heads on 512 + 64 columns) and two small
    ones, over rows of length 1, inside a block, at a block's edge, and the
    whole cache; blocks of 128 so that four of them are in play."""
    monkeypatch.setattr(da, "_LATENT_BLOCK_BYTES", 0)
    max_seq_len = 512
    assert da.latent_block_k(max_seq_len, rank + rope, dtype) == 128
    lengths = jnp.asarray([1, 77, 128, 129, 511, 512], jnp.int32)
    rows = len(lengths)
    keys = jax.random.split(jax.random.PRNGKey(heads), 4)
    q = jax.random.normal(keys[0], (rows, heads, rank), dtype)
    qr = jax.random.normal(keys[1], (rows, heads, rope), dtype)
    c = jax.random.normal(keys[2], (rows, 1, max_seq_len, rank), dtype)
    r = jax.random.normal(keys[3], (rows, 1, max_seq_len, rope), dtype)
    live = jnp.arange(max_seq_len)[None, None, :, None] < lengths[:, None, None, None]
    scale = 1.0 / math.sqrt(rank // 4 + rope)
    want = _latent_einsum(q, qr, jnp.where(live, c, 0), jnp.where(live, r, 0), lengths, scale)
    # what lies past a row's length is NaN and must not get through
    got = jax.jit(lambda *a: da.latent_decode_attention(*a, sm_scale=scale))(
        q, qr, jnp.where(live, c, jnp.nan), jnp.where(live, r, jnp.nan), lengths)
    assert got.shape == (rows, heads, rank) and got.dtype == dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    bits = 7 if dtype == jnp.bfloat16 else 23
    step = 2.0 ** (math.floor(math.log2(np.abs(want).max())) - bits)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= (1 if dtype == jnp.bfloat16 else 64) * step


def test_latent_block_is_keys_and_values_together():
    # a position's 576 bf16 values are 1152 B, its keys and its values in
    # one: 1024 positions a chunk (the cell's rows are 1024-5120 keys long),
    # where K and V of as many bytes a position would take 256 each
    assert da.latent_block_k(8192, 576, jnp.bfloat16) == 1024
    assert da.block_k(8192, 1, 576, jnp.bfloat16) == 256
    assert da.latent_block_k(256, 576, jnp.bfloat16) == 256
    with pytest.raises(ValueError, match="latent caches"):
        da.latent_decode_attention(
            jnp.zeros((1, 4, 32)), jnp.zeros((1, 4, 8)), jnp.zeros((1, 2, 64, 32)),
            jnp.zeros((1, 1, 64, 8)), jnp.ones((1,), jnp.int32), sm_scale=1.0)


# -- shared code, second model ---------------------------------------------------


@pytest.mark.parametrize("dim,inner,block", [
    (2048, 1024, 1024),  # OLMoE: an expert's matrix is one 4 MB block
    # Moonlight: 11 x 128 has no lane-multiple divisor under 4 MB but 128;
    # on the chip that ran like one whole block (ops/moe_experts.block_f)
    (2048, 1408, 128),
    (4096, 14336, 512),  # Mixtral
    (64, 32, 32),
])
def test_block_f_at_both_routed_models_widths(dim, inner, block):
    assert moe_experts.block_f(dim, inner, jnp.bfloat16) == block


def _requests():
    rng = np.random.RandomState(5)
    shapes = [(9, 6), (17, 3), (5, 9), (12, 5), (7, 2), (20, 4)]
    return [
        GenerationRequest(
            token_ids=rng.randint(3, VOCAB - 1, size=n).tolist(), max_new_tokens=m)
        for n, m in shapes
    ]


def _greedy_reference(cfg, params, prompt, generated):
    n = len(generated)
    tokens = jnp.asarray([list(prompt) + list(generated[:-1])], jnp.int32)
    lg = arch.logits(params, tokens, last=n, **_sizes(cfg))
    return np.asarray(jnp.argmax(lg[0], axis=-1)).tolist()


def test_expert_counters_have_a_row_a_routed_layer(tiny):
    """Layer 0 is dense: two rows of counters for three layers, and each
    counts steps x live rows x k."""
    cfg, params = tiny
    engine = ContinuousBatchingEngine(cfg, params, num_slots=4, seed=0)
    assert engine.expert_stats() == {
        "decode_steps": 0, "assignments": [[0] * cfg.n_experts] * 2,
        "touched": [0, 0]}
    requests = _requests()[:3]
    for r in requests:
        engine.add_request(r)
    engine.run_until_complete()
    stats = engine.expert_stats()
    # a request's first token is the prefill's; the two shorter requests
    # ride one step more each, live to the device (decode runs one ahead)
    decoded = sum(r.max_new_tokens - 1 for r in requests) + 2
    assert stats["decode_steps"] == max(r.max_new_tokens - 1 for r in requests)
    assert len(stats["assignments"]) == len(cfg.routed_layers) == 2
    for layer in range(2):
        assert sum(stats["assignments"][layer]) == decoded * cfg.experts_per_token
        assert stats["touched"][layer] >= cfg.experts_per_token * stats["decode_steps"]


def test_continuous_batching_returns_the_reference_greedy_tokens(tiny):
    """Six requests of mixed lengths through three slots of the paged
    engine, decode one step ahead and the cache donated, as for the other
    families; a cached position costs layers x (rank + rope) x 4 bytes."""
    cfg, params = tiny
    engine = ContinuousBatchingEngine(
        cfg, params, num_slots=3, kv_cache=KVCacheManager(32, 8), seed=0)
    assert engine.cache_bytes_per_token() is None
    requests = _requests()
    rids = [engine.add_request(r) for r in requests[:4]]
    out = {}
    for _ in range(3):
        out.update(dict(engine.step()))
    rids += [engine.add_request(r) for r in requests[4:]]
    out.update(engine.run_until_complete())
    for rid, request in zip(rids, requests):
        got = out[rid].token_ids
        assert len(got) == request.max_new_tokens
        assert got == _greedy_reference(cfg, params, request.token_ids, got)
    assert engine.cache_bytes_per_token() == cfg.n_layers * 40 * 4


def test_a_shared_prefix_is_served_from_the_pool(tiny):
    """The second request's first 16 tokens come from committed blocks of
    latent rows (assemble), its suffix through the absorbed-form chunk."""
    cfg, params = tiny
    kv = KVCacheManager(32, 8)
    engine = ContinuousBatchingEngine(cfg, params, num_slots=2, kv_cache=kv, seed=0)
    rng = np.random.RandomState(11)
    prefix = rng.randint(3, VOCAB - 1, size=16).tolist()
    for tail in ([5, 6, 7], [9, 8, 7, 6, 5]):
        request = GenerationRequest(token_ids=prefix + tail, max_new_tokens=4)
        got = engine.generate([request])[0].token_ids
        assert got == _greedy_reference(cfg, params, request.token_ids, got)
    assert kv.stats()["prefix_hit_tokens"] == 16


@pytest.mark.parametrize("rank,rope", [(512, 64), (32, 8)])
def test_a_latent_row_goes_through_the_kv_manager_as_it_is(rank, rope):
    """ROADMAP R3 said the manager's block shape had to change for a latent
    cache. It does not: the manager shapes a pool from any leaf of ndim >=
    3 with the sequence axis at -2. A row of (1, 1, S, 512) + (1, 1, S, 64)
    through acquire / commit / release / acquire / assemble, and through
    the engine's row insert and extract."""
    seq, block = 64, 8
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    row = {"layer_0": {"attn": {
        "cached_latent": jax.random.normal(keys[0], (1, 1, seq, rank), jnp.bfloat16),
        "cached_rope": jax.random.normal(keys[1], (1, 1, seq, rope), jnp.bfloat16),
        "cache_index": jnp.asarray([20], jnp.int32)}}}
    kv = KVCacheManager(num_blocks=6, block_size=block)
    tokens = list(range(100, 120))
    lease = kv.acquire(tokens)
    assert lease is not None and lease.num_cached_tokens == 0
    kv.initialize(row)
    assert sorted(p.shape for p in kv._pools) == [
        (6, 1, block, rope), (6, 1, block, rank)]
    kv.commit(lease, tokens, row)
    kv.release(lease)
    again = kv.acquire(tokens + [7, 8, 9])
    assert again.num_cached_tokens == 16  # two full blocks of the 20
    built = kv.assemble(again)["layer_0"]["attn"]
    for name in ("cached_latent", "cached_rope"):
        got, want = built[name], row["layer_0"]["attn"][name]
        assert got.shape == want.shape
        assert (np.asarray(got[:, :, :16], np.float32)
                == np.asarray(want[:, :, :16], np.float32)).all()
    assert int(built["cache_index"][0]) == 16
    kv.release(again)
    # the engine's slot pool: a row in, the same row out
    engine = ContinuousBatchingEngine(
        _config(), None, num_slots=3, kv_cache=KVCacheManager(4, block))
    pool = engine._empty_cache(row)
    assert sorted(l.shape for l in jax.tree.leaves(pool)) == [
        (3,), (3, 1, seq, rope), (3, 1, seq, rank)]
    pool = engine._insert_row(pool, row, jnp.asarray(2, jnp.int32))
    back = engine._extract_row(pool, jnp.asarray(2, jnp.int32))
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(row)):
        assert a.shape == b.shape
        assert (np.asarray(a, np.float32) == np.asarray(b, np.float32)).all()
    empty = engine._extract_row(pool, jnp.asarray(0, jnp.int32))
    assert all(float(jnp.abs(l.astype(jnp.float32)).max()) == 0.0
               for l in jax.tree.leaves(empty))


def test_llm_config_builds_the_family_and_refuses_what_has_no_rules():
    kwargs = dict(
        model_id="moonlight-test", model_family="deepseek", kv_cache_blocks=8,
        max_seq_len=64, model_kwargs=dict(KWARGS, max_seq_len=64))
    cfg = LLMConfig(**kwargs).build_model_config()
    assert isinstance(cfg, DeepseekConfig) and cfg.n_kv_heads == 1
    assert cfg.routed_layers == (1, 2) and cfg.routed_config().dropless
    tiny_cfg = LLMConfig(
        model_id="deepseek-tiny", model_family="deepseek").build_model_config()
    assert tiny_cfg.max_seq_len == 512 and tiny_cfg.n_layers == 3
    assert sorted(models.refusals("deepseek")) == ["adapters", "mesh"]
    with pytest.raises(ValueError, match="adapters"):
        LLMConfig(adapters={"max_live": 2}, **kwargs)
    with pytest.raises(ValueError, match="mesh"):
        LLMConfig(mesh={"tp": 2}, **kwargs)
    with pytest.raises(ValueError, match="first_dense_layers"):
        DeepseekConfig.tiny(first_dense_layers=9)
    with pytest.raises(ValueError, match="adapter bank"):
        models.build(tiny_cfg).apply({}, jnp.zeros((1, 2), jnp.int32), {"x": 1})


def test_the_other_families_programs_take_no_new_argument():
    """OLMoE's model still routes by softmax with no bias parameter and no
    scale, and its counters keep a row a layer."""
    from ray_tpu.models.moe import MoEConfig

    cfg = MoEConfig.tiny(dropless=True)
    params = unbox_params(models.init_params(cfg, jax.random.PRNGKey(0)))
    assert "router_bias" not in params["layer_0"]["moe"]
    engine = ContinuousBatchingEngine(cfg, params, num_slots=2, seed=0)
    assert len(engine.expert_stats()["touched"]) == cfg.n_layers
    text = jax.jit(models.build(cfg).apply).lower({"params": params}, _tokens((1, 4))).as_text()
    assert "logistic" not in text


def test_deepseek_serves_through_serve_run(shutdown_only):
    """serve.run -> handle -> replica -> ContinuousBatchingEngine ->
    KVCacheManager, no side script; the replica reports the counters of
    the routed layers and what a cached position costs."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_deployment

    ray_tpu.init(num_cpus=4)
    config = LLMConfig(
        model_id="moonlight-test", model_family="deepseek", max_seq_len=64,
        max_batch_size=2, kv_cache_blocks=16, kv_block_size=8, seed=3,
        model_kwargs=dict(KWARGS))
    try:
        handle = serve.run(
            build_llm_deployment(config), name="mla", route_prefix=None,
            _proxy=False)
        prompt = [5, 9, 2, 7, 11, 13, 4, 8, 15, 16]
        reply = handle.options(timeout_s=120).remote(
            {"token_ids": prompt, "max_new_tokens": 5}).result()
        model_cfg = config.build_model_config()
        params = unbox_params(
            models.init_params(model_cfg, jax.random.PRNGKey(3)))
        assert reply["token_ids"] == _greedy_reference(
            model_cfg, params, prompt, reply["token_ids"])
        info = handle.options(
            method_name="runtime_info", timeout_s=60).remote().result()
        assert info["moe"]["decode_steps"] == 4
        assert len(info["moe"]["touched"]) == 2
        assert info["kv"] == {
            "cache_bytes_per_token": 3 * 40 * 4,
            "row_write": {"cached_latent": "tile", "cached_rope": "tile"},
            # four decode steps of two rows, each one chunk (the whole
            # 64-position cache) long: nothing for the schedule to skip
            "attention_chunks_visited": 8, "attention_chunks_dense": 8,
            "attention_positions_copied": 8 * 64,
            # no per-row state without a sequence axis, so prefixes are shared
            # ... and no window layer's ring (models.WINDOW)
            "state_bytes_per_row": 0, "window_bytes_per_row": 0,
            "prefix_reuse": True, "prefix_reuse_refused": None,
        }
        assert info["kernels"]["latent_decode_attention"] == [True]
        assert info["kernels"]["moe_experts"] == [True]
    finally:
        serve.shutdown()
