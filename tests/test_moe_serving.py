"""A second architecture through the serving stack: an OLMoE-shaped model
(routed experts, q/k RMSNorm, unnormalised top-k weights) built by
``ray_tpu.models`` for the engines, its dropless expert layer, and the
expert counters, against the benchmark's plain reference
(``benchmarks/reference/olmoe_arch.py``), which imports none of the
program's model code.

Tolerance, float32 on the CPU: 1e-4 on logits of magnitude ~1. Both sides
multiply exactly here (the reference under "highest", the kernel's f32
``_dot`` too) and differ only in the order of their float32 sums: the
grouped kernel adds a token's k experts after weighting, the reference
adds all experts in index order; rotary tables and softmax are computed
separately. Measured: 1e-6 or less. A dropped assignment, a renormalised
weight or a missing q/k norm is 1e-3 and more (asserted below).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import olmoe_arch  # noqa: E402
from ray_tpu import models  # noqa: E402
from ray_tpu.llm import LLMConfig  # noqa: E402
from ray_tpu.llm.engine import (  # noqa: E402
    ContinuousBatchingEngine, GenerationRequest,
)
from ray_tpu.models.moe import ROUTING, MoEConfig  # noqa: E402
from ray_tpu.parallel import expert as ep  # noqa: E402
from ray_tpu.parallel.sharding import unbox_params  # noqa: E402

TOL = 1e-4
VOCAB = 96


def _config(**kw):
    base = dict(
        vocab_size=VOCAB, dim=64, n_layers=2, n_heads=4, n_kv_heads=4,
        intermediate=32, n_experts=8, experts_per_token=4, max_seq_len=64,
        rope_theta=10000.0, dtype=jnp.float32, param_dtype=jnp.float32,
        remat=False, dropless=True, norm_topk_prob=False, qk_norm=True,
    )
    base.update(kw)
    return MoEConfig(**base)


def _sizes(cfg):
    return dict(
        n_layers=cfg.n_layers, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        theta=cfg.rope_theta, eps=cfg.norm_eps, top_k=cfg.experts_per_token,
        norm_topk_prob=cfg.norm_topk_prob,
    )


def _params(cfg, seed=0):
    params = unbox_params(models.init_params(cfg, jax.random.PRNGKey(seed)))
    # norms away from one, so that a norm left out or misplaced shows
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def shake(path, leaf):
        if leaf.ndim == 1:
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    return jax.tree_util.tree_map_with_path(shake, params)


def _tokens(shape, seed=2):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 3, VOCAB - 1)


@pytest.fixture(scope="module")
def tiny():
    cfg = _config()
    return cfg, _params(cfg)


def _routing_of(collection, cfg):
    return [np.asarray(collection[f"layer_{i}"]["moe"]["experts"][0])
            for i in range(cfg.n_layers)]


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_full_forward_matches_the_reference(norm_topk_prob):
    cfg = _config(norm_topk_prob=norm_topk_prob)
    params = _params(cfg)
    tokens = _tokens((2, 13))
    got, sown = models.build(cfg).apply(
        {"params": params}, tokens, mutable=[ROUTING])
    routing = []
    want = olmoe_arch.logits(params, tokens, routing=routing, **_sizes(cfg))
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    for mine, theirs in zip(_routing_of(sown[ROUTING], cfg), routing):
        assert (np.sort(mine, -1) == np.sort(np.asarray(theirs), -1)).all()
    # and the two settings are different models
    other = olmoe_arch.logits(
        params, tokens, **dict(_sizes(cfg), norm_topk_prob=not norm_topk_prob))
    assert float(jnp.max(jnp.abs(got - other))) > 10 * TOL


def test_prefill_then_decode_through_the_cache_matches_the_reference(tiny):
    cfg, params = tiny
    tokens = _tokens((2, 17))
    want = olmoe_arch.logits(params, tokens, **_sizes(cfg))
    model = models.build(cfg, None, decode=True)
    got, state = model.apply({"params": params}, tokens[:, :11], mutable=["cache"])
    assert float(jnp.max(jnp.abs(got - want[:, :11]))) < TOL
    # the cache row the KV manager and the decode kernel know
    leaves = jax.tree.leaves(state["cache"]["layer_0"])
    assert sorted(leaf.shape for leaf in leaves) == [
        (2,), (2, 4, 64, 16), (2, 4, 64, 16)]
    step = jax.jit(lambda cache, token: model.apply(
        {"params": params, "cache": cache}, token, mutable=["cache"]))
    for i in range(11, 17):
        got, state = step(state["cache"], tokens[:, i:i + 1])
        assert float(jnp.max(jnp.abs(got[:, 0] - want[:, i]))) < TOL


def test_qk_norm_is_over_the_whole_projection(tiny):
    """Without the norms the same weights give other logits, and so does a
    norm a head at a time: the parity above is not blind to them."""
    cfg, params = tiny
    tokens = _tokens((1, 9))
    want = olmoe_arch.logits(params, tokens, **_sizes(cfg))
    plain = models.build(_config(qk_norm=False)).apply({"params": params}, tokens)
    assert float(jnp.max(jnp.abs(plain - want))) > 10 * TOL


def test_every_row_choosing_one_expert_loses_nothing():
    """Six rows that all choose experts 0 and 1: the capacity path
    (ceil(6 x 2 x 1.25 / 4) = 4 slots an expert) drops two rows' share of
    each, the dropless one computes all twelve assignments."""
    dim, inner, n_experts, k, rows = 16, 8, 4, 2, 6
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(keys[0], (rows, dim))
    w_gate = jax.random.normal(keys[1], (n_experts, dim, inner))
    w_up = jax.random.normal(keys[2], (n_experts, dim, inner))
    w_down = jax.random.normal(keys[3], (n_experts, inner, dim))
    logits = jnp.tile(jnp.asarray([[4.0, 3.0, -9.0, -9.0]]), (rows, 1))
    weights, experts, _ = ep.top_k_routing(logits, k, normalize=True)
    assert (np.asarray(experts) == [0, 1]).all()

    def swiglu(e):
        return (jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]

    want = weights[:, :1] * swiglu(0) + weights[:, 1:] * swiglu(1)
    got = ep.moe_apply_dropless(x, weights, experts, w_gate, w_up, w_down)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4

    capacity = ep.expert_capacity(rows, n_experts, 1.25, k)
    dispatch, combine, _ = ep.top_k_gating(logits, capacity, k=k)
    dropped = ep.moe_apply_gspmd(
        x, dispatch, combine,
        lambda inp: jnp.einsum(
            "ecf,efd->ecd",
            jax.nn.silu(jnp.einsum("ecd,edf->ecf", inp, w_gate))
            * jnp.einsum("ecd,edf->ecf", inp, w_up), w_down))
    assert capacity == 4
    assert float(jnp.max(jnp.abs(dropped[capacity:]))) == 0.0  # rows 4, 5
    assert float(jnp.min(jnp.abs(got[capacity:]).max(axis=-1))) > 1e-2


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
@pytest.mark.parametrize("rows,k", [(1, 2), (5, 3), (40, 4)])
def test_dropless_dispatch_at_ragged_sizes(rows, k, scoring):
    """Rows that do not fill a tile, and more than one tile (40 x 4 = 160
    assignments: two tiles of 128, experts that straddle the boundary),
    under either router's weights (a sigmoid's, scaled, sum to more than
    1)."""
    dim, inner, n_experts = 32, 16, 8
    keys = jax.random.split(jax.random.PRNGKey(rows), 5)
    x = jax.random.normal(keys[0], (rows, dim))
    w_gate = jax.random.normal(keys[1], (n_experts, dim, inner)) / 4
    w_up = jax.random.normal(keys[2], (n_experts, dim, inner)) / 4
    w_down = jax.random.normal(keys[3], (n_experts, inner, dim)) / 4
    weights, experts, _ = ep.top_k_routing(
        jax.random.normal(keys[4], (rows, n_experts)), k, normalize=False,
        scoring=scoring, scale=1.0 if scoring == "softmax" else 2.446)
    want = olmoe_arch.experts_loop(x, weights, experts, w_gate, w_up, w_down)
    got = jax.jit(ep.moe_apply_dropless)(x, weights, experts, w_gate, w_up, w_down)
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_a_random_models_logits_depend_on_its_experts():
    """Each expert's matrices are initialised at their own fan-in. Counted
    over all experts together they were sqrt(n_experts) too small each,
    and the logits of a random OLMoE at its published widths did not move
    when the expert layer was removed (PERF.md, PR 25): a check on random
    weights then checks nothing of the experts."""
    cfg = _config(n_experts=16)
    params = unbox_params(models.init_params(cfg, jax.random.PRNGKey(0)))
    moe = params["layer_0"]["moe"]
    for name, fan_in in (("w_gate", cfg.dim), ("w_up", cfg.dim),
                         ("w_down", cfg.intermediate)):
        assert abs(float(moe[name].std()) * fan_in ** 0.5 - 1.0) < 0.05
    tokens = _tokens((1, 12))
    with_experts = models.build(cfg).apply({"params": params}, tokens)
    without = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf * 0 if path[-1].key == "w_down" else leaf, params)
    moved = jnp.max(jnp.abs(
        with_experts - models.build(cfg).apply({"params": without}, tokens)))
    assert float(moved) > 0.1 * float(jnp.max(jnp.abs(with_experts)))


def test_decode_mode_refuses_a_capacity(tiny):
    cfg, params = tiny
    model = models.build(
        _config(dropless=False, norm_topk_prob=True), None, decode=True)
    with pytest.raises(ValueError, match="dropless"):
        model.apply({"params": params}, _tokens((1, 4)), mutable=["cache"])


def test_the_capacity_path_refuses_unnormalised_weights():
    """``top_k_gating`` always renormalises: OLMoE's setting on the capacity
    path would be Mixtral's mathematics with no error."""
    with pytest.raises(ValueError, match="norm_topk_prob"):
        MoEConfig.tiny(norm_topk_prob=False)
    assert MoEConfig.tiny(norm_topk_prob=False, dropless=True).dropless
    assert MoEConfig.tiny().norm_topk_prob  # the training default stands


def _greedy_reference(cfg, params, prompt, generated):
    """The reference's greedy token at every position of ``generated``,
    teacher-forced on it: equal to ``generated`` exactly when that is the
    reference's own greedy continuation (by induction from the first), in
    one forward pass."""
    n = len(generated)
    tokens = jnp.asarray([list(prompt) + list(generated[:-1])], jnp.int32)
    lg = olmoe_arch.logits(params, tokens, last=n, **_sizes(cfg))
    return np.asarray(jnp.argmax(lg[0], axis=-1)).tolist()


def _requests():
    rng = np.random.RandomState(5)
    shapes = [(9, 6), (17, 3), (5, 9), (12, 5), (7, 2), (20, 4)]
    return [
        GenerationRequest(
            token_ids=rng.randint(3, VOCAB - 1, size=n).tolist(), max_new_tokens=m)
        for n, m in shapes
    ]


def test_continuous_batching_returns_the_reference_greedy_tokens(tiny):
    """Six requests of mixed lengths through three slots of the paged
    engine: admissions and retirements interleave with decode steps, and
    every request still gets the full-forward greedy tokens."""
    from ray_tpu.kvcache import KVCacheManager

    cfg, params = tiny
    engine = ContinuousBatchingEngine(
        cfg, params, num_slots=3, kv_cache=KVCacheManager(32, 8), seed=0)
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


def test_expert_counters_count_live_rows_only(tiny):
    """steps x live rows x k, whatever the free rows of the pool chose. The
    step runs one ahead of the host: a row that has its last token while
    another goes on steps once more, live to the device, before the host
    has seen that token and frees it."""
    cfg, params = tiny
    engine = ContinuousBatchingEngine(cfg, params, num_slots=4, seed=0)
    assert engine.expert_stats() == {
        "decode_steps": 0,
        "assignments": [[0] * cfg.n_experts] * cfg.n_layers,
        "touched": [0] * cfg.n_layers,
    }
    requests = _requests()[:3]
    for r in requests:
        engine.add_request(r)
    engine.run_until_complete()
    stats = engine.expert_stats()
    # a request of m new tokens takes its first from the prefill and m - 1
    # from decode steps
    decoded = sum(r.max_new_tokens - 1 for r in requests)
    # all three are admitted together; the two shorter ones ride a step each
    decoded += 2
    assert stats["decode_steps"] == engine._step_count == max(
        r.max_new_tokens - 1 for r in requests)
    for layer in range(cfg.n_layers):
        assert sum(stats["assignments"][layer]) == decoded * cfg.experts_per_token
        # a step's live rows choose between k and rows x k distinct experts
        assert (cfg.experts_per_token * stats["decode_steps"]
                <= stats["touched"][layer]
                <= min(decoded * cfg.experts_per_token,
                       cfg.n_experts * stats["decode_steps"]))


def test_llm_config_builds_the_family_and_refuses_what_has_no_rules():
    kwargs = dict(
        model_id="olmoe-test", model_family="moe", kv_cache_blocks=8,
        model_kwargs=dict(n_experts=4, experts_per_token=2))
    cfg = LLMConfig(**kwargs).build_model_config()
    assert isinstance(cfg, MoEConfig) and cfg.dropless
    with pytest.raises(ValueError, match="dropless"):
        LLMConfig(**dict(kwargs, model_kwargs=dict(dropless=False)))
    with pytest.raises(ValueError, match="adapters"):
        LLMConfig(adapters={"max_live": 2}, **kwargs)
    with pytest.raises(ValueError, match="mesh"):
        LLMConfig(mesh={"tp": 2}, **kwargs)
    with pytest.raises(ValueError, match="mesh"):
        LLMConfig(tensor_parallel_size=2, **kwargs)
    with pytest.raises(ValueError, match="unknown model family"):
        LLMConfig(model_family="mamba")
    # the dense family still takes both
    LLMConfig(kv_cache_blocks=8, adapters={"max_live": 2}, mesh={"tp": 2})


def test_the_llama_engine_is_what_it_was():
    """The dense family through the same interface: the module the engine
    builds is ``Llama(cfg, mesh, decode=True)``, its decode program takes
    no counters and returns two outputs, and its tokens are the greedy
    tokens of whole forward passes."""
    from conftest import greedy_reference

    from ray_tpu.models.llama import Llama, LlamaConfig

    # float32: in bf16 two of this toy's logits tie exactly
    cfg = LlamaConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    params = unbox_params(models.init_params(cfg, jax.random.PRNGKey(0)))
    engine = ContinuousBatchingEngine(cfg, params, num_slots=2, seed=0)
    assert engine._model == Llama(cfg, None, decode=True)
    assert engine._expert_counts is None and engine.expert_stats() is None
    requests = [
        GenerationRequest(token_ids=[5, 9, 2, 7, 11], max_new_tokens=6),
        GenerationRequest(token_ids=[3, 1, 4, 1, 5, 9, 2, 6], max_new_tokens=4),
    ]
    got = engine.generate(requests)
    assert [r.token_ids for r in got] == [
        greedy_reference(cfg, params, r.token_ids, r.max_new_tokens)
        for r in requests
    ]
    text = engine._decode.lower(
        params, engine._cache, jnp.zeros((2, 1), jnp.int32),
        active=np.ones(2, bool)).as_text()
    assert "moe" not in text
    hlo_outputs = jax.eval_shape(
        engine._decode_impl, params, engine._cache, jnp.zeros((2, 1), jnp.int32))
    assert len(hlo_outputs) == 2


def test_moe_serves_through_serve_run(shutdown_only):
    """serve.run -> handle -> replica -> ContinuousBatchingEngine ->
    KVCacheManager, with no side script; the replica reports the counters."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_deployment

    ray_tpu.init(num_cpus=4)
    config = LLMConfig(
        model_id="olmoe-test", model_family="moe", max_seq_len=64,
        max_batch_size=2, kv_cache_blocks=16, kv_block_size=8, seed=3,
        model_kwargs=dict(
            vocab_size=VOCAB, dim=64, n_layers=2, n_heads=4, n_kv_heads=4,
            intermediate=32, n_experts=8, experts_per_token=4,
            norm_topk_prob=False, qk_norm=True, remat=False,
            dtype=jnp.float32, param_dtype=jnp.float32))
    try:
        handle = serve.run(
            build_llm_deployment(config), name="moe", route_prefix=None,
            _proxy=False)
        prompt = [5, 9, 2, 7, 11, 13, 4]
        reply = handle.options(timeout_s=120).remote(
            {"token_ids": prompt, "max_new_tokens": 5}).result()
        model_cfg = config.build_model_config()
        params = unbox_params(
            models.init_params(model_cfg, jax.random.PRNGKey(3)))
        assert len(reply["token_ids"]) == 5
        assert reply["token_ids"] == _greedy_reference(
            model_cfg, params, prompt, reply["token_ids"])
        info = handle.options(
            method_name="runtime_info", timeout_s=60).remote().result()
        assert info["moe"]["decode_steps"] == 4
        assert info["kernels"]["moe_experts"] == [True]
        assert info["kernels"]["kv_row_write"] == [True]
        assert info["kv"]["row_write"] == {
            "cached_key": "tile", "cached_value": "tile"}
    finally:
        serve.shutdown()
