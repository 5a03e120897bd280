"""A fourth architecture through the serving stack: a Falcon-H1-shaped model
(a Mamba-2 mixer beside grouped-query attention in every block, muP
multipliers on every branch) built by ``ray_tpu.models`` for the engines,
against the benchmark's plain reference
(``benchmarks/reference/falcon_h1_arch.py``), which imports none of the
program's model code and runs the recurrence one position at a time.

What is new to the stack is a cached leaf that is per row and has **no
sequence axis** (``models.STATE``): the interface names a leaf's kind, the
engine and the KV manager ask it there, and a family with such a leaf gets
no prefix reuse.

The toy has the published shape: 2 blocks, hidden 64, GQA 4/2 x 16, 4 mixer
heads x 16, ``d_state`` 16, 2 groups, chunk 8, every multiplier another
value than 1, and ``dt_bias``, ``A_log``, ``D`` and the convolution's bias
non-zero (as initialised, and the norms shaken away from one).

Tolerance, float32 on the CPU: 1e-4 on logits of magnitude ~1-3. Both sides
multiply exactly here; they differ in the order of their float32 sums, and
the prefill besides in its *form* (Mamba-2's chunked matmuls against the
reference's scan over positions). Measured: 3e-6 or less. Every fault
asserted below is 1e-3 and more.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import falcon_h1_arch as arch  # noqa: E402
from ray_tpu import models  # noqa: E402
from ray_tpu.kvcache import KVCacheManager  # noqa: E402
from ray_tpu.llm import LLMConfig  # noqa: E402
from ray_tpu.llm.engine import (  # noqa: E402
    ContinuousBatchingEngine, GenerationRequest,
)
from ray_tpu.models import falcon_h1  # noqa: E402
from ray_tpu.models.falcon_h1 import FalconH1Config  # noqa: E402
from ray_tpu.parallel.sharding import unbox_params  # noqa: E402

TOL = 1e-4
VOCAB = 96
SEQ = 384
KWARGS = dict(
    vocab_size=VOCAB, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    head_dim=16, intermediate=96, mamba_n_heads=4, mamba_d_head=16,
    mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=8,
    embedding_multiplier=3.0, lm_head_multiplier=0.3,
    attention_in_multiplier=0.8, attention_out_multiplier=0.2,
    key_multiplier=0.1, ssm_in_multiplier=0.5, ssm_out_multiplier=0.3,
    ssm_multipliers=(0.4, 0.3, 0.2, 0.6, 0.35), mlp_multipliers=(0.2, 0.05),
    max_seq_len=SEQ, rope_theta=1e11, dtype=jnp.float32,
    param_dtype=jnp.float32,
)
# the same toy as a benchmark configuration file would state it
PUBLISHED = dict(
    name="toy", vocab_size=VOCAB, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=96, mamba_n_heads=4, mamba_d_head=16, mamba_d_ssm=64,
    mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=8,
    embedding_multiplier=3.0, lm_head_multiplier=0.3,
    attention_in_multiplier=0.8, attention_out_multiplier=0.2,
    key_multiplier=0.1, ssm_in_multiplier=0.5, ssm_out_multiplier=0.3,
    ssm_multipliers=[0.4, 0.3, 0.2, 0.6, 0.35], mlp_multipliers=[0.2, 0.05],
    rope_theta=1e11, rms_norm_eps=1e-5,
)
SIZES = arch.sizes_of(PUBLISHED)


def _params(cfg, seed=0):
    """Seeded weights with the norms away from one, so that a norm left out
    or taken over other channels shows."""
    params = unbox_params(models.init_params(cfg, jax.random.PRNGKey(seed)))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def shake(path, leaf):
        if path[-1].key.endswith("norm"):
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    return jax.tree_util.tree_map_with_path(shake, params)


def _tokens(shape, seed=2):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 3, VOCAB - 1)


def _diff(a, b):
    return float(jnp.max(jnp.abs(jnp.asarray(a) - jnp.asarray(b))))


@pytest.fixture(scope="module")
def tiny():
    cfg = FalconH1Config(**KWARGS)
    return cfg, _params(cfg)


def _engine(cfg, params, slots=3, blocks=8, block_size=8, **kw):
    return ContinuousBatchingEngine(
        cfg, params, num_slots=slots,
        kv_cache=KVCacheManager(num_blocks=blocks, block_size=block_size),
        seed=0, **kw)


def _request(tokens, n):
    return GenerationRequest(
        token_ids=[int(t) for t in tokens], max_new_tokens=n)


def _applier(cfg):
    """The serving module's ``apply`` jitted: ``(params, tokens, cache or
    None) -> (logits, cache)``."""
    model = models.build(cfg, None, decode=True)

    @jax.jit
    def apply(params, tokens, cache=None):
        variables = {"params": params}
        if cache is not None:
            variables["cache"] = cache
        logits, state = model.apply(variables, tokens, mutable=["cache"])
        return logits, state["cache"]

    return apply


def _reference_rows(params, prompt, answer):
    """The reference's logits at the positions that chose ``answer``, from
    one pass over prompt + answer[:-1]."""
    toks = [int(t) for t in prompt] + [int(t) for t in answer[:-1]]
    return arch.logits(
        params, jnp.asarray([toks], jnp.int32), last=len(answer), **SIZES)[0]


def _is_the_references_greedy(params, prompt, answer) -> bool:
    """Whether ``answer`` is the reference's own greedy continuation: each
    token the argmax of the reference's logits after the ones before it."""
    rows = _reference_rows(params, prompt, answer)
    return [int(t) for t in jnp.argmax(rows, axis=-1)] == list(answer)


# -- the model against the reference -----------------------------------------


def test_the_configuration_keys_reach_the_program(tiny):
    cfg, _ = tiny
    arguments = arch.llm_arguments(PUBLISHED)
    assert arguments["model_family"] == "falcon_h1"
    built = LLMConfig(
        model_id="toy", max_seq_len=SEQ, kv_cache_blocks=1,
        model_kwargs=dict(arguments["model_kwargs"], dtype=jnp.float32,
                          param_dtype=jnp.float32),
        model_family="falcon_h1",
    ).build_model_config()
    assert built == cfg


def test_init_leaves_every_bias_and_multiplier_live(tiny):
    cfg, params = tiny
    mixer = params["layer_0"]["mixer"]
    for name in ("dt_bias", "A_log", "D", "conv_bias"):
        assert float(jnp.min(jnp.abs(mixer[name]))) > 0, name
    for m in (cfg.embedding_multiplier, cfg.lm_head_multiplier,
              cfg.attention_in_multiplier, cfg.attention_out_multiplier,
              cfg.key_multiplier, cfg.ssm_in_multiplier,
              cfg.ssm_out_multiplier, *cfg.ssm_multipliers,
              *cfg.mlp_multipliers):
        assert m != 1.0


def test_prefill_then_decode_through_the_cache_matches_the_reference(tiny):
    """The prefill runs the chunked form, the steps the update a position:
    logits, every position, against the reference's scan."""
    cfg, params = tiny
    tokens = _tokens((2, 29))
    want = arch.logits(params, tokens, **SIZES)
    apply = _applier(cfg)
    got, cache = apply(params, tokens[:, :19])
    assert _diff(got, want[:, :19]) < TOL
    mixer = cache["layer_0"]["mixer"]
    assert mixer["state_ssm"].shape == (2, 4, 16, 16)
    assert mixer["state_ssm"].dtype == jnp.float32
    assert mixer["state_conv"].shape == (2, 3, 64 + 2 * 2 * 16)
    for t in range(19, 29):
        step, cache = apply(params, tokens[:, t:t + 1], cache)
        assert _diff(step[:, 0], want[:, t]) < TOL, t


@pytest.mark.parametrize("pieces", [(29,), (1,) * 29, (12, 12, 5), (8, 16, 5),
                                    (3, 26)])
def test_chunked_form_step_form_and_pieces_are_one_function(tiny, pieces):
    """One prompt fed whole, a position at a time, in pieces of 12 and in
    pieces that end on and off a chunk's edge: the chunked form continues
    from a row's state and convolution tail. All equal the reference."""
    cfg, params = tiny
    tokens = _tokens((1, 29), seed=5)
    want = arch.logits(params, tokens, **SIZES)
    apply = _applier(cfg)
    cache, got, at = None, [], 0
    for n in pieces:
        out, cache = apply(params, tokens[:, at:at + n], cache)
        got.append(out)
        at += n
    assert _diff(jnp.concatenate(got, axis=1), want) < TOL


def test_recurrence_forms_agree_from_a_nonzero_state():
    """``ssm_chunked`` from a state equals ``ssm_step`` a position at a
    time from the same state, at a length that is no multiple of the
    chunk."""
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    b, s, h, p, g, n = 2, 21, 4, 8, 2, 16
    state = jax.random.normal(keys[0], (b, h, p, n))
    x = jax.random.normal(keys[1], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[2], (b, s, h)) - 2.0)
    a = -jnp.exp(jax.random.normal(keys[3], (h,)))
    b_in = jax.random.normal(keys[4], (b, s, g, n))
    c_in = jax.random.normal(keys[5], (b, s, g, n))
    skip = jax.random.normal(keys[6], (h,))
    end, ys = state, []
    for t in range(s):
        end, y = falcon_h1.ssm_step(
            end, x[:, t], dt[:, t], a, b_in[:, t], c_in[:, t], skip)
        ys.append(y)
    got_end, got = falcon_h1.ssm_chunked(state, x, dt, a, b_in, c_in, skip, 8)
    assert _diff(got, jnp.stack(ys, axis=1)) < 1e-4
    assert _diff(got_end, end) < 1e-4


@pytest.mark.parametrize("fault", ["no_mixer", "no_skip", "zero_state",
                                   "no_tail", "bf16_state"])
def test_what_the_check_has_to_see_moves_the_logits(tiny, fault):
    """The mixer left out, ``D x`` left out, and what a K/V-only prefix hit
    would do (the state zero, or the convolution tail dropped, at the first
    decoded step), and a state rounded to bf16 at each step: each moves the
    logits by more than the tolerance (measured: 5e-4 for the rounded
    state after 8 steps, 0.02 to 1 for the others)."""
    cfg, params = tiny
    tokens = _tokens((1, 24), seed=7)
    want = arch.logits(params, tokens, **SIZES)
    apply = _applier(cfg)
    faulted = params
    if fault in ("no_mixer", "no_skip"):
        def spoil(path, leaf):
            names = [k.key for k in path]
            if fault == "no_mixer" and names[-2:] == ["out_proj", "kernel"]:
                return jnp.zeros_like(leaf)
            if fault == "no_skip" and names[-1] == "D":
                return jnp.zeros_like(leaf)
            return leaf
        faulted = jax.tree_util.tree_map_with_path(spoil, params)
    _, cache = apply(faulted, tokens[:, :16])

    def lose(path, leaf):
        name = path[-1].key
        if (fault, name) in (("zero_state", "state_ssm"), ("no_tail", "state_conv")):
            return jnp.zeros_like(leaf)
        return leaf

    cache = jax.tree_util.tree_map_with_path(lose, cache)
    worst = 0.0
    for t in range(16, 24):
        step, cache = apply(faulted, tokens[:, t:t + 1], cache)
        if fault == "bf16_state":
            cache = jax.tree_util.tree_map_with_path(
                lambda path, leaf: leaf.astype(jnp.bfloat16).astype(leaf.dtype)
                if path[-1].key == "state_ssm" else leaf, cache)
        worst = max(worst, _diff(step[:, 0], want[:, t]))
    assert worst > 3 * TOL, worst


# -- the interface: a leaf's kind, by name ------------------------------------


@pytest.mark.parametrize("family,expected", [
    ("llama", {"cached_key": "sequence", "cached_value": "sequence",
               "cache_index": "index"}),
    ("moe", {"cached_key": "sequence", "cached_value": "sequence",
             "cache_index": "index"}),
    ("deepseek", {"cached_latent": "sequence", "cached_rope": "sequence",
                  "cache_index": "index"}),
    ("falcon_h1", {"cached_key": "sequence", "cached_value": "sequence",
                   "cache_index": "index", "state_ssm": "state",
                   "state_conv": "state"}),
])
def test_cache_leaves_classify_by_name(family, expected):
    """Each old family's cache leaves are what they were; the new family's
    state leaves are per-row state. Told by name, whatever the rank."""
    cfg = LLMConfig(
        model_id=f"{family}-tiny", model_family=family, max_seq_len=32,
    ).build_model_config()
    params = jax.eval_shape(
        lambda: unbox_params(models.init_params(cfg, jax.random.PRNGKey(0))))
    model = models.build(cfg, None, decode=True)
    cache = jax.eval_shape(
        lambda p: model.apply(
            {"params": p}, jnp.zeros((1, 4), jnp.int32), mutable=["cache"])[1],
        params)["cache"]
    kinds = models.cache_kinds(cache)
    got = {path[-1].key: kind for path, kind
           in jax.tree_util.tree_leaves_with_path(kinds)}
    assert got == expected
    assert models.carries_row_state(cfg) == ("state" in expected.values())
    # the rank would have said otherwise: a state leaf has 3 or 4 axes
    ranks = {path[-1].key: len(leaf.shape) for path, leaf
             in jax.tree_util.tree_leaves_with_path(cache)}
    for name, kind in expected.items():
        assert (ranks[name] == 1) == (kind == "index")


@pytest.mark.parametrize("feature,kwargs", [
    ("adapters", {"adapters": {"max_live": 2}}),
    ("mesh", {"mesh": {"tp": 2}}),
])
def test_refusals(feature, kwargs):
    assert set(models.refusals("falcon_h1")) == {"adapters", "mesh"}
    with pytest.raises(ValueError, match=feature):
        LLMConfig(model_id="falcon-tiny", model_family="falcon_h1",
                  kv_cache_blocks=4, **kwargs)


def test_no_partition_rule_for_a_state_leaf():
    from ray_tpu.parallel.plan import PartitionPlan

    plan = PartitionPlan.__new__(PartitionPlan)
    plan.kv_sharding = lambda: "kv"
    plan.replicated = lambda: "rep"
    shapes = {"attn": {"cached_key": jax.ShapeDtypeStruct((1, 2, 8, 4), jnp.float32),
                       "cache_index": jax.ShapeDtypeStruct((1,), jnp.int32)}}
    assert plan.cache_shardings(shapes) == {
        "attn": {"cached_key": "kv", "cache_index": "rep"}}
    shapes["mixer"] = {"state_ssm": jax.ShapeDtypeStruct((1, 2, 4, 4), jnp.float32)}
    with pytest.raises(ValueError, match="state"):
        plan.cache_shardings(shapes)


# -- the engine ---------------------------------------------------------------


def test_engine_tokens_and_state_through_the_slot_cache(tiny):
    """Three requests of different lengths through admission, the slot
    cache (row insert, the pool's decode step one ahead) and retirement:
    each gets the reference's own greedy tokens; the slot rows carry the
    state leaves as they stand."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompts = [_tokens((n,), seed=10 + n) for n in (9, 16, 21)]
    results = engine.generate([_request(p, 12) for p in prompts])
    for prompt, result in zip(prompts, results):
        assert len(result.token_ids) == 12
        assert _is_the_references_greedy(params, prompt, result.token_ids)
    kinds = jax.tree.leaves(models.cache_kinds(engine._cache))
    shapes = [leaf.shape for leaf in jax.tree.leaves(engine._cache)]
    assert (3, 4, 16, 16) in shapes and (3, 3, 128) in shapes
    assert kinds.count("state") == 2 * cfg.n_layers
    # a row leaves the pool and comes back as it was
    at = jnp.asarray(1, jnp.int32)
    row = engine._extract_row(engine._cache, at)
    assert all(leaf.shape[0] == 1 for leaf in jax.tree.leaves(row))
    before = [np.asarray(leaf) for leaf in jax.tree.leaves(engine._cache)]
    engine._cache = engine._insert_row(engine._cache, row, at)
    for a, b in zip(before, jax.tree.leaves(engine._cache)):
        assert (a == np.asarray(b)).all()


def test_engine_steps_match_the_reference_logits(tiny):
    """The engine's own jitted prefill, row insert and decode at the pool's
    shape, fed the reference's tokens: every step's logits."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompt = _tokens((14,), seed=21)
    tokens = _engine(cfg, params).generate([_request(prompt, 10)])[0].token_ids
    want = _reference_rows(params, prompt, tokens)
    assert [int(t) for t in jnp.argmax(want, axis=-1)] == tokens
    logits, row = engine._prefill(params, jnp.asarray([prompt], jnp.int32))
    assert _diff(logits[0], want[0]) < TOL
    engine._cache = engine._insert_row(
        engine._empty_cache(row), row, jnp.asarray(2, jnp.int32))
    active = np.array([False, False, True])
    for step, token in enumerate(tokens[:-1]):
        last = np.zeros((3, 1), np.int32)
        last[2] = token
        logits, engine._cache = engine._decode(
            params, engine._cache, jnp.asarray(last), active=active)
        assert _diff(logits[2], want[step + 1]) < TOL, step
        assert bool(jnp.all(jnp.isfinite(logits)))


def test_a_freed_row_taken_again_is_a_fresh_row(tiny):
    """A pool steps every row, live or free. Slot 0's request ends after 4
    tokens; the slot stays free for 300 steps of another request and is
    then taken again: the answer is a fresh engine's, the free row's state
    stayed finite and is zero while it is free."""
    cfg, params = tiny
    engine = _engine(cfg, params, slots=2)
    short = _request(_tokens((11,), seed=31), 4)
    long_ = _request(_tokens((10,), seed=32), 320)
    rid_short, rid_long = engine.add_request(short), engine.add_request(long_)
    done = {}
    while rid_short not in done:
        done.update(engine.step())
    for _ in range(300):
        done.update(engine.step())
    assert rid_long not in done and list(engine._slots) == [1]
    for leaf, kind in zip(jax.tree.leaves(engine._cache),
                          jax.tree.leaves(models.cache_kinds(engine._cache))):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))
    again = _request(_tokens((13,), seed=33), 10)
    rid = engine.add_request(again)
    while rid not in done:
        done.update(engine.step())
    assert rid_long not in done or len(done[rid_long].token_ids) == 320
    fresh = _engine(cfg, params, slots=2).generate([again])[0]
    assert done[rid].token_ids == fresh.token_ids
    assert _is_the_references_greedy(params, again.token_ids, fresh.token_ids)


def test_a_free_rows_state_is_zeroed_each_step(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params, slots=2)
    _, row = engine._prefill(params, jnp.asarray([_tokens((9,), seed=3)], jnp.int32))
    cache = engine._empty_cache(row)
    for at in (0, 1):
        cache = engine._insert_row(cache, row, jnp.asarray(at, jnp.int32))
    last = jnp.asarray([[5], [5]], jnp.int32)
    active = np.array([True, False])
    _, stepped = engine._decode(params, cache, last, active=active)
    _, alone = engine._decode(
        params, engine._insert_row(
            engine._empty_cache(row), row, jnp.asarray(0, jnp.int32)),
        last, active=active)
    # the free row holds what one step from zero leaves, not the request's
    for a, b, kind in zip(jax.tree.leaves(stepped), jax.tree.leaves(alone),
                          jax.tree.leaves(models.cache_kinds(stepped))):
        if kind == "state":
            assert _diff(a[1], b[1]) == 0.0 and _diff(a[0], b[0]) == 0.0


@pytest.mark.parametrize("second", ["same", "extended"])
def test_no_request_is_served_a_cached_prefix(tiny, second):
    """One prompt twice, and a prompt that extends it by two blocks, through
    one engine with a block pool that could hold both: every answer is a
    fresh engine's (which is the reference's), nothing was matched,
    committed or pooled, and the manager counts what it skipped."""
    cfg, params = tiny
    engine = _engine(cfg, params, blocks=16)
    prompt = [int(t) for t in _tokens((24,), seed=41)]
    first = engine.generate([_request(prompt, 9)])[0]
    other = prompt if second == "same" else prompt + [
        int(t) for t in _tokens((16,), seed=42)]
    answer = engine.generate([_request(other, 9)])[0]
    fresh = _engine(cfg, params, blocks=16).generate([_request(other, 9)])[0]
    assert answer.token_ids == fresh.token_ids
    assert _is_the_references_greedy(params, other, answer.token_ids)
    assert _is_the_references_greedy(params, prompt, first.token_ids)
    stats = engine._kv.stats()
    assert stats["prefix_reuse"] is False and "no sequence axis" in stats[
        "prefix_reuse_refused"]
    assert stats["hits"] == 0 and stats["prefix_hit_tokens"] == 0
    assert stats["blocks_in_use"] == 0 and stats["index_nodes"] == 0
    assert stats["kv_pool_bytes_total"] == 0 and not engine._kv.ready
    assert stats["reuse_refused_leases"] == 2
    assert stats["reuse_refused_blocks"] == 3 + len(other) // 8
    assert engine._kv.cached_blocks(prompt) == 0


def test_the_manager_refuses_by_itself_and_shapes_no_pool(tiny):
    """A manager nobody told: handed a row with a state leaf it refuses
    reuse there and then, and shapes no pool from it."""
    cfg, params = tiny
    _, row = _engine(cfg, params)._prefill(
        params, jnp.asarray([_tokens((9,))], jnp.int32))
    kv = KVCacheManager(num_blocks=4, block_size=8)
    assert kv.prefix_reuse
    kv.initialize(row)
    assert not kv.prefix_reuse and not kv.ready
    lease = kv.acquire(list(range(20)))
    assert lease.cacheable is False and lease.num_cached_tokens == 0
    assert kv.commit(lease, list(range(20)), row) == 0
    kv.release(lease)
    # and one that already shares blocks cannot start refusing
    plain = KVCacheManager(num_blocks=4, block_size=8)
    plain.initialize({"k": jnp.zeros((1, 2, 32, 4)),
                      "cache_index": jnp.zeros((1,), jnp.int32)})
    with pytest.raises(RuntimeError, match="already"):
        plain.refuse_prefix_reuse("late")


def test_chunked_prefill_in_pieces_of_96_equals_one_prefill(tiny):
    """``prefill_chunk_tokens`` 96 (pieces of at most a block of 64 and a
    budget of 96 a step): the chunks continue from the row's state."""
    cfg, params = tiny
    prompt = [int(t) for t in _tokens((200,), seed=51)]
    want = _engine(cfg, params, block_size=64).generate([_request(prompt, 8)])[0]
    engine = _engine(cfg, params, block_size=64, prefill_chunk_tokens=96)
    rid = engine.add_request(_request(prompt, 8))
    steps_with_prefill, done = 0, {}
    while rid not in done:
        done.update(engine.step())
        steps_with_prefill += bool(engine.last_step_prefill_tokens)
    assert steps_with_prefill == 3  # 96 + 96 + 8
    assert done[rid].token_ids == want.token_ids
    assert _is_the_references_greedy(params, prompt, want.token_ids)


def test_runtime_info_kv_keys(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    assert engine.state_bytes_per_row() is None
    engine.generate([_request(_tokens((9,)), 3)])
    # K and V: 2 leaves x 2 heads x 16 x 4 B a layer; state: 4 x 16 x 16 x 4 B
    # + the tail 3 x 128 x 4 B a layer
    assert engine.cache_bytes_per_token() == 2 * (2 * 2 * 16 * 4)
    assert engine.state_bytes_per_row() == 2 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    assert set(engine.row_write()) == {"cached_key", "cached_value"}
    assert engine._state_span == {"state_rows": 3}
    llama = ContinuousBatchingEngine(
        LLMConfig(model_id="llama-tiny", max_seq_len=32).build_model_config(),
        None, num_slots=2)
    assert llama._state_span == {}
