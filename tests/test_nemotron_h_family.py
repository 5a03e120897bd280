"""A seventh architecture through the serving stack: a Nemotron-H-shaped
model (layers that are a Mamba-2 mixer, a NoPE grouped-query attention or a
latent mixture of experts *alone*, a held share of ungated relu^2 experts
that work in a latent narrower than the model) built by ``ray_tpu.models``
for the engines, against the benchmark's plain reference
(``benchmarks/reference/nemotron_h_arch.py``), which imports none of the
program's model code and runs the recurrence one position at a time.

What is new to the stack: layers that keep unlike cache leaves (state,
sequence, nothing), routed layers that are nothing but experts, the expert
layer's latent and its two-matrix expert; the mixer is ``falcon_h1``'s
module at other numbers, so ``tests/test_falcon_h1_family.py`` runs over
the same code unedited.

The toy has the published shape: pattern ``MEM*E`` (all three kinds),
hidden 64, 16 mixer heads of 8 in 2 groups, state 16, chunk 8, GQA 4/2 x
16, 16 experts routed over of which 4..8 are held, top 4, latent 32, inner
48, shared 96, norms shaken away from one, the router's bias non-zero.

Tolerance, float32 on the CPU: 1e-4 on logits of magnitude ~1-4, under the
experts the program chose (``follow=``: top-4 of 16 is a discontinuity).
Both sides multiply exactly here; they differ in the order of their float32
sums, and the prefill besides in its *form* (Mamba-2's chunked form against
the reference's scan over positions). Measured: 1e-5 or less.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import nemotron_h_arch as arch  # noqa: E402
from ray_tpu import models  # noqa: E402
from ray_tpu.kvcache import KVCacheManager  # noqa: E402
from ray_tpu.llm import LLMConfig  # noqa: E402
from ray_tpu.llm.engine import (  # noqa: E402
    ContinuousBatchingEngine, GenerationRequest,
)
from ray_tpu.models import falcon_h1, nemotron_h  # noqa: E402
from ray_tpu.models.nemotron_h import NemotronHConfig  # noqa: E402
from ray_tpu.parallel.sharding import unbox_params  # noqa: E402

TOL = 1e-4
VOCAB = 96
SEQ = 256
HELD = (4, 8)
PATTERN = "MEM*E"
KWARGS = dict(
    vocab_size=VOCAB, dim=64, pattern=PATTERN, n_heads=4, n_kv_heads=2,
    head_dim=16, mamba_n_heads=16, mamba_d_head=8, mamba_d_state=16,
    mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=8,
    moe_intermediate=48, moe_latent=32, shared_intermediate=96,
    n_experts=16, experts_per_token=4, norm_topk_prob=True, routed_scale=5,
    experts_held=HELD, norm_eps=1e-5, max_seq_len=SEQ, dtype=jnp.float32,
    param_dtype=jnp.float32,
)
# the same toy as a benchmark configuration file would state it
PUBLISHED = dict(
    name="toy", vocab_size=VOCAB, hidden_size=64, num_hidden_layers=5,
    hybrid_override_pattern=PATTERN, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, expand=2, mamba_num_heads=16,
    mamba_head_dim=8, ssm_state_size=16, n_groups=2, conv_kernel=4,
    chunk_size=8, layer_norm_epsilon=1e-5, moe_intermediate_size=48,
    moe_latent_size=32, moe_shared_expert_intermediate_size=96,
    n_routed_experts=4, experts_first=4, published={"n_routed_experts": 16},
    n_shared_experts=1, num_experts_per_tok=4, norm_topk_prob=True,
    routed_scaling_factor=5, mlp_hidden_act="relu2", use_conv_bias=True,
    num_nextn_predict_layers=0,
)


def _sizes(**changed):
    sizes = arch.sizes_of(dict(PUBLISHED, **changed))
    for key in ("guaranteed", "n_routed", "n_held"):
        sizes.pop(key)
    return sizes


SIZES = _sizes()


def _params(cfg, seed=0):
    """Seeded weights with the norms away from one and the router's bias
    away from zero, so that a norm or a bias left out shows."""
    params = unbox_params(models.init_params(cfg, jax.random.PRNGKey(seed)))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def shake(path, leaf):
        name = path[-1].key
        if name.endswith("norm"):
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)
        if name == "router_bias":
            return 0.2 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    return jax.tree_util.tree_map_with_path(shake, params)


def _tokens(shape, seed=2):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 3, VOCAB - 1)


def _diff(a, b):
    return float(jnp.max(jnp.abs(jnp.asarray(a) - jnp.asarray(b))))


@pytest.fixture(scope="module")
def tiny():
    cfg = NemotronHConfig(**KWARGS)
    return cfg, _params(cfg)


@pytest.fixture(scope="module")
def apply(tiny):
    """The serving module's ``apply`` jitted: ``(params, tokens, cache or
    None) -> (logits, cache, each expert layer's chosen experts)``."""
    cfg, _ = tiny
    model = models.build(cfg, None, decode=True)

    @jax.jit
    def apply(params, tokens, cache=None):
        variables = {"params": params}
        if cache is not None:
            variables["cache"] = cache
        logits, state = model.apply(
            variables, tokens, mutable=["cache", models.ROUTING])
        return logits, state["cache"], arch.program_routing(
            state[models.ROUTING], cfg.n_layers)

    return apply


def _engine(cfg, params, slots=3, blocks=8, block_size=8, **kw):
    return ContinuousBatchingEngine(
        cfg, params, num_slots=slots,
        kv_cache=KVCacheManager(num_blocks=blocks, block_size=block_size),
        seed=0, **kw)


def _request(tokens, n):
    return GenerationRequest(
        token_ids=[int(t) for t in tokens], max_new_tokens=n)


def _followed(params, tokens, chosen, slack=None):
    """The reference's logits under the experts the program chose."""
    return arch.logits(params, tokens, follow=chosen, slack=slack, **SIZES)


def _pieces(apply, params, tokens, pieces, cache=None):
    """``tokens`` fed in ``pieces``: the logits, the last cache, and each
    expert layer's chosen experts over all the positions."""
    got, chosen, at = [], None, 0
    for n in pieces:
        out, cache, chose = apply(params, tokens[:, at:at + n], cache)
        got.append(out)
        b = tokens.shape[0]
        chose = [c.reshape(b, n, -1) for c in chose]
        chosen = chose if chosen is None else [
            jnp.concatenate(pair, axis=1) for pair in zip(chosen, chose)]
        at += n
    return (jnp.concatenate(got, axis=1), cache,
            [c.reshape(-1, c.shape[-1]) for c in chosen])


def _is_the_references_greedy(params, prompt, answer) -> bool:
    toks = [int(t) for t in prompt] + [int(t) for t in answer[:-1]]
    rows = arch.logits(
        params, jnp.asarray([toks], jnp.int32), last=len(answer), **SIZES)[0]
    return [int(t) for t in jnp.argmax(rows, axis=-1)] == list(answer)


# -- the model against the reference -----------------------------------------

def test_the_configuration_keys_reach_the_program(tiny):
    cfg, _ = tiny
    arguments = arch.llm_arguments(PUBLISHED)
    assert arguments["model_family"] == "nemotron_h"
    built = NemotronHConfig(**dict(
        arguments["model_kwargs"], max_seq_len=SEQ, dtype=jnp.float32,
        param_dtype=jnp.float32))
    assert built == cfg
    assert cfg.n_layers == 5 and cfg.routed_layers == (1, 4)
    assert cfg.n_experts == 16 and cfg.routed_config().n_experts_held == 4
    # the published model's own numbers are the defaults
    full = NemotronHConfig()
    assert (full.n_layers, len(full.routed_layers)) == (88, 40)
    assert [i for i, k in enumerate(full.pattern) if k == "*"] == [
        7, 16, 25, 36, 47, 58, 69, 78]
    assert full.mixer_config().in_proj_columns == (8192, 8192, 1024, 1024, 128)
    assert full.routed_config().expert_dim == 1024
    with pytest.raises(ValueError, match="pattern"):
        NemotronHConfig.tiny(pattern="MXE")


def test_whole_sequence_matches_the_reference(tiny, apply):
    cfg, params = tiny
    tokens = _tokens((2, 29))
    got, _, chosen = _pieces(apply, params, tokens, (29,))
    assert len(chosen) == 2  # the two expert layers, and they alone, route
    slack: list = []
    assert _diff(got, _followed(params, tokens, chosen, slack=slack)) < TOL
    # the program's choice is the reference's own nearly everywhere
    assert float(jnp.mean(jnp.stack(slack) == 0)) > 0.95
    assert float(jnp.max(jnp.stack(slack))) < 1e-4
    # ... and some of it falls on experts held elsewhere, some here
    here = (chosen[0] >= HELD[0]) & (chosen[0] < HELD[1])
    assert 0 < int(here.sum()) < here.size


def test_prefill_then_decode_through_the_cache_matches_the_reference(
        tiny, apply):
    """The prefill runs the chunked form, the steps the update a position:
    logits, every position, against the reference's scan; and a layer
    keeps the leaves of its kind, an expert layer none."""
    cfg, params = tiny
    tokens = _tokens((2, 29))
    got, cache, chosen = _pieces(apply, params, tokens, (19,) + (1,) * 10)
    assert _diff(got, _followed(params, tokens, chosen)) < TOL
    assert set(cache) == {"layer_0", "layer_2", "layer_3"}
    for layer in ("layer_0", "layer_2"):
        mixer = cache[layer]["mixer"]
        assert set(mixer) == {"state_ssm", "state_conv"}
        assert mixer["state_ssm"].shape == (2, 16, 8, 16)
        assert mixer["state_ssm"].dtype == jnp.float32
        assert mixer["state_conv"].shape == (2, 3, 128 + 2 * 2 * 16)
    attn = cache["layer_3"]["attn"]
    assert set(attn) == {"cached_key", "cached_value", "cache_index"}
    assert attn["cached_key"].shape == (2, 2, SEQ, 16)
    assert [int(i) for i in attn["cache_index"]] == [29, 29]
    kinds = models.cache_kinds(cache)
    assert set(jax.tree.leaves(kinds["layer_0"])) == {models.STATE}
    assert sorted(jax.tree.leaves(kinds["layer_3"])) == [
        models.INDEX, models.SEQUENCE, models.SEQUENCE]


@pytest.mark.parametrize("pieces", [(1,) * 29, (12, 12, 5), (8, 16, 5),
                                    (3, 26)])
def test_chunked_form_step_form_and_pieces_are_one_function(
        tiny, apply, pieces):
    """One prompt fed a position at a time, in pieces of 12 and in pieces
    that end on and off a chunk's edge: the chunked form continues from a
    row's state and convolution tail, attention from its cache. All equal
    the reference."""
    cfg, params = tiny
    tokens = _tokens((1, 29), seed=5)
    got, _, chosen = _pieces(apply, params, tokens, pieces)
    assert _diff(got, _followed(params, tokens, chosen)) < TOL


def test_the_mixers_two_forms_agree_at_eight_groups_of_sixteen_heads():
    """The shared recurrence at the published grouping (128 heads, 8
    groups: 16 heads share a group's B and C; Falcon-H1's is 32 in 2), from
    a non-zero state, a length that is no multiple of the chunk."""
    b, s, h, p, n, g = 2, 21, 128, 4, 8, 8
    keys = jax.random.split(jax.random.PRNGKey(3), 7)
    state = jax.random.normal(keys[0], (b, h, p, n))
    x = jax.random.normal(keys[1], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[2], (b, s, h)))
    a = -jnp.exp(jax.random.uniform(keys[3], (h,), minval=0.0, maxval=2.0))
    b_in = jax.random.normal(keys[4], (b, s, g, n))
    c_in = jax.random.normal(keys[5], (b, s, g, n))
    skip = jax.random.normal(keys[6], (h,))
    end, want = state, []
    for t in range(s):
        end, y = falcon_h1.ssm_step(
            end, x[:, t], dt[:, t], a, b_in[:, t], c_in[:, t], skip)
        want.append(y)
    got_end, got = falcon_h1.ssm_chunked(state, x, dt, a, b_in, c_in, skip, 8)
    assert _diff(got, jnp.stack(want, axis=1)) < 1e-4
    assert _diff(got_end, end) < 1e-4
    # ... and a head reads its own group's B and C: heads 0-15 group 0
    moved = b_in.at[:, :, 1].add(1.0)
    _, other = falcon_h1.ssm_chunked(state, x, dt, a, moved, c_in, skip, 8)
    changed = jnp.max(jnp.abs(other - got), axis=(0, 1, 3)) > 1e-6
    assert [bool(c) for c in changed] == [16 <= i < 32 for i in range(h)]


def test_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """One latent expert layer cut four ways: each share's routed part (its
    own experts' sum through the shared ``W_lat_out``), and the shared
    expert and nothing else counted once, add up to what the uncut
    reference gives for the whole layer."""
    whole = NemotronHConfig(**dict(KWARGS, pattern="E", experts_held=None))
    layer = nemotron_h.Layer(whole, "E")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 64))
    params = unbox_params(layer.init(jax.random.PRNGKey(1), x)["params"])
    params["moe"]["router_bias"] = 0.2 * jax.random.normal(
        jax.random.PRNGKey(2), (16,))
    sizes = dict(SIZES["expert_sizes"], experts_first=0)
    want, own, _ = arch.experts_layer(
        x, arch.layer_weights({"layer_0": params}, 0, "E"), eps=1e-5, **sizes)
    uncut, sown = layer.apply(
        {"params": params}, x, mutable=[models.ROUTING])
    assert _diff(uncut, want) < 1e-5
    assert _diff(sown[models.ROUTING]["moe"]["experts"][0], own) == 0
    # what every chip computes alike
    h = arch.rmsnorm(x, params["norm"], 1e-5)
    shared = nemotron_h.SharedExpert(whole).apply(
        {"params": params["shared"]}, h)
    parts = []
    for first in range(0, 16, 4):
        cut = NemotronHConfig(**dict(
            KWARGS, pattern="E", experts_held=(first, first + 4)))
        held = dict(params, moe=dict(
            params["moe"], w_up=params["moe"]["w_up"][first:first + 4],
            w_down=params["moe"]["w_down"][first:first + 4]))
        out = nemotron_h.Layer(cut, "E").apply({"params": held}, x)
        parts.append(out - x - shared)
    assert all(float(jnp.max(jnp.abs(p))) > 1e-3 for p in parts)
    assert _diff(x + sum(parts) + shared, want) < 1e-5
    # a share is the reference's share
    share, _, _ = arch.experts_layer(
        x, arch.layer_weights({"layer_0": held}, 0, "E"), eps=1e-5,
        **dict(sizes, experts_first=12))
    assert _diff(out, share) < 1e-5


def test_bf16_weights_are_drawn_in_float32():
    cfg = NemotronHConfig.tiny(param_dtype=jnp.bfloat16)
    got = unbox_params(models.init_params(cfg, jax.random.PRNGKey(0)))
    want = unbox_params(models.init_params(
        NemotronHConfig.tiny(param_dtype=jnp.float32), jax.random.PRNGKey(0)))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == jnp.bfloat16
        assert _diff(a.astype(jnp.float32), b.astype(jnp.bfloat16)) == 0.0


@pytest.mark.parametrize("feature,kwargs", [
    ("adapters", {"adapters": {"max_live": 2}}),
    ("mesh", {"mesh": {"tp": 2}}),
])
def test_refusals(feature, kwargs):
    reasons = models.refusals("nemotron_h")
    assert set(reasons) == {"adapters", "mesh"}
    with pytest.raises(ValueError) as refused:
        LLMConfig(model_id="nemotron-tiny", model_family="nemotron_h",
                  kv_cache_blocks=4, **kwargs)
    assert feature in str(refused.value)
    assert reasons[feature] in str(refused.value)


def test_llm_config_builds_the_family():
    cfg = LLMConfig(
        model_id="nemotron-tiny", model_family="nemotron_h",
        model_kwargs={"pattern": "M*E"}, max_seq_len=64, kv_cache_blocks=1,
    ).build_model_config()
    assert isinstance(cfg, NemotronHConfig)
    assert (cfg.pattern, cfg.max_seq_len) == ("M*E", 64)
    assert models.carries_row_state(cfg)
    assert not models.restarts_own_state(cfg)
    with pytest.raises(NotImplementedError, match="serving"):
        models.build(cfg, None, decode=False)


# -- the engine ---------------------------------------------------------------

def test_engine_tokens_and_state_through_the_slot_cache(tiny):
    """Three requests of different lengths through admission, the slot
    cache (row insert, the pool's decode step one ahead) and retirement:
    each gets the reference's own greedy tokens; layers without leaves
    carry nothing."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompts = [_tokens((n,), seed=10 + n) for n in (9, 16, 21)]
    results = engine.generate([_request(p, 12) for p in prompts])
    for prompt, result in zip(prompts, results):
        assert len(result.token_ids) == 12
        assert _is_the_references_greedy(params, prompt, result.token_ids)
    assert set(engine._cache) == {"layer_0", "layer_2", "layer_3"}
    kinds = jax.tree.leaves(models.cache_kinds(engine._cache))
    assert kinds.count("state") == 2 * 2 and kinds.count("sequence") == 2
    assert engine._state_span == {"state_rows": 3}
    stats = engine._kv.stats()
    assert stats["prefix_reuse"] is False and stats["hits"] == 0
    engine.close()


def test_engine_steps_match_the_reference_logits_two_rows_live(tiny, apply):
    """The engine's own jitted prefill, row insert and decode at the pool's
    shape, two rows live and one of them in a slot another row left, every
    step's logits under the step's own choice of experts (the counters'
    ``choice``): what the benchmark's check does at the cell's size."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompt = _tokens((14,), seed=21)
    solo = _engine(cfg, params)
    tokens = solo.generate([_request(prompt, 10)])[0].token_ids
    solo.close()
    logits, row = engine._prefill(params, jnp.asarray([prompt], jnp.int32))
    other = engine._prefill(
        params, jnp.asarray([_tokens((9,), seed=22)], jnp.int32))[1]
    cache = engine._empty_cache(row)
    cache = engine._insert_row(cache, other, jnp.asarray(2, jnp.int32))
    zeroed = jax.tree.map(jnp.zeros_like, engine._expert_counts)
    assert zeroed["assignments"].shape == (2, 4)  # expert layers x held
    # slot 2: someone else for 3 steps, then free for 2, then the request
    got, chose = [], []
    for step in range(5 + len(tokens) - 1):
        active = np.array([True, False, step < 3 or step >= 5])
        last = np.full((3, 1), 7, np.int32)
        if step == 5:
            cache = engine._insert_row(cache, row, jnp.asarray(2, jnp.int32))
        if step >= 5:
            last[2] = tokens[step - 5]
        out, cache, counts = engine._decode(
            params, cache, jnp.asarray(last), active=active,
            expert_counts=zeroed)
        assert bool(jnp.all(jnp.isfinite(out)))
        live = int(active.sum()) * cfg.experts_per_token
        assert [int(n) for n in counts["assignments"].sum(1) + counts["absent"]
                ] == [live] * 2
        if step >= 5:
            got.append(out[2])
            chose.append(counts["choice"][:, 2])
    chose = jnp.stack(chose)  # (steps, expert layers, k)
    fed = jnp.asarray([list(map(int, prompt)) + tokens[:-1]], jnp.int32)
    prefilled = apply(params, fed[:, :14])[2]
    follow = [jnp.concatenate([prefilled[layer], chose[:, layer]])
              for layer in range(2)]
    want = _followed(params, fed, follow)[0]
    assert _diff(jnp.stack(got), want[14:]) < TOL
    assert _diff(logits[0], want[13]) < TOL
    engine.close()


def test_a_freed_row_taken_again_is_a_fresh_row(tiny):
    """Slot 0's request ends after 4 tokens; the slot stays free for 20
    steps of another request and is then taken again: the answer is a fresh
    engine's, so the freed row's state restarted (the engine zeroes a free
    row's mixer state every step it is free)."""
    cfg, params = tiny
    engine = _engine(cfg, params, slots=2)
    short = _request(_tokens((11,), seed=31), 4)
    long_ = _request(_tokens((10,), seed=32), 40)
    rid_short, rid_long = engine.add_request(short), engine.add_request(long_)
    done = {}
    while rid_short not in done:
        done.update(engine.step())
    for _ in range(20):
        done.update(engine.step())
    assert rid_long not in done and list(engine._slots) == [1]
    for leaf, kind in zip(jax.tree.leaves(engine._cache),
                          jax.tree.leaves(models.cache_kinds(engine._cache))):
        if kind == models.STATE:
            assert float(jnp.max(jnp.abs(leaf[1].astype(jnp.float32)))) > 0
    again = _request(_tokens((13,), seed=33), 10)
    rid = engine.add_request(again)
    while rid not in done:
        done.update(engine.step())
    fresh_engine = _engine(cfg, params, slots=2)
    fresh = fresh_engine.generate([again])[0]
    assert done[rid].token_ids == fresh.token_ids
    assert _is_the_references_greedy(params, again.token_ids, fresh.token_ids)
    engine.close(), fresh_engine.close()


def test_a_free_rows_state_is_zeroed_each_step(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params, slots=2)
    _, row = engine._prefill(
        params, jnp.asarray([_tokens((9,), seed=3)], jnp.int32))
    cache = engine._empty_cache(row)
    for at in (0, 1):
        cache = engine._insert_row(cache, row, jnp.asarray(at, jnp.int32))
    counts = jax.tree.map(jnp.zeros_like, engine._expert_counts)
    _, stepped, _ = engine._decode(
        params, cache, jnp.asarray([[5], [5]], jnp.int32),
        active=np.array([True, False]), expert_counts=counts)
    _, alone, _ = engine._decode(
        params, engine._insert_row(
            engine._empty_cache(row), row, jnp.asarray(0, jnp.int32)),
        jnp.asarray([[5], [5]], jnp.int32),
        active=np.array([True, False]), expert_counts=counts)
    # the free row holds what one step from zero leaves, not the request's
    for a, b, kind in zip(jax.tree.leaves(stepped), jax.tree.leaves(alone),
                          jax.tree.leaves(models.cache_kinds(stepped))):
        if kind == models.STATE:
            assert _diff(a[1], b[1]) == 0.0 and _diff(a[0], b[0]) == 0.0
    engine.close()


def test_the_counters_count_the_layers_that_keep_or_route(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    assert engine.state_bytes_per_row() is None
    engine.generate([_request(_tokens((9,)), 6), _request(_tokens((12,), 4), 6)])
    # one attention layer of five: K and V of 2 heads x 16 x 4 B
    assert engine.cache_bytes_per_token() == 2 * 2 * 16 * 4
    # two mixer layers: the state 16 x 8 x 16 x 4 B + the tail 3 x 192 x 4 B
    assert engine.state_bytes_per_row() == 2 * (16 * 8 * 16 * 4 + 3 * 192 * 4)
    assert engine.window_bytes_per_row() == 0
    stats = engine.expert_stats()
    assert (stats["experts_routed"], stats["experts_held"]) == (16, 4)
    # a row a layer that is an expert layer, a column an expert held
    assert np.asarray(stats["assignments"]).shape == (2, 4)
    assert len(stats["touched"]) == len(stats["assignments_absent"]) == 2
    live = (np.asarray(stats["assignments"]).sum(1)
            + np.asarray(stats["assignments_absent"]))
    assert len(set(live)) == 1 and live[0] % cfg.experts_per_token == 0
    assert live[0] >= stats["decode_steps"] * cfg.experts_per_token
    assert all(0 < gone < total for gone, total
               in zip(stats["assignments_absent"], live))
    assert all(t <= 4 * stats["decode_steps"] for t in stats["touched"])
    engine.close()
