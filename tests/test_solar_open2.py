"""A fifth architecture through the serving stack: a Solar-Open2-shaped model
(a KDA gated-delta-rule mixer three layers in four, a gated NoPE GQA layer
the fourth, a held share of routed experts beside a shared one in every
layer) built by ``ray_tpu.models`` for the engines, against the benchmark's
plain reference (``benchmarks/reference/solar_open2_arch.py``), which
imports none of the program's model code and runs the recurrence one
position at a time.

What is new to the stack: a family with ``models.STATE`` leaves *and* routed
layers, layers that differ by index, a family that restarts its own state
(``RESTARTS_OWN_STATE``), and the first Pallas kernel over a recurrent
state (``ops/kda_step.py``).

The toy has the published shape: 4 layers (GQA, KDA, KDA, KDA), hidden 64,
GQA 4/2 x 16, 4 KDA heads of 16 x 16, chunk 8, 16 experts routed over of
which 4..8 are held, top 4, norms shaken away from one, the router's bias
and the gate's bias non-zero.

Tolerance, float32 on the CPU: 1e-4 on logits of magnitude ~1-4, under the
experts the program chose (``follow=``: top-4 of 16 is a discontinuity,
and the test holds the choice itself to be the reference's own wherever
its slack is zero). Both sides multiply exactly here; they differ in the
order of their float32 sums, and the prefill besides in its *form* (the
chunked WY form against the reference's scan over positions). Measured:
5e-6 or less. Every fault asserted below is 1e-3 and more.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import solar_open2_arch as arch  # noqa: E402
from ray_tpu import models  # noqa: E402
from ray_tpu.kvcache import KVCacheManager  # noqa: E402
from ray_tpu.llm import LLMConfig  # noqa: E402
from ray_tpu.llm.engine import (  # noqa: E402
    ContinuousBatchingEngine, GenerationRequest,
)
from ray_tpu.models import solar_open2  # noqa: E402
from ray_tpu.models.solar_open2 import SolarOpen2Config  # noqa: E402
from ray_tpu.ops import kda_step as kda_kernel  # noqa: E402
from ray_tpu.parallel.sharding import unbox_params  # noqa: E402

TOL = 1e-4
VOCAB = 96
SEQ = 384
HELD = (4, 8)
KWARGS = dict(
    vocab_size=VOCAB, dim=64, n_layers=4, gqa_layers=(0, 4, 8), n_heads=4,
    n_kv_heads=2, head_dim=16, kda_heads=4, kda_head_dim=16, kda_conv=4,
    kda_gate_rank=16, kda_chunk_size=8, moe_intermediate=32, n_experts=16,
    experts_per_token=4, experts_held=HELD, max_seq_len=SEQ,
    dtype=jnp.float32, param_dtype=jnp.float32,
)
# the same toy as a benchmark configuration file would state it
PUBLISHED = dict(
    name="toy", vocab_size=VOCAB, hidden_size=64, num_hidden_layers=4,
    gqa_layers=[0, 4, 8], num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, moe_intermediate_size=32, rms_norm_eps=1e-5,
    rope_theta=10000, use_rope=False, use_gqa_gate=True,
    kda_use_full_proj=False, kda_allow_neg_eigval=True,
    first_k_dense_replace=0, tie_word_embeddings=False,
    linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                        "num_heads": 4, "num_kv_heads": None},
    n_routed_experts=4, experts_first=4, published={"n_routed_experts": 16},
    n_shared_experts=1, norm_topk_prob=True, routed_scaling_factor=1,
    num_experts_per_tok=4,
)


def _sizes(**changed):
    sizes = arch.sizes_of(dict(PUBLISHED, **changed))
    for key in ("guaranteed", "n_routed", "n_held"):
        sizes.pop(key)
    return sizes


SIZES = _sizes()


def _params(cfg, seed=0):
    """Seeded weights with the norms away from one and the two biases away
    from zero, so that a norm or a bias left out shows."""
    params = unbox_params(models.init_params(cfg, jax.random.PRNGKey(seed)))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def shake(path, leaf):
        name = path[-1].key
        if name.endswith("norm"):
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)
        if name in ("router_bias", "bias"):
            return 0.2 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    return jax.tree_util.tree_map_with_path(shake, params)


def _tokens(shape, seed=2):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 3, VOCAB - 1)


def _diff(a, b):
    return float(jnp.max(jnp.abs(jnp.asarray(a) - jnp.asarray(b))))


@pytest.fixture(scope="module")
def tiny():
    cfg = SolarOpen2Config(**KWARGS)
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
    None) -> (logits, cache, each layer's chosen experts)``."""
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


def _followed(params, tokens, chosen, sizes=SIZES, slack=None):
    """The reference's logits under the experts the program chose."""
    return arch.logits(params, tokens, follow=chosen, slack=slack, **sizes)


def _pieces(apply, params, tokens, pieces, cache=None):
    """``tokens`` fed in ``pieces``: the logits, the last cache, and each
    layer's chosen experts over all the positions."""
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


def _reference_rows(params, prompt, answer):
    """The reference's own logits (its own routing) at the positions that
    chose ``answer``."""
    toks = [int(t) for t in prompt] + [int(t) for t in answer[:-1]]
    return arch.logits(
        params, jnp.asarray([toks], jnp.int32), last=len(answer), **SIZES)[0]


def _is_the_references_greedy(params, prompt, answer) -> bool:
    rows = _reference_rows(params, prompt, answer)
    return [int(t) for t in jnp.argmax(rows, axis=-1)] == list(answer)


# -- the model against the reference -----------------------------------------

def test_the_configuration_keys_reach_the_program(tiny):
    cfg, _ = tiny
    arguments = arch.llm_arguments(PUBLISHED)
    assert arguments["model_family"] == "solar_open2"
    built = SolarOpen2Config(**dict(
        arguments["model_kwargs"], max_seq_len=SEQ, kda_chunk_size=8,
        dtype=jnp.float32, param_dtype=jnp.float32))
    assert built == cfg
    assert built.experts_held == HELD and built.n_experts == 16
    assert [built.is_gqa(i) for i in range(4)] == [True, False, False, False]
    for key, value in (("use_rope", True), ("use_gqa_gate", False),
                       ("kda_allow_neg_eigval", False)):
        # the reference has both sides of these; the program the published
        with pytest.raises(SystemExit, match=key):
            arch.llm_arguments(dict(PUBLISHED, **{key: value}))
        arch.sizes_of(dict(PUBLISHED, **{key: value}))
    with pytest.raises(SystemExit, match="kda_use_full_proj"):
        arch.sizes_of(dict(PUBLISHED, kda_use_full_proj=True))
    with pytest.raises(ValueError, match="experts_held"):
        SolarOpen2Config(**dict(KWARGS, experts_held=(12, 20)))


def test_init_makes_one_program_and_a_useful_decay(tiny):
    cfg, params = tiny
    raw = unbox_params(models.init_params(cfg, jax.random.PRNGKey(0)))
    kda = raw["layer_1"]["kda"]
    assert raw["layer_0"]["moe"]["w_gate"].shape == (4, 64, 32)
    assert raw["layer_0"]["moe"]["router"].shape == (64, 16)
    assert float(jnp.max(jnp.abs(raw["layer_0"]["moe"]["router_bias"]))) == 0.0
    assert "kda" not in raw["layer_0"] and "attn" not in raw["layer_1"]
    # alpha at the bias alone: between exp(-4 x 0.2) and exp(-0.005)
    alpha = jnp.exp(-jnp.exp(kda["A_log"])[:, None] * jax.nn.softplus(
        kda["dt_bias"]).reshape(4, 16))
    assert 0.4 < float(jnp.min(alpha)) and float(jnp.max(alpha)) < 0.999
    assert float(jnp.max(alpha)) - float(jnp.min(alpha)) > 0.2


def test_bf16_weights_are_drawn_in_float32():
    """A bf16 draw of ``jax.random.normal`` has 128 values a sign and a
    mean of -0.012 sigma; through ``W_o``'s 8192 inputs that is one offset
    on every output, and every row's router follows it (finding 36.5). The
    family's weights are float32 draws rounded to bf16."""
    shape = (512, 512)
    biased = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.bfloat16)
    assert len(np.unique(np.asarray(biased, np.float32))) <= 512
    assert float(jnp.mean(biased.astype(jnp.float32))) < -0.008
    cfg = SolarOpen2Config(**dict(
        KWARGS, vocab_size=1024, dim=256, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16))
    raw = unbox_params(models.init_params(cfg, jax.random.PRNGKey(0)))
    assert {w.dtype for w in jax.tree.leaves(raw)} == {jnp.dtype(jnp.bfloat16)}
    embed = np.asarray(raw["embed"], np.float32)  # a unit normal, 262144
    assert len(np.unique(embed)) > 2000
    assert abs(float(embed.mean())) < 0.006  # 3 sigma of the mean's error


def test_whole_sequence_matches_the_reference(tiny):
    cfg, params = tiny
    tokens = _tokens((2, 29))
    got, _, chosen = _pieces(_applier(cfg), params, tokens, (29,))
    slack: list = []
    want = _followed(params, tokens, chosen, slack=slack)
    assert _diff(got, want) < TOL
    # the program's choice is the reference's own nearly everywhere
    assert float(jnp.mean(jnp.stack(slack) == 0)) > 0.95
    assert float(jnp.max(jnp.stack(slack))) < 1e-4
    # ... and some of it falls on experts held elsewhere, some here
    here = (chosen[0] >= HELD[0]) & (chosen[0] < HELD[1])
    assert 0 < int(here.sum()) < here.size


def test_prefill_then_decode_through_the_cache_matches_the_reference(tiny):
    """The prefill runs the chunked form, the steps the kernel a position:
    logits, every position, against the reference's scan."""
    cfg, params = tiny
    tokens = _tokens((2, 29))
    apply = _applier(cfg)
    got, cache, chosen = _pieces(apply, params, tokens, (19,) + (1,) * 10)
    assert _diff(got, _followed(params, tokens, chosen)) < TOL
    kda = cache["layer_1"]["kda"]
    assert kda["state_kda"].shape == (2, 4, 16, 16)
    assert kda["state_kda"].dtype == jnp.float32
    assert kda["state_conv"].shape == (2, 3, 3 * 64)
    assert [int(i) for i in kda["cache_index"]] == [29, 29]
    assert set(cache["layer_0"]["attn"]) == {
        "cached_key", "cached_value", "cache_index"}


@pytest.mark.parametrize("pieces", [(29,), (1,) * 29, (12, 12, 5), (8, 16, 5),
                                    (3, 26)])
def test_chunked_form_step_form_and_pieces_are_one_function(tiny, pieces):
    """One prompt fed whole, a position at a time, in pieces of 12 and in
    pieces that end on and off a chunk's edge: the chunked form continues
    from a row's state and convolution tail. All equal the reference."""
    cfg, params = tiny
    tokens = _tokens((1, 29), seed=5)
    got, _, chosen = _pieces(_applier(cfg), params, tokens, pieces)
    assert _diff(got, _followed(params, tokens, chosen)) < TOL


def _recurrence_inputs(b=2, s=21, h=4, d=8, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    state = jax.random.normal(keys[0], (b, h, d, d))
    q = arch.l2norm(jax.random.normal(keys[1], (b, s, h, d))) * d ** -0.5
    k = arch.l2norm(jax.random.normal(keys[2], (b, s, h, d)))
    v = jax.random.normal(keys[3], (b, s, h, d))
    g = -jax.nn.softplus(jax.random.normal(keys[4], (b, s, h, d)) - 1.0) * 3
    beta = 2 * jax.nn.sigmoid(jax.random.normal(keys[5], (b, s, h)))
    return state, q, k, v, g, beta


def _by_the_rule(state, q, k, v, g, beta):
    """The recurrence as the issue writes it, a position at a time."""
    outs = []
    for t in range(q.shape[1]):
        state = state * jnp.exp(g[:, t])[..., None]
        seen = jnp.einsum("bhkv,bhk->bhv", state, k[:, t])
        state = state + beta[:, t, :, None, None] * (
            k[:, t, :, :, None] * (v[:, t] - seen)[:, :, None, :])
        outs.append(jnp.einsum("bhkv,bhk->bhv", state, q[:, t]))
    return state, jnp.stack(outs, axis=1)


@pytest.mark.parametrize("chunk", [4, 8, 32])
def test_recurrence_forms_agree_from_a_nonzero_state(chunk):
    """``kda_chunked`` from a state, ``kda_step`` and the kernel a position
    at a time from the same state, and the rule itself, at a length that is
    no multiple of the chunk and under a decay strong enough that a
    factored ``e^{G_t} e^{-G_i}`` would overflow (g down to -9 a step)."""
    state, q, k, v, g, beta = _recurrence_inputs()
    g = g.at[:, 5:9].multiply(3.0)
    want_end, want = _by_the_rule(state, q, k, v, g, beta)
    got_end, got = solar_open2.kda_chunked(state, q, k, v, g, beta, chunk)
    assert _diff(got, want) < 1e-4 and _diff(got_end, want_end) < 1e-4
    nobody = jnp.zeros((2,), bool)
    for step in (solar_open2.kda_step,
                 lambda *a: kda_kernel.kda_step(*a, nobody)):
        end, outs = state, []
        for t in range(q.shape[1]):
            end, o = step(end, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
            outs.append(o)
        assert _diff(jnp.stack(outs, axis=1), want) < 1e-4
        assert _diff(end, want_end) < 1e-4


def test_the_kernel_reads_a_fresh_rows_state_as_zero():
    state, q, k, v, g, beta = _recurrence_inputs(b=3, s=1, h=4, d=16)
    state = state.at[1].set(jnp.nan)  # what a free row holds may be anything
    fresh = jnp.asarray([False, True, False])
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    got_end, got = kda_kernel.kda_step(state, *args, fresh)
    want_end, want = solar_open2.kda_step(
        jnp.where(fresh[:, None, None, None], 0.0, state), *args)
    assert bool(jnp.all(jnp.isfinite(got))) and _diff(got, want) < 1e-5
    assert _diff(got_end, want_end) < 1e-5


# -- the controls: what the check has to see ---------------------------------

def _with(params, layer, group, name, value):
    """``params`` with one leaf replaced (by a function of the old one)."""
    def change(path, leaf):
        names = [k.key for k in path]
        if names[0] == layer and names[1] == group and names[-1] == name:
            return value(leaf)
        return leaf

    return jax.tree_util.tree_map_with_path(change, params)


def _everywhere(params, group, name, value):
    def change(path, leaf):
        names = [k.key for k in path]
        if len(names) > 2 and names[1] == group and names[-1] == name \
                and (group != "shared" or names[2] == "w_down"):
            return value(leaf)
        return leaf

    return jax.tree_util.tree_map_with_path(change, params)


REFERENCE_FAULTS = {
    # the reference computes something else than the published layer: the
    # program, which computes the published one, is then off by that much
    "beta_not_doubled": lambda p, m: (p, _sizes(kda_allow_neg_eigval=False)),
    "decay_left_at_1": lambda p, m: (
        _everywhere(p, "kda", "A_log", lambda a: jnp.full_like(a, -jnp.inf)),
        SIZES),
    "l2_norm_left_out": lambda p, m: (
        m.setattr(arch, "l2norm", lambda x: x) or p, SIZES),
    "kda_gate_left_out": lambda p, m: (
        _everywhere(p, "kda", "bias", lambda a: jnp.full_like(a, jnp.inf)),
        SIZES),
    "gqa_gate_left_out": lambda p, m: (p, _sizes(use_gqa_gate=False)),
    "rope_on_the_gqa_layers": lambda p, m: (p, _sizes(use_rope=True)),
    "shared_expert_left_out": lambda p, m: (
        _everywhere(p, "shared", "kernel", jnp.zeros_like), SIZES),
    "kept_weights_over_the_held": lambda p, m: (
        m.setattr(arch, "route", _route_over_the_held) or p, SIZES),
}
_route = arch.route


def _route_over_the_held(*args, **kwargs):
    """``arch.route`` with the kept weights normalised over the experts held
    here instead of over the experts chosen."""
    kept, experts, own, slack = _route(*args, **kwargs)
    here = (experts >= HELD[0]) & (experts < HELD[1])
    held = jnp.where(here, kept, 0.0)
    return (held / jnp.maximum(held.sum(-1, keepdims=True), 1e-20),
            experts, own, slack)


@pytest.mark.parametrize("fault", sorted(REFERENCE_FAULTS))
def test_what_the_check_has_to_see_moves_the_logits(tiny, fault, monkeypatch):
    """Each of the issue's controls on the reference's side (the published
    layer against a neighbour of it): the program's logits, prefill then
    decode through the cache, leave the faulted reference by far more than
    the tolerance (measured: 0.03 to 3)."""
    cfg, params = tiny
    tokens = _tokens((1, 24), seed=7)
    got, _, chosen = _pieces(
        _applier(cfg), params, tokens, (16,) + (1,) * 8)
    assert _diff(got, _followed(params, tokens, chosen)) < TOL
    # the faulted reference is traced anew, not read from the honest trace
    monkeypatch.setattr(arch, "block", arch.block.__wrapped__)
    faulted, sizes = REFERENCE_FAULTS[fault](params, monkeypatch)
    worst = _diff(got, _followed(faulted, tokens, chosen, sizes))
    assert worst > 30 * TOL, worst


@pytest.mark.parametrize("fault", ["zero_state", "no_tail", "bf16_state"])
def test_what_only_a_check_through_the_carried_state_sees(tiny, fault):
    """What a K/V-only prefix hit would do (the state zero, or the
    convolution tail dropped, at the first decoded step), and a state
    rounded to bf16 at each step: each moves the decoded positions' logits
    by more than the tolerance."""
    cfg, params = tiny
    tokens = _tokens((1, 24), seed=7)
    apply = _applier(cfg)
    _, _, chosen = _pieces(apply, params, tokens, (16,) + (1,) * 8)
    want = _followed(params, tokens, chosen)
    _, cache, _ = apply(params, tokens[:, :16])

    def lose(path, leaf):
        name = path[-1].key
        if (fault, name) in (("zero_state", "state_kda"), ("no_tail", "state_conv")):
            return jnp.zeros_like(leaf)
        return leaf

    cache = jax.tree_util.tree_map_with_path(lose, cache)
    worst = 0.0
    for t in range(16, 24):
        step, cache, _ = apply(params, tokens[:, t:t + 1], cache)
        if fault == "bf16_state":
            cache = jax.tree_util.tree_map_with_path(
                lambda path, leaf: leaf.astype(jnp.bfloat16).astype(leaf.dtype)
                if path[-1].key == "state_kda" else leaf, cache)
        worst = max(worst, _diff(step[:, 0], want[:, t]))
    assert worst > 3 * TOL, worst


# -- the interface -----------------------------------------------------------

def test_cache_leaves_classify_by_name():
    cfg = LLMConfig(
        model_id="solar-tiny", model_family="solar_open2", max_seq_len=32,
    ).build_model_config()
    assert isinstance(cfg, SolarOpen2Config)
    params = jax.eval_shape(
        lambda: unbox_params(models.init_params(cfg, jax.random.PRNGKey(0))))
    model = models.build(cfg, None, decode=True)
    cache = jax.eval_shape(
        lambda p: model.apply(
            {"params": p}, jnp.zeros((1, 4), jnp.int32), mutable=["cache"])[1],
        params)["cache"]
    got = {(path[-2].key, path[-1].key): kind for path, kind
           in jax.tree_util.tree_leaves_with_path(models.cache_kinds(cache))}
    assert got == {
        ("attn", "cached_key"): "sequence", ("attn", "cached_value"): "sequence",
        ("attn", "cache_index"): "index", ("kda", "state_kda"): "state",
        ("kda", "state_conv"): "state", ("kda", "cache_index"): "index"}
    assert models.carries_row_state(cfg) and models.restarts_own_state(cfg)
    falcon = LLMConfig(
        model_id="falcon-tiny", model_family="falcon_h1", max_seq_len=32,
    ).build_model_config()
    assert models.carries_row_state(falcon)
    assert not models.restarts_own_state(falcon)
    with pytest.raises(NotImplementedError, match="serving"):
        models.build(cfg, None, decode=False)


@pytest.mark.parametrize("feature,kwargs", [
    ("adapters", {"adapters": {"max_live": 2}}),
    ("mesh", {"mesh": {"tp": 2}}),
])
def test_refusals(feature, kwargs):
    assert set(models.refusals("solar_open2")) == {"adapters", "mesh"}
    with pytest.raises(ValueError, match=feature):
        LLMConfig(model_id="solar-tiny", model_family="solar_open2",
                  kv_cache_blocks=4, **kwargs)


# -- the engine ---------------------------------------------------------------

def test_engine_tokens_and_state_through_the_slot_cache(tiny):
    """Three requests of different lengths through admission, the slot
    cache (row insert, the pool's decode step one ahead) and retirement:
    each gets the reference's own greedy tokens."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompts = [_tokens((n,), seed=10 + n) for n in (9, 16, 21)]
    results = engine.generate([_request(p, 12) for p in prompts])
    for prompt, result in zip(prompts, results):
        assert len(result.token_ids) == 12
        assert _is_the_references_greedy(params, prompt, result.token_ids)
    kinds = jax.tree.leaves(models.cache_kinds(engine._cache))
    shapes = [leaf.shape for leaf in jax.tree.leaves(engine._cache)]
    assert (3, 4, 16, 16) in shapes and (3, 3, 192) in shapes
    assert kinds.count("state") == 2 * 3 and kinds.count("sequence") == 2
    assert engine._state_span == {"state_rows": 3}
    stats = engine._kv.stats()
    assert stats["prefix_reuse"] is False and stats["hits"] == 0


def test_engine_steps_match_the_reference_logits_two_rows_live(tiny):
    """The engine's own jitted prefill, row insert and decode at the pool's
    shape, two rows live and one of them in a slot another row left, every
    step's logits under the step's own choice of experts (the counters'
    ``choice``): what the benchmark's check does at the cell's size."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompt = _tokens((14,), seed=21)
    tokens = _engine(cfg, params).generate([_request(prompt, 10)])[0].token_ids
    logits, row = engine._prefill(params, jnp.asarray([prompt], jnp.int32))
    other = engine._prefill(
        params, jnp.asarray([_tokens((9,), seed=22)], jnp.int32))[1]
    cache = engine._empty_cache(row)
    cache = engine._insert_row(cache, other, jnp.asarray(2, jnp.int32))
    zeroed = jax.tree.map(jnp.zeros_like, engine._expert_counts)
    # slot 2: someone else for 3 steps, then free for 2, then the request
    got, chose, absent = [], [], []
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
                ] == [live] * cfg.n_layers
        if step >= 5:
            got.append(out[2])
            chose.append(counts["choice"][:, 2])
    chose = jnp.stack(chose)  # (steps, layers, k)
    fed = jnp.asarray([list(map(int, prompt)) + tokens[:-1]], jnp.int32)
    prefilled = _applier(cfg)(params, fed[:, :14])[2]
    follow = [jnp.concatenate([prefilled[layer], chose[:, layer]])
              for layer in range(cfg.n_layers)]
    want = _followed(params, fed, follow)[0, 14:]
    assert _diff(jnp.stack(got), want) < TOL
    assert _diff(logits[0], _followed(params, fed, follow)[0, 13]) < TOL


def test_a_freed_row_taken_again_is_a_fresh_row(tiny):
    """Slot 0's request ends after 4 tokens; the slot stays free for 80
    steps of another request and is then taken again: the answer is a fresh
    engine's. The engine leaves this family's state alone
    (``RESTARTS_OWN_STATE``): the free row's state stays finite because the
    family reads it as zero at every step."""
    cfg, params = tiny
    engine = _engine(cfg, params, slots=2)
    short = _request(_tokens((11,), seed=31), 4)
    long_ = _request(_tokens((10,), seed=32), 100)
    rid_short, rid_long = engine.add_request(short), engine.add_request(long_)
    done = {}
    while rid_short not in done:
        done.update(engine.step())
    for _ in range(80):
        done.update(engine.step())
    assert rid_long not in done and list(engine._slots) == [1]
    for leaf in jax.tree.leaves(engine._cache):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))
    again = _request(_tokens((13,), seed=33), 10)
    rid = engine.add_request(again)
    while rid not in done:
        done.update(engine.step())
    fresh = _engine(cfg, params, slots=2).generate([again])[0]
    assert done[rid].token_ids == fresh.token_ids
    assert _is_the_references_greedy(params, again.token_ids, fresh.token_ids)


def test_a_free_row_holds_one_step_from_zero(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params, slots=2)
    _, row = engine._prefill(params, jnp.asarray([_tokens((9,), seed=3)], jnp.int32))
    cache = engine._empty_cache(row)
    for at in (0, 1):
        cache = engine._insert_row(cache, row, jnp.asarray(at, jnp.int32))
    last = jnp.asarray([[5], [5]], jnp.int32)
    active = np.array([True, False])
    counts = jax.tree.map(jnp.zeros_like, engine._expert_counts)
    _, stepped, _ = engine._decode(
        params, cache, last, active=active, expert_counts=counts)
    _, alone, _ = engine._decode(
        params, engine._insert_row(
            engine._empty_cache(row), row, jnp.asarray(0, jnp.int32)),
        last, active=active, expert_counts=counts)
    # the free row holds what one step from zero leaves, not the request's
    for a, b, kind in zip(jax.tree.leaves(stepped), jax.tree.leaves(alone),
                          jax.tree.leaves(models.cache_kinds(stepped))):
        if kind == "state":
            assert _diff(a[1], b[1]) == 0.0 and _diff(a[0], b[0]) == 0.0


def test_chunked_prefill_in_pieces_equals_one_prefill(tiny):
    cfg, params = tiny
    prompt = [int(t) for t in _tokens((200,), seed=51)]
    want = _engine(cfg, params, block_size=64).generate([_request(prompt, 8)])[0]
    engine = _engine(cfg, params, block_size=64, prefill_chunk_tokens=96)
    rid = engine.add_request(_request(prompt, 8))
    done = {}
    while rid not in done:
        done.update(engine.step())
    assert done[rid].token_ids == want.token_ids
    assert _is_the_references_greedy(params, prompt, want.token_ids)


def test_runtime_info_counts_over_the_experts_held(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    assert engine.state_bytes_per_row() is None
    engine.generate([_request(_tokens((9,)), 6), _request(_tokens((12,), 4), 6)])
    # one GQA layer: K and V of 2 heads x 16 x 4 B; three KDA layers: state
    # 4 x 16 x 16 x 4 B + the tail 3 x 192 x 4 B
    assert engine.cache_bytes_per_token() == 2 * 2 * 16 * 4
    assert engine.state_bytes_per_row() == 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    stats = engine.expert_stats()
    assert (stats["experts_routed"], stats["experts_held"]) == (16, 4)
    assert np.asarray(stats["assignments"]).shape == (4, 4)
    # every live row's every choice is counted on a held expert or absent
    live = (np.asarray(stats["assignments"]).sum(1)
            + np.asarray(stats["assignments_absent"]))
    assert len(set(live)) == 1 and live[0] % cfg.experts_per_token == 0
    assert live[0] >= stats["decode_steps"] * cfg.experts_per_token
    assert all(0 < gone < total for gone, total
               in zip(stats["assignments_absent"], live))
    assert all(t <= 4 * stats["decode_steps"] for t in stats["touched"])
