"""A ninth architecture through the serving stack: a SmallThinker-shaped
model (a router that reads the attention's input, ReGLU experts every
layer, a full layer without positions and then three rotary window layers
a period, 7 query heads a K/V head, an untied head) built by
``ray_tpu.models`` for the engine, against the benchmark's plain reference
(``benchmarks/reference/smallthinker_arch.py``), which imports none of the
program's model code, keeps no cache and masks a band.

What is new to the stack: ``MoEFFN`` in two steps (``route`` on one tensor,
the experts on another), the ``"reglu"`` expert, the order of layers (the
full one first) and a group of 7; the rest is the other families' code,
whose tests run over it unedited.

The toy is ``SmallThinkerConfig.tiny()``'s shape: 4 layers (a full one and
three window layers), hidden 64, 7 query heads over one K/V head of 16, a
ring of 24 positions, 16 experts all held, top 4, width 48, norms shaken
away from one.

Tolerance, float32 on the CPU: 1e-4 on logits of magnitude ~1-4, under the
experts the program chose (``follow=``: top-4 of 16 is a discontinuity).
Both sides multiply exactly here; they differ in the order of their float32
sums. A reference-side fault must move the logits by more than ``FAULT``, a
hundred times the tolerance.
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import flops_stmoe  # noqa: E402
from benchmarks.reference import smallthinker_arch as arch  # noqa: E402
from ray_tpu import models  # noqa: E402
from ray_tpu.kvcache import KVCacheManager  # noqa: E402
from ray_tpu.llm import LLMConfig  # noqa: E402
from ray_tpu.llm.engine import (  # noqa: E402
    ContinuousBatchingEngine, GenerationRequest,
)
from ray_tpu.models import llama, smallthinker  # noqa: E402
from ray_tpu.models.moe import MoEConfig, MoEFFN  # noqa: E402
from ray_tpu.models.smallthinker import SmallThinkerConfig  # noqa: E402
from ray_tpu.ops.rope import rope_table  # noqa: E402
from ray_tpu.parallel.expert import top_k_routing  # noqa: E402
from ray_tpu.parallel.sharding import unbox_params  # noqa: E402

TOL = 1e-4
FAULT = 1e-2
VOCAB = 96
SEQ = 128
RING = 24
HELD = (0, 16)
KWARGS = dict(
    vocab_size=VOCAB, dim=64, n_layers=4, n_heads=7, n_kv_heads=1,
    head_dim=16, sliding_window=RING, layer_period=4, moe_intermediate=48,
    n_experts=16, experts_per_token=4, norm_topk_prob=True,
    experts_held=HELD, rope_theta=1.5e6, norm_eps=1e-6, max_seq_len=SEQ,
    dtype=jnp.float32, param_dtype=jnp.float32,
)
# the same toy as a benchmark configuration file would state it
PUBLISHED = dict(
    name="toy", vocab_size=VOCAB, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=7, num_key_value_heads=1, head_dim=16,
    sliding_window_size=RING, sliding_window_layout=[0, 1, 1, 1],
    rope_layout=[0, 1, 1, 1], moe_ffn_hidden_size=48,
    moe_num_primary_experts=16, moe_num_active_primary_experts=4,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    rope_theta=1500000, rope_scaling=None, rms_norm_eps=1e-6,
    tie_word_embeddings=False,
)


def _sizes(**changed):
    sizes = arch.sizes_of(dict(PUBLISHED, **changed))
    for key in ("guaranteed", "n_routed", "n_held", "step_from_zero"):
        sizes.pop(key)
    return sizes


SIZES = _sizes()


def _params(cfg, seed=0):
    """Seeded weights with the norms away from one, so that a norm's weight
    left out shows."""
    params = unbox_params(models.init_params(cfg, jax.random.PRNGKey(seed)))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))

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
    cfg = SmallThinkerConfig(**KWARGS)
    return cfg, _params(cfg)


def _applier(cfg):
    """The serving module's ``apply`` jitted (a function object of its own,
    so that a patched module global is traced anew): ``(params, tokens,
    cache or None) -> (logits, cache, each layer's chosen experts)``."""
    model = models.build(cfg, None, decode=True)

    def apply(params, tokens, cache=None):
        variables = {"params": params}
        if cache is not None:
            variables["cache"] = cache
        logits, state = model.apply(
            variables, tokens, mutable=["cache", models.ROUTING])
        return logits, state["cache"], arch.program_routing(
            state[models.ROUTING], cfg.n_layers)

    return jax.jit(apply)


@pytest.fixture(scope="module")
def apply(tiny):
    return _applier(tiny[0])


def _engine(cfg, params, slots=3, blocks=8, block_size=8, **kw):
    return ContinuousBatchingEngine(
        cfg, params, num_slots=slots,
        kv_cache=KVCacheManager(num_blocks=blocks, block_size=block_size),
        seed=0, **kw)


def _request(tokens, n):
    return GenerationRequest(
        token_ids=[int(t) for t in tokens], max_new_tokens=n)


def _followed(params, tokens, chosen, slack=None, faults=()):
    """The reference's logits under the experts the program chose, a
    sequence at a time."""
    return jnp.concatenate([
        arch.logits(
            params, tokens[r:r + 1],
            follow=[c.reshape(tokens.shape + c.shape[1:])[r] for c in chosen],
            slack=slack, faults=faults, **SIZES)
        for r in range(tokens.shape[0])])


def _pieces(apply, params, tokens, pieces, cache=None):
    """``tokens`` fed in ``pieces``: the logits, the last cache, and each
    layer's chosen experts over all the positions, (batch x seq, k)."""
    got, chosen, at = [], None, 0
    b = tokens.shape[0]
    for n in pieces:
        out, cache, chose = apply(params, tokens[:, at:at + n], cache)
        got.append(out)
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
    assert arguments["model_family"] == "smallthinker"
    built = SmallThinkerConfig(**dict(
        arguments["model_kwargs"], max_seq_len=SEQ, dtype=jnp.float32,
        param_dtype=jnp.float32))
    assert built == cfg
    assert cfg.routed_layers == (0, 1, 2, 3)
    assert [cfg.is_window(i) for i in range(4)] == [False, True, True, True]
    assert cfg.n_experts == 16 and cfg.routed_config().n_experts_held == 16
    # the published model's own numbers are the defaults
    full = SmallThinkerConfig()
    assert (full.n_layers, full.dim, full.n_heads, full.n_kv_heads,
            full.vocab_size) == (52, 2560, 28, 4, 151936)
    assert [i for i in range(52) if not full.is_window(i)] == list(
        range(0, 52, 4))
    window, whole = full.attention_config(True), full.attention_config(False)
    assert (window.window, window.rope, window.rope_interleaved,
            window.rope_theta) == (4096, True, False, 1.5e6)
    assert (whole.window, whole.rope, whole.head_dim) == (None, False, 128)
    routed = full.routed_config()
    assert (routed.router_scoring, routed.expert_activation,
            routed.intermediate, routed.n_experts, routed.experts_per_token,
            routed.experts_held) == ("softmax", "reglu", 768, 64, 6, None)
    tiny_ = SmallThinkerConfig.tiny()
    assert (tiny_.sliding_window, tiny_.n_heads // tiny_.n_kv_heads) == (24, 7)
    with pytest.raises(SystemExit, match="moe_primary_router_apply_softmax"):
        arch.sizes_of(dict(PUBLISHED, moe_primary_router_apply_softmax=False))
    with pytest.raises(SystemExit, match="rope_layout"):
        arch.sizes_of(dict(PUBLISHED, rope_layout=[1, 1, 1, 1]))
    with pytest.raises(SystemExit, match="sliding_window_layout"):
        arch.sizes_of(dict(PUBLISHED, rope_layout=[1, 1, 1, 0],
                           sliding_window_layout=[1, 1, 1, 0]))
    with pytest.raises(ValueError, match="heads"):
        SmallThinkerConfig.tiny(n_kv_heads=2)


def test_whole_sequence_under_the_band_matches_the_reference(tiny, apply):
    """Two prompts of 53 tokens, more than twice the ring: the window
    layers' whole prompt goes through the flash kernel under the band, the
    full layer's (the first) over every position."""
    cfg, params = tiny
    tokens = _tokens((2, 53))
    got, _, chosen = _pieces(apply, params, tokens, (53,))
    assert len(chosen) == 4  # every layer routes
    slack: list = []
    assert _diff(got, _followed(params, tokens, chosen, slack=slack)) < TOL
    # the program's choice is the reference's own nearly everywhere
    assert float(jnp.mean(jnp.stack(slack) == 0)) > 0.95
    assert float(jnp.max(jnp.stack(slack))) < 1e-4


@pytest.mark.parametrize("prompt", [31, 24, 9])
def test_prefill_then_decode_through_the_ring_matches_the_reference(
        tiny, apply, prompt):
    """A prompt longer than the ring, as long as it, and shorter: the ring a
    prefill leaves, then steps that write at ``p % ring`` and wrap it (a row
    younger than the ring reads its live slots alone); logits, every
    position, against the reference's banded mask."""
    cfg, params = tiny
    tokens = _tokens((2, 56), seed=prompt)
    got, cache, chosen = _pieces(
        apply, params, tokens, (prompt,) + (1,) * (56 - prompt))
    assert _diff(got, _followed(params, tokens, chosen)) < TOL
    attn = cache["layer_0"]["attn"]  # the full layer comes first
    assert set(attn) == {"cached_key", "cached_value", "cache_index"}
    assert attn["cached_key"].shape == (2, 1, SEQ, 16)
    assert [int(i) for i in attn["cache_index"]] == [56, 56]
    for i in range(1, 4):
        attn = cache[f"layer_{i}"]["attn"]
        assert set(attn) == {"window_key", "window_value", "cache_index"}
        assert attn["window_key"].shape == (2, 1, RING, 16)
    kinds = models.cache_kinds(cache)
    assert sorted(jax.tree.leaves(kinds["layer_0"])) == [
        models.INDEX, models.SEQUENCE, models.SEQUENCE]
    assert sorted(jax.tree.leaves(kinds["layer_3"])) == [
        models.INDEX, models.WINDOW, models.WINDOW]


def test_two_rows_of_different_lengths_share_their_steps(tiny, apply):
    """A row past the ring and one younger than it in one batch: each
    prefilled alone, their caches joined, then stepped together; each row's
    logits are its own reference's."""
    cfg, params = tiny
    old, young = _tokens((1, 50), seed=5), _tokens((1, 28), seed=6)
    _, cache_old, chose_old = apply(params, old[:, :31])
    _, cache_young, chose_young = apply(params, young[:, :9])
    cache = jax.tree.map(
        lambda a, b: jnp.concatenate([a, b]), cache_old, cache_young)
    got, chosen = [], []
    for step in range(19):
        fed = jnp.concatenate(
            [old[:, 31 + step:32 + step], young[:, 9 + step:10 + step]])
        out, cache, chose = apply(params, fed, cache)
        got.append(out[:, 0])
        chosen.append(jnp.stack(chose))  # (layers, 2, k)
    got, chosen = jnp.stack(got, axis=1), jnp.stack(chosen, axis=2)
    for row, (tokens, first, plen) in enumerate(
            ((old, chose_old, 31), (young, chose_young, 9))):
        follow = [jnp.concatenate([first[layer], chosen[layer, row]])
                  for layer in range(4)]
        want = arch.logits(
            params, tokens[:, :plen + 19], follow=follow, **SIZES)[0]
        assert _diff(got[row], want[plen:]) < TOL, row
    assert [int(i) for i in cache["layer_2"]["attn"]["cache_index"]] == [50, 28]


def test_the_router_reads_the_attentions_input(tiny, apply):
    """The test that pins the data flow. The program's sown choice is the
    reference's own when the reference routes on ``n1``
    (``input_layernorm``'s output) and another when it routes on ``n2``
    (``post_attention_layernorm``'s); and, on one block by hand,
    ``MoEFFN.route`` of ``n1`` is what the block sows, of ``n2`` is not."""
    cfg, params = tiny
    tokens = _tokens((1, 40), seed=11)
    _, _, chosen = _pieces(apply, params, tokens, (40,))
    on_n1, on_n2 = [], []
    arch.logits(params, tokens, routing=on_n1, **SIZES)
    arch.logits(params, tokens, routing=on_n2, faults=("route_on_n2",), **SIZES)
    agree = lambda mine: float(jnp.mean(jnp.stack([  # noqa: E731
        jnp.all(jnp.sort(a) == jnp.sort(b), axis=-1)
        for a, b in zip(chosen, mine)])))
    assert agree(on_n1) > 0.97
    assert agree(on_n2) < 0.5
    # ... and weights that follow the program's choice from the wrong
    # tensor are the wrong weights
    got = apply(params, tokens)[0]
    assert _diff(got, _followed(
        params, tokens, chosen, faults=("route_on_n2",))) > FAULT
    # one block by hand
    block = smallthinker.Block(cfg, True)
    cos, sin = rope_table(SEQ, 16, 1.5e6)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 30, 64))
    layer = params["layer_1"]
    out, sown = block.apply(
        {"params": layer}, x, cos, sin, mutable=["cache", models.ROUTING])
    n1 = arch.rms_norm(x, layer["attn_norm"], 1e-6)
    attended = llama.Attention(cfg.attention_config(True), None, True).apply(
        {"params": layer["attn"]}, n1, cos, sin, mutable=["cache"])[0]
    n2 = arch.rms_norm(x + attended, layer["ffn_norm"], 1e-6)
    moe = MoEFFN(cfg.routed_config())

    def routed(n):
        return moe.apply({"params": layer["moe"]}, n, method=MoEFFN.route,
                         mutable=[models.ROUTING, "losses"])[0]

    sown = sown[models.ROUTING]["moe"]["experts"][0]
    assert _diff(routed(n1)[1], sown) == 0
    assert float(jnp.mean(routed(n2)[1] == sown)) < 0.9
    want = x + attended + moe.apply(
        {"params": layer["moe"]}, n2, routed(n1),
        mutable=[models.ROUTING, "losses"])[0]
    assert _diff(out, want) < 1e-5


def test_softmax_over_the_kept_is_the_normalised_softmax_over_all():
    """``exp(s_j) / sum_{k in T} exp(s_k)`` (the published form, the
    reference's) is ``top_k_routing("softmax", normalize=True)`` (the
    program's): a softmax over all 64 divided by the kept six's sum."""
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(4), (50, 64))
    weights, experts, _ = top_k_routing(logits, 6, True, "softmax")
    top, index = jax.lax.top_k(logits, 6)
    assert _diff(jnp.sort(index), jnp.sort(experts)) == 0
    assert _diff(weights, jax.nn.softmax(top, axis=-1)) < 1e-6
    assert _diff(jnp.sum(weights, axis=-1), jnp.ones(50)) < 1e-6
    # the reference's ``route`` on the same logits (its router the identity)
    kept, chosen, own, slack = arch.route(logits, jnp.eye(64), top_k=6)
    assert _diff(kept, weights) < 1e-6 and _diff(chosen, experts) == 0
    assert float(jnp.max(slack)) == 0.0
    # followed: a worse choice has slack, and the kept weights are over it
    worse = experts.at[:, -1].set(jnp.argmin(logits, axis=-1))
    kept, chosen, own, slack = arch.route(
        logits, jnp.eye(64), worse, top_k=6)
    assert _diff(own, experts) == 0 and _diff(chosen, worse) == 0
    assert float(jnp.min(slack)) > 0.0
    assert _diff(jnp.sum(kept, axis=-1), jnp.ones(50)) < 1e-6


def test_route_then_experts_is_the_one_call(tiny):
    """``moe(x)`` is ``moe(x, moe.route(x))``: what every other family's
    one call does."""
    cfg, params = tiny
    moe = MoEFFN(cfg.routed_config())
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 64))
    weights = {"params": params["layer_0"]["moe"]}
    one = moe.apply(weights, x, mutable=[models.ROUTING, "losses"])
    routing = moe.apply(weights, x, method=MoEFFN.route,
                        mutable=[models.ROUTING, "losses"])[0]
    two = moe.apply(weights, x, routing, mutable=[models.ROUTING, "losses"])
    assert _diff(one[0], two[0]) == 0
    assert models.ROUTING in one[1] and models.ROUTING not in two[1]


def test_the_capacity_path_gates_through_relu_too():
    """``expert_activation="reglu"`` without ``dropless``: with room for
    every assignment the GShard einsums give the dropless kernel's sum."""
    kw = dict(dim=32, intermediate=48, n_experts=4, experts_per_token=2,
              expert_activation="reglu", dtype=jnp.float32,
              param_dtype=jnp.float32, capacity_factor=4.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 32))
    capacity, dropless = (MoEFFN(MoEConfig.tiny(**kw, dropless=d))
                          for d in (False, True))
    params = capacity.init(jax.random.PRNGKey(1), x)["params"]
    got = capacity.apply({"params": params}, x, mutable=["losses"])[0]
    want = dropless.apply(
        {"params": params}, x, mutable=["losses", models.ROUTING])[0]
    assert _diff(got, want) < 1e-5
    silu = MoEFFN(MoEConfig.tiny(**dict(kw, expert_activation="swiglu")))
    assert _diff(silu.apply({"params": params}, x, mutable=["losses"])[0],
                 want) > 1e-3
    with pytest.raises(ValueError, match="dropless"):
        MoEConfig.tiny(**dict(kw, expert_activation="relu2"))


@pytest.mark.parametrize("fault", [
    "route_on_n2", "swiglu", "experts_e4m3", "lost_expert", "no_window",
    "rope_on_full", "rope_pairs",
])
def test_a_reference_that_computes_a_neighbour_fails(tiny, apply, fault):
    """The router fed the post-attention norm's output, the gate through
    silu, the experts through an 8-bit float, a token's last expert lost,
    the band left out of one window layer, rotary on the full layer, GPT-J's
    pairs in place of rotate-half: the program computes the published
    layer, so each moves the comparison past ``FAULT``."""
    cfg, params = tiny
    tokens = _tokens((1, 48), seed=19)
    got, _, chosen = _pieces(apply, params, tokens, (31,) + (1,) * 17)
    assert _diff(got, _followed(params, tokens, chosen)) < TOL
    assert _diff(
        got, _followed(params, tokens, chosen, faults=(fault,))) > FAULT


def test_rings_through_an_8_bit_float_fail(tiny, apply):
    """Program side: the rings a prefill leaves through e4m3 (a scale a
    position and head) before the steps read them."""
    cfg, params = tiny
    tokens = _tokens((1, 40), seed=7)
    out, cache, first = apply(params, tokens[:, :31])

    def narrowed(leaf):
        scale = jnp.max(jnp.abs(leaf), axis=-1, keepdims=True) / 240.0
        scale = jnp.where(scale == 0, 1.0, scale)
        return jax.lax.reduce_precision(
            leaf / scale, exponent_bits=4, mantissa_bits=3) * scale

    for narrow, moved in ((False, False), (True, True)):
        kept = jax.tree.map(
            lambda leaf, kind: narrowed(leaf)
            if narrow and kind == models.WINDOW else leaf,
            cache, models.cache_kinds(cache))
        got, _, chosen = _pieces(apply, params, tokens[:, 31:], (1,) * 9, kept)
        follow = [jnp.concatenate(pair) for pair in zip(first, chosen)]
        want = arch.logits(params, tokens, follow=follow, **SIZES)[0, 31:]
        assert (_diff(got[0], want) > FAULT) == moved, narrow


def test_the_head_is_not_the_embedding(tiny):
    cfg, params = tiny
    assert params["embed"].shape == (VOCAB, 64)
    assert params["lm_head"].shape == (64, VOCAB)
    assert set(params["layer_0"]) == {"attn", "attn_norm", "ffn_norm", "moe"}
    assert set(params["layer_0"]["moe"]) == {
        "router", "w_gate", "w_up", "w_down"}


def test_bf16_weights_are_drawn_in_float32():
    cfg = SmallThinkerConfig.tiny(param_dtype=jnp.bfloat16)
    got = unbox_params(models.init_params(cfg, jax.random.PRNGKey(0)))
    want = unbox_params(models.init_params(
        SmallThinkerConfig.tiny(param_dtype=jnp.float32), jax.random.PRNGKey(0)))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == jnp.bfloat16
        assert _diff(a.astype(jnp.float32), b.astype(jnp.bfloat16)) == 0.0


@pytest.mark.parametrize("feature,kwargs", [
    ("adapters", {"adapters": {"max_live": 2}}),
    ("mesh", {"mesh": {"tp": 2}}),
    ("prefill_chunk", {"prefill_chunk_tokens": 16}),
])
def test_refusals(feature, kwargs):
    reasons = models.refusals("smallthinker")
    assert set(reasons) == {"adapters", "mesh", "prefill_chunk"}
    with pytest.raises(ValueError) as refused:
        LLMConfig(model_id="sthink-tiny", model_family="smallthinker",
                  kv_cache_blocks=4, **kwargs)
    assert feature in str(refused.value)
    assert reasons[feature] in str(refused.value)


def test_llm_config_builds_the_family():
    cfg = LLMConfig(
        model_id="sthink-tiny", model_family="smallthinker",
        model_kwargs={"sliding_window": 16}, max_seq_len=64,
        kv_cache_blocks=1,
    ).build_model_config()
    assert isinstance(cfg, SmallThinkerConfig)
    assert (cfg.sliding_window, cfg.max_seq_len) == (16, 64)
    assert models.carries_row_state(cfg)
    assert not models.restarts_own_state(cfg)
    with pytest.raises(NotImplementedError, match="serving"):
        models.build(cfg, None, decode=False)


def test_the_published_configuration_counts_what_the_issue_reckoned():
    """The cell's file through the byte counts: 50,331,648 B of rings and
    4096 B a position a row, 21.14 M parameters a layer outside its
    experts, 5.90 M an expert, 7.93 GB of weights at depth 8."""
    import json

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "configs", "smallthinker-21ba3b-serve-1chip.json")
    with open(path) as f:
        config = json.load(f)
    sizes = arch.sizes_of(config)
    assert sizes["guaranteed"] == {
        "window_bytes_per_row": 50331648, "kv_bytes_per_token": 4096}
    assert {k: config["guaranteed"][k] for k in sizes["guaranteed"]
            } == sizes["guaranteed"]
    assert (sizes["n_held"], sizes["n_routed"], sizes["n_layers"]) == (64, 64, 8)
    assert sizes["layout"] == (0, 1, 1, 1, 0, 1, 1, 1)
    assert flops_stmoe.expert_params(config) == 5898240
    assert (flops_stmoe.attention_params(config)
            + flops_stmoe.router_params(config)) == 21135360
    weights = 8 * (21135360 + 64 * 5898240 + 2 * 2560) + 2560 + (
        2 * 2560 * 151936)
    assert round(weights * 2 / 1e9, 2) == 7.93
    built = SmallThinkerConfig(**arch.llm_arguments(config)["model_kwargs"])
    assert built == SmallThinkerConfig(n_layers=8, experts_held=(0, 64))
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 52}
    assert {"router_input", "reglu_expert", "no_secondary_experts",
            "no_bias_no_qk_norm", "nope_full_layers"} <= set(config["assumed"])
    # counted by hand: a step of 64 rows, a fifth of them past the ring
    lengths = [1500] * 51 + [4600] * 13
    assert flops_stmoe.live_positions(config, lengths) == (
        2 * sum(lengths) + 6 * (51 * 1500 + 13 * 4096))
    per_byte = (flops_stmoe.attention_step_flops(config, lengths)
                / flops_stmoe.attention_step_min_bytes(config, lengths))
    assert per_byte == 7
    assert flops_stmoe.experts_kernel_min_bytes(config, 64, 384) == (
        8 * (64 * 5898240 + 384 * 2 * 2560) * 2)
    whole = flops_stmoe.decode_step_min_bytes(config, 64, 384, lengths)
    assert 8.5e9 < whole < 10.0e9
