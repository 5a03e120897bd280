"""An eighth architecture through the serving stack: a Cohere2-MoE-shaped
model (a parallel block on one LayerNorm, plain K/V heads in a ring three
layers in four with interleaved rotary pairs, a full layer without
positions the fourth, a held share of routed experts beside shared experts
that are averaged, a tied head) built by ``ray_tpu.models`` for the engine,
against the benchmark's plain reference
(``benchmarks/reference/cohere2_moe_arch.py``), which imports none of the
program's model code, keeps no cache and masks a band.

What is new to the stack: ``llama.Attention``'s ``window`` (the ring, its
write at ``p % ring``, its read up to the live slots, the ring a prefill
leaves), a whole prompt through ``ops/flash_attention.py`` (under the band
in a window layer), ``ops/rope.py``'s interleaved pairs; the rest is the
other families' code, whose tests run over it unedited.

The toy has the published shape: 4 layers (three window layers and a full
one), hidden 64, 8 query heads over 2 K/V heads of 16, a ring of 12
positions (shorter than every prompt here and than what is decoded), 16
experts routed over of which 4..8 are held, top 4, 2 shared experts, width
48, norms shaken away from one.

Tolerance, float32 on the CPU: 1e-4 on logits of magnitude ~1-4, under the
experts the program chose (``follow=``: top-4 of 16 is a discontinuity).
Both sides multiply exactly here; they differ in the order of their float32
sums (an online softmax over tiles against one softmax over a masked row).
Measured: 3e-6 or less. A reference-side fault must move the logits by more
than ``FAULT``, a hundred times the tolerance (measured: 0.02 at the least).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import flops_c2moe  # noqa: E402
from benchmarks.reference import cohere2_moe_arch as arch  # noqa: E402
from ray_tpu import models  # noqa: E402
from ray_tpu.kvcache import KVCacheManager  # noqa: E402
from ray_tpu.llm import LLMConfig  # noqa: E402
from ray_tpu.llm.engine import (  # noqa: E402
    ContinuousBatchingEngine, GenerationRequest,
)
from ray_tpu.models import cohere2_moe, llama  # noqa: E402
from ray_tpu.models.cohere2_moe import Cohere2MoEConfig  # noqa: E402
from ray_tpu.ops.rope import rope_table  # noqa: E402
from ray_tpu.parallel.sharding import unbox_params  # noqa: E402

TOL = 1e-4
FAULT = 1e-2
VOCAB = 96
SEQ = 128
RING = 12
HELD = (4, 8)
KWARGS = dict(
    vocab_size=VOCAB, dim=64, n_layers=4, n_heads=8, n_kv_heads=2,
    head_dim=16, sliding_window=RING, layer_switch=4, intermediate=48,
    n_experts=16, experts_per_token=4, n_shared_experts=2,
    norm_topk_prob=True, experts_held=HELD, logit_scale=1.0,
    rope_theta=50000.0, norm_eps=1e-5, max_seq_len=SEQ, dtype=jnp.float32,
    param_dtype=jnp.float32,
)
# the same toy as a benchmark configuration file would state it
PUBLISHED = dict(
    name="toy", vocab_size=VOCAB, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    sliding_window=RING, layer_switch=4,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    intermediate_size=48, num_experts=4, experts_first=4,
    published={"num_experts": 16}, num_experts_per_tok=4,
    num_shared_experts=2, norm_topk_prob=True, logit_scale=1,
    rope_theta=50000, layer_norm_eps=1e-5, tie_word_embeddings=True,
    use_parallel_block=True, position_embedding_type="rope_gptj",
    shared_expert_combination_strategy="average",
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
    cfg = Cohere2MoEConfig(**KWARGS)
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
    assert arguments["model_family"] == "cohere2_moe"
    built = Cohere2MoEConfig(**dict(
        arguments["model_kwargs"], max_seq_len=SEQ, dtype=jnp.float32,
        param_dtype=jnp.float32))
    assert built == cfg
    assert cfg.routed_layers == (0, 1, 2, 3)
    assert [cfg.is_window(i) for i in range(4)] == [True, True, True, False]
    assert cfg.n_experts == 16 and cfg.routed_config().n_experts_held == 4
    # the published model's own numbers are the defaults
    full = Cohere2MoEConfig()
    assert (full.n_layers, full.n_heads, full.n_kv_heads) == (32, 128, 8)
    assert [i for i in range(32) if not full.is_window(i)] == list(
        range(3, 32, 4))
    window, whole = full.attention_config(True), full.attention_config(False)
    assert (window.window, window.rope, window.rope_interleaved) == (
        4096, True, True)
    assert (whole.window, whole.rope, whole.head_dim) == (None, False, 128)
    assert full.routed_config().router_scoring == "sigmoid"
    with pytest.raises(SystemExit, match="shared_expert_combination"):
        arch.sizes_of(dict(
            PUBLISHED, shared_expert_combination_strategy="sum"))
    with pytest.raises(SystemExit, match="layer_types"):
        arch.sizes_of(dict(PUBLISHED, layer_types=["full_attention"] * 4))


def test_whole_sequence_under_the_band_matches_the_reference(tiny, apply):
    """Two prompts of 29 tokens, more than twice the ring: the window
    layers' whole prompt goes through the flash kernel under the band."""
    cfg, params = tiny
    tokens = _tokens((2, 29))
    got, _, chosen = _pieces(apply, params, tokens, (29,))
    assert len(chosen) == 4  # every layer routes
    slack: list = []
    assert _diff(got, _followed(params, tokens, chosen, slack=slack)) < TOL
    # the program's choice is the reference's own nearly everywhere
    assert float(jnp.mean(jnp.stack(slack) == 0)) > 0.95
    assert float(jnp.max(jnp.stack(slack))) < 1e-4
    # ... and some of it falls on experts held elsewhere, some here
    here = (chosen[0] >= HELD[0]) & (chosen[0] < HELD[1])
    assert 0 < int(here.sum()) < here.size


@pytest.mark.parametrize("prompt", [19, 12, 7])
def test_prefill_then_decode_through_the_ring_matches_the_reference(
        tiny, apply, prompt):
    """A prompt longer than the ring, as long as it, and shorter: the ring a
    prefill leaves, then steps that write at ``p % ring`` and wrap it (a row
    younger than the ring reads its live slots alone); logits, every
    position, against the reference's banded mask."""
    cfg, params = tiny
    tokens = _tokens((2, 36), seed=prompt)
    got, cache, chosen = _pieces(
        apply, params, tokens, (prompt,) + (1,) * (36 - prompt))
    assert _diff(got, _followed(params, tokens, chosen)) < TOL
    for i in range(3):
        attn = cache[f"layer_{i}"]["attn"]
        assert set(attn) == {"window_key", "window_value", "cache_index"}
        assert attn["window_key"].shape == (2, 2, RING, 16)
    attn = cache["layer_3"]["attn"]
    assert set(attn) == {"cached_key", "cached_value", "cache_index"}
    assert attn["cached_key"].shape == (2, 2, SEQ, 16)
    assert [int(i) for i in attn["cache_index"]] == [36, 36]
    kinds = models.cache_kinds(cache)
    assert sorted(jax.tree.leaves(kinds["layer_0"])) == [
        models.INDEX, models.WINDOW, models.WINDOW]
    assert sorted(jax.tree.leaves(kinds["layer_3"])) == [
        models.INDEX, models.SEQUENCE, models.SEQUENCE]


def test_a_prefills_ring_holds_the_last_positions_at_their_slots():
    rows = jnp.arange(2 * 3 * 29 * 4, dtype=jnp.float32).reshape(2, 3, 29, 4)
    ring = np.asarray(llama.ring_of(rows, RING))
    assert ring.shape == (2, 3, RING, 4)
    for p in range(29 - RING, 29):
        assert np.array_equal(ring[:, :, p % RING], np.asarray(rows[:, :, p]))
    short = np.asarray(llama.ring_of(rows[:, :, :7], RING))
    assert np.array_equal(short[:, :, :7], np.asarray(rows[:, :, :7]))
    assert not short[:, :, 7:].any()


def test_more_than_one_position_against_a_ring_is_refused(tiny, apply):
    cfg, params = tiny
    tokens = _tokens((1, 20))
    _, cache, _ = apply(params, tokens[:, :16])
    with pytest.raises(NotImplementedError, match="ring"):
        apply(params, tokens[:, 16:], cache)


def test_the_full_layer_takes_the_kernel_where_the_scores_do_not_fit(
        tiny, monkeypatch):
    """Which form a whole prompt's attention takes is one rule over the
    layer's configuration and the call's shapes; both forms are one
    function."""
    cfg, params = tiny
    window, whole = cfg.attention_config(True), cfg.attention_config(False)
    assert llama.prefills_through_kernel(window, 1, 2)
    assert not llama.prefills_through_kernel(whole, 1, 29)
    published = Cohere2MoEConfig().attention_config(False)
    assert llama.prefills_through_kernel(published, 1, 2048)
    assert not llama.prefills_through_kernel(published, 1, 256)
    # what the benchmark's other cells prefill stays on the einsum
    for heads, seq in ((32, 512), (16, 512), (64, 1024), (32, 2048)):
        assert not llama.prefills_through_kernel(
            llama.LlamaConfig(n_heads=heads), 1, seq)
    tokens = _tokens((2, 29))
    einsum = _applier(cfg)(params, tokens)[0]
    monkeypatch.setattr(llama, "_EINSUM_SCORE_BYTES", 0)
    assert llama.prefills_through_kernel(whole, 1, 29)
    assert _diff(_applier(cfg)(params, tokens)[0], einsum) < 1e-5


@pytest.mark.parametrize("fault", [
    "no_window", "rope_on_full", "rotate_half", "shared_summed", "mean_kept",
    "lost_expert",
])
def test_a_reference_that_computes_a_neighbour_fails(tiny, apply, fault):
    """The band left out of one window layer, rotary on the full layer,
    rotate-half in place of interleaved pairs, the shared experts summed
    not averaged, LayerNorm's mean kept in, a token's last expert lost: the
    program computes the published layer, so each moves the comparison past
    ``FAULT``."""
    cfg, params = tiny
    tokens = _tokens((1, 36), seed=19)
    got, _, chosen = _pieces(apply, params, tokens, (19,) + (1,) * 17)
    assert _diff(got, _followed(params, tokens, chosen)) < TOL
    assert _diff(
        got, _followed(params, tokens, chosen, faults=(fault,))) > FAULT


def test_a_ring_read_past_its_live_slots_fails(tiny, apply, monkeypatch):
    """Program side: a decode step that reads a whole ring whatever the
    row's age. A row older than the ring does not see it; a row younger
    than it does."""
    cfg, params = tiny
    whole_ring = llama.decode_attention

    def past_live(q, k, v, lengths, mesh=None):
        if k.shape[2] == RING:
            lengths = jnp.full_like(lengths, RING)
        return whole_ring(q, k, v, lengths, mesh)

    monkeypatch.setattr(llama, "decode_attention", past_live)
    faulty = _applier(cfg)
    for prompt, moved in ((19, False), (5, True)):
        tokens = _tokens((1, prompt + 4), seed=prompt)
        got, _, chosen = _pieces(
            faulty, params, tokens, (prompt,) + (1,) * 4)
        off = _diff(got, _followed(params, tokens, chosen))
        assert (off > FAULT) == moved, (prompt, off)


def test_eight_shares_and_what_every_chip_computes_add_up_to_the_uncut_layer():
    """One block cut eight ways: each share's routed part, and attention
    and the shared experts counted once, add up to what the uncut reference
    gives for the whole layer."""
    whole = Cohere2MoEConfig(**dict(KWARGS, n_layers=1, experts_held=None))
    block = cohere2_moe.Block(whole, True)
    cos, sin = rope_table(SEQ, 16, 50000.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 29, 64))
    params = unbox_params(
        block.init(jax.random.PRNGKey(1), x, cos, sin)["params"])
    params["norm"] = params["norm"] + 0.3 * jax.random.normal(
        jax.random.PRNGKey(2), (64,))
    sizes = {k: v for k, v in SIZES.items() if k != "n_layers"}
    attn, ffn, own, _ = arch.block_parts(
        x[0], arch.layer_weights({"layer_0": params}, 0), 0,
        **dict(sizes, experts_first=0))
    want = x[0] + attn + ffn
    uncut, sown = block.apply(
        {"params": params}, x, cos, sin, mutable=["cache", models.ROUTING])
    assert _diff(uncut[0], want) < 1e-5
    assert _diff(sown[models.ROUTING]["moe"]["experts"][0], own) == 0
    # what every chip computes alike
    n = cohere2_moe.layer_norm(x, params["norm"], 1e-5)
    attended = llama.Attention(whole.attention_config(True), None, True).apply(
        {"params": params["attn"]}, n, cos, sin, mutable=["cache"])[0]
    shared = cohere2_moe.SwiGLU(whole, 2 * 48).apply(
        {"params": params["shared"]}, n) / 2
    assert _diff(attended[0], attn) < 1e-5
    parts = []
    for first in range(0, 16, 2):
        cut = Cohere2MoEConfig(**dict(
            KWARGS, n_layers=1, experts_held=(first, first + 2)))
        held = dict(params, moe=dict(params["moe"], **{
            name: params["moe"][name][first:first + 2]
            for name in ("w_gate", "w_up", "w_down")}))
        out = cohere2_moe.Block(cut, True).apply(
            {"params": held}, x, cos, sin, mutable=["cache"])[0]
        parts.append(out - x - attended - shared)
    assert all(float(jnp.max(jnp.abs(p))) > 1e-3 for p in parts)
    assert _diff((x + sum(parts) + attended + shared)[0], want) < 1e-5
    # a share is the reference's share
    share = arch.block_parts(
        x[0], arch.layer_weights({"layer_0": held}, 0), 0,
        **dict(sizes, experts_first=14))
    assert _diff(out[0], x[0] + share[0] + share[1]) < 1e-5


def test_the_head_is_the_embedding_and_is_scaled(tiny):
    cfg, params = tiny
    assert "embed" not in params and params["lm_head"].shape == (VOCAB, 64)
    tokens = _tokens((1, 9))
    plain = _applier(cfg)(params, tokens)[0]
    scaled = _applier(Cohere2MoEConfig(**dict(KWARGS, logit_scale=0.25)))(
        params, tokens)[0]
    assert _diff(scaled, 0.25 * plain) < 1e-6


def test_bf16_weights_are_drawn_in_float32():
    cfg = Cohere2MoEConfig.tiny(param_dtype=jnp.bfloat16)
    got = unbox_params(models.init_params(cfg, jax.random.PRNGKey(0)))
    want = unbox_params(models.init_params(
        Cohere2MoEConfig.tiny(param_dtype=jnp.float32), jax.random.PRNGKey(0)))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == jnp.bfloat16
        assert _diff(a.astype(jnp.float32), b.astype(jnp.bfloat16)) == 0.0


@pytest.mark.parametrize("feature,kwargs", [
    ("adapters", {"adapters": {"max_live": 2}}),
    ("mesh", {"mesh": {"tp": 2}}),
    ("prefill_chunk", {"prefill_chunk_tokens": 16}),
])
def test_refusals(feature, kwargs):
    reasons = models.refusals("cohere2_moe")
    assert set(reasons) == {"adapters", "mesh", "prefill_chunk"}
    with pytest.raises(ValueError) as refused:
        LLMConfig(model_id="c2moe-tiny", model_family="cohere2_moe",
                  kv_cache_blocks=4, **kwargs)
    assert feature in str(refused.value)
    assert reasons[feature] in str(refused.value)


def test_llm_config_builds_the_family():
    cfg = LLMConfig(
        model_id="c2moe-tiny", model_family="cohere2_moe",
        model_kwargs={"sliding_window": 16}, max_seq_len=64,
        kv_cache_blocks=1,
    ).build_model_config()
    assert isinstance(cfg, Cohere2MoEConfig)
    assert (cfg.sliding_window, cfg.max_seq_len) == (16, 64)
    assert models.carries_row_state(cfg)
    assert not models.restarts_own_state(cfg)
    with pytest.raises(NotImplementedError, match="serving"):
        models.build(cfg, None, decode=False)


def test_the_published_configuration_counts_what_the_issue_reckoned():
    """The cell's file through the byte counts: 50,331,648 B of rings and
    4096 B a position a row, 344.47 M parameters a layer outside its routed
    experts, 50.33 M an expert."""
    import json

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "configs", "command-a-plus-05-2026-serve-1chip.json")
    with open(path) as f:
        config = json.load(f)
    sizes = arch.sizes_of(config)
    assert sizes["guaranteed"] == {
        "window_bytes_per_row": 50331648, "kv_bytes_per_token": 4096}
    assert (sizes["n_held"], sizes["n_routed"]) == (16, 128)
    assert flops_c2moe.expert_params(config) == 50331648
    assert (flops_c2moe.dense_params(config)
            + flops_c2moe.router_params(config) + 4096) == 344461312
    built = Cohere2MoEConfig(**arch.llm_arguments(config)["model_kwargs"])
    assert built == Cohere2MoEConfig(
        vocab_size=32768, n_layers=4, experts_held=(0, 16))
