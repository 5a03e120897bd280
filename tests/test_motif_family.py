"""A sixth architecture through the serving stack: a Motif-shaped model
(grouped differential latent attention on window and full layers, a
four-stream mHC residual, PolyNorm feed-forwards, two dense layers and then
a held share of routed experts beside a shared one) built by
``ray_tpu.models`` for the engine, against the benchmark's plain reference
(``benchmarks/reference/motif_arch.py``), which imports none of the
program's code, has no cache and no ring, and attends in the published form
under a banded mask.

What is new to the stack: a fourth kind of cache leaf (``models.WINDOW``: a
ring of a window layer's last positions beside full-length latent rows in
the same slot row), an expert activation other than SwiGLU inside the
grouped kernel, a query low-rank path, more than one residual stream.

The toy has the published shape at a period of two: 4 layers (window, full,
window, full; the first dense, three routed), hidden 64, 10 heads in 2
groups of 4 signal + 1 noise, ring 16, 16 experts routed over of which
4..12 are held, top 2, norms shaken away from one. (Most of this file's
time is the CPU's compiles: one engine serves most of the engine's tests.)

Tolerance, float32 on the CPU: 1e-4 on logits of magnitude ~1-4, under the
experts the program chose (``follow=``: a top-k is a discontinuity).
Measured: 5e-6 or less. Every fault asserted below moves them by 0.1 and
more.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import motif_arch as arch  # noqa: E402
from ray_tpu import models  # noqa: E402
from ray_tpu.kvcache import KVCacheManager  # noqa: E402
from ray_tpu.llm import LLMConfig  # noqa: E402
from ray_tpu.llm.engine import (  # noqa: E402
    ContinuousBatchingEngine, GenerationRequest,
)
from ray_tpu.models import motif  # noqa: E402
from ray_tpu.models.moe import (  # noqa: E402
    MoEConfig, MoEFFN, poly_coefficients, poly_norm,
)
from ray_tpu.models.motif import MotifConfig  # noqa: E402
from ray_tpu.ops.moe_experts import moe_experts  # noqa: E402
from ray_tpu.parallel.sharding import unbox_params  # noqa: E402

TOL = 1e-4
VOCAB = 96
SEQ = 128
RING = 16
HELD = (4, 12)
KWARGS = dict(
    vocab_size=VOCAB, dim=64, n_layers=4, n_heads=10, n_kv_heads=2,
    q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, sliding_window=RING, sliding_window_period=2,
    first_dense_layers=1, intermediate=96, moe_intermediate=32,
    n_experts=16, experts_per_token=2, experts_held=HELD, max_seq_len=SEQ,
    # (three iterations: twenty unrolled in sixteen sub-layers are most of
    # a CPU compile; the normalisation itself is held at twenty below)
    mhc_sinkhorn_iters=3, dtype=jnp.float32, param_dtype=jnp.float32,
)
# the same toy as a benchmark configuration file would state it
PUBLISHED = dict(
    name="toy", attention_cls="gdla", diff_v2=True,
    elementwise_attn_output_gate=True, headwise_attn_output_gate=False,
    hidden_act="poly_norm", mhc_enabled=True, interleave_moe_layer_step=1,
    mscale=1, score_func="sigmoid", score_before_experts=False,
    sliding_window_pattern="interleave", use_sliding_window=True,
    tie_word_embeddings=False, num_nextn_predict_layers=0,
    polynorm_output_scale_per_layer={},
    rope_scaling={"apply_yarn_scaling": False}, rope_theta=10000,
    swa_rope_theta=10000, vocab_size=VOCAB, hidden_size=64,
    num_hidden_layers=4, num_attention_heads=10, num_key_value_heads=2,
    num_noise_heads=2, head_dim=24, qk_rope_head_dim=8, v_head_dim=16,
    q_lora_rank=24, kv_lora_rank=32, sliding_window=RING,
    sliding_window_period=2, mhc_expansion_rate=4, mhc_sinkhorn_iters=3,
    hidden_clamp=1000000, intermediate_size=96, moe_intermediate_size=32,
    num_experts=8, experts_first=4, published={"num_experts": 16},
    experts_top_k=2, num_shared_experts=1, n_dense_first_layers=1,
    route_norm=True, route_scale=2, polynorm_output_scale=0.5,
    polynorm_bias_clamp=0.5, rms_norm_eps=1e-5,
)


def _sizes(**changed):
    sizes = arch.sizes_of(dict(PUBLISHED, **changed))
    for key in ("guaranteed", "n_routed", "n_held"):
        sizes.pop(key)
    return sizes


SIZES = _sizes()


def _params(cfg, seed=0):
    """Seeded weights with the norms away from one, so that a norm left
    out shows."""
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
    cfg = MotifConfig(**KWARGS)
    return cfg, _params(cfg)


def _engine(cfg, params, slots=3, **kw):
    return ContinuousBatchingEngine(
        cfg, params, num_slots=slots,
        kv_cache=KVCacheManager(num_blocks=8, block_size=8), seed=0, **kw)


PROMPTS = (7, 16, 41)  # shorter than, as long as and longer than the ring


@pytest.fixture(scope="module")
def served(tiny):
    """One engine that has served three requests of different lengths side
    by side, and what it answered."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompts = [_tokens((n,), seed=10 + n) for n in PROMPTS]
    return engine, prompts, engine.generate([_request(p, 24) for p in prompts])


def _request(tokens, n):
    return GenerationRequest(
        token_ids=[int(t) for t in tokens], max_new_tokens=n)


_APPLIERS = {}


def _applier(cfg):
    """The serving module's ``apply`` jitted: ``(params, tokens, cache or
    None) -> (logits, cache, each routed layer's chosen experts)``."""
    if cfg in _APPLIERS:
        return _APPLIERS[cfg]
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

    _APPLIERS[cfg] = apply
    return apply


def _followed(params, tokens, chosen, sizes=SIZES, **kw):
    """The reference's logits under the experts the program chose."""
    return arch.logits(params, tokens, follow=chosen, **sizes, **kw)


def _stepped(apply, params, tokens, plen):
    """``tokens (1, n)``: a prefill of the first ``plen`` and then a token a
    step. The logits of every position, the last cache, and each routed
    layer's chosen experts over all the positions."""
    got, cache, chosen = apply(params, tokens[:, :plen])
    got = [got]
    for at in range(plen, tokens.shape[1]):
        out, cache, chose = apply(params, tokens[:, at:at + 1], cache)
        got.append(out)
        chosen = [jnp.concatenate(pair) for pair in zip(chosen, chose)]
    return jnp.concatenate(got, axis=1), cache, chosen


def _reference_rows(params, prompt, answer):
    toks = [int(t) for t in prompt] + [int(t) for t in answer[:-1]]
    return arch.logits(
        params, jnp.asarray([toks], jnp.int32), last=len(answer), **SIZES)[0]


def _is_the_references_greedy(params, prompt, answer) -> bool:
    rows = _reference_rows(params, prompt, answer)
    return [int(t) for t in jnp.argmax(rows, axis=-1)] == list(answer)


# -- the model against the reference -----------------------------------------

def test_the_configuration_keys_reach_the_program():
    arguments = arch.llm_arguments(PUBLISHED)
    assert arguments["model_family"] == "motif"
    built = LLMConfig(
        model_id="toy", max_seq_len=SEQ, kv_cache_blocks=4,
        model_family="motif",
        model_kwargs=dict(arguments["model_kwargs"], dtype=jnp.float32,
                          param_dtype=jnp.float32),
    ).build_model_config()
    assert built == MotifConfig(**KWARGS)
    assert [built.is_window(i) for i in range(4)] == [True, False] * 2
    assert [MotifConfig().is_window(i) for i in range(8)] == [
        True, True, True, False] * 2
    assert built.routed_layers == (1, 2, 3)
    assert built.signal_heads == 4


def test_init_draws_in_float32_and_keeps_the_maps_wide():
    two = dict(KWARGS, n_layers=2)
    cfg = MotifConfig(**dict(two, param_dtype=jnp.bfloat16))
    narrow = unbox_params(models.init_params(cfg, jax.random.PRNGKey(0)))
    wide = unbox_params(models.init_params(
        MotifConfig(**two), jax.random.PRNGKey(0)))
    kept = {"phi", "alpha", "bias", "poly"}
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(narrow),
                            jax.tree.leaves(wide)):
        want = jnp.float32 if path[-1].key in kept else jnp.bfloat16
        assert a.dtype == want, path
        assert _diff(a.astype(jnp.float32), b.astype(want)) == 0.0


def test_whole_sequence_matches_the_reference(tiny):
    cfg, params = tiny
    tokens = _tokens((1, 45))
    got, _, chosen = _applier(cfg)(params, tokens)
    own = []
    want = arch.logits(params, tokens, routing=own, **SIZES)
    assert _diff(got, _followed(params, tokens, chosen)) < TOL
    # where the program chose the reference's own experts they agree as is
    if all(bool(jnp.all(jnp.sort(a) == jnp.sort(b)))
           for a, b in zip(chosen, own)):
        assert _diff(got, want) < TOL


@pytest.mark.parametrize("plen,decoded", [
    (9, 5),    # the prompt shorter than the ring
    (16, 5),   # ... as long as it
    (37, 5),   # ... longer: the prefill leaves its last 16 positions, turned
    (5, 44),   # a decode that wraps the ring twice
])
def test_prefill_then_decode_through_the_ring_matches_the_reference(
        tiny, plen, decoded):
    cfg, params = tiny
    tokens = _tokens((1, plen + decoded), seed=plen)
    got, cache, chosen = _stepped(_applier(cfg), params, tokens, plen)
    assert _diff(got, _followed(params, tokens, chosen)) < TOL
    ring = cache["layer_0"]["attn"]
    assert ring["window_latent"].shape == (1, 1, RING, 32)
    assert int(ring["cache_index"][0]) == plen + decoded
    assert cache["layer_1"]["attn"]["cached_latent"].shape == (1, 1, SEQ, 32)


@pytest.mark.parametrize("s", [3, 16, 17, 40, 48])
def test_a_prefills_ring_holds_position_p_at_slot_p_mod_ring(s):
    rows = jnp.arange(s, dtype=jnp.float32).reshape(1, 1, s, 1) + 1.0
    ring = np.asarray(motif.ring_of(rows, RING))[0, 0, :, 0]
    want = np.zeros(RING)
    for p in range(max(0, s - RING), s):
        want[p % RING] = p + 1.0
    assert ring.tolist() == want.tolist()


@pytest.mark.parametrize("window", [True, False])
def test_the_absorbed_differential_form_equals_the_published_form(window):
    """One attention layer alone: a prefill of 29 positions and then a step
    against the cache (absorbed, the subtraction on the latents) against
    the published form over all 30."""
    cfg = MotifConfig(**KWARGS)
    layer = motif.GDLA(cfg, window)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 30, cfg.dim))
    cos, sin = motif.rope_table(SEQ, cfg.qk_rope_head_dim, cfg.rope_theta)
    params = layer.init(jax.random.PRNGKey(5), x, cos, sin)["params"]
    want, _ = layer.apply({"params": params}, x, cos, sin, mutable=["cache"])
    _, state = layer.apply(
        {"params": params}, x[:, :29], cos, sin, mutable=["cache"])
    got, _ = layer.apply(
        {"params": params, "cache": state["cache"]}, x[:, 29:], cos, sin,
        mutable=["cache"])
    assert _diff(got[:, 0], want[:, 29]) < 1e-5
    with pytest.raises(NotImplementedError, match="more than one"):
        layer.apply({"params": params, "cache": state["cache"]}, x[:, 28:],
                    cos, sin, mutable=["cache"])


@pytest.mark.parametrize("window", [None, RING])
def test_a_prompts_key_blocks_are_the_whole_softmax(window, monkeypatch):
    """The published-form attention in blocks of queries, and for a full
    layer of keys under an online softmax, is the attention in one block
    (ragged last blocks of both kinds)."""
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(keys[0], (2, 2, 5, 45, 24))
    k = jax.random.normal(keys[1], (2, 2, 45, 24))
    v = jax.random.normal(keys[2], (2, 2, 45, 16))
    whole = motif._banded_attention(q, k, v, 0.2, window)
    monkeypatch.setattr(motif, "_QUERY_BLOCK", 8)
    monkeypatch.setattr(motif, "_KEY_BLOCK", 16)
    assert _diff(motif._banded_attention(q, k, v, 0.2, window), whole) < 1e-5
    positions = jnp.arange(45)
    seen = positions[None, :] <= positions[:, None]
    if window:
        seen &= positions[None, :] > positions[:, None] - window
    scores = jnp.einsum("bgjqd,bgkd->bgjqk", q, k) * 0.2
    plain = jnp.einsum("bgjqk,bgkd->bgjqd", jax.nn.softmax(
        jnp.where(seen, scores, -jnp.inf), axis=-1), v)
    assert _diff(whole, plain) < 1e-5


FAULTS = ("no_noise", "no_window", "no_sinkhorn", "no_cubic", "no_shared",
          "no_route_scale", "no_latent_norm", "lost_expert")


@pytest.mark.parametrize("fault", FAULTS)
def test_what_the_check_has_to_see_moves_the_logits(tiny, fault):
    """A reference that leaves one mechanism out is no longer the
    program's function: the noise branch, a window layer's band, the
    Sinkhorn normalisation, PolyNorm's cubic term, the shared expert, the
    route's scale, the latent norm, a token's last expert."""
    cfg, params = tiny
    tokens = _tokens((1, 45))
    got, _, chosen = _applier(cfg)(params, tokens)
    assert _diff(got, _followed(params, tokens, chosen, faults=(fault,))) > 0.1


def test_sinkhorn_rows_and_columns_sum_to_one():
    m = jnp.exp(jax.random.normal(jax.random.PRNGKey(7), (4, 4, 33)))
    got = motif.sinkhorn(m, 20)
    assert _diff(got.sum(axis=0), 1.0) < 1e-5
    assert _diff(got.sum(axis=1), 1.0) < 1e-5
    want = arch.sinkhorn(jnp.moveaxis(m, -1, 0), 20)
    assert _diff(jnp.moveaxis(got, -1, 0), want) < 1e-6


def test_the_sliced_head_equals_the_uncut_heads_columns():
    one = dict(KWARGS, n_layers=1, first_dense_layers=0)
    cfg = MotifConfig(**one)
    wide = MotifConfig(**dict(one, vocab_size=2 * VOCAB))
    uncut = _params(wide)
    cut = dict(uncut, embed=uncut["embed"][:VOCAB],
               lm_head=uncut["lm_head"][:, :VOCAB])
    tokens = _tokens((1, 21))
    whole = _applier(wide)(uncut, tokens)[0]
    assert _diff(_applier(cfg)(cut, tokens)[0], whole[..., :VOCAB]) < 1e-5


# -- the experts ----------------------------------------------------------------

def _poly_einsum(x, w_gate, w_up, w_down, group_sizes, poly, eps):
    owner = jnp.repeat(jnp.arange(len(group_sizes)), group_sizes,
                       total_repeat_length=x.shape[0])
    gate = jnp.einsum("md,mdf->mf", x, w_gate[owner])
    up = jnp.einsum("md,mdf->mf", x, w_up[owner])
    hidden = jax.vmap(poly_norm, in_axes=(0, 0, None))(gate, poly[owner], eps)
    return jnp.einsum("mf,mfd->md", hidden * up, w_down[owner])


@pytest.mark.parametrize("rows,inner", [(16, 32), (48, 256), (256, 384)])
def test_the_polynorm_kernel_is_the_einsum(rows, inner, monkeypatch):
    """The grouped kernel's PolyNorm form (two sweeps of the inner width,
    the row's power sums kept between them) against the plain form, with an
    inner width of one block and of several, tiles that experts share, and
    an expert nobody chose."""
    from ray_tpu.ops import moe_experts as kernel

    monkeypatch.setattr(kernel, "_BLOCK_BYTES", 64 * 128 * 4)
    keys = jax.random.split(jax.random.PRNGKey(rows), 6)
    experts, d = 5, 64
    x = jax.random.normal(keys[0], (rows, d))
    w_gate = jax.random.normal(keys[1], (experts, d, inner)) / 8
    w_up = jax.random.normal(keys[2], (experts, d, inner)) / 8
    w_down = jax.random.normal(keys[3], (experts, inner, d)) / 8
    poly = poly_coefficients(
        jax.random.normal(keys[4], (experts, 4)), 0.5, 0.5)
    cut = jnp.sort(jax.random.randint(keys[5], (3,), 0, rows + 1))
    sizes = jnp.diff(jnp.concatenate(
        [jnp.zeros(1, jnp.int32), cut, jnp.asarray([rows])]))
    sizes = jnp.concatenate([sizes[:2], jnp.zeros(1, jnp.int32), sizes[2:]])
    got = moe_experts(x, w_gate, w_up, w_down, sizes,
                      activation="poly_norm", poly=poly, eps=1e-5)
    want = _poly_einsum(x, w_gate, w_up, w_down, sizes, poly, 1e-5)
    assert _diff(got, want) < 2e-4 * float(jnp.max(jnp.abs(want)))


def test_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The routed part a share gives, summed over the eight shares of a
    layer's sixteen experts, is the uncut layer's routed part (the weights
    are normalised over the experts chosen wherever they live), and the
    shared expert is added once, not once a share."""
    base = dict(dim=64, intermediate=32, n_experts=16, experts_per_token=4,
                dropless=True, router_scoring="sigmoid", routed_scale=2.0,
                expert_activation="poly_norm", dtype=jnp.float32,
                param_dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 7, 64))
    uncut = MoEFFN(MoEConfig(**base))
    params = unbox_params(uncut.init(jax.random.PRNGKey(2), x)["params"])
    whole = uncut.apply({"params": params}, x)
    parts = 0.0
    for first in range(0, 16, 2):
        share = dict(params, **{
            name: params[name][first:first + 2]
            for name in ("w_gate", "w_up", "w_down", "poly")})
        parts = parts + MoEFFN(MoEConfig(
            **base, experts_held=(first, first + 2))).apply({"params": share}, x)
    assert _diff(parts, whole) < 1e-5
    assert float(jnp.max(jnp.abs(whole))) > 0.05
    with pytest.raises(ValueError, match="poly_norm"):
        MoEConfig(**dict(base, dropless=False, router_scoring="softmax",
                         routed_scale=1.0))


# -- the serving stack -----------------------------------------------------------

def test_cache_leaves_classify_by_name():
    assert models.cache_leaf_kind("window_latent") == models.WINDOW
    assert models.cache_leaf_kind("window_rope") == models.WINDOW
    assert models.cache_leaf_kind("cached_latent") == models.SEQUENCE
    assert models.cache_leaf_kind("cache_index") == models.INDEX
    assert models.cache_leaf_kind("state_kda") == models.STATE
    cfg = MotifConfig(**KWARGS)
    assert models.carries_row_state(cfg)
    assert not models.restarts_own_state(cfg)


@pytest.mark.parametrize("feature,kwargs", [
    ("adapters", {"adapters": {"max_adapters": 2}}),
    ("mesh", {"mesh": {"tp": 2}}),
    ("prefill_chunk", {"prefill_chunk_tokens": 64}),
])
def test_refusals(feature, kwargs):
    assert set(models.refusals("motif")) == {
        "adapters", "mesh", "prefill_chunk"}
    with pytest.raises(ValueError, match=feature):
        LLMConfig(model_id="motif-tiny", model_family="motif",
                  kv_cache_blocks=4, **kwargs)
    # ... and the other families keep the chunk path
    LLMConfig(model_id="tiny", kv_cache_blocks=4, prefill_chunk_tokens=64)


def test_the_manager_leases_a_window_family_without_match_commit_or_pool(
        tiny, served):
    cfg, params = tiny
    engine, prompts, results = served
    prompt = prompts[1]
    again = engine.generate([_request(prompt, 24)])[0]
    assert again.token_ids == results[1].token_ids
    stats = engine._kv.stats()
    assert stats["prefix_reuse"] is False and stats["hits"] == 0
    assert stats["blocks_in_use"] == 0 and not engine._kv.ready
    assert "ring" in engine._kv.prefix_reuse_refused
    # a manager that was never told refuses at the first row it is shown
    alone = KVCacheManager(num_blocks=8, block_size=8)
    alone.initialize(engine._prefill(
        params, jnp.asarray([prompt], jnp.int32))[1])
    assert not alone.prefix_reuse and not alone.ready


def test_engine_tokens_through_the_slot_cache_rows_of_different_lengths(
        tiny, served):
    """Three requests of different lengths (shorter than, as long as and
    longer than the ring) side by side in one pool: each gets the
    reference's own greedy tokens, through decode steps that wrap each
    row's ring at its own position."""
    cfg, params = tiny
    engine, prompts, results = served
    for prompt, result in zip(prompts, results):
        assert len(result.token_ids) == 24
        assert _is_the_references_greedy(params, prompt, result.token_ids)
    kinds = jax.tree.leaves(models.cache_kinds(engine._cache))
    assert kinds.count("window") == 2 * 2 and kinds.count("sequence") == 2 * 2
    assert kinds.count("index") == 4
    assert engine._state_span == {"state_rows": 3}


def test_engine_steps_match_the_reference_logits_two_rows_live(tiny, served):
    """The engine's own jitted prefill, row insert and decode at the pool's
    shape, two rows of different lengths live and one of them in a slot
    another row left, every step's logits under the step's own choice of
    experts (the counters' ``choice``): what the benchmark's check does at
    the cell's size."""
    cfg, params = tiny
    engine, prompts, results = served
    prompt, tokens = prompts[2], results[2].token_ids
    plen = len(prompt)
    logits, row = engine._prefill(params, jnp.asarray([prompt], jnp.int32))
    other = engine._prefill(params, jnp.asarray([prompts[0]], jnp.int32))[1]
    cache = engine._empty_cache(row)
    cache = engine._insert_row(cache, other, jnp.asarray(2, jnp.int32))
    cache = engine._insert_row(cache, other, jnp.asarray(0, jnp.int32))
    zeroed = jax.tree.map(jnp.zeros_like, engine._expert_counts)
    routed = len(cfg.routed_layers)
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
                ] == [live] * routed
        if step >= 5:
            got.append(out[2])
            chose.append(counts["choice"][:, 2])
    chose = jnp.stack(chose)  # (steps, routed layers, k)
    fed = jnp.asarray([list(map(int, prompt)) + tokens[:-1]], jnp.int32)
    prefilled = _applier(cfg)(params, fed[:, :plen])[2]
    follow = [jnp.concatenate([prefilled[layer], chose[:, layer]])
              for layer in range(routed)]
    want = _followed(params, fed, follow)[0]
    assert _diff(jnp.stack(got), want[plen:]) < TOL
    assert _diff(logits[0], want[plen - 1]) < TOL


def test_a_freed_slot_taken_again_is_a_fresh_row(tiny):
    """Slot 0's request ends after 4 tokens; the slot stays free for 40
    steps of another request (each of which writes the free row's slot 0 of
    every ring) and is then taken again: the answer is the reference's,
    without any zeroing of the ring."""
    cfg, params = tiny
    engine = _engine(cfg, params, slots=2)
    short = _request(_tokens((16,), seed=31), 4)
    long_ = _request(_tokens((7,), seed=32), 70)
    rid_short, rid_long = engine.add_request(short), engine.add_request(long_)
    done = {}
    while rid_short not in done:
        done.update(engine.step())
    for _ in range(40):
        done.update(engine.step())
    assert rid_long not in done and list(engine._slots) == [1]
    again = _request(_tokens((16,), seed=33), 20)
    rid = engine.add_request(again)
    while rid not in done:
        done.update(engine.step())
    assert _is_the_references_greedy(
        params, again.token_ids, done[rid].token_ids)


def test_runtime_counters_the_ring_apart_from_what_grows(tiny, served):
    cfg, params = tiny
    assert _engine(cfg, params).window_bytes_per_row() is None
    engine = served[0]
    # two full layers of a (32 + 8)-wide float32 row a position; two rings
    # of 16 positions
    assert engine.cache_bytes_per_token() == 2 * 40 * 4
    assert engine.window_bytes_per_row() == 2 * RING * 40 * 4
    assert engine.state_bytes_per_row() == 0
    published = dict(PUBLISHED)
    assert arch.flops_gdla.kv_bytes_per_token(published, 4) == 2 * 40 * 4
    assert arch.flops_gdla.window_bytes_per_row(published, 4) == 2 * RING * 40 * 4
    chunks = engine.attention_chunks()
    assert chunks["attention_chunks_dense"] and (
        chunks["attention_chunks_dense"] % (3 * (SEQ // 128)) == 0)
    stats = engine.expert_stats()
    assert (stats["experts_routed"], stats["experts_held"]) == (16, 8)
    assert np.asarray(stats["assignments"]).shape == (3, 8)
    live = (np.asarray(stats["assignments"]).sum(1)
            + np.asarray(stats["assignments_absent"]))
    assert len(set(live)) == 1 and live[0] % cfg.experts_per_token == 0
