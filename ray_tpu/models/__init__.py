"""ray_tpu.models: TPU-first model families (GSPMD logical-axis sharding).

Llama (causal LM + LoRA + KV-cache decode), MoE transformer (routed
experts, dropless for serving), DeepSeek-V3-shaped transformer (latent
attention, shared + routed experts), Falcon-H1-shaped transformer (a
Mamba-2 mixer beside attention in every block; serving only),
Solar-Open2-shaped transformer (a gated delta-rule linear-attention mixer
three layers in four, a gated NoPE GQA layer the fourth, routed experts of
which one chip's share may be held; serving only), Motif-shaped
transformer (grouped differential latent attention on window and full
layers, a four-stream mHC residual, PolyNorm experts of which a share may be
held; serving only), Nemotron-H-shaped transformer (layers that are a
Mamba-2 mixer, an attention or a latent expert layer alone; serving only),
Cohere2-MoE-shaped transformer (a parallel block on one LayerNorm, plain K/V
heads in a ring three layers in four, experts as wide as the model of which
a share may be held, shared experts averaged, a tied head; serving only),
SmallThinker-shaped transformer (a router that reads the attention's input,
ReGLU experts every layer, a full layer without positions then three rotary
window layers a period; serving only),
ViT (vision encoder).
The reference delegates model execution to torch/vLLM; this framework owns
it.

This module is also the one place that chooses a family for the serving
stack (``llm/engine.py``, ``llm/serving.py``, ``llm/batch.py``), by the
type of the model config. What the engine asks of a family:

- ``build(config, mesh, decode)`` returns a flax module whose
  ``apply({"params": p[, "cache": c]}, tokens, adapters, adapter_slots)``
  gives ``(batch, seq, vocab)`` logits; with ``decode=True`` and
  ``mutable=["cache"]`` it writes a ``cache`` collection, applied without
  one it makes a fresh one (every row at position 0) and fills it
- of that collection the engine and ``kvcache.KVCacheManager`` ask only
  each leaf's *kind*, never what it means, and they ask it here
  (``cache_leaf_kind`` / ``cache_kinds``), by the leaf's name; nobody tells
  a kind by ``ndim``. Four kinds:

  - ``SEQUENCE``: cached state of ``(batch, ..., max_seq_len, width)``,
    the sequence axis at -2 and one ``max_seq_len`` for all of them (a slot
    row is a slice of axis 0, a pool block a slice of axis -2); any name
    that is neither of the two below. ``models/llama.py``'s ``Attention``
    (shared by ``moe``) keeps ``cached_key`` / ``cached_value`` of
    ``(batch, kv_heads, max_seq_len, head_dim)``; ``models/deepseek.py`` a
    ``cached_latent`` of ``(batch, 1, max_seq_len, kv_lora_rank)`` and a
    ``cached_rope`` of ``(batch, 1, max_seq_len, qk_rope_head_dim)``
  - ``INDEX``: a per-row write position ``(batch,)`` that a step advances
    by the tokens it was fed and that the engine may reset; the name
    ``cache_index``
  - ``STATE``: per-row state ``(batch, ...)`` with **no sequence axis**,
    carried from step to step and not indexed; a name that starts with
    ``state_``. A slot row is a slice of axis 0 as for the others, but
    there is nothing to cut into pool blocks, no position to move back and
    no prefix to share: a family that has such a leaf gets no prefix reuse
    (the manager leases without matching or committing and allocates no
    pool), a free row's state is zeroed at every step it is free, and the
    admission's row insert replaces it whole. The engine does the zeroing
    before the step, unless the family says it does it itself
    (``RESTARTS_OWN_STATE``): such a family keeps an ``INDEX`` leaf beside
    its state and reads the state of a row whose index is 0 as zero, which
    is what the engine's reset of a free row's index makes of it, inside
    the update that reads the state anyway (``models/solar_open2.py``).
    ``models/falcon_h1.py`` keeps
    ``state_ssm`` ``(batch, heads, d_head, d_state)`` float32 and
    ``state_conv`` ``(batch, d_conv - 1, channels)`` a layer beside
    ``llama``'s three; ``models/solar_open2.py`` a ``state_kda`` ``(batch,
    heads, d_k, d_v)`` float32 and a ``state_conv`` in each KDA layer, and
    ``llama``'s three in each GQA layer
  - ``WINDOW``: a ring of a window layer's last ``ring`` positions,
    ``(batch, heads, ring, width)``, beside the layer's ``INDEX``; a name
    that starts with ``window_``. Position ``p`` lives at slot ``p % ring``:
    a step writes its token there and attends ``min(index + 1, ring)`` slots
    (a cached latent row or value carries no position and a cached rotary
    row or key is already rotated, and a softmax over a set does not depend
    on its order, so the ring read up to that length *is* the window); a
    whole-prompt prefill leaves the prompt's last ``min(len, ring)``
    positions; a free row needs no zeroing (index 0 is an empty ring). A
    slot row is a slice of axis 0 as for the others; like ``STATE`` it is
    nothing a pool block holds, so a family with such a leaf gets no prefix
    reuse either (``carries_row_state``), and a chunk behind a cached
    prefix has no ring-aware form (``_NO_RULES``: ``prefill_chunk``).
    ``models/motif.py`` keeps ``window_latent`` ``(batch, 1, 128, 512)``
    and ``window_rope`` ``(batch, 1, 128, 64)`` in three layers of four,
    ``deepseek``'s two ``SEQUENCE`` leaves in the fourth;
    ``models/cohere2_moe.py`` ``llama.Attention``'s ``window_key`` /
    ``window_value`` ``(batch, kv_heads, 4096, head_dim)`` in three layers
    of four (``LlamaConfig.window``), its ``cached_key`` / ``cached_value``
    in the fourth; ``models/smallthinker.py`` the same two pairs of names
    at 4 K/V heads, the full layer *first* in each four
- a step of one token a row (``seq == 1`` against a cache) attends each
  row up to its own position; a longer ``seq`` against a cache is a chunk
  behind a cached prefix, row ``r``'s token ``i`` at ``index[r] + i``, and
  continues from the row's ``STATE`` leaves
- ``init_params(config, rng, mesh)`` returns the boxed parameter tree
- the config carries ``max_seq_len``, ``n_heads``, ``n_kv_heads``,
  ``n_layers``, ``dtype`` and ``param_dtype``
- a family with routed experts has ``n_experts`` on its config and sows
  ``layer_<i>/moe/experts`` into the ``ROUTING`` collection when it is
  mutable, for every layer ``i`` that has them: all ``n_layers`` unless
  the config says which (``routed_layers``, ``deepseek``'s leading layers
  are dense). A config whose ``experts_held`` is a ``(first, stop)`` range
  holds that share of each layer's experts (``MoEConfig.experts_held``):
  the choices sown are still over all ``n_experts``
- a feature the family has no rules for (adapter bank, speculative draft,
  a ``tp``/``sp`` mesh) is listed in ``_NO_RULES`` with the reason, and
  ``LLMConfig`` refuses it at construction: no silent fallback

A family is added by a module with those two functions, a branch in
``_family`` and ``LLMConfig.build_model_config``, and its line here.
``deepseek`` was added so (PR 30): ``models/deepseek.py`` with its own
attention and cache leaves, the routed part ``moe.MoEFFN``'s under three new
``MoEConfig`` fields, a latent form of the decode kernel
(``ops/decode_attention.latent_decode_attention``); the engine changed
only where it counted one expert row a layer. ``falcon_h1`` (PR 32) forced
the leaf kinds above: its mixer's state is the first cached leaf without a
sequence axis. ``solar_open2`` (PR 36) is the first with ``STATE`` leaves
*and* routed layers, the first whose layers differ by index (``gqa_layers``),
and the first to hold a share of its experts: ``llama.Attention`` gained
``rope`` / ``attn_gate`` / ``attn_head_dim``, ``MoEConfig`` ``experts_held``,
and the engine's expert counters count over the experts held. ``motif``
(PR 50) forced the fourth leaf kind (two kinds of attention cache side by
side in one row), shares ``deepseek``'s latent rows as functions
(``latent_rows`` / ``latent_cache``), and gave ``MoEConfig`` an
``expert_activation`` (``ops/moe_experts.py``'s PolyNorm form).
``nemotron_h`` (PR 54) is the first stack of *unlike single-mixer layers*:
a layer is a Mamba-2 mixer (``STATE`` leaves), an attention (``SEQUENCE``
leaves and an ``INDEX``) or an expert layer (no leaf at all), so a row's
cache tree has no entry for five layers in eleven and ``routed_layers``
names layers that are nothing but experts. It brought no module of its
own but the order: ``falcon_h1.Mixer`` came out from under
``FalconH1Config`` (``MixerConfig``, which both families build),
``MoEConfig`` gained ``latent_dim`` (the routed experts work between two
shared projections, narrower than the model) and the ungated
``expert_activation="relu2"`` (``ops/moe_experts.py``'s two-matrix form);
the engine and the cache manager changed nowhere: they already walked the
cache by leaf kind and counted experts over ``routed_layers``.
``cohere2_moe`` (PR 59) is the first whose ring holds plain K/V heads (50 MB
of a 92 MB row, read by ``ops/decode_attention.decode_attention`` at 16
query heads a K/V head) and the first to prefill through
``ops/flash_attention.py``: ``llama.Attention`` gained ``window`` (the ring,
and the band in the flash kernel's forward), ``rope_interleaved``
(``ops/rope.py``'s GPT-J pairs) and the rule that says which whole prompts
take the kernel (``llama.prefills_through_kernel``). Its own are a
LayerNorm, the parallel block and a tied head (one ``lm_head`` of ``(vocab,
dim)``); the engine, the cache manager and the expert kernel changed
nowhere.
``smallthinker`` (PR 61) is the first whose routing does not depend on its
layer's attention: ``MoEFFN`` became two steps (``route`` on the
attention's input, the experts on the post-attention norm's output; every
other family's one call is the two in a row), ``MoEConfig`` gained the
``expert_activation`` ``"reglu"`` (``ops/moe_experts.py``'s SwiGLU kernel
under ``relu``), and the sort that follows from the routing alone has a
scope of its own (``moe.sort``, ``parallel/expert.py``). It holds every
expert, and says so as the range ``(0, n_experts)`` where a check wants each
row's last choice kept. Window layers as ``cohere2_moe``'s (rotate-half
pairs), at 7 query heads a K/V head; the engine and the cache manager
changed nowhere.
"""

from __future__ import annotations

from typing import Any, Dict

# the kinds of a cache leaf (module docstring)
SEQUENCE, INDEX, STATE, WINDOW = "sequence", "index", "state", "window"
_INDEX_NAME = "cache_index"
_STATE_PREFIX = "state_"
_WINDOW_PREFIX = "window_"

# the collection a routed family's decode step sows each layer's (rows, k)
# expert choices into, for the engine's expert counters (llm/engine.py)
ROUTING = "moe_routing"

# family -> feature -> why LLMConfig refuses it
_NO_RULES: Dict[str, Dict[str, str]] = {
    "llama": {},
    "moe": {
        "adapters": (
            "lora.AdapterStore sizes its slot bank from a LlamaConfig and "
            "has no placement for routed expert weights"
        ),
        "draft_model": (
            "speculative verify has not been checked against routed "
            "experts (a draft's accepted run changes which rows share an "
            "expert), and the expert counters count plain decode steps"
        ),
        "mesh": (
            "parallel/plan.py has no partition rule for the (expert, ...) "
            "weights and the grouped expert kernel has no shard_map form "
            "yet (ROADMAP R1: ep rules)"
        ),
    },
    "deepseek": {
        "adapters": (
            "lora.AdapterStore sizes its slot bank from a LlamaConfig's "
            "wq/wk/wv/wo and has no placement for the latent projections "
            "(wkv_a, wkv_b) or for expert weights"
        ),
        "draft_model": (
            "speculative verify feeds several tokens a row against the "
            "cache, which here is the absorbed-form einsum over all of "
            "max_seq_len: unchecked against the published form at a "
            "draft's shapes, and the expert counters count plain decode "
            "steps"
        ),
        "mesh": (
            "the latent cache row has no head axis for parallel/plan.py's "
            "KV_SPEC to shard and the latent decode kernel no shard_map "
            "form; the expert weights have no ep rule (ROADMAP R1)"
        ),
    },
    "falcon_h1": {
        "adapters": (
            "lora.AdapterStore sizes its slot bank from a LlamaConfig's "
            "wq/wk/wv/wo at dim = n_heads x head_dim and has no placement "
            "for the mixer's projections"
        ),
        "draft_model": (
            "a rejected draft run cannot be undone by moving an index "
            "back: the mixer's state has moved on, and no snapshot of it "
            "is kept to return to"
        ),
        "mesh": (
            "parallel/plan.py has no partition rule for a per-row state "
            "leaf (models.STATE) or for the mixer's projections"
        ),
    },
    "solar_open2": {
        "adapters": (
            "lora.AdapterStore sizes its slot bank from a LlamaConfig's "
            "wq/wk/wv/wo at dim = n_heads x head_dim and has no placement "
            "for the KDA mixer's projections or for expert weights"
        ),
        "draft_model": (
            "a rejected draft run cannot be undone by moving an index "
            "back: the delta rule's state has moved on and no snapshot of "
            "it is kept to return to, and the expert counters count plain "
            "decode steps"
        ),
        "mesh": (
            "parallel/plan.py has no partition rule for a per-row state "
            "leaf (models.STATE), for the KDA mixer's projections or for "
            "the (expert, ...) weights; a held share of the experts has no "
            "ep exchange yet (ROADMAP R1)"
        ),
    },
    "motif": {
        "adapters": (
            "lora.AdapterStore sizes its slot bank from a LlamaConfig's "
            "wq/wk/wv/wo and has no placement for the query and latent "
            "low-rank projections or for expert weights"
        ),
        "draft_model": (
            "a rejected draft run cannot be undone by moving an index "
            "back: a window layer's ring has overwritten the positions the "
            "run would return to, and the expert counters count plain "
            "decode steps (the model's own MTP head: ROADMAP R3)"
        ),
        "mesh": (
            "the latent row and the ring have no head axis for "
            "parallel/plan.py's KV_SPEC to shard and the latent decode "
            "kernel no shard_map form; a held share of the experts has no "
            "ep exchange yet (ROADMAP R1)"
        ),
        "prefill_chunk": (
            "a chunk behind a cached prefix would have to read a window "
            "layer's ring while it overwrites it: the ring has no form "
            "for more than one new position a row (ROADMAP R4)"
        ),
    },
    "cohere2_moe": {
        "adapters": (
            "lora.AdapterStore sizes its slot bank from a LlamaConfig's "
            "wq/wk/wv/wo at dim = n_heads x head_dim (here 4096 against "
            "128 x 128) and has no placement for expert weights"
        ),
        "draft_model": (
            "a rejected draft run cannot be undone by moving an index "
            "back: a window layer's ring has overwritten the positions the "
            "run would return to, and the expert counters count plain "
            "decode steps"
        ),
        "mesh": (
            "parallel/plan.py has no partition rule for a ring leaf "
            "(models.WINDOW) or for the (expert, ...) weights; a held "
            "share of the experts has no ep exchange of model-wide rows "
            "yet (ROADMAP R1)"
        ),
        "prefill_chunk": (
            "a chunk behind a cached prefix would have to read a window "
            "layer's ring while it overwrites it: the ring has no form "
            "for more than one new position a row (ROADMAP R4)"
        ),
    },
    "smallthinker": {
        "adapters": (
            "lora.AdapterStore sizes its slot bank from a LlamaConfig's "
            "wq/wk/wv/wo at dim = n_heads x head_dim (here 2560 against "
            "28 x 128) and has no placement for expert weights"
        ),
        "draft_model": (
            "a rejected draft run cannot be undone by moving an index "
            "back: a window layer's ring has overwritten the positions the "
            "run would return to, and the expert counters count plain "
            "decode steps"
        ),
        "mesh": (
            "parallel/plan.py has no partition rule for a ring leaf "
            "(models.WINDOW) or for the (expert, ...) weights, and the "
            "grouped expert kernel has no shard_map form yet (ROADMAP R1: "
            "ep rules)"
        ),
        "prefill_chunk": (
            "a chunk behind a cached prefix would have to read a window "
            "layer's ring while it overwrites it: the ring has no form "
            "for more than one new position a row (ROADMAP R4)"
        ),
    },
    "nemotron_h": {
        "adapters": (
            "lora.AdapterStore sizes its slot bank from a LlamaConfig's "
            "wq/wk/wv/wo in every layer and has no placement for a stack "
            "in which one layer in eleven has them, nor for the mixer's "
            "projections or for expert weights"
        ),
        "draft_model": (
            "a rejected draft run cannot be undone by moving an index "
            "back: the mixer layers' state has moved on and no snapshot of "
            "it is kept to return to, and the expert counters count plain "
            "decode steps (the model's own MTP head: ROADMAP R3)"
        ),
        "mesh": (
            "parallel/plan.py has no partition rule for a per-row state "
            "leaf (models.STATE), for the mixer's projections or for the "
            "(expert, ...) weights; a held share of the experts has no ep "
            "exchange of latent rows yet (ROADMAP R1)"
        ),
    },
}


def cache_leaf_kind(name: str) -> str:
    """The kind of the cache leaf called ``name`` (module docstring)."""
    if name == _INDEX_NAME:
        return INDEX
    if name.startswith(_STATE_PREFIX):
        return STATE
    if name.startswith(_WINDOW_PREFIX):
        return WINDOW
    return SEQUENCE


def cache_kinds(cache: Any) -> Any:
    """``cache``'s tree with each leaf's kind in the leaf's place: what a
    ``jax.tree.map`` over a cache takes beside it to treat each kind."""
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda path, _: cache_leaf_kind(str(path[-1].key)), cache,
    )


def carries_row_state(model_config) -> bool:
    """Whether the family keeps ``STATE`` or ``WINDOW`` leaves, which no
    pool block holds: known from the config alone, before any cache exists
    (a lease is asked for before the first prefill)."""
    family = _family(model_config)
    return getattr(family, "ROW_STATE", False) or getattr(
        family, "ROW_WINDOW", False)


def restarts_own_state(model_config) -> bool:
    """Whether the family zeroes a free row's ``STATE`` leaves itself
    (module docstring): the engine then leaves them as they are."""
    return getattr(_family(model_config), "RESTARTS_OWN_STATE", False)


def refusals(family: str) -> Dict[str, str]:
    """Serving features ``family`` has no rules for yet, with the reason."""
    if family not in _NO_RULES:
        raise ValueError(f"unknown model family {family!r}")
    return _NO_RULES[family]


def _family(model_config):
    from . import (
        cohere2_moe, deepseek, falcon_h1, llama, moe, motif, nemotron_h,
        smallthinker, solar_open2,
    )

    if isinstance(model_config, smallthinker.SmallThinkerConfig):
        return smallthinker
    if isinstance(model_config, cohere2_moe.Cohere2MoEConfig):
        return cohere2_moe
    if isinstance(model_config, nemotron_h.NemotronHConfig):
        return nemotron_h
    if isinstance(model_config, motif.MotifConfig):
        return motif
    if isinstance(model_config, solar_open2.SolarOpen2Config):
        return solar_open2
    if isinstance(model_config, falcon_h1.FalconH1Config):
        return falcon_h1
    if isinstance(model_config, deepseek.DeepseekConfig):
        return deepseek
    if isinstance(model_config, moe.MoEConfig):
        return moe
    if isinstance(model_config, llama.LlamaConfig):
        return llama
    raise TypeError(
        f"no model family for a {type(model_config).__name__}"
    )


def build(model_config, mesh=None, decode: bool = False):
    """The family's flax module for ``model_config``."""
    return _family(model_config).build(model_config, mesh, decode)


def init_params(model_config, rng, mesh=None):
    return _family(model_config).init_params(model_config, rng, mesh)
