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
stack (``llm/config.py``, ``llm/engine.py``, ``llm/serving.py``,
``llm/batch.py``): ``FAMILIES`` is the one table of them, read by name
(``config_type``, ``refusals``) and by the type of the model config
(``build``, ``init_params``). What the engine asks of a family:

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
    prefix has no ring-aware form (``FAMILIES``: ``prefill_chunk``).
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
- a feature the family has no rules for (adapter bank, a ``tp``/``sp``
  mesh, a budgeted prefill) is listed in its entry of ``FAMILIES`` with the
  reason, and ``LLMConfig`` refuses it at construction: no silent fallback

A family is added by its module, with those two functions, and its entry
in ``FAMILIES``: no line of ``llm/config.py``, ``llm/engine.py`` or
``kvcache/`` names a family (what each family forced of the shared code is
in ``CHANGES.md``, under the PR that added it).
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, NamedTuple

# the kinds of a cache leaf (module docstring)
SEQUENCE, INDEX, STATE, WINDOW = "sequence", "index", "state", "window"
_INDEX_NAME = "cache_index"
_STATE_PREFIX = "state_"
_WINDOW_PREFIX = "window_"

# the collection a routed family's decode step sows each layer's (rows, k)
# expert choices into, for the engine's expert counters (llm/engine.py)
ROUTING = "moe_routing"


class _Family(NamedTuple):
    config: str  # the config class's name in the family's module
    refuses: Dict[str, str]  # serving feature -> why LLMConfig refuses it


# the one table of families, by ``LLMConfig.model_family``, which is also the
# name of the family's module under ``ray_tpu.models``
FAMILIES: Dict[str, _Family] = {
    "llama": _Family("LlamaConfig", {}),
    "moe": _Family("MoEConfig", {
        "adapters": (
            "lora.AdapterStore sizes its slot bank from a LlamaConfig and "
            "has no placement for routed expert weights"
        ),
        "mesh": (
            "parallel/plan.py has no partition rule for the (expert, ...) "
            "weights and the grouped expert kernel has no shard_map form "
            "yet (ROADMAP R1: ep rules)"
        ),
    }),
    "deepseek": _Family("DeepseekConfig", {
        "adapters": (
            "lora.AdapterStore sizes its slot bank from a LlamaConfig's "
            "wq/wk/wv/wo and has no placement for the latent projections "
            "(wkv_a, wkv_b) or for expert weights"
        ),
        "mesh": (
            "the latent cache row has no head axis for parallel/plan.py's "
            "KV_SPEC to shard and the latent decode kernel no shard_map "
            "form; the expert weights have no ep rule (ROADMAP R1)"
        ),
    }),
    "falcon_h1": _Family("FalconH1Config", {
        "adapters": (
            "lora.AdapterStore sizes its slot bank from a LlamaConfig's "
            "wq/wk/wv/wo at dim = n_heads x head_dim and has no placement "
            "for the mixer's projections"
        ),
        "mesh": (
            "parallel/plan.py has no partition rule for a per-row state "
            "leaf (models.STATE) or for the mixer's projections"
        ),
    }),
    "solar_open2": _Family("SolarOpen2Config", {
        "adapters": (
            "lora.AdapterStore sizes its slot bank from a LlamaConfig's "
            "wq/wk/wv/wo at dim = n_heads x head_dim and has no placement "
            "for the KDA mixer's projections or for expert weights"
        ),
        "mesh": (
            "parallel/plan.py has no partition rule for a per-row state "
            "leaf (models.STATE), for the KDA mixer's projections or for "
            "the (expert, ...) weights; a held share of the experts has no "
            "ep exchange yet (ROADMAP R1)"
        ),
    }),
    "motif": _Family("MotifConfig", {
        "adapters": (
            "lora.AdapterStore sizes its slot bank from a LlamaConfig's "
            "wq/wk/wv/wo and has no placement for the query and latent "
            "low-rank projections or for expert weights"
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
    }),
    "cohere2_moe": _Family("Cohere2MoEConfig", {
        "adapters": (
            "lora.AdapterStore sizes its slot bank from a LlamaConfig's "
            "wq/wk/wv/wo at dim = n_heads x head_dim (here 4096 against "
            "128 x 128) and has no placement for expert weights"
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
    }),
    "smallthinker": _Family("SmallThinkerConfig", {
        "adapters": (
            "lora.AdapterStore sizes its slot bank from a LlamaConfig's "
            "wq/wk/wv/wo at dim = n_heads x head_dim (here 2560 against "
            "28 x 128) and has no placement for expert weights"
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
    }),
    "nemotron_h": _Family("NemotronHConfig", {
        "adapters": (
            "lora.AdapterStore sizes its slot bank from a LlamaConfig's "
            "wq/wk/wv/wo in every layer and has no placement for a stack "
            "in which one layer in eleven has them, nor for the mixer's "
            "projections or for expert weights"
        ),
        "mesh": (
            "parallel/plan.py has no partition rule for a per-row state "
            "leaf (models.STATE), for the mixer's projections or for the "
            "(expert, ...) weights; a held share of the experts has no ep "
            "exchange of latent rows yet (ROADMAP R1)"
        ),
    }),
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


def _entry(family: str) -> _Family:
    if family not in FAMILIES:
        raise ValueError(
            f"unknown model family {family!r}: the families are "
            f"{', '.join(FAMILIES)}"
        )
    return FAMILIES[family]


def _module(family: str):
    return importlib.import_module(f".{family}", __name__)


def refusals(family: str) -> Dict[str, str]:
    """Serving features ``family`` has no rules for yet, with the reason."""
    return _entry(family).refuses


def config_type(family: str):
    """The config class of ``family`` (its module is imported here)."""
    entry = _entry(family)
    return getattr(_module(family), entry.config)


def _family(model_config):
    """The module of the family ``model_config`` belongs to, by its exact
    class: no family's config class subclasses another's."""
    for family in FAMILIES:
        if type(model_config) is config_type(family):
            return _module(family)
    raise TypeError(
        f"no model family for a {type(model_config).__name__}"
    )


def build(model_config, mesh=None, decode: bool = False):
    """The family's flax module for ``model_config``."""
    return _family(model_config).build(model_config, mesh, decode)


def init_params(model_config, rng, mesh=None):
    return _family(model_config).init_params(model_config, rng, mesh)
