"""The plain reference for the Nemotron-H architecture (``model_type``
nemotron_h) at the settings NVIDIA-Nemotron-3-Super-120B-A12B publishes: a
stack of layers each of which is **one** pre-norm sub-block, a Mamba-2
mixer, a grouped-query attention or a latent mixture of experts, by its
character of ``hybrid_override_pattern`` (``M`` / ``*`` / ``E``).

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
the recurrence **one position at a time** (a ``lax.scan`` over positions:
no chunked form, so it shares no algebra with the program's prefill), no
kernel, no cache, no batching. Names in ``code`` are the published keys.

- every layer ``i``: ``x = x + f_i(rmsnorm(x))``, ``layer_norm_epsilon``
  (``residual_in_fp32`` false: here everything is float32); after the last
  layer ``rmsnorm``, then the untied head.
- ``M``: ``u = h W_in``, columns ``[z | x | B | C | dt]`` = ``[d_inner |
  d_inner | n_groups x ssm_state_size | the same | mamba_num_heads]``,
  ``d_inner = mamba_num_heads x mamba_head_dim`` (``mamba_proj_bias``
  false). ``[x | B | C] = silu(conv([x | B | C]))``, a causal depthwise
  convolution of ``conv_kernel`` taps a channel with bias
  (``use_conv_bias``), zeros before the first position. ``dt = softplus(dt
  + dt_bias)`` a head; ``A = -exp(A_log)`` a head. A head's state ``S``
  (``mamba_head_dim`` x ``ssm_state_size``) is zero before the first token:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t (x_t (x) B_t)``; ``y_t = S_t C_t + D
  x_t``; ``mamba_num_heads / n_groups`` heads share a group's ``B`` and
  ``C``. ``y = rmsnorm_group(y * silu(z))``, the mean square over each
  group's channels; ``W_out``. ``time_step_min`` / ``max`` / ``floor``
  only initialise ``dt_bias``.
- ``*``: ``num_attention_heads`` query heads over ``num_key_value_heads`` of
  ``head_dim``, no bias; causal ``softmax(q k^T / sqrt(head_dim)) v``; no
  rotary embedding; ``W_o``.
- ``E``: ``s = sigmoid(h W_r)`` over ``n_routed_experts``; the
  ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``
  (``n_group`` 1, ``topk_group`` 1: no group limit); ``w_j`` the unbiased
  scores of the chosen divided by their sum (``norm_topk_prob``), times
  ``routed_scaling_factor``; ``l = h W_lat_in`` (``moe_latent_size`` wide);
  ``r = sum_j w_j W_down_j relu(W_up_j l)^2`` (``moe_intermediate_size``,
  ``mlp_hidden_act`` relu2, no gate, no bias); ``out = r W_lat_out +
  W_sdown relu(W_sup h)^2`` (one shared expert of
  ``moe_shared_expert_intermediate_size`` on the model's width).

**The share.** The configuration may hold a chip's share of each expert
layer's experts (``experts_first .. experts_first + held - 1`` of
``n_routed``): the router keeps its ``n_routed`` outputs and its experts a
token, the weights are normalised over the experts chosen wherever they
live, and ``r`` sums over the experts held (``experts_loop``); ``W_lat_out``
is linear, so the shares' parts add. What the experts held elsewhere would
add is left out, here as in the program. A sliced vocabulary is a smaller
vocabulary.

Assumed, where the published config does not say (the configuration file
lists the same): no rotary embedding in the attention layers (the
Nemotron-H family applies none; ``rope_theta`` and
``partial_rotary_factor`` are read by nothing); the column order of
``W_in``; the gated norm over each *group's* channels with one learned
weight a channel; the router's scoring (sigmoid, the DeepSeek-V3
convention of the key names); the shared expert reads the layer's
``hidden_size``-wide normed input, not the latent (its width, 5376, is
given on that side; ``moe_shared_expert_overlap`` false says only that it
is not overlapped with the dispatch); the state is float32.

Memory, because the check runs beside 11 GB of resident state: weights
arrive in the program's tree (bf16) and are cast to float32 a matrix at a
time, the routed experts **one expert at a time**; attention in blocks of
``QUERY_BLOCK`` queries.

What it knows of the program, all of it *names*: those in its parameter
tree (``layer_weights``); in ``llm_arguments`` those of ``LLMConfig``'s and
``NemotronHConfig``'s arguments; and what ``drivers/serve_arch_common.py``
reads of a running program (``TRACE_SCOPES``, ``TRACE_KERNELS``,
``PROGRAM_COUNTERS``, ``ROUTING_COLLECTION``). Which layer is of which kind
it takes from the published pattern, never from the tree: a program that
built another kind of layer at an index has no weights under the names
asked for. No import from ``ray_tpu.models``, ``ray_tpu.ops`` or
``ray_tpu.parallel``, and nothing under ``ray_tpu/`` imports this.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from ..harness import flops_lmoe

F32 = jnp.float32
QUERY_BLOCK = 512
MIXER, ATTENTION, EXPERTS = "M", "*", "E"

# jax.named_scope names of the decode program (and of the prefill programs)
# whose device time a traced run keeps (harness/xplane_scopes.py), and the
# Pallas kernels of the decode program
TRACE_SCOPES = ("moe.route", "moe.experts", "moe.shared", "moe.latent",
                "ssm.proj", "ssm.conv", "ssm.scan")
# those of them that are no part of the expert layers: kept apart in a
# traced run's result, so that what sums the expert layers' scopes
# (``moe_experts_busy_share``) sums no mixer
ATTENTION_SCOPES = ("ssm.proj", "ssm.conv", "ssm.scan")
TRACE_KERNELS = ("moe_experts", "decode_attention", "kv_row_write")
# groups of the replica's runtime_info() kept at both ends of the window
PROGRAM_COUNTERS = ("moe", "kv")
# the flax collection the model sows each layer's chosen experts into
# (ray_tpu.models.ROUTING, by value: nothing of the program is imported)
ROUTING_COLLECTION = "moe_routing"


def rmsnorm(x, weight, eps):
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + eps) * weight


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def attention(h, w, *, n_heads, n_kv_heads, head_dim):
    b, s, _ = h.shape
    positions = jnp.arange(s)
    q = (h @ w["wq"]).reshape(b, s, n_heads, head_dim)
    k = (h @ w["wk"]).reshape(b, s, n_kv_heads, head_dim)
    v = (h @ w["wv"]).reshape(b, s, n_kv_heads, head_dim)
    group = n_heads // n_kv_heads
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    out = []
    for start in range(0, s, QUERY_BLOCK):
        end = min(start + QUERY_BLOCK, s)
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q[:, start:end], k[:, :end]
        ) / math.sqrt(head_dim)
        causal = positions[start:end, None] >= positions[None, :end]
        probs = jax.nn.softmax(
            jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", probs, v[:, :end]))
    out = jnp.concatenate(out, axis=1).reshape(b, s, n_heads * head_dim)
    return out @ w["wo"]


def mixer(h, w, *, m_heads, m_head_dim, d_state, n_groups, d_conv, eps):
    b, s, _ = h.shape
    d_inner = m_heads * m_head_dim
    gn = n_groups * d_state
    u = h @ w["in_proj"]
    z = u[..., :d_inner]
    xbc = u[..., d_inner:2 * d_inner + 2 * gn]
    dt = u[..., 2 * d_inner + 2 * gn:]

    # causal depthwise convolution over [x | B | C], zeros before position
    # 0; tap j reads position t - (d_conv - 1) + j
    padded = jnp.pad(xbc, ((0, 0), (d_conv - 1, 0), (0, 0)))
    xbc = jax.nn.silu(w["conv_bias"] + sum(
        padded[:, j:j + s] * w["conv_weight"][j] for j in range(d_conv)))
    x = xbc[..., :d_inner].reshape(b, s, m_heads, m_head_dim)
    per = m_heads // n_groups
    # every head its group's B and C
    b_in = jnp.repeat(
        xbc[..., d_inner:d_inner + gn].reshape(b, s, n_groups, d_state),
        per, axis=2)
    c_in = jnp.repeat(
        xbc[..., d_inner + gn:].reshape(b, s, n_groups, d_state), per, axis=2)
    dt = jax.nn.softplus(dt + w["dt_bias"])  # (b, s, heads)
    a = -jnp.exp(w["A_log"])

    def position(state, inputs):
        x_t, b_t, c_t, dt_t = inputs  # (b, heads, p) (b, heads, n) x2 (b, heads)
        state = (
            state * jnp.exp(dt_t * a)[..., None, None]
            + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :])
        return state, jnp.sum(state * c_t[..., None, :], axis=-1)

    _, y = jax.lax.scan(
        position, jnp.zeros((b, m_heads, m_head_dim, d_state), F32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, b_in, c_in, dt)))
    y = jnp.moveaxis(y, 0, 1)  # (b, s, heads, p)
    y = (y + w["D"][:, None] * x).reshape(b, s, d_inner) * jax.nn.silu(z)
    # the mean square over each group's channels, one weight a channel
    y = rmsnorm(
        y.reshape(b, s, n_groups, d_inner // n_groups), 1.0, eps
    ).reshape(b, s, d_inner) * w["mixer_norm"]
    return y @ w["out_proj"]


def route(h, router, bias, top_k, norm_topk_prob, scale, follow=None):
    """(tokens, dim) -> kept weights and their experts, (tokens, top_k)
    each, over all the experts routed over; this reference's own choice;
    and ``slack`` (tokens,), zero without ``follow``: how far the least
    biased score followed lies under this reference's ``top_k``-th largest,
    as a share of it."""
    scores = jax.nn.sigmoid(h @ router)
    biased = scores + bias[None, :]
    kth, own = jax.lax.top_k(biased, top_k)
    experts, slack = own, jnp.zeros(h.shape[0], F32)
    if follow is not None:
        experts = follow
        followed = jnp.take_along_axis(biased, follow, axis=-1)
        slack = (kth[:, -1] - jnp.min(followed, axis=-1)) / jnp.abs(kth[:, -1])
    kept = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk_prob:
        kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20)
    return kept * scale, experts, own, slack


def experts_loop(latent, kept, experts, w_up, w_down, first):
    """Every token's latent row through every expert *held*, one expert at
    a time, weighted by what the token kept for it (zero where not
    chosen). Held expert ``e`` is expert ``first + e`` of those routed
    over; what a token kept for an expert held elsewhere adds nothing."""
    def one(e, y):
        up = jax.lax.dynamic_index_in_dim(w_up, e, 0, False).astype(F32)
        down = jax.lax.dynamic_index_in_dim(w_down, e, 0, False).astype(F32)
        weight = jnp.sum(jnp.where(experts == first + e, kept, 0.0), axis=-1)
        return y + weight[:, None] * (relu2(latent @ up) @ down)

    return jax.lax.fori_loop(
        0, w_up.shape[0], one, jnp.zeros_like(latent))


def _f32(w: dict, but=()) -> dict:
    return {k: (v if k in but else v.astype(F32)) for k, v in w.items()}


@partial(jax.jit, static_argnames=(
    "m_heads", "m_head_dim", "d_state", "n_groups", "d_conv", "eps"))
def mixer_layer(x, w, **sizes):
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        return x + mixer(rmsnorm(x, w["norm"], sizes["eps"]), w, **sizes)


@partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "head_dim", "eps"))
def attention_layer(x, w, *, eps, **sizes):
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        return x + attention(rmsnorm(x, w["norm"], eps), w, **sizes)


@partial(jax.jit, static_argnames=(
    "eps", "top_k", "norm_topk_prob", "scale", "experts_first"))
def experts_layer(x, w, follow=None, *, eps, top_k, norm_topk_prob, scale,
                  experts_first):
    """An ``E`` layer on the float32 residual ``x (batch, seq, dim)``.
    Returns the new hidden state, this reference's own choice of experts
    (batch * seq, top_k) and ``route``'s slack (batch * seq,)."""
    w = _f32(w, but=("w_up", "w_down"))  # an expert at a time
    with jax.default_matmul_precision("highest"):
        h = rmsnorm(x, w["norm"], eps).reshape(-1, x.shape[-1])
        kept, experts, own, slack = route(
            h, w["router"], w["router_bias"], top_k, norm_topk_prob, scale,
            follow)
        routed = experts_loop(
            h @ w["latent_in"], kept, experts, w["w_up"], w["w_down"],
            experts_first)
        y = routed @ w["latent_out"] + relu2(
            h @ w["shared_up"]) @ w["shared_down"]
        return x + y.reshape(x.shape), own, slack


@jax.jit
def embed(table, tokens):
    return table[tokens].astype(F32)


@partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, lm_head, *, eps):
    """(batch, seq, dim) -> logits over the vocabulary held."""
    with jax.default_matmul_precision("highest"):
        return rmsnorm(x, final_norm.astype(F32), eps) @ lm_head.astype(F32)


def layer_weights(params, i: int, kind: str) -> dict:
    """Layer ``i``'s weights from the program's parameter tree, by name,
    for the kind of layer the published pattern says it is."""
    blk = params[f"layer_{i}"]
    if kind == MIXER:
        mix = blk["mixer"]
        return {
            "norm": blk["norm"], "in_proj": mix["in_proj"]["kernel"],
            "conv_weight": mix["conv_weight"], "conv_bias": mix["conv_bias"],
            "dt_bias": mix["dt_bias"], "A_log": mix["A_log"], "D": mix["D"],
            "mixer_norm": mix["norm"], "out_proj": mix["out_proj"]["kernel"]}
    if kind == ATTENTION:
        attn = blk["attn"]
        return {
            "norm": blk["norm"], "wq": attn["wq"]["base"]["kernel"],
            "wk": attn["wk"]["base"]["kernel"],
            "wv": attn["wv"]["base"]["kernel"],
            "wo": attn["wo"]["base"]["kernel"]}
    moe, shared = blk["moe"], blk["shared"]
    return {
        "norm": blk["norm"], "router": moe["router"],
        "router_bias": moe["router_bias"],
        "latent_in": moe["w_latent_in"]["kernel"],
        "latent_out": moe["w_latent_out"]["kernel"],
        "w_up": moe["w_up"], "w_down": moe["w_down"],
        "shared_up": shared["w_up"]["kernel"],
        "shared_down": shared["w_down"]["kernel"]}


def hidden_states(params, tokens, *, n_layers, pattern, mixer_sizes,
                  attention_sizes, expert_sizes, eps, routing=None,
                  follow=None, slack=None, guaranteed=None, n_routed=None,
                  n_held=None):
    """Final-layer output (batch, seq, dim), float32, before the last norm.
    ``routing``: a list that receives each *expert* layer's own choice of
    experts, in layer order; ``follow``: the experts to use instead, one
    entry an expert layer; ``slack``: a list that receives each expert
    layer's slack. ``mixer_sizes`` / ``attention_sizes`` / ``expert_sizes``:
    each kind of layer's own keyword sizes (``sizes_of``)."""
    del guaranteed, n_routed, n_held  # the check's; the weights' shapes say them
    x = embed(params["embed"], tokens)
    routed = 0
    for i in range(n_layers):
        kind = pattern[i]
        w = layer_weights(params, i, kind)
        if kind == MIXER:
            x = mixer_layer(x, w, eps=eps, **mixer_sizes)
        elif kind == ATTENTION:
            x = attention_layer(x, w, eps=eps, **attention_sizes)
        else:
            x, own, loose = experts_layer(
                x, w, follow[routed] if follow is not None else None,
                eps=eps, **expert_sizes)
            routed += 1
            if routing is not None:
                routing.append(own)
            if slack is not None:
                slack.append(loose)
    return x


def logits(params, tokens, *, last: int = 0, routing=None, follow=None,
           slack=None, **sizes):
    """Logits (batch, seq or last, vocab) of a full causal forward pass.
    ``last`` keeps only that many trailing positions."""
    x = hidden_states(
        params, tokens, routing=routing, follow=follow, slack=slack, **sizes)
    if last:
        x = x[:, -last:]
    return head(x, params["final_norm"], params["lm_head"], eps=sizes["eps"])


def program_routing(sown, n_layers: int) -> list:
    """The program's sown ``ROUTING_COLLECTION`` in the form ``routing=``
    fills above: each expert layer's chosen experts, (tokens, top_k), over
    all the experts routed over, in layer order (a layer that routes
    nothing sows nothing)."""
    return [sown[f"layer_{i}"]["moe"]["experts"][0]
            for i in range(n_layers) if f"layer_{i}" in sown]


def _refuse_what_is_not_here(config: dict) -> None:
    name = config["name"]
    for key, want in (("attention_bias", False), ("mamba_proj_bias", False),
                      ("mlp_bias", False), ("use_bias", False),
                      ("use_conv_bias", True), ("mamba_hidden_act", "silu"),
                      ("mlp_hidden_act", "relu2"), ("n_group", 1),
                      ("topk_group", 1), ("n_shared_experts", 1),
                      ("num_nextn_predict_layers", 0),
                      ("sliding_window", None),
                      ("tie_word_embeddings", False)):
        if config.get(key, want) != want:
            raise SystemExit(
                f"{name}: this reference has no {key}={config[key]!r}")
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"] or set(pattern) - set(
            MIXER + ATTENTION + EXPERTS):
        raise SystemExit(
            f"{name}: hybrid_override_pattern {pattern!r} is not "
            f"{config['num_hidden_layers']} layers of M, * and E")
    if config["expand"] * config["hidden_size"] != (
            config["mamba_num_heads"] * config["mamba_head_dim"]):
        raise SystemExit(f"{name}: expand x hidden_size is not heads x head size")


def _routed(config: dict) -> int:
    """The router's width: the published count where the file holds a
    share (``n_routed_experts`` is then the experts held)."""
    return config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"])


def sizes_of(config: dict) -> dict:
    """The keyword sizes above, from a configuration file's published keys,
    and ``guaranteed`` / ``n_held`` / ``n_routed`` for the check
    (``drivers/serve_closed_loop_arch_stateful_routed.py``)."""
    _refuse_what_is_not_here(config)
    return dict(
        n_layers=config["num_hidden_layers"],
        pattern=config["hybrid_override_pattern"],
        eps=float(config["layer_norm_epsilon"]),
        mixer_sizes=dict(
            m_heads=config["mamba_num_heads"],
            m_head_dim=config["mamba_head_dim"],
            d_state=config["ssm_state_size"], n_groups=config["n_groups"],
            d_conv=config["conv_kernel"]),
        attention_sizes=dict(
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"]),
        expert_sizes=dict(
            top_k=config["num_experts_per_tok"],
            norm_topk_prob=bool(config["norm_topk_prob"]),
            scale=float(config["routed_scaling_factor"]),
            experts_first=int(config.get("experts_first", 0))),
        n_routed=_routed(config), n_held=config["n_routed_experts"],
        # what a slot row takes at the precisions the configuration states
        # (float32 state, bf16 convolution tail and K/V): the check holds
        # the program's live rows to these counts
        guaranteed={
            "state_bytes_per_row": flops_lmoe.state_bytes_per_row(config),
            "kv_bytes_per_token": flops_lmoe.kv_bytes_per_token(config)},
    )


def llm_arguments(config: dict) -> dict:
    """A configuration file's published keys as ``LLMConfig`` arguments: the
    family and its model arguments
    (``ray_tpu.models.nemotron_h.NemotronHConfig``)."""
    _refuse_what_is_not_here(config)
    first, held, routed = (int(config.get("experts_first", 0)),
                           config["n_routed_experts"], _routed(config))
    return dict(
        model_family="nemotron_h",
        model_kwargs=dict(
            vocab_size=config["vocab_size"],
            dim=config["hidden_size"],
            pattern=config["hybrid_override_pattern"],
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            mamba_n_heads=config["mamba_num_heads"],
            mamba_d_head=config["mamba_head_dim"],
            mamba_d_state=config["ssm_state_size"],
            mamba_n_groups=config["n_groups"],
            mamba_d_conv=config["conv_kernel"],
            mamba_chunk_size=config["chunk_size"],
            moe_intermediate=config["moe_intermediate_size"],
            moe_latent=config["moe_latent_size"],
            shared_intermediate=config["moe_shared_expert_intermediate_size"],
            n_experts=routed,
            experts_per_token=config["num_experts_per_tok"],
            norm_topk_prob=bool(config["norm_topk_prob"]),
            routed_scale=config["routed_scaling_factor"],
            experts_held=None if held == routed else (first, first + held),
            norm_eps=config["layer_norm_epsilon"],
        ),
    )
