"""The plain reference for the Solar-Open2 architecture (``model_type``
solar_open2) at the settings Solar-Open2-250B publishes: three layers in
four a Kimi Delta Attention (KDA) mixer, the fourth a gated grouped-query
attention without positional embedding, every layer's feed-forward routed
experts beside a shared one.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
the KDA recurrence **one position at a time** (a ``lax.scan`` over
positions: no chunked form, so it shares no algebra with the program's
prefill), no kernel, no cache, no batching. Names in ``code`` are the
published keys.

- Block: ``h = x + Mixer_i(rmsnorm(x))``; ``y = h + MoE(rmsnorm(h))``;
  layer ``i``'s mixer is GQA if ``i`` is in ``gqa_layers``, else KDA.
  ``first_k_dense_replace`` 0: every layer routes. Final ``rmsnorm``,
  untied head. ``rms_norm_eps`` everywhere.
- ``KDA(x)``, ``H = linear_attn_config.num_heads`` heads of ``d =
  linear_attn_config.head_dim`` (keys and values alike; ``num_kv_heads``
  null: as many): ``[q | k | v] = silu(conv(x W_qkv))``, a causal depthwise
  convolution of ``short_conv_kernel_size`` taps a channel, zeros before
  the first position, no bias. ``q_t = q / ||q|| d^-1/2``, ``k_t = k /
  ||k||``, the norm a head. ``g_t = -exp(A_log) softplus(W_f2 (W_f1 x) +
  dt_bias)`` a channel of a head's ``d`` keys (``A_log`` a head,
  ``dt_bias`` a channel), ``alpha_t = exp(g_t)``. ``beta_t = 2
  sigmoid(W_b x)`` a head (``kda_allow_neg_eigval``). A head's state ``S (d
  x d)`` is zero before the first token: ``S' = Diag(alpha_t) S_{t-1}``;
  ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``; ``o_t = S_t^T q_t``.
  ``y = W_o concat_h(rmsnorm_d(o_t) * sigmoid(W_g2 (W_g1 x) + b_g))``.
- ``GQA(x)``: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` of ``head_dim``; causal ``softmax(q k^T /
  sqrt(head_dim)) v``, no rotary embedding (``use_rope`` false), no q/k
  norm; ``y = W_o (attn * sigmoid(x W_gate))`` (``use_gqa_gate``).
- ``MoE(h) = shared(h) + sum_{j in top} w_j SwiGLU_j(h)``: each expert's
  score its own sigmoid of the router's logit; the ``num_experts_per_tok``
  largest of ``score + bias``; ``w_j`` the unbiased scores divided by their
  sum (``norm_topk_prob``), times ``routed_scaling_factor``.

**The share.** The configuration may hold a chip's share of each layer's
experts (``experts_first .. experts_first + held - 1`` of
``n_routed``): the router keeps its ``n_routed`` outputs and its experts a
token, the weights are normalised over the experts chosen wherever they
live, and what the experts held elsewhere would add is left out, here as
in the program (``experts_loop`` walks the experts held). A sliced
vocabulary is a smaller vocabulary: embedding and head have the slice's
rows and columns.

Assumed, where the published config does not say (the configuration file
lists the same): the two gates' low rank (``kda_use_full_proj`` false: the
head size) and which has a bias (``g``'s second matrix); no convolution
bias; ``1e-6`` under the L2 norm's root; the GQA gate's width (every
head's every channel) and place (before ``W_o``, from the layer's normed
input); the router's scoring (sigmoid, the DeepSeek-V3 convention of the
key names); the column order ``[q | k | v]`` of ``W_qkv``.

Memory, because the check runs beside 14 GB of resident state: weights
arrive in the program's tree (bf16) and are cast to float32 a matrix at a
time, the routed experts **one expert at a time**; attention in blocks of
``QUERY_BLOCK`` queries.

What it knows of the program, all of it *names*: those in its parameter
tree (``layer_weights``); in ``llm_arguments`` those of ``LLMConfig``'s and
``SolarOpen2Config``'s arguments; and what ``drivers/serve_arch_common.py``
reads of a running program (``TRACE_SCOPES``, ``TRACE_KERNELS``,
``PROGRAM_COUNTERS``, ``ROUTING_COLLECTION``). No import from
``ray_tpu.models``, ``ray_tpu.ops`` or ``ray_tpu.parallel``, and nothing
under ``ray_tpu/`` imports this.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from ..harness import flops_kda

F32 = jnp.float32
QUERY_BLOCK = 512
L2_EPS = 1e-6

# jax.named_scope names of the decode program (and of the prefill programs)
# whose device time a traced run keeps (harness/xplane_scopes.py), and the
# Pallas kernels of the decode program
TRACE_SCOPES = ("moe.route", "moe.experts", "moe.shared",
                "kda.proj", "kda.conv", "kda.state", "attn.gate")
# those of them that are no part of the expert layers: kept apart in a
# traced run's result, so that what sums the expert layers' scopes
# (``moe_experts_busy_share``) sums no mixer
ATTENTION_SCOPES = ("kda.proj", "kda.conv", "kda.state", "attn.gate")
TRACE_KERNELS = ("moe_experts", "decode_attention", "kv_row_write", "kda_step")
# groups of the replica's runtime_info() kept at both ends of the window
PROGRAM_COUNTERS = ("moe", "kv")
# the flax collection the model sows each layer's chosen experts into
# (ray_tpu.models.ROUTING, by value: nothing of the program is imported)
ROUTING_COLLECTION = "moe_routing"


def rmsnorm(x, weight, eps):
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + eps) * weight


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, positions, theta):
    """x: (batch, seq, heads, d); positions: (seq,)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = positions.astype(F32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(emb)[None, :, None, :], jnp.sin(emb)[None, :, None, :]
    return x * cos + rotate_half(x) * sin


def gqa(h, w, *, n_heads, n_kv_heads, head_dim, gate, theta):
    """``theta``: rotate-half RoPE on q and k at that base (``use_rope``),
    None for none; ``gate``: ``use_gqa_gate``."""
    b, s, _ = h.shape
    positions = jnp.arange(s)
    q = (h @ w["wq"]).reshape(b, s, n_heads, head_dim)
    k = (h @ w["wk"]).reshape(b, s, n_kv_heads, head_dim)
    v = (h @ w["wv"]).reshape(b, s, n_kv_heads, head_dim)
    if theta is not None:
        q, k = rope(q, positions, theta), rope(k, positions, theta)
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
    if gate:
        out = out * jax.nn.sigmoid(h @ w["w_gate_attn"])
    return out @ w["wo"]


def kda(h, w, *, heads, d, taps, eps, beta_scale):
    """``beta_scale``: 2 where ``kda_allow_neg_eigval`` (the transition's
    eigenvalue along ``k_t`` then lies in (-1, 1)), else 1."""
    b, s, _ = h.shape
    width = heads * d
    qkv = h @ w["wqkv"]
    # causal depthwise convolution, zeros before position 0; tap j reads
    # position t - (taps - 1) + j
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(
        padded[:, j:j + s] * w["conv_weight"][j] for j in range(taps)))
    q, k, v = (qkv[..., i * width:(i + 1) * width].reshape(b, s, heads, d)
               for i in range(3))
    q, k = l2norm(q) * d ** -0.5, l2norm(k)
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
        (h @ w["wf1"]) @ w["wf2"] + w["dt_bias"]).reshape(b, s, heads, d)
    beta = beta_scale * jax.nn.sigmoid(h @ w["wb"])  # (b, s, heads)

    def position(state, inputs):
        q_t, k_t, v_t, g_t, beta_t = inputs  # (b, heads, d) x4, (b, heads)
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + beta_t[..., None, None] * (
            k_t[..., :, None] * (v_t - seen)[..., None, :])
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    _, o = jax.lax.scan(
        position, jnp.zeros((b, heads, d, d), F32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    o = rmsnorm(jnp.moveaxis(o, 0, 1), w["kda_norm"], eps)  # (b, s, heads, d)
    gate = jax.nn.sigmoid((h @ w["wg1"]) @ w["wg2"] + w["wg2_bias"])
    return (o.reshape(b, s, width) * gate) @ w["wo"]


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def route(h, router, bias, top_k, norm_topk_prob, scale, follow=None):
    """(tokens, dim) -> kept weights and their experts, (tokens, top_k)
    each, over all the experts routed over; this reference's own choice;
    and ``slack`` (tokens,), zero without ``follow``
    (``deepseek_v3_arch.route``, whose router this is)."""
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


def experts_loop(h, kept, experts, w_gate, w_up, w_down, first):
    """Every token through every expert *held*, one expert at a time,
    weighted by what the token kept for it (zero where not chosen). Held
    expert ``e`` is expert ``first + e`` of those routed over; what a
    token kept for an expert held elsewhere adds nothing."""
    def one(e, y):
        gate = jax.lax.dynamic_index_in_dim(w_gate, e, 0, False).astype(F32)
        up = jax.lax.dynamic_index_in_dim(w_up, e, 0, False).astype(F32)
        down = jax.lax.dynamic_index_in_dim(w_down, e, 0, False).astype(F32)
        weight = jnp.sum(jnp.where(experts == first + e, kept, 0.0), axis=-1)
        return y + weight[:, None] * swiglu(h, gate, up, down)

    return jax.lax.fori_loop(0, w_gate.shape[0], one, jnp.zeros_like(h))


_STATIC = ("n_heads", "n_kv_heads", "head_dim", "kda_heads", "kda_head_dim",
           "taps", "eps", "top_k", "norm_topk_prob", "scale",
           "experts_first", "beta_scale", "gqa_gate", "rope_theta")


@partial(jax.jit, static_argnames=_STATIC)
def block(x, w, follow=None, *, n_heads, n_kv_heads, head_dim, kda_heads,
          kda_head_dim, taps, eps, top_k, norm_topk_prob, scale,
          experts_first, beta_scale, gqa_gate, rope_theta):
    """One layer on the float32 residual ``x (batch, seq, dim)``: a GQA
    layer if its weights have a ``wq``, else
    KDA. Returns the new hidden state, this reference's own choice of
    experts (batch * seq, top_k) and ``route``'s slack (batch * seq,)."""
    big = ("w_gate", "w_up", "w_down")  # cast an expert at a time
    experts_w = {k: w[k] for k in big}
    w = jax.tree.map(
        lambda a: a.astype(F32), {k: v for k, v in w.items() if k not in big})
    with jax.default_matmul_precision("highest"):
        h = rmsnorm(x, w["attn_norm"], eps)
        if "wq" in w:
            x = x + gqa(h, w, n_heads=n_heads, n_kv_heads=n_kv_heads,
                        head_dim=head_dim, gate=gqa_gate, theta=rope_theta)
        else:
            x = x + kda(h, w, heads=kda_heads, d=kda_head_dim, taps=taps,
                        eps=eps, beta_scale=beta_scale)
        h = rmsnorm(x, w["ffn_norm"], eps).reshape(-1, x.shape[-1])
        kept, experts, own, slack = route(
            h, w["router"], w["router_bias"], top_k, norm_topk_prob, scale,
            follow)
        y = experts_loop(h, kept, experts, first=experts_first, **experts_w)
        y = y + swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"])
        return x + y.reshape(x.shape), own, slack


@jax.jit
def embed(table, tokens):
    return table[tokens].astype(F32)


@partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, lm_head, *, eps):
    """(batch, seq, dim) -> logits over the vocabulary held."""
    with jax.default_matmul_precision("highest"):
        return rmsnorm(x, final_norm.astype(F32), eps) @ lm_head.astype(F32)


def layer_weights(params, i: int) -> dict:
    """Layer ``i``'s weights from the program's parameter tree, by name; a
    GQA layer is one with an ``attn`` group."""
    blk = params[f"layer_{i}"]
    moe, shared = blk["moe"], blk["shared"]
    w = {
        "attn_norm": blk["attn_norm"], "ffn_norm": blk["ffn_norm"],
        "router": moe["router"], "router_bias": moe["router_bias"],
        "w_gate": moe["w_gate"], "w_up": moe["w_up"], "w_down": moe["w_down"],
        "shared_gate": shared["w_gate"]["kernel"],
        "shared_up": shared["w_up"]["kernel"],
        "shared_down": shared["w_down"]["kernel"],
    }
    if "attn" in blk:
        attn = blk["attn"]
        return dict(
            w, wq=attn["wq"]["base"]["kernel"], wk=attn["wk"]["base"]["kernel"],
            wv=attn["wv"]["base"]["kernel"], wo=attn["wo"]["base"]["kernel"],
            w_gate_attn=attn["w_gate"]["kernel"])
    mix = blk["kda"]
    return dict(
        w, wqkv=mix["wqkv"]["kernel"], conv_weight=mix["conv_weight"],
        wf1=mix["wf1"]["kernel"], wf2=mix["wf2"]["kernel"],
        dt_bias=mix["dt_bias"], A_log=mix["A_log"], wb=mix["wb"]["kernel"],
        wg1=mix["wg1"]["kernel"], wg2=mix["wg2"]["kernel"],
        wg2_bias=mix["wg2"]["bias"], kda_norm=mix["norm"],
        wo=mix["wo"]["kernel"])


def hidden_states(params, tokens, *, n_layers, routing=None, follow=None,
                  slack=None, guaranteed=None, n_routed=None, n_held=None,
                  **sizes):
    """Final-block output (batch, seq, dim), float32, before the last norm.
    ``routing``: a list that receives each layer's own choice of experts;
    ``follow``: the experts to use instead, one entry a layer; ``slack``: a
    list that receives each layer's slack."""
    del guaranteed, n_routed, n_held  # the check's; the weights' shapes say them
    x = embed(params["embed"], tokens)
    for i in range(n_layers):
        x, own, loose = block(
            x, layer_weights(params, i),
            follow[i] if follow is not None else None, **sizes)
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
    fills above: each layer's chosen experts, (tokens, top_k), over all
    the experts routed over, in layer order."""
    return [sown[f"layer_{i}"]["moe"]["experts"][0] for i in range(n_layers)]


def _refuse_what_is_not_here(config: dict, also=()) -> None:
    name = config["name"]
    for key, want in (("first_k_dense_replace", 0),
                      ("kda_use_full_proj", False),
                      ("tie_word_embeddings", False),
                      ("n_shared_experts", 1)) + tuple(also):
        if config.get(key, want) != want:
            raise SystemExit(
                f"{name}: this reference has no {key}={config[key]!r}")
    linear = config["linear_attn_config"]
    if linear["num_kv_heads"] not in (None, linear["num_heads"]):
        raise SystemExit(f"{name}: KDA with fewer key heads than heads")


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
    linear = config["linear_attn_config"]
    return dict(
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        taps=linear["short_conv_kernel_size"],
        eps=float(config["rms_norm_eps"]),
        top_k=config["num_experts_per_tok"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        scale=float(config["routed_scaling_factor"]),
        experts_first=int(config.get("experts_first", 0)),
        beta_scale=2.0 if config["kda_allow_neg_eigval"] else 1.0,
        gqa_gate=bool(config["use_gqa_gate"]),
        rope_theta=float(config["rope_theta"]) if config["use_rope"] else None,
        n_routed=_routed(config), n_held=config["n_routed_experts"],
        # what a slot row takes at the precisions the configuration states
        # (float32 state, bf16 convolution tail and K/V): the check holds
        # the program's live rows to these counts
        guaranteed={
            "state_bytes_per_row": flops_kda.state_bytes_per_row(config),
            "kv_bytes_per_token": flops_kda.kv_bytes_per_token(config)},
    )


def llm_arguments(config: dict) -> dict:
    """A configuration file's published keys as ``LLMConfig`` arguments: the
    family and its model arguments
    (``ray_tpu.models.solar_open2.SolarOpen2Config``). The program has the
    published setting of three switches the reference has both sides of."""
    _refuse_what_is_not_here(config, also=(
        ("use_rope", False), ("use_gqa_gate", True),
        ("kda_allow_neg_eigval", True)))
    linear = config["linear_attn_config"]
    first, held, routed = (int(config.get("experts_first", 0)),
                           config["n_routed_experts"], _routed(config))
    return dict(
        model_family="solar_open2",
        model_kwargs=dict(
            vocab_size=config["vocab_size"],
            dim=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            gqa_layers=tuple(config["gqa_layers"]),
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            kda_heads=linear["num_heads"],
            kda_head_dim=linear["head_dim"],
            kda_conv=linear["short_conv_kernel_size"],
            kda_gate_rank=linear["head_dim"],
            moe_intermediate=config["moe_intermediate_size"],
            n_experts=routed,
            experts_per_token=config["num_experts_per_tok"],
            n_shared_experts=config["n_shared_experts"],
            norm_topk_prob=bool(config["norm_topk_prob"]),
            routed_scale=config["routed_scaling_factor"],
            experts_held=None if held == routed else (first, first + held),
            norm_eps=config["rms_norm_eps"],
        ),
    )
