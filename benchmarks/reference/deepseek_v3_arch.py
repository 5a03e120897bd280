"""The plain reference for the DeepSeek-V3 architecture (``model_type``
deepseek_v3) at the settings Moonlight-16B-A3B publishes: latent attention
without a query low-rank path (``q_lora_rank`` null), no rope scaling, a
sigmoid router with a selection bias and no group limit (``n_group`` 1).

Follows the published description (``modeling_deepseek.py`` of
moonshotai/Moonlight-16B-A3B) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. A layer:

- ``h = rmsnorm(x, input_layernorm)``; ``q = h Wq`` -> heads x (nope |
  rope); ``[c_raw | k_r] = h Wkva`` (rank | rope); ``c = rmsnorm(c_raw,
  kv_a_layernorm)``; rotate-half RoPE on the rope part of q and on ``k_r``,
  which is one row shared by all heads; ``[k_nope | v] = c Wkvb`` a head;
  ``k = [k_nope | k_rope]``; causal ``softmax(q k^T / sqrt(nope + rope))
  v``; ``x = x + attn Wo``. The published form everywhere: nothing is
  absorbed and nothing cached.
- ``h = rmsnorm(x, post_attention_layernorm)``. The first
  ``first_k_dense_replace`` layers: ``x = x + down(silu(gate h) * up h)``.
  Every later one: ``s = sigmoid(h Wg)``; the experts are the ``k`` largest
  of ``s + b`` (``e_score_correction_bias``, in the choice only); weights
  ``s[experts] / (sum + 1e-20)`` if ``norm_topk_prob``, times
  ``routed_scaling_factor``; ``x = x + sum_j w_j E_j(h) + Shared(h)``,
  ``Shared`` one SwiGLU of ``n_shared_experts x moe_intermediate_size``.
  No capacity: no token is dropped.
- final ``rmsnorm``, untied head.

Departures from the description, none in the function computed for weights
of this layout: (1) RoPE. The published code de-interleaves the rotary
columns (``view(d/2, 2).transpose``) before ``rotate_half``; this
reference rotates the half-split form directly. With seeded random weights
that is a fixed permutation of ``Wq``'s and ``Wkva``'s rotary columns, and
a published checkpoint would be permuted so at load. (2) Memory, because
the check runs beside 13 GB of resident state on prompts of up to 4096
tokens: weights arrive in the program's tree (bf16) and are cast to
float32 a layer at a time, the experts one expert at a time (every token
through every expert, weighted by what it kept for it, zero where not
chosen), and attention goes in blocks of 512 queries against the keys up
to the block's end. Measured peak: PERF.md.

Asked to (``follow=``), the routed layers use the experts the program
chose in place of their own top-k, at this reference's own scores, and say
how fair that choice was (``route``), as ``olmoe_arch`` does and for the
same reason. The slack is taken on the *biased* score, where the choice is
made. No kernel, no sort, no cache, and no import from ``ray_tpu.models``,
``ray_tpu.ops`` or ``ray_tpu.parallel``.

What it knows of the program, all of it *names*: those in its parameter
tree (``layer_weights``); in ``llm_arguments`` those of ``LLMConfig``'s and
``DeepseekConfig``'s arguments; and what ``drivers/serve_arch_common.py``
reads of a running program (``TRACE_SCOPES``, ``TRACE_KERNELS``,
``PROGRAM_COUNTERS``, ``ROUTING_COLLECTION``, ``program_routing``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512
VOCAB_BLOCK = 16384

# jax.named_scope names and Pallas kernel names of the decode program whose
# device time a traced run keeps (harness/xplane_scopes.py)
TRACE_SCOPES = ("moe.route", "moe.experts", "moe.shared", "mla.absorb")
# those of them that are no part of the expert layers: kept apart in a traced
# run's result (drivers/serve_closed_loop_arch_blockwise.py), so that what
# sums the expert layers' scopes sums no attention
ATTENTION_SCOPES = ("mla.absorb",)
TRACE_KERNELS = ("moe_experts", "latent_decode_attention")
# groups of the replica's runtime_info() kept at both ends of the window
PROGRAM_COUNTERS = ("moe", "kv")
# the flax collection the model sows each routed layer's chosen experts
# into (ray_tpu.models.ROUTING, by value: nothing of the program is imported)
ROUTING_COLLECTION = "moe_routing"


def rmsnorm(x, weight, eps):
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + eps) * weight


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, positions, theta):
    """x: (batch, seq, heads, d); positions: (seq,)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = positions.astype(F32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([angles, angles], axis=-1)  # (seq, d)
    cos, sin = jnp.cos(emb)[None, :, None, :], jnp.sin(emb)[None, :, None, :]
    return x * cos + rotate_half(x) * sin


def attention(x, w, n_heads, rank, nope, rope_dim, theta, eps):
    b, s, _ = x.shape
    positions = jnp.arange(s)
    q = (x @ w["wq"]).reshape(b, s, n_heads, nope + rope_dim)
    q = jnp.concatenate(
        [q[..., :nope], rope(q[..., nope:], positions, theta)], axis=-1)
    kva = x @ w["wkv_a"]
    c = rmsnorm(kva[..., :rank], w["kv_norm"], eps)
    k_rope = rope(kva[..., None, rank:], positions, theta)  # (b, s, 1, rope)
    kv = jnp.einsum("bsr,rhd->bshd", c, w["wkv_b"])  # heads x (nope | v)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, n_heads, rope_dim))],
        axis=-1)
    v = kv[..., nope:]
    out = []
    for start in range(0, s, QUERY_BLOCK):
        end = min(start + QUERY_BLOCK, s)
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q[:, start:end], k[:, :end]
        ) / math.sqrt(nope + rope_dim)
        causal = positions[start:end, None] >= positions[None, :end]
        probs = jax.nn.softmax(
            jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", probs, v[:, :end]))
    out = jnp.concatenate(out, axis=1).reshape(b, s, -1)
    return out @ w["wo"]


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def route(h, router, bias, top_k, norm_topk_prob, scale, follow=None):
    """(tokens, dim) -> kept weights and their experts, (tokens, top_k)
    each; this reference's own choice of experts; and ``slack`` (tokens,),
    zero without ``follow``.

    ``follow`` (tokens, top_k): experts to use in place of the ``top_k``
    largest biased scores, each at the (unbiased) score computed here.
    ``slack``: how far the least biased score among the followed experts
    lies under the ``top_k``-th largest biased score, as a share of it:
    zero where ``follow`` is the top-k set in any order (see
    ``olmoe_arch.route`` for why a check follows at all)."""
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


def experts_loop(h, kept, experts, w_gate, w_up, w_down):
    """Every token through every expert, one expert at a time, weighted by
    what the token kept for it (zero where not chosen)."""
    def one(e, y):
        gate = jax.lax.dynamic_index_in_dim(w_gate, e, 0, False).astype(F32)
        up = jax.lax.dynamic_index_in_dim(w_up, e, 0, False).astype(F32)
        down = jax.lax.dynamic_index_in_dim(w_down, e, 0, False).astype(F32)
        weight = jnp.sum(jnp.where(experts == e, kept, 0.0), axis=-1)
        return y + weight[:, None] * swiglu(h, gate, up, down)

    return jax.lax.fori_loop(0, w_gate.shape[0], one, jnp.zeros_like(h))


_STATIC = ("n_heads", "rank", "nope", "rope_dim", "theta", "eps", "top_k",
           "norm_topk_prob", "scale")


@partial(jax.jit, static_argnames=_STATIC)
def block(x, w, follow=None, *, n_heads, rank, nope, rope_dim, theta, eps,
          top_k, norm_topk_prob, scale):
    """One layer. Returns the new hidden state and, for a routed layer,
    this reference's own choice of experts (batch * seq, top_k) and
    ``route``'s slack (batch * seq,); None twice for a dense one."""
    big = ("w_gate", "w_up", "w_down")  # cast an expert at a time
    routed = "router" in w
    experts_w = {k: w[k] for k in big} if routed else {}
    w = jax.tree.map(
        lambda a: a.astype(F32), {k: v for k, v in w.items() if k not in experts_w})
    with jax.default_matmul_precision("highest"):
        x = x + attention(
            rmsnorm(x, w["attn_norm"], eps), w, n_heads, rank, nope, rope_dim,
            theta, eps)
        h = rmsnorm(x, w["ffn_norm"], eps).reshape(-1, x.shape[-1])
        if not routed:
            y = swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
            return x + y.reshape(x.shape), None, None
        kept, experts, own, slack = route(
            h, w["router"], w["router_bias"], top_k, norm_topk_prob, scale,
            follow)
        y = experts_loop(h, kept, experts, **experts_w)
        y = y + swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"])
        return x + y.reshape(x.shape), own, slack


@jax.jit
def embed(table, tokens):
    return table[tokens].astype(F32)


@partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, lm_head, *, eps):
    """(batch, seq, dim) -> logits. The head's columns go through in
    ``VOCAB_BLOCK``s where they divide so: a float32 copy of all of a
    163840-column head at once would be 1.3 GB."""
    vocab = lm_head.shape[1]
    with jax.default_matmul_precision("highest"):
        h = rmsnorm(x, final_norm.astype(F32), eps)
        if vocab <= VOCAB_BLOCK or vocab % VOCAB_BLOCK:
            return h @ lm_head.astype(F32)
        blocks = jax.lax.map(
            lambda i: h @ jax.lax.dynamic_slice_in_dim(
                lm_head, i * VOCAB_BLOCK, VOCAB_BLOCK, axis=1).astype(F32),
            jnp.arange(vocab // VOCAB_BLOCK))  # (blocks, batch, seq, block)
        return jnp.moveaxis(blocks, 0, -2).reshape(*h.shape[:-1], vocab)


def layer_weights(params, i: int) -> dict:
    """Layer ``i``'s weights from the program's parameter tree, by name; a
    routed layer is one with a ``moe`` group."""
    blk = params[f"layer_{i}"]
    attn = blk["attn"]
    w = {
        "attn_norm": blk["attn_norm"], "ffn_norm": blk["ffn_norm"],
        "wq": attn["wq"]["kernel"], "wkv_a": attn["wkv_a"]["kernel"],
        "kv_norm": attn["kv_norm"], "wkv_b": attn["wkv_b"],
        "wo": attn["wo"]["kernel"],
    }
    if "moe" not in blk:
        mlp = blk["mlp"]
        return dict(w, w_gate=mlp["w_gate"]["kernel"], w_up=mlp["w_up"]["kernel"],
                    w_down=mlp["w_down"]["kernel"])
    moe, shared = blk["moe"], blk["shared"]
    return dict(
        w, router=moe["router"], router_bias=moe["router_bias"],
        w_gate=moe["w_gate"], w_up=moe["w_up"], w_down=moe["w_down"],
        shared_gate=shared["w_gate"]["kernel"], shared_up=shared["w_up"]["kernel"],
        shared_down=shared["w_down"]["kernel"])


def hidden_states(params, tokens, *, n_layers, routing=None, follow=None,
                  slack=None, **sizes):
    """Final-block output (batch, seq, dim), float32, before the last norm.
    ``routing``: a list that receives each *routed* layer's own choice of
    experts; ``follow``: the experts to use instead, one entry a routed
    layer in layer order (``route``); ``slack``: a list that receives each
    routed layer's slack."""
    x = embed(params["embed"], tokens)
    given = iter(follow) if follow is not None else None
    for i in range(n_layers):
        w = layer_weights(params, i)
        x, own, loose = block(
            x, w, next(given) if given is not None and "router" in w else None,
            **sizes)
        if own is not None and routing is not None:
            routing.append(own)
        if loose is not None and slack is not None:
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
    fills above: each routed layer's chosen experts, (tokens, top_k), in
    layer order (a dense layer sows nothing)."""
    return [sown[f"layer_{i}"]["moe"]["experts"][0]
            for i in range(n_layers) if f"layer_{i}" in sown]


def _refuse_what_is_not_here(config: dict) -> None:
    name = config["name"]
    for key, want in (("q_lora_rank", None), ("rope_scaling", None),
                      ("n_group", 1), ("topk_group", 1),
                      ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                      ("moe_layer_freq", 1), ("hidden_act", "silu"),
                      ("attention_bias", False), ("tie_word_embeddings", False)):
        if config.get(key, want) != want:
            raise SystemExit(
                f"{name}: this reference has no {key}={config[key]!r}")
    if config["num_attention_heads"] != config["num_key_value_heads"]:
        raise SystemExit(f"{name}: latent attention has one row for all heads")


def sizes_of(config: dict) -> dict:
    """The keyword sizes above, from a configuration file's published keys."""
    _refuse_what_is_not_here(config)
    return dict(
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        rank=config["kv_lora_rank"], nope=config["qk_nope_head_dim"],
        rope_dim=config["qk_rope_head_dim"],
        theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]),
        top_k=config["num_experts_per_tok"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        scale=float(config["routed_scaling_factor"]),
    )


def llm_arguments(config: dict) -> dict:
    """A configuration file's published keys as ``LLMConfig`` arguments: the
    family and its model arguments (``ray_tpu.models.deepseek.DeepseekConfig``)."""
    _refuse_what_is_not_here(config)
    return dict(
        model_family="deepseek",
        model_kwargs=dict(
            vocab_size=config["vocab_size"],
            dim=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            n_heads=config["num_attention_heads"],
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"],
            qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            intermediate=config["intermediate_size"],
            moe_intermediate=config["moe_intermediate_size"],
            n_experts=config["n_routed_experts"],
            experts_per_token=config["num_experts_per_tok"],
            n_shared_experts=config["n_shared_experts"],
            first_dense_layers=config["first_k_dense_replace"],
            norm_topk_prob=bool(config["norm_topk_prob"]),
            routed_scale=config["routed_scaling_factor"],
            rope_theta=config["rope_theta"],
            norm_eps=config["rms_norm_eps"],
            remat=False,
        ),
    )
