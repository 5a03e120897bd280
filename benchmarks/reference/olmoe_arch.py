"""The plain reference for the OLMoE architecture (``model_type`` olmoe).

Follows the published description (``modeling_olmoe.py`` of
allenai/OLMoE-1B-7B-0125-Instruct) line by line, in float32 ``jax.numpy``
under ``jax.default_matmul_precision("highest")``. A layer:

- ``h = rmsnorm(x, input_layernorm)``; ``q = rmsnorm(h Wq, q_norm)``,
  ``k = rmsnorm(h Wk, k_norm)``: the norm runs over the whole projection,
  before the split into heads; ``v = h Wv``; no bias, no clamp
  (``clip_qkv`` null); rotate-half RoPE on q and k; causal softmax
  attention; ``x = x + attn Wo``
- ``h = rmsnorm(x, post_attention_layernorm)``; ``p = softmax(h Wg)`` over
  all experts; the ``k`` largest ``p`` and their experts, divided by their
  sum only if ``norm_topk_prob``; ``y = sum_j p_j down_j(silu(gate_j h) *
  up_j h)``; ``x = x + y``. No capacity: no token is dropped.
- final ``rmsnorm``, untied head.

The experts are a plain loop over all of them: every token goes through
every expert and is weighted by that expert's kept probability, zero where
it was not chosen. Asked to (``follow=``), it uses the experts the program
chose in place of its own top-k, at its own probabilities, and says how
fair that choice was (``route``): the benchmark's check compares logits so,
because a swap between two near-equal experts is no fault and moves the
logits more than a lower precision does. No kernel, no sort, no cache, and no import from
``ray_tpu.models``, ``ray_tpu.ops`` or ``ray_tpu.parallel``.

Departures from the description: none in the mathematics. Weights arrive in
the program's own tree (bf16) and are cast to float32 a layer at a time,
the experts one expert at a time, so the reference fits beside the system
it checks.

What it knows of the program, all of it *names*: those in its parameter
tree (``layer_weights``); in ``llm_arguments`` those of ``LLMConfig``'s and
``MoEConfig``'s arguments, the one place the published keys meet them (for
the Llama reference that place is ``harness/manifest.llama_kwargs``); and
what ``drivers/serve_arch_common.py`` reads of a running program for this
architecture: the scopes and kernels of its decode program
(``TRACE_SCOPES``, ``TRACE_KERNELS``), the ``runtime_info()`` groups it
keeps as counters (``PROGRAM_COUNTERS``), and where the model sows the
experts it chose (``ROUTING_COLLECTION``, ``program_routing``), which the
check hands back as ``follow=``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32

# jax.named_scope names and Pallas kernel names of the decode program whose
# device time a traced run keeps (harness/xplane_scopes.py)
TRACE_SCOPES = ("moe.route", "moe.experts")
TRACE_KERNELS = ("moe_experts", "decode_attention")
# groups of the replica's runtime_info() kept at both ends of the window
PROGRAM_COUNTERS = ("moe",)
# the flax collection the model sows each layer's chosen experts into
# (ray_tpu.models.ROUTING, by value: nothing of the program is imported)
ROUTING_COLLECTION = "moe_routing"


def rmsnorm(x, weight, eps):
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + eps) * weight


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, positions, theta):
    """x: (batch, seq, heads, head_dim); positions: (seq,)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = positions.astype(F32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([angles, angles], axis=-1)  # (seq, d)
    cos, sin = jnp.cos(emb)[None, :, None, :], jnp.sin(emb)[None, :, None, :]
    return x * cos + rotate_half(x) * sin


def attention(x, w, n_heads, n_kv_heads, theta, eps):
    b, s, _ = x.shape
    q = rmsnorm(x @ w["wq"], w["q_norm"], eps)
    k = rmsnorm(x @ w["wk"], w["k_norm"], eps)
    v = x @ w["wv"]
    d = q.shape[-1] // n_heads
    q = q.reshape(b, s, n_heads, d)
    k = k.reshape(b, s, n_kv_heads, d)
    v = v.reshape(b, s, n_kv_heads, d)
    positions = jnp.arange(s)
    q, k = rope(q, positions, theta), rope(k, positions, theta)
    group = n_heads // n_kv_heads
    q = q.reshape(b, s, n_kv_heads, group, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k) / math.sqrt(d)
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v).reshape(b, s, n_heads * d)
    return out @ w["wo"]


def route(h, router, top_k, norm_topk_prob, follow=None):
    """(tokens, dim) -> kept probabilities and their experts, (tokens,
    top_k) each; this reference's own choice of experts; and ``slack``
    (tokens,), zero without ``follow``.

    ``follow`` (tokens, top_k): experts to use in place of the ``top_k``
    largest, each at the probability computed here. Top-k is a
    discontinuity: a bfloat16 hidden state that swaps two experts whose
    probabilities differ by less than its rounding gives other logits than
    this float32 pass, and neither is wrong. Following the program's choice
    takes the discontinuity out of the comparison, and ``slack`` says
    whether the choice was a fair one: how far the least probable followed
    expert lies under the ``top_k``-th largest probability, as a share of
    it. Zero where ``follow`` is the top-k set in any order."""
    probs = jax.nn.softmax(h @ router, axis=-1)
    kept, own = jax.lax.top_k(probs, top_k)
    experts, slack = own, jnp.zeros(h.shape[0], F32)
    if follow is not None:
        experts = follow
        followed = jnp.take_along_axis(probs, follow, axis=-1)
        slack = (kept[:, -1] - jnp.min(followed, axis=-1)) / kept[:, -1]
        kept = followed
    if norm_topk_prob:
        kept = kept / jnp.sum(kept, axis=-1, keepdims=True)
    return kept, experts, own, slack


def experts_loop(h, kept, experts, w_gate, w_up, w_down):
    """Every token through every expert, one expert at a time, weighted by
    the probability the token kept for it (zero where not chosen)."""
    def one(e, y):
        gate = jax.lax.dynamic_index_in_dim(w_gate, e, 0, False).astype(F32)
        up = jax.lax.dynamic_index_in_dim(w_up, e, 0, False).astype(F32)
        down = jax.lax.dynamic_index_in_dim(w_down, e, 0, False).astype(F32)
        weight = jnp.sum(jnp.where(experts == e, kept, 0.0), axis=-1)
        out = (jax.nn.silu(h @ gate) * (h @ up)) @ down
        return y + weight[:, None] * out

    return jax.lax.fori_loop(0, w_gate.shape[0], one, jnp.zeros_like(h))


@partial(jax.jit, static_argnames=(
    "n_heads", "n_kv_heads", "theta", "eps", "top_k", "norm_topk_prob"))
def block(x, w, follow=None, *, n_heads, n_kv_heads, theta, eps, top_k, norm_topk_prob):
    """One layer. Returns the new hidden state, this reference's own choice
    of experts (batch * seq, top_k) and ``route``'s slack (batch * seq,)."""
    experts_w = {k: w[k] for k in ("w_gate", "w_up", "w_down")}
    w = jax.tree.map(
        lambda a: a.astype(F32), {k: v for k, v in w.items() if k not in experts_w})
    with jax.default_matmul_precision("highest"):
        x = x + attention(
            rmsnorm(x, w["attn_norm"], eps), w, n_heads, n_kv_heads, theta, eps)
        h = rmsnorm(x, w["ffn_norm"], eps).reshape(-1, x.shape[-1])
        kept, experts, own, slack = route(
            h, w["router"], top_k, norm_topk_prob, follow)
        y = experts_loop(h, kept, experts, **experts_w)
        return x + y.reshape(x.shape), own, slack


@jax.jit
def embed(table, tokens):
    return table[tokens].astype(F32)


@partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return rmsnorm(x, final_norm.astype(F32), eps) @ lm_head.astype(F32)


def layer_weights(params, i: int) -> dict:
    """Layer ``i``'s weights from the program's parameter tree, by name."""
    blk = params[f"layer_{i}"]
    attn, moe = blk["attn"], blk["moe"]
    return {
        "attn_norm": blk["attn_norm"], "ffn_norm": blk["ffn_norm"],
        "wq": attn["wq"]["base"]["kernel"], "wk": attn["wk"]["base"]["kernel"],
        "wv": attn["wv"]["base"]["kernel"], "wo": attn["wo"]["base"]["kernel"],
        "q_norm": attn["q_norm"], "k_norm": attn["k_norm"],
        "router": moe["router"], "w_gate": moe["w_gate"],
        "w_up": moe["w_up"], "w_down": moe["w_down"],
    }


def hidden_states(params, tokens, *, n_layers, routing=None, follow=None,
                  slack=None, **sizes):
    """Final-block output (batch, seq, dim), float32, before the last norm.
    ``routing``: a list that receives each layer's own choice of experts;
    ``follow``: a layer's experts to use instead, one entry a layer
    (``route``); ``slack``: a list that receives each layer's slack."""
    x = embed(params["embed"], tokens)
    for i in range(n_layers):
        x, own, loose = block(
            x, layer_weights(params, i),
            None if follow is None else follow[i], **sizes)
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
    fills above: each layer's chosen experts, (tokens, top_k)."""
    return [sown[f"layer_{i}"]["moe"]["experts"][0] for i in range(n_layers)]


def sizes_of(config: dict) -> dict:
    """The keyword sizes above, from a configuration file's published keys."""
    if config.get("clip_qkv") is not None:
        raise SystemExit(f"{config['name']}: this reference has no clip_qkv")
    return dict(
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]),
        top_k=config["num_experts_per_tok"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
    )


def llm_arguments(config: dict) -> dict:
    """A configuration file's published keys as ``LLMConfig`` arguments:
    the family and its model arguments (``ray_tpu.models.moe.MoEConfig``)."""
    heads = config["num_attention_heads"]
    if config["hidden_size"] != heads * config.get(
            "head_dim", config["hidden_size"] // heads):
        raise SystemExit(f"{config['name']}: MoEConfig derives head_dim from hidden_size")
    if config.get("tie_word_embeddings") or config.get("attention_bias"):
        raise SystemExit(f"{config['name']}: MoEConfig has no tied head and no bias")
    return dict(
        model_family="moe",
        model_kwargs=dict(
            vocab_size=config["vocab_size"],
            dim=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            n_heads=heads,
            n_kv_heads=config["num_key_value_heads"],
            intermediate=config["intermediate_size"],
            n_experts=config["num_experts"],
            experts_per_token=config["num_experts_per_tok"],
            norm_topk_prob=bool(config["norm_topk_prob"]),
            qk_norm=True,
            rope_theta=config["rope_theta"],
            norm_eps=config["rms_norm_eps"],
            remat=False,
        ),
    )
