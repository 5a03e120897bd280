"""The plain reference for the Cohere2-MoE architecture (``model_type``
cohere2_moe) at the settings command-a-plus-05-2026 publishes: a parallel
block on one LayerNorm, 128 query heads over 8 K/V heads, a 4096-position
window with interleaved rotary pairs three layers in four and full
attention without positions the fourth, 128 routed experts as wide as the
model beside four shared experts whose outputs are averaged, a tied head.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
the published form everywhere: no cache, no ring (a window layer is a
banded mask), no kernel, no batching. Names in ``code`` are the published
keys. ``h`` is the residual stream; layer ``i`` is a *window* layer when
``i % layer_switch != layer_switch - 1`` (``layer_types``:
``sliding_attention``), else *full*::

    n      = LayerNorm(h) = (h - mean(h)) / sqrt(var(h) + layer_norm_eps) * g     # no bias
    q,k,v  = n W_q, n W_k, n W_v     (num_attention_heads | num_key_value_heads) x head_dim
    window : q, k = rope(q, k; rope_theta), pairs (x[2i], x[2i+1]) (rope_gptj);
             key j visible to query t iff t - sliding_window < j <= t
    full   : no rotary embedding; key j visible iff j <= t
    a      = softmax(q k^T / sqrt(head_dim)) v, query head u reads K/V head
             u // (heads / kv heads);  attn = concat(a) W_o
    s      = sigmoid(n W_r);  T = the num_experts_per_tok largest;
    w_j    = s_j / (sum_T s + 1e-20)   # norm_topk_prob
    E_j(x) = (silu(x W_gate_j) * (x W_up_j)) W_down_j
    ffn    = sum_{j in T, held here} w_j E_j(n) + (1 / num_shared_experts) sum_m S_m(n)
    h'     = h + attn + ffn   # use_parallel_block
    logits = logit_scale * LayerNorm(h_L) Emb^T   # tie_word_embeddings

Departures, each of them a reading the configuration file lists under
``assumed``: an expert's width is ``intermediate_size``; "average" is the
mean of the shared experts' outputs added to the routed sum; the full
layers carry no rotary embedding; no routed scale, no selection bias, no
q/k norm; ``prefix_dense_*`` is read by nothing (``first_k_dense_replace``
0); ``logit_scale`` is 1 and anything else is refused here (the check's
``head`` is handed no scale). The program keeps the four shared experts as
one matrix triple, expert ``m`` the columns ``m I .. (m + 1) I`` of gate
and up and the same rows of down: here each is computed alone.

**The share**, as ``solar_open2_arch``: the configuration may hold
``experts_first .. experts_first + num_experts - 1`` of
``published.num_experts``; the router keeps its width, the weights are
normalised over the experts chosen wherever they live, and what the
experts held elsewhere would add is left out (``experts_loop``). A sliced
vocabulary is a smaller vocabulary.

Memory, because the check runs beside 12 GB of resident state at 8192
positions: a layer is not one program. The norm runs over the whole
sequence, attention a block of ``QUERY_BLOCK`` queries at a time against
the whole sequence's keys, a K/V head at a time (128 heads x 8192 x 8192
float32 scores would be 34 GB), the feed-forward ``FFN_BLOCK`` positions
at a time, the routed experts and the shared ones one expert at a time,
the weights cast to float32 a matrix at a time (a layer in float32 is 4.6
GB).

What it knows of the program, all of it *names*: those in its parameter
tree (``layer_weights``); in ``llm_arguments`` those of ``LLMConfig``'s and
``Cohere2MoEConfig``'s arguments; and what ``drivers/serve_arch_common.py``
reads of a running program. No import from ``ray_tpu``, and nothing under
``ray_tpu/`` imports this.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from ..harness import flops_c2moe

F32 = jnp.float32
QUERY_BLOCK = 512
FFN_BLOCK = 1024

TRACE_SCOPES = ("moe.route", "moe.experts", "moe.shared",
                "c2moe.attn_window", "c2moe.attn_full", "c2moe.norm")
# those of them that are no part of the expert layers (kept apart in a
# traced run's result, as ``solar_open2_arch`` says)
ATTENTION_SCOPES = ("c2moe.attn_window", "c2moe.attn_full", "c2moe.norm")
TRACE_KERNELS = ("moe_experts", "decode_attention", "kv_row_write")
PROGRAM_COUNTERS = ("moe", "kv")
ROUTING_COLLECTION = "moe_routing"

# controls: what ``hidden_states(faults=)`` may leave out or get wrong
FAULTS = ("no_window", "rope_on_full", "rotate_half", "shared_summed",
          "mean_kept", "lost_expert", "experts_e4m3")


def layer_norm(x, weight, eps, centred=True):
    """``centred`` false keeps the mean in (a control: RMSNorm)."""
    if centred:
        x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def rope(x, positions, theta, interleaved=True):
    """x: (seq, heads, d); positions: (seq,). Pairs ``(x[2i], x[2i+1])``,
    or with ``interleaved`` false the halves ``(x[i], x[i + d/2])`` (a
    control: the rotate-half form)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = positions.astype(F32)[:, None, None] * inv_freq  # (seq, 1, d/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if interleaved:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack(
            [a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _f32(w: dict) -> dict:
    return jax.tree.map(lambda a: a.astype(F32), w)


@partial(jax.jit, static_argnames=("eps", "centred"))
def normed(x, weight, *, eps, centred=True):
    return layer_norm(x, weight.astype(F32), eps, centred)


@partial(jax.jit, static_argnames=("kv_heads", "theta", "rotary"))
def keys_values(n, w, *, kv_heads, theta, rotary):
    """The whole sequence's keys and values: ``(seq, kv_heads, head_dim)``
    each. ``rotary``: None (a full layer), ``"interleaved"`` or
    ``"halves"``."""
    w = _f32(w)
    seq = n.shape[0]
    with jax.default_matmul_precision("highest"):
        k = (n @ w["wk"]).reshape(seq, kv_heads, -1)
        v = (n @ w["wv"]).reshape(seq, kv_heads, -1)
        if rotary:
            k = rope(k, jnp.arange(seq), theta, rotary == "interleaved")
        return k, v


@partial(jax.jit, static_argnames=("heads", "theta", "rotary", "window"))
def attend(n, start, k, v, w, *, heads, theta, rotary, window):
    """One block of queries ``n (block, d)`` at positions ``start ..``
    against the whole sequence's ``k`` / ``v``, a K/V head at a time: the
    attention's output ``(block, d)``. ``window`` None: causal only."""
    w = _f32(w)
    block, kv_heads = n.shape[0], k.shape[1]
    positions = start + jnp.arange(block)
    with jax.default_matmul_precision("highest"):
        q = (n @ w["wq"]).reshape(block, heads, -1)
        if rotary:
            q = rope(q, positions, theta, rotary == "interleaved")
        q = q.reshape(block, kv_heads, heads // kv_heads, -1)
        k_pos = jnp.arange(k.shape[0])[None, :]
        visible = k_pos <= positions[:, None]
        if window is not None:
            visible &= k_pos > positions[:, None] - window
        scale = 1.0 / math.sqrt(q.shape[-1])

        def one(group):
            q_g, k_g, v_g = group  # (block, group, d), (seq, d), (seq, d)
            scores = jnp.einsum("qjd,kd->jqk", q_g, k_g) * scale
            probs = jax.nn.softmax(
                jnp.where(visible[None], scores, -jnp.inf), axis=-1)
            return jnp.einsum("jqk,kd->qjd", probs, v_g)

        attended = jax.lax.map(one, (
            jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
        # (kv_heads, block, group, d) -> head u = kv head u // group
        return jnp.moveaxis(attended, 0, 1).reshape(block, -1) @ w["wo"]


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(n, router, top_k, norm_topk_prob, follow=None):
    """(tokens, dim) -> kept weights and their experts, (tokens, top_k)
    each, over all the experts routed over; this reference's own choice;
    and ``slack`` (tokens,), zero without ``follow``
    (``motif_arch.route`` without a scale)."""
    scores = jax.nn.sigmoid(n @ router)
    kth, own = jax.lax.top_k(scores, top_k)
    experts, slack = own, jnp.zeros(n.shape[0], F32)
    if follow is not None:
        experts = follow
        followed = jnp.take_along_axis(scores, follow, axis=-1)
        slack = (kth[:, -1] - jnp.min(followed, axis=-1)) / jnp.abs(kth[:, -1])
    kept = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk_prob:
        kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20)
    return kept, experts, own, slack


def through_e4m3(w):
    """A matrix ``(in, out)`` as an 8-bit float with 4 exponent and 3
    mantissa bits would hold it, a scale an output channel (a control: the
    nearest precision below bf16; ``lax.reduce_precision`` at these bits
    tops out at 240)."""
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 240.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jax.lax.reduce_precision(
        w / scale, exponent_bits=4, mantissa_bits=3) * scale


def experts_loop(n, kept, experts, w_gate, w_up, w_down, first, e4m3=False):
    """Every token through every expert *held*, one expert at a time,
    weighted by what the token kept for it (``solar_open2_arch``)."""
    def one(e, y):
        gate, up, down = (
            jax.lax.dynamic_index_in_dim(w, e, 0, False).astype(F32)
            for w in (w_gate, w_up, w_down))
        if e4m3:
            gate, up, down = (through_e4m3(w) for w in (gate, up, down))
        weight = jnp.sum(jnp.where(experts == first + e, kept, 0.0), axis=-1)
        return y + weight[:, None] * swiglu(n, gate, up, down)

    return jax.lax.fori_loop(0, w_gate.shape[0], one, jnp.zeros_like(n))


def shared_experts(n, w, n_shared: int, summed=False):
    """The mean of the ``n_shared`` shared experts' outputs, each computed
    alone from its columns of the program's matrix triple. ``summed``: their
    sum (a control)."""
    width = w["shared_gate"].shape[1] // n_shared
    total = jnp.zeros_like(n)
    for m in range(n_shared):
        cols = slice(m * width, (m + 1) * width)
        total = total + swiglu(
            n, w["shared_gate"][:, cols].astype(F32),
            w["shared_up"][:, cols].astype(F32),
            w["shared_down"][cols].astype(F32))
    return total if summed else total / n_shared


@partial(jax.jit, static_argnames=(
    "top_k", "norm_topk_prob", "experts_first", "n_shared", "summed", "lost",
    "e4m3"))
def feed_forward(n, w, follow=None, *, top_k, norm_topk_prob, experts_first,
                 n_shared, summed=False, lost=False, e4m3=False):
    """``lost`` leaves out a token's last chosen expert (a control)."""
    with jax.default_matmul_precision("highest"):
        kept, experts, own, slack = route(
            n, w["router"].astype(F32), top_k, norm_topk_prob, follow)
        if lost:
            kept = kept.at[:, -1].set(0.0)
        y = experts_loop(n, kept, experts, w["w_gate"], w["w_up"], w["w_down"],
                         experts_first, e4m3)
        return y + shared_experts(n, w, n_shared, summed), own, slack


@jax.jit
def embed(table, tokens):
    return table[tokens].astype(F32)


@partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, lm_head, *, eps):
    """(batch, seq, dim) -> logits over the vocabulary held: the tied
    matrix ``(vocab, dim)`` read the other way (``logit_scale`` 1)."""
    with jax.default_matmul_precision("highest"):
        return layer_norm(x, final_norm.astype(F32), eps) @ lm_head.astype(F32).T


def layer_weights(params, i: int) -> dict:
    """Layer ``i``'s weights from the program's parameter tree, by name."""
    blk = params[f"layer_{i}"]
    attn, moe, shared = blk["attn"], blk["moe"], blk["shared"]
    return {
        "norm": blk["norm"],
        "keys": {"wk": attn["wk"]["base"]["kernel"],
                 "wv": attn["wv"]["base"]["kernel"]},
        "attn": {"wq": attn["wq"]["base"]["kernel"],
                 "wo": attn["wo"]["base"]["kernel"]},
        "ffn": {"router": moe["router"], "w_gate": moe["w_gate"],
                "w_up": moe["w_up"], "w_down": moe["w_down"],
                "shared_gate": shared["w_gate"]["kernel"],
                "shared_up": shared["w_up"]["kernel"],
                "shared_down": shared["w_down"]["kernel"]},
    }


def _blocks(rows: int, size: int):
    return [(start, min(start + size, rows)) for start in range(0, rows, size)]


def block_parts(x, w, i: int, follow=None, *, heads, kv_heads, theta, eps,
                window, period, top_k, norm_topk_prob, experts_first,
                n_shared, faults=()):
    """Layer ``i``'s two branches on ``x (seq, dim)``: ``(attn, ffn, own
    choice of experts, slack)``. The block is ``x + attn + ffn``."""
    seq = x.shape[0]
    banded = i % period != period - 1
    rotary = None
    if banded or "rope_on_full" in faults:
        rotary = "halves" if "rotate_half" in faults else "interleaved"
    if banded and "no_window" in faults and i == 0:
        banded = False
    n = normed(x, w["norm"], eps=eps, centred="mean_kept" not in faults)
    k, v = keys_values(n, w["keys"], kv_heads=kv_heads, theta=theta,
                       rotary=rotary)
    padded = jnp.pad(n, ((0, -seq % QUERY_BLOCK), (0, 0)))
    attn = jnp.concatenate([
        attend(padded[start:stop], start, k, v, w["attn"], heads=heads,
               theta=theta, rotary=rotary, window=window if banded else None)
        for start, stop in _blocks(padded.shape[0], QUERY_BLOCK)])[:seq]
    parts = [
        feed_forward(n[start:stop], w["ffn"],
                     None if follow is None else follow[start:stop],
                     top_k=top_k, norm_topk_prob=norm_topk_prob,
                     experts_first=experts_first, n_shared=n_shared,
                     summed="shared_summed" in faults,
                     lost="lost_expert" in faults,
                     e4m3="experts_e4m3" in faults)
        for start, stop in _blocks(seq, FFN_BLOCK)]
    return (attn, jnp.concatenate([p[0] for p in parts]),
            jnp.concatenate([p[1] for p in parts]),
            jnp.concatenate([p[2] for p in parts]))


def hidden_states(params, tokens, *, n_layers, routing=None, follow=None,
                  slack=None, guaranteed=None, n_routed=None, n_held=None,
                  step_from_zero=None, faults=(), **sizes):
    """The residual stream after the last block, (batch 1, seq, dim),
    float32, before the last norm. ``routing``: a list that receives each
    layer's own choice of experts; ``follow``: the experts to use instead,
    one entry a layer; ``slack``: a list that receives each layer's slack.
    ``faults``: controls, names of what to get wrong (``FAULTS``:
    ``no_window`` of the first window layer, ``rope_on_full``,
    ``rotate_half``, ``shared_summed``, ``mean_kept``, ``lost_expert``: a
    token's last chosen one, ``experts_e4m3``: the routed experts' matrices
    through an 8-bit float)."""
    # the check's; the weights' shapes say them
    del guaranteed, n_routed, n_held, step_from_zero
    unknown = set(faults) - set(FAULTS)
    if tokens.shape[0] != 1 or unknown:
        raise ValueError(
            f"the reference takes one sequence at a time, and no {unknown}")
    x = embed(params["lm_head"], tokens[0])
    for i in range(n_layers):
        attn, ffn, own, off = block_parts(
            x, layer_weights(params, i), i,
            None if follow is None else follow[i], faults=tuple(faults),
            **sizes)
        x = x + attn + ffn
        if routing is not None:
            routing.append(own)
        if slack is not None:
            slack.append(off)
    return x[None]


def logits(params, tokens, *, last: int = 0, routing=None, follow=None,
           slack=None, **sizes):
    """Logits (1, seq or last, vocab) of a full causal forward pass.
    ``last`` keeps only that many trailing positions."""
    x = hidden_states(
        params, tokens, routing=routing, follow=follow, slack=slack, **sizes)
    if last:
        x = x[:, -last:]
    return head(x, params["final_norm"], params["lm_head"], eps=sizes["eps"])


def program_routing(sown, n_layers: int) -> list:
    """The program's sown ``ROUTING_COLLECTION`` in the form ``routing=``
    fills above: each layer's chosen experts, (tokens, top_k), over all the
    experts routed over, in layer order."""
    return [sown[f"layer_{i}"]["moe"]["experts"][0] for i in range(n_layers)]


def _refuse_what_is_not_here(config: dict) -> None:
    name = config["name"]
    for key, want in (("attention_bias", False), ("hidden_act", "silu"),
                      ("expert_selection_fn", "sigmoid"),
                      ("first_k_dense_replace", 0), ("logit_scale", 1),
                      ("position_embedding_type", "rope_gptj"),
                      ("order_of_interleaved_layers", "local_attn_first"),
                      ("shared_expert_combination_strategy", "average"),
                      ("rotary_pct", 1), ("tie_word_embeddings", True),
                      ("use_gated_activation", True),
                      ("use_parallel_block", True), ("use_qk_norm", False)):
        if config.get(key, want) != want:
            raise SystemExit(
                f"{name}: this reference has no {key}={config[key]!r}")
    switch, layers = config["layer_switch"], config["num_hidden_layers"]
    want = ["full_attention" if i % switch == switch - 1
            else "sliding_attention" for i in range(layers)]
    if config.get("layer_types", want)[:layers] != want:
        raise SystemExit(
            f"{name}: layer_types is not {switch - 1} window layers and a "
            "full one, in that order")
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        raise SystemExit(f"{name}: query heads in whole groups a K/V head")


def _routed(config: dict) -> int:
    """The router's width: the published count where the file holds a
    share (``num_experts`` is then the experts held)."""
    return config.get("published", {}).get("num_experts", config["num_experts"])


def sizes_of(config: dict) -> dict:
    """The keyword sizes above, from a configuration file's published keys,
    and ``guaranteed`` / ``n_held`` / ``n_routed`` / ``step_from_zero`` for
    the check (``drivers/serve_closed_loop_arch_window_routed.py``)."""
    _refuse_what_is_not_here(config)
    return dict(
        n_layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        theta=float(config["rope_theta"]),
        eps=float(config["layer_norm_eps"]),
        window=config["sliding_window"],
        period=config["layer_switch"],
        top_k=config["num_experts_per_tok"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        experts_first=int(config.get("experts_first", 0)),
        n_shared=config["num_shared_experts"],
        n_routed=_routed(config), n_held=config["num_experts"],
        # the longest prompt the check feeds a token a step from position 0
        # (a toy's file lowers it)
        step_from_zero=int(config.get("check_step_from_zero", 256)),
        # what a slot row takes at the precision the configuration states
        # (bf16 keys and values): the check holds the program's live rows
        # to these counts
        guaranteed={
            "window_bytes_per_row": flops_c2moe.window_bytes_per_row(config),
            "kv_bytes_per_token": flops_c2moe.kv_bytes_per_token(config)},
    )


def llm_arguments(config: dict) -> dict:
    """A configuration file's published keys as ``LLMConfig`` arguments: the
    family and its model arguments
    (``ray_tpu.models.cohere2_moe.Cohere2MoEConfig``)."""
    _refuse_what_is_not_here(config)
    first, held, routed = (int(config.get("experts_first", 0)),
                           config["num_experts"], _routed(config))
    return dict(
        model_family="cohere2_moe",
        model_kwargs=dict(
            vocab_size=config["vocab_size"],
            dim=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            sliding_window=config["sliding_window"],
            layer_switch=config["layer_switch"],
            intermediate=config["intermediate_size"],
            n_experts=routed,
            experts_per_token=config["num_experts_per_tok"],
            n_shared_experts=config["num_shared_experts"],
            norm_topk_prob=bool(config["norm_topk_prob"]),
            experts_held=None if held == routed else (first, first + held),
            logit_scale=float(config["logit_scale"]),
            rope_theta=float(config["rope_theta"]),
            norm_eps=config["layer_norm_eps"],
        ),
    )
