"""The plain reference for the SmallThinker architecture (``model_name``
smallthinker_21b_instruct) at the settings SmallThinker-21BA3B-Instruct
publishes: a layer whose router reads the *attention's* input, 28 query
heads over 4 K/V heads, a full layer without positions and then three
rotary window layers of 4096 a period, 64 ReGLU experts of 768 of which a
token takes 6, RMSNorm twice a layer, an untied head.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
the published form everywhere: no cache, no ring (a window layer is a
banded mask), no kernel, no batching, each expert alone in a loop. Names in
``code`` are the published keys. ``h`` is the residual stream; layer ``i``
is *full* when ``sliding_window_layout[i] == 0`` (where ``rope_layout[i]``
is 0 too: ``i % 4 == 0``), else *window*::

    n1     = RMSNorm(h; input_layernorm)               # rms_norm_eps
    s      = n1 W_r                                    # the router reads the ATTENTION's input
    T      = the moe_num_active_primary_experts largest of s
    w_j    = exp(s_j) / sum_{k in T} exp(s_k)          # moe_primary_router_apply_softmax
    q,k,v  = n1 W_q, n1 W_k, n1 W_v     (num_attention_heads | num_key_value_heads) x head_dim
    window : q, k = rope(q, k; rope_theta), halves (x[i], x[i + d/2]);
             key j visible to query t iff t - sliding_window_size < j <= t
    full   : no rotary embedding; key j visible iff j <= t
    a      = h + concat(softmax(q k^T / sqrt(head_dim)) v) W_o, query head u
             reads K/V head u // (heads / kv heads)
    n2     = RMSNorm(a; post_attention_layernorm)
    h'     = a + sum_{j in T} w_j (relu(n2 W_gate_j) * (n2 W_up_j)) W_down_j     # ReGLU
    logits = RMSNorm(h_L; norm) W_head                 # tie_word_embeddings false

Departures, each of them a reading the configuration file lists under
``assumed``: the router's input is ``input_layernorm``'s output; the expert
is ``down(relu(gate x) * up x)`` without bias; there are no secondary
experts; q/k/v/o carry no bias and q and k no norm; the full layers carry
no rotary embedding and a window layer's edge is as above. ``norm_topk_prob``
true beside ``moe_primary_router_apply_softmax`` true changes nothing (a
softmax over the kept already sums to one) and anything else is refused
here. The slack of a followed choice is measured on the softmax over all
the experts, which orders them as the logits do.

**The share**, as ``cohere2_moe_arch``: the configuration may hold
``experts_first .. experts_first + moe_num_primary_experts - 1`` of
``published.moe_num_primary_experts``; the published model on one chip
holds them all.

Memory, because the check runs beside 12.5 GB of resident state at 4096
positions: a layer is not one program. The norms run over the whole
sequence, attention a block of ``QUERY_BLOCK`` queries at a time against
the whole sequence's keys, a K/V head at a time, the router and the experts
``FFN_BLOCK`` positions at a time, one expert at a time, the weights cast
to float32 a matrix at a time; ``logits`` takes the head ``HEAD_BLOCK``
positions at a time (a block of 151936 float32 columns is 0.3 GB).

What it knows of the program, all of it *names*: those in its parameter
tree (``layer_weights``); in ``llm_arguments`` those of ``LLMConfig``'s and
``SmallThinkerConfig``'s arguments; and what ``drivers/serve_arch_common.py``
reads of a running program. No import from ``ray_tpu``, and nothing under
``ray_tpu/`` imports this.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from ..harness import flops_stmoe

F32 = jnp.float32
QUERY_BLOCK = 512
FFN_BLOCK = 1024
HEAD_BLOCK = 512

# ``sthink.route`` wraps ``MoEFFN``'s own ``moe.route``: the first name an
# instruction carries is the one it is counted under
TRACE_SCOPES = ("sthink.route", "moe.sort", "moe.experts",
                "sthink.attn_window", "sthink.attn_full", "sthink.norm")
# those of them that are no part of the expert layers (kept apart in a
# traced run's result, as ``solar_open2_arch`` says)
ATTENTION_SCOPES = ("sthink.attn_window", "sthink.attn_full", "sthink.norm")
TRACE_KERNELS = ("moe_experts", "decode_attention", "kv_row_write")
PROGRAM_COUNTERS = ("moe", "kv")
ROUTING_COLLECTION = "moe_routing"

# controls: what ``hidden_states(faults=)`` may leave out or get wrong
FAULTS = ("route_on_n2", "swiglu", "experts_e4m3", "lost_expert",
          "no_window", "rope_on_full", "rope_pairs")


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def rope(x, positions, theta, pairs=False):
    """x: (seq, heads, d); positions: (seq,). The halves ``(x[i], x[i +
    d/2])`` (rotate-half), or with ``pairs`` the neighbours ``(x[2i],
    x[2i+1])`` (a control: GPT-J's form)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = positions.astype(F32)[:, None, None] * inv_freq  # (seq, 1, d/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if pairs:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack(
            [a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _f32(w: dict) -> dict:
    return jax.tree.map(lambda a: a.astype(F32), w)


@partial(jax.jit, static_argnames=("eps",))
def normed(x, weight, *, eps):
    return rms_norm(x, weight.astype(F32), eps)


@partial(jax.jit, static_argnames=("kv_heads", "theta", "rotary"))
def keys_values(n, w, *, kv_heads, theta, rotary):
    """The whole sequence's keys and values: ``(seq, kv_heads, head_dim)``
    each. ``rotary``: None (a full layer), ``"halves"`` or ``"pairs"``."""
    w = _f32(w)
    seq = n.shape[0]
    with jax.default_matmul_precision("highest"):
        k = (n @ w["wk"]).reshape(seq, kv_heads, -1)
        v = (n @ w["wv"]).reshape(seq, kv_heads, -1)
        if rotary:
            k = rope(k, jnp.arange(seq), theta, rotary == "pairs")
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
            q = rope(q, positions, theta, rotary == "pairs")
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


def reglu(x, gate, up, down, gated=jax.nn.relu):
    return (gated(x @ gate) * (x @ up)) @ down


@partial(jax.jit, static_argnames=("top_k",))
def route(n, router, follow=None, *, top_k):
    """(tokens, dim) -> kept weights and their experts, (tokens, top_k)
    each, over all the experts routed over; this reference's own choice;
    and ``slack`` (tokens,), zero without ``follow``: how far the least
    probable expert followed lies under the ``top_k``-th largest
    probability, as a share of it."""
    with jax.default_matmul_precision("highest"):
        logits = n @ router.astype(F32)
    probs = jax.nn.softmax(logits, axis=-1)
    kth, own = jax.lax.top_k(probs, top_k)
    experts, slack = own, jnp.zeros(n.shape[0], F32)
    if follow is not None:
        experts = follow
        followed = jnp.take_along_axis(probs, follow, axis=-1)
        slack = (kth[:, -1] - jnp.min(followed, axis=-1)) / kth[:, -1]
    # the published form: a softmax over the kept logits alone
    kept = jax.nn.softmax(
        jnp.take_along_axis(logits, experts, axis=-1), axis=-1)
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


@partial(jax.jit, static_argnames=("first", "swiglu", "e4m3"))
def experts_loop(n, kept, experts, w, *, first, swiglu=False, e4m3=False):
    """Every token through every expert *held*, one expert at a time,
    weighted by what the token kept for it (``solar_open2_arch``).
    ``swiglu``: the gate through silu (a control)."""
    gated = jax.nn.silu if swiglu else jax.nn.relu

    def one(e, y):
        gate, up, down = (
            jax.lax.dynamic_index_in_dim(w[name], e, 0, False).astype(F32)
            for name in ("w_gate", "w_up", "w_down"))
        if e4m3:
            gate, up, down = (through_e4m3(m) for m in (gate, up, down))
        weight = jnp.sum(jnp.where(experts == first + e, kept, 0.0), axis=-1)
        return y + weight[:, None] * reglu(n, gate, up, down, gated)

    with jax.default_matmul_precision("highest"):
        return jax.lax.fori_loop(
            0, w["w_gate"].shape[0], one, jnp.zeros_like(n))


@jax.jit
def embed(table, tokens):
    return table[tokens].astype(F32)


@partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, lm_head, *, eps):
    """(batch, seq, dim) -> logits over the vocabulary: the untied head
    ``(dim, vocab)``."""
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm.astype(F32), eps) @ lm_head.astype(F32)


def layer_weights(params, i: int) -> dict:
    """Layer ``i``'s weights from the program's parameter tree, by name."""
    blk = params[f"layer_{i}"]
    attn, moe = blk["attn"], blk["moe"]
    return {
        "input_layernorm": blk["attn_norm"],
        "post_attention_layernorm": blk["ffn_norm"],
        "keys": {"wk": attn["wk"]["base"]["kernel"],
                 "wv": attn["wv"]["base"]["kernel"]},
        "attn": {"wq": attn["wq"]["base"]["kernel"],
                 "wo": attn["wo"]["base"]["kernel"]},
        "router": moe["router"],
        "experts": {"w_gate": moe["w_gate"], "w_up": moe["w_up"],
                    "w_down": moe["w_down"]},
    }


def _blocks(rows: int, size: int):
    return [(start, min(start + size, rows)) for start in range(0, rows, size)]


def _joined(parts, item: int):
    return jnp.concatenate([p[item] for p in parts])


def block(x, w, i: int, follow=None, *, heads, kv_heads, theta, eps, window,
          layout, top_k, experts_first, faults=()):
    """Layer ``i`` on ``x (seq, dim)``: ``(h', own choice of experts,
    slack)``."""
    seq = x.shape[0]
    banded = bool(layout[i])
    rotary = None
    if banded or "rope_on_full" in faults:
        rotary = "pairs" if "rope_pairs" in faults else "halves"
    if banded and "no_window" in faults and i == min(
            j for j, kind in enumerate(layout) if kind):
        banded = False
    n1 = normed(x, w["input_layernorm"], eps=eps)

    def routed(n):
        return [route(n[start:stop], w["router"],
                      None if follow is None else follow[start:stop],
                      top_k=top_k)
                for start, stop in _blocks(seq, FFN_BLOCK)]

    k, v = keys_values(n1, w["keys"], kv_heads=kv_heads, theta=theta,
                       rotary=rotary)
    padded = jnp.pad(n1, ((0, -seq % QUERY_BLOCK), (0, 0)))
    a = x + jnp.concatenate([
        attend(padded[start:stop], start, k, v, w["attn"], heads=heads,
               theta=theta, rotary=rotary, window=window if banded else None)
        for start, stop in _blocks(padded.shape[0], QUERY_BLOCK)])[:seq]
    n2 = normed(a, w["post_attention_layernorm"], eps=eps)
    # on the attention's input: nothing of it waits for the attention
    routes = routed(n2 if "route_on_n2" in faults else n1)
    kept, experts = _joined(routes, 0), _joined(routes, 1)
    if "lost_expert" in faults:
        kept = kept.at[:, -1].set(0.0)
    ffn = jnp.concatenate([
        experts_loop(n2[start:stop], kept[start:stop], experts[start:stop],
                     w["experts"], first=experts_first,
                     swiglu="swiglu" in faults, e4m3="experts_e4m3" in faults)
        for start, stop in _blocks(seq, FFN_BLOCK)])
    return a + ffn, _joined(routes, 2), _joined(routes, 3)


def hidden_states(params, tokens, *, n_layers, routing=None, follow=None,
                  slack=None, guaranteed=None, n_routed=None, n_held=None,
                  step_from_zero=None, faults=(), **sizes):
    """The residual stream after the last block, (batch 1, seq, dim),
    float32, before the last norm. ``routing``: a list that receives each
    layer's own choice of experts; ``follow``: the experts to use instead,
    one entry a layer; ``slack``: a list that receives each layer's slack.
    ``faults``: controls, names of what to get wrong (``FAULTS``:
    ``route_on_n2``: the router fed the post-attention norm's output;
    ``swiglu``: the experts' gate through silu; ``experts_e4m3``: the
    experts' matrices through an 8-bit float; ``lost_expert``: a token's
    last chosen one; ``no_window`` of the first window layer;
    ``rope_on_full``; ``rope_pairs``: GPT-J's pairs for the halves)."""
    # the check's; the weights' shapes say them
    del guaranteed, n_routed, n_held, step_from_zero
    unknown = set(faults) - set(FAULTS)
    if tokens.shape[0] != 1 or unknown:
        raise ValueError(
            f"the reference takes one sequence at a time, and no {unknown}")
    x = embed(params["embed"], tokens[0])
    for i in range(n_layers):
        x, own, off = block(
            x, layer_weights(params, i), i,
            None if follow is None else follow[i], faults=tuple(faults),
            **sizes)
        if routing is not None:
            routing.append(own)
        if slack is not None:
            slack.append(off)
    return x[None]


def logits(params, tokens, *, last: int = 0, routing=None, follow=None,
           slack=None, **sizes):
    """Logits (1, seq or last, vocab) of a full causal forward pass, the
    head ``HEAD_BLOCK`` positions at a time. ``last`` keeps only that many
    trailing positions."""
    x = hidden_states(
        params, tokens, routing=routing, follow=follow, slack=slack, **sizes)
    if last:
        x = x[:, -last:]
    return jnp.concatenate([
        head(x[:, start:stop], params["final_norm"], params["lm_head"],
             eps=sizes["eps"])
        for start, stop in _blocks(x.shape[1], HEAD_BLOCK)], axis=1)


def program_routing(sown, n_layers: int) -> list:
    """The program's sown ``ROUTING_COLLECTION`` in the form ``routing=``
    fills above: each layer's chosen experts, (tokens, top_k), over all the
    experts routed over, in layer order."""
    return [sown[f"layer_{i}"]["moe"]["experts"][0] for i in range(n_layers)]


def _refuse_what_is_not_here(config: dict) -> None:
    name = config["name"]
    for key, want in (("moe_primary_router_apply_softmax", True),
                      ("norm_topk_prob", True), ("rope_scaling", None),
                      ("tie_word_embeddings", False)):
        if config.get(key, want) != want:
            raise SystemExit(
                f"{name}: this reference has no {key}={config[key]!r}")
    layers = config["num_hidden_layers"]
    layout = config["sliding_window_layout"][:layers]
    if config["rope_layout"][:layers] != layout or len(layout) != layers:
        raise SystemExit(
            f"{name}: rope_layout is not sliding_window_layout (a window "
            "layer rotates, a full layer does not), or shorter than the "
            "layers")
    if layout != [int(i % 4 != 0) for i in range(layers)]:
        raise SystemExit(
            f"{name}: sliding_window_layout is not a full layer and then "
            "three window layers a period, which is all the program's "
            "family builds")
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        raise SystemExit(f"{name}: query heads in whole groups a K/V head")


def _routed(config: dict) -> int:
    """The router's width: the published count where the file holds a
    share (``moe_num_primary_experts`` is then the experts held)."""
    return config.get("published", {}).get(
        "moe_num_primary_experts", config["moe_num_primary_experts"])


def sizes_of(config: dict) -> dict:
    """The keyword sizes above, from a configuration file's published keys,
    and ``guaranteed`` / ``n_held`` / ``n_routed`` / ``step_from_zero`` for
    the check (``drivers/serve_closed_loop_arch_window_routed.py``)."""
    _refuse_what_is_not_here(config)
    layers = config["num_hidden_layers"]
    return dict(
        n_layers=layers,
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        window=config["sliding_window_size"],
        layout=tuple(config["sliding_window_layout"][:layers]),
        top_k=config["moe_num_active_primary_experts"],
        experts_first=int(config.get("experts_first", 0)),
        n_routed=_routed(config), n_held=config["moe_num_primary_experts"],
        # the longest prompt the check feeds a token a step from position 0
        # (a toy's file lowers it)
        step_from_zero=int(config.get("check_step_from_zero", 256)),
        # what a slot row takes at the precision the configuration states
        # (bf16 keys and values): the check holds the program's live rows
        # to these counts
        guaranteed={
            "window_bytes_per_row": flops_stmoe.window_bytes_per_row(config),
            "kv_bytes_per_token": flops_stmoe.kv_bytes_per_token(config)},
    )


def llm_arguments(config: dict) -> dict:
    """A configuration file's published keys as ``LLMConfig`` arguments: the
    family and its model arguments
    (``ray_tpu.models.smallthinker.SmallThinkerConfig``). The experts held
    are always given as a range, the whole of it too: the program then
    keeps each row's last choice of experts, which this kind's check
    follows (``llm/engine.py``)."""
    _refuse_what_is_not_here(config)
    first, held = (int(config.get("experts_first", 0)),
                   config["moe_num_primary_experts"])
    return dict(
        model_family="smallthinker",
        model_kwargs=dict(
            vocab_size=config["vocab_size"],
            dim=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            sliding_window=config["sliding_window_size"],
            layer_period=4,
            moe_intermediate=config["moe_ffn_hidden_size"],
            n_experts=_routed(config),
            experts_per_token=config["moe_num_active_primary_experts"],
            norm_topk_prob=bool(config["norm_topk_prob"]),
            experts_held=(first, first + held),
            rope_theta=float(config["rope_theta"]),
            norm_eps=config["rms_norm_eps"],
        ),
    )
