"""The plain reference for the Motif architecture (``model_type`` Motif) at
the settings Motif-3-Beta publishes: grouped differential latent attention
(GDLA) on window and full layers, a residual of four streams mixed by
Sinkhorn-normalised maps around every sub-layer (mHC), PolyNorm in every
feed-forward, leading dense layers and then routed experts beside a shared
one.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
the published form everywhere: no cache, no ring (a window layer is a
banded mask), no absorbed form, no kernel, the Sinkhorn iterations a loop.
Names in ``code`` are the published keys.

- Residual: ``n = mhc_expansion_rate`` streams of ``hidden_size``. The
  embedding row is copied into every stream; the final ``rmsnorm`` and the
  untied head read the streams' sum. Each of a layer's two sub-layers ``F``
  (attention, feed-forward) has maps of its own (mHC, DeepSeek-AI 2025)::

      x~     = rmsnorm(vec(X))                  (n d values, no weight)
      H_pre  = sigmoid(a_pre (x~ Phi_pre) + b_pre)          (n)
      H_post = 2 sigmoid(a_post (x~ Phi_post) + b_post)     (n)
      H_res  = SK(exp(a_res mat(x~ Phi_res) + b_res))       (n x n)
      u = H_pre X;  y = F(rmsnorm_w(u))
      X' = clip(H_res X + H_post^T y, -hidden_clamp, hidden_clamp)

  ``SK``: ``mhc_sinkhorn_iters`` times rows over row sums, then columns
  over column sums.
- ``GDLA(h)``: ``c_q = rmsnorm(h W_dq)``, ``q = c_q W_uq`` ->
  ``num_attention_heads`` x (nope | ``qk_rope_head_dim``), nope =
  ``head_dim - qk_rope_head_dim``; ``[c_raw | k_r] = h W_dkv``, ``c =
  rmsnorm(c_raw)`` (``kv_lora_rank``); rotate-half RoPE (``rope_theta``, no
  scaling: ``apply_yarn_scaling`` false) on q's rope part and on ``k_r``.
  ``G = num_key_value_heads`` groups, each ``S`` signal heads and one of
  the ``num_noise_heads`` noise heads (head ``g (S + 1) + j``, noise ``j =
  S``): ``[k_nope_g | v_g] = c W_ukv,g``, ``k_g = [k_nope_g | k_r]``,
  ``A(q) = softmax(q k_g^T / sqrt(head_dim) + mask) v_g``, the mask causal
  and in a window layer (``i % sliding_window_period !=
  sliding_window_period - 1``) also ``j > i - sliding_window``;
  ``lambda = sigmoid(h w_lambda)`` a token a signal head; ``o_g,j =
  A(s_g,j) - lambda_g,j A(n_g)`` (``diff_v2``); ``out = (o * sigmoid(h
  W_gate)) W_o`` (``elementwise_attn_output_gate``).
- ``FFN(h) = (P(h W_gate) * (h W_up)) W_down``, ``P(z) =
  polynorm_output_scale (w1 N(z^3) + w2 N(z^2) + w3 N(z) + clip(b,
  +-polynorm_bias_clamp))``, ``N(z) = z / sqrt(mean(z^2) + eps)`` over the
  last axis. The first ``n_dense_first_layers`` layers one FFN of
  ``intermediate_size``; later ones ``Shared(h) + sum_{j in top} w_j
  E_j(h)``: ``s = sigmoid(h W_r)``, the ``experts_top_k`` largest, ``w =
  route_scale s_j / sum s`` (``route_norm``; no selection bias).

**The share**, as ``solar_open2_arch``: the configuration may hold
``experts_first .. experts_first + num_experts - 1`` of
``published.num_experts``; the router keeps its width, the weights are
normalised over the experts chosen wherever they live, and what the
experts held elsewhere would add is left out (``experts_loop``). A sliced
vocabulary is a smaller vocabulary.

Memory, because the check runs beside 12 GB of resident state at 8192
positions: a layer is not one program. The maps and the mixes run over the
whole sequence (a few hundred MB), attention a block of ``QUERY_BLOCK``
queries at a time against the whole sequence's keys, a group at a time (80
heads x 8192 x 8192 float32 scores would be 21 GB), the feed-forward
``FFN_BLOCK`` positions at a time, the routed experts one expert at a
time, the weights cast to float32 a program at a time.

What it knows of the program, all of it *names*: those in its parameter
tree (``layer_weights``); in ``llm_arguments`` those of ``LLMConfig``'s and
``MotifConfig``'s arguments; and what ``drivers/serve_arch_common.py``
reads of a running program. No import from ``ray_tpu``, and nothing under
``ray_tpu/`` imports this.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from ..harness import flops_gdla

F32 = jnp.float32
QUERY_BLOCK = 512
FFN_BLOCK = 1024

TRACE_SCOPES = ("moe.route", "moe.experts", "moe.shared",
                "gdla.absorb", "gdla.diff", "mhc.maps", "mhc.mix")
# those of them that are no part of the expert layers (kept apart in a
# traced run's result, as ``solar_open2_arch`` says)
ATTENTION_SCOPES = ("gdla.absorb", "gdla.diff", "mhc.maps", "mhc.mix")
TRACE_KERNELS = ("moe_experts", "latent_decode_attention", "kv_row_write")
PROGRAM_COUNTERS = ("moe", "kv")
ROUTING_COLLECTION = "moe_routing"


def rmsnorm(x, weight, eps):
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(variance + eps)
    return y if weight is None else y * weight


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, positions, theta):
    """x: (seq, heads, d); positions: (seq,)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = positions.astype(F32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([angles, angles], axis=-1)
    return x * jnp.cos(emb)[:, None, :] + rotate_half(x) * jnp.sin(emb)[:, None, :]


def sinkhorn(m, iters: int):
    """(..., n, n) positive: rows over row sums, then columns over column
    sums, ``iters`` times."""
    for _ in range(iters):
        m = m / jnp.sum(m, axis=-1, keepdims=True)
        m = m / jnp.sum(m, axis=-2, keepdims=True)
    return m


def _f32(w: dict) -> dict:
    return jax.tree.map(lambda a: a.astype(F32), w)


@partial(jax.jit, static_argnames=("iters", "eps"))
def mix_in(x, w, *, iters, eps):
    """``x (seq, n, d)`` -> the sub-layer's input ``rmsnorm_w(H_pre X)
    (seq, d)`` and its ``H_post (seq, n)`` and ``H_res (seq, n, n)``."""
    w = _f32(w)
    s, n, d = x.shape
    with jax.default_matmul_precision("highest"):
        proj = rmsnorm(x.reshape(s, n * d), None, eps) @ w["phi"]
        a, b = w["alpha"], w["bias"]
        pre = jax.nn.sigmoid(a[0] * proj[:, :n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(a[1] * proj[:, n:2 * n] + b[n:2 * n])
        res = sinkhorn(jnp.exp(
            a[2] * proj[:, 2 * n:].reshape(s, n, n) + b[2 * n:].reshape(n, n)),
            iters)
        u = jnp.einsum("sn,snd->sd", pre, x)
        return rmsnorm(u, w["norm"], eps), post, res


@partial(jax.jit, static_argnames=("clamp",), donate_argnums=(0,))
def mix_out(x, y, post, res, *, clamp):
    with jax.default_matmul_precision("highest"):
        return jnp.clip(
            jnp.einsum("snm,smd->snd", res, x) + post[:, :, None] * y[:, None, :],
            -clamp, clamp)


@partial(jax.jit, static_argnames=("groups", "nope", "theta", "eps", "normed"))
def latent_keys(h, w, *, groups, nope, theta, eps, normed=True):
    """The whole sequence's keys and values a group: ``k (seq, groups,
    nope + rope)``, ``v (seq, groups, dv)``. ``normed`` false leaves the
    latent norm out (a control)."""
    w = _f32(w)
    rank = w["kv_norm"].shape[0]
    with jax.default_matmul_precision("highest"):
        kva = h @ w["wkv_a"]
        c = rmsnorm(kva[:, :rank], w["kv_norm"], eps) if normed else kva[:, :rank]
        k_r = rope(kva[:, None, rank:], jnp.arange(h.shape[0]), theta)
        kv = jnp.einsum("sr,rgd->sgd", c, w["wkv_b"])
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r, (h.shape[0], groups, k_r.shape[-1]))],
            axis=-1)
        return k, kv[..., nope:]


@partial(jax.jit, static_argnames=("heads", "groups", "nope", "theta", "eps",
                                   "window", "noise"))
def attend(h, start, k, v, w, *, heads, groups, nope, theta, eps, window, noise):
    """One block of queries ``h (block, d)`` at positions ``start ..``
    against the whole sequence's ``k`` / ``v``, a group at a time: the
    attention sub-layer's output ``(block, d)``. ``noise`` false leaves the
    noise branch out (``lambda`` 0: a control)."""
    w = _f32(w)
    block = h.shape[0]
    signal = heads // groups - 1
    positions = start + jnp.arange(block)
    with jax.default_matmul_precision("highest"):
        c_q = rmsnorm(h @ w["wq_a"], w["q_norm"], eps)
        q = (c_q @ w["wq_b"]).reshape(block, heads, -1)
        q = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], positions, theta)], axis=-1)
        q = q.reshape(block, groups, signal + 1, -1)
        k_pos = jnp.arange(k.shape[0])[None, :]
        visible = k_pos <= positions[:, None]
        if window is not None:
            visible &= k_pos > positions[:, None] - window
        scale = 1.0 / math.sqrt(q.shape[-1])

        def one(group):
            q_g, k_g, v_g = group  # (block, S + 1, d), (seq, d), (seq, dv)
            scores = jnp.einsum("qjd,kd->jqk", q_g, k_g) * scale
            probs = jax.nn.softmax(
                jnp.where(visible[None], scores, -jnp.inf), axis=-1)
            return jnp.einsum("jqk,kd->qjd", probs, v_g)

        attended = jax.lax.map(one, (
            jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
        attended = jnp.moveaxis(attended, 0, 1)  # (block, groups, S + 1, dv)
        lam = jax.nn.sigmoid(h @ w["w_lambda"]).reshape(block, groups, signal, 1)
        if not noise:
            lam = jnp.zeros_like(lam)
        out = attended[:, :, :signal] - lam * attended[:, :, signal:]
        out = out.reshape(block, -1) * jax.nn.sigmoid(h @ w["w_gate"])
        return out @ w["wo"]


def poly_norm(z, poly, scale, clamp, eps, cubic=True):
    """``poly (..., 4)``: ``w1, w2, w3, b``. ``cubic`` false drops the
    cubic term (a control)."""
    def normed(t):
        return t * jax.lax.rsqrt(jnp.mean(t * t, axis=-1, keepdims=True) + eps)

    terms = poly[..., 1:2] * normed(z ** 2) + poly[..., 2:3] * normed(z)
    if cubic:
        terms = terms + poly[..., 0:1] * normed(z ** 3)
    return scale * (terms + jnp.clip(poly[..., 3:4], -clamp, clamp))


def ffn(h, gate, up, down, poly, *, scale, clamp, eps, cubic=True):
    return (poly_norm(h @ gate, poly, scale, clamp, eps, cubic) * (h @ up)) @ down


@partial(jax.jit, static_argnames=("scale", "clamp", "eps", "cubic"))
def dense_ffn(h, w, *, scale, clamp, eps, cubic=True):
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        return ffn(h, w["gate"], w["up"], w["down"], w["poly"],
                   scale=scale, clamp=clamp, eps=eps, cubic=cubic)


def route(h, router, top_k, route_norm, route_scale, follow=None):
    """(tokens, dim) -> kept weights and their experts, (tokens, top_k)
    each, over all the experts routed over; this reference's own choice;
    and ``slack`` (tokens,), zero without ``follow``
    (``deepseek_v3_arch.route``, whose router this is without a bias)."""
    scores = jax.nn.sigmoid(h @ router)
    kth, own = jax.lax.top_k(scores, top_k)
    experts, slack = own, jnp.zeros(h.shape[0], F32)
    if follow is not None:
        experts = follow
        followed = jnp.take_along_axis(scores, follow, axis=-1)
        slack = (kth[:, -1] - jnp.min(followed, axis=-1)) / jnp.abs(kth[:, -1])
    kept = jnp.take_along_axis(scores, experts, axis=-1)
    if route_norm:
        kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20)
    return kept * route_scale, experts, own, slack


def experts_loop(h, kept, experts, w_gate, w_up, w_down, poly, first, **act):
    """Every token through every expert *held*, one expert at a time,
    weighted by what the token kept for it (``solar_open2_arch``)."""
    def one(e, y):
        gate = jax.lax.dynamic_index_in_dim(w_gate, e, 0, False).astype(F32)
        up = jax.lax.dynamic_index_in_dim(w_up, e, 0, False).astype(F32)
        down = jax.lax.dynamic_index_in_dim(w_down, e, 0, False).astype(F32)
        coeff = jax.lax.dynamic_index_in_dim(poly, e, 0, False).astype(F32)
        weight = jnp.sum(jnp.where(experts == first + e, kept, 0.0), axis=-1)
        return y + weight[:, None] * ffn(h, gate, up, down, coeff, **act)

    return jax.lax.fori_loop(0, w_gate.shape[0], one, jnp.zeros_like(h))


@partial(jax.jit, static_argnames=(
    "top_k", "route_norm", "route_scale", "experts_first", "scale", "clamp",
    "eps", "cubic", "shared", "lost"))
def routed_ffn(h, w, follow=None, *, top_k, route_norm, route_scale,
               experts_first, scale, clamp, eps, cubic=True, shared=True,
               lost=False):
    """``shared`` false leaves the shared expert out, ``lost`` a token's
    last chosen expert (controls)."""
    big = ("w_gate", "w_up", "w_down", "w_poly")
    small = _f32({k: v for k, v in w.items() if k not in big})
    act = dict(scale=scale, clamp=clamp, eps=eps, cubic=cubic)
    with jax.default_matmul_precision("highest"):
        kept, experts, own, slack = route(
            h, small["router"], top_k, route_norm, route_scale, follow)
        if lost:
            kept = kept.at[:, -1].set(0.0)
        y = experts_loop(h, kept, experts, w["w_gate"], w["w_up"], w["w_down"],
                         w["w_poly"], experts_first, **act)
        if shared:
            y = y + ffn(h, small["gate"], small["up"], small["down"],
                        small["poly"], **act)
        return y, own, slack


@partial(jax.jit, static_argnames=("streams",))
def embed(table, tokens, *, streams):
    x = table[tokens].astype(F32)
    return jnp.broadcast_to(x[:, None, :], (x.shape[0], streams, x.shape[1]))


@partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, lm_head, *, eps):
    """(batch, seq, dim) summed streams -> logits over the vocabulary held."""
    with jax.default_matmul_precision("highest"):
        return rmsnorm(x, final_norm.astype(F32), eps) @ lm_head.astype(F32)


def _mhc(blk, name) -> dict:
    maps = blk[f"{name}_mhc"]
    return {"phi": maps["phi"], "alpha": maps["alpha"], "bias": maps["bias"],
            "norm": blk[f"{name}_norm"]}


def _glu(group, prefix="") -> dict:
    return {prefix + "gate": group["w_gate"]["kernel"],
            prefix + "up": group["w_up"]["kernel"],
            prefix + "down": group["w_down"]["kernel"],
            prefix + "poly": group["poly"]}


def layer_weights(params, i: int) -> dict:
    """Layer ``i``'s weights from the program's parameter tree, by name, in
    three groups and the two sub-layers' maps; a routed layer is one with a
    ``moe`` group."""
    blk = params[f"layer_{i}"]
    attn = blk["attn"]
    w = {
        "attn_mhc": _mhc(blk, "attn"), "ffn_mhc": _mhc(blk, "ffn"),
        "keys": {"wkv_a": attn["wkv_a"]["kernel"], "kv_norm": attn["kv_norm"],
                 "wkv_b": attn["wkv_b"]},
        "attn": {"wq_a": attn["wq_a"]["kernel"], "q_norm": attn["q_norm"],
                 "wq_b": attn["wq_b"]["kernel"],
                 "w_lambda": attn["w_lambda"]["kernel"],
                 "w_gate": attn["w_gate"]["kernel"], "wo": attn["wo"]["kernel"]},
    }
    if "moe" in blk:
        moe = blk["moe"]
        w["ffn"] = dict(
            _glu(blk["shared"]), router=moe["router"], w_gate=moe["w_gate"],
            w_up=moe["w_up"], w_down=moe["w_down"], w_poly=moe["poly"])
    else:
        w["ffn"] = _glu(blk["mlp"])
    return w


def _blocks(rows: int, size: int):
    return [(start, min(start + size, rows)) for start in range(0, rows, size)]


def hidden_states(params, tokens, *, n_layers, routing=None, follow=None,
                  slack=None, guaranteed=None, n_routed=None, n_held=None,
                  step_from_zero=None, heads, groups, nope, theta, eps, window, period, streams,
                  iters, hidden_clamp, dense_layers, top_k, route_norm,
                  route_scale, experts_first, poly_scale, poly_clamp,
                  faults=()):
    """The streams' sum after the last block, (batch 1, seq, dim), float32,
    before the last norm. ``routing``: a list that receives each routed
    layer's own choice of experts; ``follow``: the experts to use instead,
    one entry a routed layer; ``slack``: a list that receives each routed
    layer's slack. ``faults``: controls, names of what to leave out
    (``no_noise``, ``no_window``: of the first window layer, ``no_sinkhorn``,
    ``no_cubic``, ``no_shared``, ``no_route_scale``, ``no_latent_norm``,
    ``lost_expert``: a token's last chosen one)."""
    # the check's; the weights' shapes say them
    del guaranteed, n_routed, n_held, step_from_zero
    if tokens.shape[0] != 1:
        raise ValueError("the reference takes one sequence at a time")
    x = embed(params["embed"], tokens[0], streams=streams)
    seq = x.shape[0]
    given = iter(follow) if follow is not None else None
    act = dict(scale=poly_scale, clamp=poly_clamp, eps=eps,
               cubic="no_cubic" not in faults)
    iters = 0 if "no_sinkhorn" in faults else iters
    for i in range(n_layers):
        w = layer_weights(params, i)
        banded = i % period != period - 1
        if banded and "no_window" in faults and i == 0:
            banded = False
        h, post, res = mix_in(x, w["attn_mhc"], iters=iters, eps=eps)
        k, v = latent_keys(h, w["keys"], groups=groups, nope=nope, theta=theta,
                           eps=eps, normed="no_latent_norm" not in faults)
        padded = jnp.pad(h, ((0, -seq % QUERY_BLOCK), (0, 0)))
        y = jnp.concatenate([
            attend(padded[start:stop], start, k, v, w["attn"], heads=heads,
                   groups=groups, nope=nope, theta=theta, eps=eps,
                   window=window if banded else None,
                   noise="no_noise" not in faults)
            for start, stop in _blocks(padded.shape[0], QUERY_BLOCK)])[:seq]
        x = mix_out(x, y, post, res, clamp=hidden_clamp)
        h, post, res = mix_in(x, w["ffn_mhc"], iters=iters, eps=eps)
        if i < dense_layers:
            y = jnp.concatenate([
                dense_ffn(h[start:stop], w["ffn"], **act)
                for start, stop in _blocks(seq, FFN_BLOCK)])
        else:
            chosen = next(given) if given is not None else None
            parts = [
                routed_ffn(h[start:stop], w["ffn"],
                           None if chosen is None else chosen[start:stop],
                           top_k=top_k, route_norm=route_norm,
                           route_scale=(1.0 if "no_route_scale" in faults
                                        else route_scale),
                           experts_first=experts_first,
                           shared="no_shared" not in faults,
                           lost="lost_expert" in faults, **act)
                for start, stop in _blocks(seq, FFN_BLOCK)]
            y = jnp.concatenate([p[0] for p in parts])
            if routing is not None:
                routing.append(jnp.concatenate([p[1] for p in parts]))
            if slack is not None:
                slack.append(jnp.concatenate([p[2] for p in parts]))
        x = mix_out(x, y, post, res, clamp=hidden_clamp)
    return jnp.sum(x, axis=1)[None]


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
    fills above: each routed layer's chosen experts, (tokens, top_k), over
    all the experts routed over, in layer order (a dense layer sows
    nothing and has no entry)."""
    return [sown[f"layer_{i}"]["moe"]["experts"][0] for i in range(n_layers)
            if f"layer_{i}" in sown]


def _refuse_what_is_not_here(config: dict) -> None:
    name = config["name"]
    for key, want in (("attention_cls", "gdla"), ("diff_v2", True),
                      ("elementwise_attn_output_gate", True),
                      ("headwise_attn_output_gate", False),
                      ("hidden_act", "poly_norm"), ("mhc_enabled", True),
                      ("interleave_moe_layer_step", 1), ("mscale", 1),
                      ("score_func", "sigmoid"), ("score_before_experts", False),
                      ("sliding_window_pattern", "interleave"),
                      ("use_sliding_window", True),
                      ("tie_word_embeddings", False),
                      ("num_nextn_predict_layers", 0),
                      ("polynorm_output_scale_per_layer", {})):
        if config.get(key, want) != want:
            raise SystemExit(
                f"{name}: this reference has no {key}={config[key]!r}")
    if config["rope_scaling"].get("apply_yarn_scaling"):
        raise SystemExit(f"{name}: this reference has no YaRN scaling")
    if config["swa_rope_theta"] != config["rope_theta"]:
        raise SystemExit(f"{name}: one rope_theta for window and full layers")
    if config["num_noise_heads"] != config["num_key_value_heads"] or (
            config["num_attention_heads"] % config["num_key_value_heads"]):
        raise SystemExit(f"{name}: one noise head a KV group")


def _routed(config: dict) -> int:
    """The router's width: the published count where the file holds a
    share (``num_experts`` is then the experts held)."""
    return config.get("published", {}).get("num_experts", config["num_experts"])


def sizes_of(config: dict) -> dict:
    """The keyword sizes above, from a configuration file's published keys,
    and ``guaranteed`` / ``n_held`` / ``n_routed`` / ``step_from_zero`` for the check
    (``drivers/serve_closed_loop_arch_window_routed.py``)."""
    _refuse_what_is_not_here(config)
    return dict(
        n_layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        groups=config["num_key_value_heads"],
        nope=config["head_dim"] - config["qk_rope_head_dim"],
        theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        window=config["sliding_window"],
        period=config["sliding_window_period"],
        streams=config["mhc_expansion_rate"],
        iters=config["mhc_sinkhorn_iters"],
        hidden_clamp=float(config["hidden_clamp"]),
        dense_layers=config["n_dense_first_layers"],
        top_k=config["experts_top_k"],
        route_norm=bool(config["route_norm"]),
        route_scale=float(config["route_scale"]),
        experts_first=int(config.get("experts_first", 0)),
        poly_scale=float(config["polynorm_output_scale"]),
        poly_clamp=float(config["polynorm_bias_clamp"]),
        n_routed=_routed(config), n_held=config["num_experts"],
        # the longest prompt the check feeds a token a step from position 0
        # (a toy's file lowers it)
        step_from_zero=int(config.get("check_step_from_zero", 256)),
        # what a slot row takes at the precision the configuration states
        # (bf16 latent and rotary rows): the check holds the program's live
        # rows to these counts
        guaranteed={
            "window_bytes_per_row": flops_gdla.window_bytes_per_row(config),
            "kv_bytes_per_token": flops_gdla.kv_bytes_per_token(config)},
    )


def llm_arguments(config: dict) -> dict:
    """A configuration file's published keys as ``LLMConfig`` arguments: the
    family and its model arguments (``ray_tpu.models.motif.MotifConfig``)."""
    _refuse_what_is_not_here(config)
    first, held, routed = (int(config.get("experts_first", 0)),
                           config["num_experts"], _routed(config))
    return dict(
        model_family="motif",
        model_kwargs=dict(
            vocab_size=config["vocab_size"],
            dim=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            q_lora_rank=config["q_lora_rank"],
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["head_dim"] - config["qk_rope_head_dim"],
            qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            sliding_window=config["sliding_window"],
            sliding_window_period=config["sliding_window_period"],
            mhc_streams=config["mhc_expansion_rate"],
            mhc_sinkhorn_iters=config["mhc_sinkhorn_iters"],
            hidden_clamp=float(config["hidden_clamp"]),
            intermediate=config["intermediate_size"],
            moe_intermediate=config["moe_intermediate_size"],
            n_experts=routed,
            experts_per_token=config["experts_top_k"],
            n_shared_experts=config["num_shared_experts"],
            first_dense_layers=config["n_dense_first_layers"],
            norm_topk_prob=bool(config["route_norm"]),
            routed_scale=float(config["route_scale"]),
            polynorm_scale=config["polynorm_output_scale"],
            polynorm_bias_clamp=config["polynorm_bias_clamp"],
            experts_held=None if held == routed else (first, first + held),
            rope_theta=float(config["rope_theta"]),
            norm_eps=config["rms_norm_eps"],
        ),
    )
