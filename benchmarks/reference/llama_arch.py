"""The plain reference for the Llama/Mistral dense architecture.

Follows the published description (Mistral-7B-v0.3 ``modeling_mistral.py``:
pre-norm residual blocks, RMSNorm, rotary embeddings in the rotate-half
form, grouped-query causal attention, SwiGLU, untied output head), in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")`` (on
a TPU a float32 matmul otherwise runs in bf16 passes). No kernel, no cache,
no batching tricks, and no import from ``ray_tpu.models`` or ``ray_tpu.ops``.

Departures from the description: none in the mathematics. Weights arrive in
the program's own tree (a dict of arrays, bf16) and are cast to float32 one
layer at a time, so the reference fits beside the system it checks.

The only thing it knows of the program is the *names* in its parameter
tree, in ``layer_weights`` and ``top_weights``: per-layer dicts
(``layer_<i>``, serving) or one stacked dict (``layers/block``, the scanned
training form).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32


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


def attention(x, w, n_heads, n_kv_heads, theta):
    b, s, dim = x.shape
    d = dim // n_heads
    q = (x @ w["wq"]).reshape(b, s, n_heads, d)
    k = (x @ w["wk"]).reshape(b, s, n_kv_heads, d)
    v = (x @ w["wv"]).reshape(b, s, n_kv_heads, d)
    positions = jnp.arange(s)
    q, k = rope(q, positions, theta), rope(k, positions, theta)
    group = n_heads // n_kv_heads
    q = q.reshape(b, s, n_kv_heads, group, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k) / math.sqrt(d)
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v).reshape(b, s, dim)
    return out @ w["wo"]


def mlp(x, w):
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


@partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "theta", "eps"))
def block(x, w, *, n_heads, n_kv_heads, theta, eps):
    w = jax.tree.map(lambda a: a.astype(F32), w)
    with jax.default_matmul_precision("highest"):
        h = x + attention(
            rmsnorm(x, w["attn_norm"], eps), w, n_heads, n_kv_heads, theta)
        return h + mlp(rmsnorm(h, w["mlp_norm"], eps), w)


@jax.jit
def embed(table, tokens):
    return table[tokens].astype(F32)


@partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return rmsnorm(x, final_norm.astype(F32), eps) @ lm_head.astype(F32)


@jax.jit
def _take(stacked, i):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), stacked)


def layer_weights(params, i: int) -> dict:
    """Layer ``i``'s weights from the program's parameter tree, by name."""
    if "layers" in params:  # scanned: one stacked dict, layer on axis 0
        blk = _take(params["layers"]["block"], jnp.asarray(i, jnp.int32))
    else:
        blk = params[f"layer_{i}"]
    attn, ff = blk["attn"], blk["mlp"]
    return {
        "attn_norm": blk["attn_norm"], "mlp_norm": blk["mlp_norm"],
        "wq": attn["wq"]["base"]["kernel"], "wk": attn["wk"]["base"]["kernel"],
        "wv": attn["wv"]["base"]["kernel"], "wo": attn["wo"]["base"]["kernel"],
        "w_gate": ff["w_gate"]["kernel"], "w_up": ff["w_up"]["kernel"],
        "w_down": ff["w_down"]["kernel"],
    }


def hidden_states(params, tokens, *, n_layers, n_heads, n_kv_heads, theta, eps):
    """Final-block output (batch, seq, dim), float32, before the last norm."""
    x = embed(params["embed"], tokens)
    for i in range(n_layers):
        x = block(x, layer_weights(params, i), n_heads=n_heads,
                  n_kv_heads=n_kv_heads, theta=theta, eps=eps)
    return x


def logits(params, tokens, *, last: int = 0, **sizes):
    """Logits (batch, seq or last, vocab) of a full causal forward pass.
    ``last`` keeps only that many trailing positions: the head over a long
    prompt's every position is memory nothing reads."""
    x = hidden_states(params, tokens, **sizes)
    if last:
        x = x[:, -last:]
    return head(x, params["final_norm"], params["lm_head"], eps=sizes["eps"])


def next_token_loss(params, tokens, **sizes):
    """Mean cross-entropy of position t's logits against token t+1."""
    lg = logits(params, tokens, **sizes)[:, :-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -picked.mean()


def sizes_of(config: dict) -> dict:
    """The keyword sizes above, from a configuration file's published keys."""
    return dict(
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]),
    )
