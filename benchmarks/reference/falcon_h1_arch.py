"""The plain reference for the Falcon-H1 architecture (``model_type``
falcon_h1) at the settings Falcon-H1-34B-Instruct publishes: in every block
a grouped-query attention and a Mamba-2 mixer side by side on one normed
input, then a SwiGLU, a muP multiplier on every branch.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
the recurrence **one position at a time** (a ``lax.scan`` over positions:
no chunked form, so it shares no algebra with the program's prefill), no
kernel, no cache, no batching. Names in ``code`` are the published keys.

- ``x_0 = E[token] * embedding_multiplier``. Block: ``h =
  rmsnorm(x, in_norm)``; ``x = x + Attn(h) + Mixer(h)``; ``x = x +
  MLP(rmsnorm(x, ff_norm))``. Final ``rmsnorm``; untied head; ``logits = x
  W_head * lm_head_multiplier``. ``rms_norm_eps`` everywhere.
- ``Attn(h)``: ``h' = h * attention_in_multiplier``; ``q = h' W_q``
  (heads x ``head_dim``), ``k = (h' W_k) * key_multiplier``, ``v = h'
  W_v``; rotate-half RoPE over the whole ``head_dim`` on q and k; causal
  ``softmax(q k^T / sqrt(head_dim)) v``, ``num_attention_heads /
  num_key_value_heads`` query heads a KV head; ``W_o``; ``*
  attention_out_multiplier``. No bias.
- ``Mixer(h)``: ``u = (h * ssm_in_multiplier) W_in``, columns ``[z | x | B
  | C | dt]`` = ``[mamba_d_ssm | mamba_d_ssm | n_groups x d_state |
  n_groups x d_state | n_heads]``, each group of columns times its entry of
  ``ssm_multipliers`` (in that order). ``[x | B | C] = silu(conv([x | B |
  C]))``, a causal depthwise convolution of ``mamba_d_conv`` taps a channel
  with bias (zeros before the first position). ``dt = softplus(dt +
  dt_bias)`` a head; ``A = -exp(A_log)`` a head. A head's state ``S``
  (``mamba_d_head`` x ``mamba_d_state``) is zero before the first token:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t (x_t (x) B_t)``; ``y_t = S_t C_t + D
  x_t``; ``n_heads / n_groups`` heads share a group's ``B`` and ``C``.
  ``y = rmsnorm_group(y * silu(z))`` (``mamba_rms_norm`` true,
  ``mamba_norm_before_gate`` false). ``W_out``; ``* ssm_out_multiplier``.
  No projection bias.
- ``MLP(g) = (silu(g W_gate * mlp_multipliers[0]) * g W_up) W_down *
  mlp_multipliers[1]``.

Assumed, where the published config does not say (the configuration file
lists the same): (1) the column order of ``W_in`` above, which for seeded
random weights is a fixed permutation; (2) the gated norm's mean square is
taken over each *group's* channels (``mamba_d_ssm / mamba_n_groups``), one
learned weight a channel, not over all of ``mamba_d_ssm``; (3) the state is
float32 (here everything is).

Memory, because the check runs beside 13 GB of resident state: weights
arrive in the program's tree (bf16) and are cast to float32 a matrix at a
time, the SwiGLU's three in column blocks (``MLP_BLOCK``), the head's in
``head``'s blocks; attention in blocks of ``QUERY_BLOCK`` queries.

What it knows of the program, all of it *names*: those in its parameter
tree (``layer_weights``); in ``llm_arguments`` those of ``LLMConfig``'s and
``FalconH1Config``'s arguments; and what ``drivers/serve_arch_common.py``
reads of a running program (``TRACE_SCOPES``, ``TRACE_KERNELS``,
``PROGRAM_COUNTERS``). No import from ``ray_tpu.models``, ``ray_tpu.ops``
or ``ray_tpu.parallel``, and nothing under ``ray_tpu/`` imports this.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from ..harness import flops_ssm

F32 = jnp.float32
QUERY_BLOCK = 512
MLP_BLOCK = 3072
VOCAB_BLOCK_MAX = 32768

# jax.named_scope names of the decode program (and of the prefill programs)
# whose device time a traced run keeps (harness/xplane_scopes.py), and the
# Pallas kernels of the decode program
TRACE_SCOPES = ("ssm.proj", "ssm.conv", "ssm.scan")
TRACE_KERNELS = ("decode_attention", "kv_row_write")
# groups of the replica's runtime_info() kept at both ends of the window
PROGRAM_COUNTERS = ("kv",)


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
    emb = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(emb)[None, :, None, :], jnp.sin(emb)[None, :, None, :]
    return x * cos + rotate_half(x) * sin


def attention(h, w, *, n_heads, n_kv_heads, head_dim, theta, attn_in,
              attn_out, key_mult):
    b, s, _ = h.shape
    positions = jnp.arange(s)
    h = h * attn_in
    q = (h @ w["wq"].astype(F32)).reshape(b, s, n_heads, head_dim)
    k = ((h @ w["wk"].astype(F32)) * key_mult).reshape(
        b, s, n_kv_heads, head_dim)
    v = (h @ w["wv"].astype(F32)).reshape(b, s, n_kv_heads, head_dim)
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
    return (out @ w["wo"].astype(F32)) * attn_out


def mixer(h, w, *, m_heads, m_head_dim, d_state, n_groups, d_conv, eps,
          ssm_in, ssm_out, ssm_mults):
    b, s, _ = h.shape
    d_ssm = m_heads * m_head_dim
    gn = n_groups * d_state
    mults = ssm_mults  # z, x, B, C, dt
    u = (h * ssm_in) @ w["in_proj"].astype(F32)
    z = u[..., :d_ssm] * mults[0]
    x = u[..., d_ssm:2 * d_ssm] * mults[1]
    b_in = u[..., 2 * d_ssm:2 * d_ssm + gn] * mults[2]
    c_in = u[..., 2 * d_ssm + gn:2 * d_ssm + 2 * gn] * mults[3]
    dt = u[..., 2 * d_ssm + 2 * gn:] * mults[4]

    # causal depthwise convolution over [x | B | C], zeros before position 0
    xbc = jnp.concatenate([x, b_in, c_in], axis=-1)
    taps = w["conv_weight"].astype(F32)  # (d_conv, channels); tap j reads t - (d_conv - 1) + j
    padded = jnp.pad(xbc, ((0, 0), (d_conv - 1, 0), (0, 0)))
    conv = w["conv_bias"].astype(F32) + sum(
        padded[:, j:j + s] * taps[j] for j in range(d_conv))
    xbc = jax.nn.silu(conv)
    x = xbc[..., :d_ssm].reshape(b, s, m_heads, m_head_dim)
    per = m_heads // n_groups
    # every head its group's B and C
    b_in = jnp.repeat(
        xbc[..., d_ssm:d_ssm + gn].reshape(b, s, n_groups, d_state), per, axis=2)
    c_in = jnp.repeat(
        xbc[..., d_ssm + gn:].reshape(b, s, n_groups, d_state), per, axis=2)
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(F32))  # (b, s, heads)
    a = -jnp.exp(w["A_log"].astype(F32))
    skip = w["D"].astype(F32)

    def position(state, inputs):
        x_t, b_t, c_t, dt_t = inputs  # (b, heads, p) (b, heads, n) x2 (b, heads)
        state = (
            state * jnp.exp(dt_t * a)[..., None, None]
            + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :])
        return state, jnp.sum(state * c_t[..., None, :], axis=-1)

    state0 = jnp.zeros((b, m_heads, m_head_dim, d_state), F32)
    _, y = jax.lax.scan(
        position, state0,
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, b_in, c_in, dt)))
    y = jnp.moveaxis(y, 0, 1)  # (b, s, heads, p)
    y = (y + skip[:, None] * x).reshape(b, s, d_ssm) * jax.nn.silu(z)
    # the mean square over each group's channels, one weight a channel
    y = rmsnorm(
        y.reshape(b, s, n_groups, d_ssm // n_groups), 1.0, eps
    ).reshape(b, s, d_ssm) * w["mixer_norm"].astype(F32)
    return (y @ w["out_proj"].astype(F32)) * ssm_out


def mlp(g, w, mults):
    """The SwiGLU in blocks of ``MLP_BLOCK`` of its columns where they
    divide so: a float32 copy of the three matrices at once is 1.3 GB."""
    width = w["w_gate"].shape[1]

    def part(gate, up, down):
        return (jax.nn.silu((g @ gate.astype(F32)) * mults[0])
                * (g @ up.astype(F32))) @ down.astype(F32)

    if width <= MLP_BLOCK or width % MLP_BLOCK:
        return part(w["w_gate"], w["w_up"], w["w_down"]) * mults[1]

    def block(y, i):
        cols = partial(
            jax.lax.dynamic_slice_in_dim, start_index=i * MLP_BLOCK,
            slice_size=MLP_BLOCK)
        return y + part(cols(w["w_gate"], axis=1), cols(w["w_up"], axis=1),
                        cols(w["w_down"], axis=0)), None

    y, _ = jax.lax.scan(
        block, jnp.zeros_like(g), jnp.arange(width // MLP_BLOCK))
    return y * mults[1]


_STATIC = ("n_heads", "n_kv_heads", "head_dim", "theta", "eps", "m_heads",
           "m_head_dim", "d_state", "n_groups", "d_conv", "attn_in",
           "attn_out", "key_mult", "ssm_in", "ssm_out", "ssm_mults",
           "mlp_mults")


@partial(jax.jit, static_argnames=_STATIC)
def block(x, w, *, n_heads, n_kv_heads, head_dim, theta, eps, m_heads,
          m_head_dim, d_state, n_groups, d_conv, attn_in, attn_out,
          key_mult, ssm_in, ssm_out, ssm_mults, mlp_mults):
    """One block on the float32 residual ``x (batch, seq, dim)``."""
    with jax.default_matmul_precision("highest"):
        h = rmsnorm(x, w["in_norm"].astype(F32), eps)
        x = x + attention(
            h, w, n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
            theta=theta, attn_in=attn_in, attn_out=attn_out,
            key_mult=key_mult)
        x = x + mixer(
            h, w, m_heads=m_heads, m_head_dim=m_head_dim, d_state=d_state,
            n_groups=n_groups, d_conv=d_conv, eps=eps, ssm_in=ssm_in,
            ssm_out=ssm_out, ssm_mults=ssm_mults)
        return x + mlp(rmsnorm(x, w["ff_norm"].astype(F32), eps), w, mlp_mults)


@partial(jax.jit, static_argnames=("multiplier",))
def embed(table, tokens, *, multiplier):
    return table[tokens].astype(F32) * multiplier


def _vocab_block(vocab: int) -> int:
    """The largest divisor of ``vocab`` that is a multiple of 128 and at
    most ``VOCAB_BLOCK_MAX``; the whole of a vocabulary that has none."""
    return next(
        (vocab // k for k in range(1, vocab // 128 + 1)
         if vocab % k == 0 and (vocab // k) % 128 == 0
         and vocab // k <= VOCAB_BLOCK_MAX),
        vocab)


@partial(jax.jit, static_argnames=("eps", "multiplier"))
def head(x, final_norm, lm_head, *, eps, multiplier=1.0):
    """(batch, seq, dim) -> logits, the head's columns in blocks: a float32
    copy of all 261120 at once would be 5.3 GB."""
    vocab = lm_head.shape[1]
    width = _vocab_block(vocab)
    with jax.default_matmul_precision("highest"):
        h = rmsnorm(x, final_norm.astype(F32), eps)
        if width == vocab:
            return (h @ lm_head.astype(F32)) * multiplier
        blocks = jax.lax.map(
            lambda i: h @ jax.lax.dynamic_slice_in_dim(
                lm_head, i * width, width, axis=1).astype(F32),
            jnp.arange(vocab // width))  # (blocks, batch, seq, width)
        return jnp.moveaxis(blocks, 0, -2).reshape(
            *h.shape[:-1], vocab) * multiplier


def layer_weights(params, i: int) -> dict:
    """Layer ``i``'s weights from the program's parameter tree, by name."""
    blk = params[f"layer_{i}"]
    attn, mix, ff = blk["attn"], blk["mixer"], blk["mlp"]
    return {
        "in_norm": blk["in_norm"], "ff_norm": blk["ff_norm"],
        "wq": attn["wq"]["kernel"], "wk": attn["wk"]["kernel"],
        "wv": attn["wv"]["kernel"], "wo": attn["wo"]["kernel"],
        "in_proj": mix["in_proj"]["kernel"], "conv_weight": mix["conv_weight"],
        "conv_bias": mix["conv_bias"], "dt_bias": mix["dt_bias"],
        "A_log": mix["A_log"], "D": mix["D"], "mixer_norm": mix["norm"],
        "out_proj": mix["out_proj"]["kernel"],
        "w_gate": ff["w_gate"]["kernel"], "w_up": ff["w_up"]["kernel"],
        "w_down": ff["w_down"]["kernel"],
    }


def hidden_states(params, tokens, *, n_layers, embedding_multiplier,
                  lm_head_multiplier=None, guaranteed=None, **sizes):
    """Final-block output (batch, seq, dim), float32, before the last norm."""
    del lm_head_multiplier, guaranteed  # the head's, the check's
    x = embed(params["embed"], tokens, multiplier=embedding_multiplier)
    for i in range(n_layers):
        x = block(x, layer_weights(params, i), **sizes)
    return x


def logits(params, tokens, *, last: int = 0, **sizes):
    """Logits (batch, seq or last, vocab) of a full causal forward pass.
    ``last`` keeps only that many trailing positions."""
    x = hidden_states(params, tokens, **sizes)
    if last:
        x = x[:, -last:]
    return head(x, params["final_norm"], params["lm_head"], eps=sizes["eps"],
                multiplier=sizes["lm_head_multiplier"])


def _refuse_what_is_not_here(config: dict) -> None:
    name = config["name"]
    for key, want in (("attention_bias", False), ("mamba_proj_bias", False),
                      ("mlp_bias", False), ("projectors_bias", False),
                      ("mamba_conv_bias", True), ("mamba_rms_norm", True),
                      ("mamba_norm_before_gate", False),
                      ("mamba_use_mlp", True), ("attn_layer_indices", None),
                      ("rope_scaling", None), ("hidden_act", "silu"),
                      ("tie_word_embeddings", False)):
        if config.get(key, want) != want:
            raise SystemExit(
                f"{name}: this reference has no {key}={config[key]!r}")
    if config["mamba_d_ssm"] != config["mamba_n_heads"] * config["mamba_d_head"]:
        raise SystemExit(f"{name}: mamba_d_ssm is not heads x head size")


def sizes_of(config: dict) -> dict:
    """The keyword sizes above, from a configuration file's published keys,
    and ``guaranteed`` (``drivers/serve_closed_loop_arch_stateful.py``)."""
    _refuse_what_is_not_here(config)
    return dict(
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]),
        m_heads=config["mamba_n_heads"], m_head_dim=config["mamba_d_head"],
        d_state=config["mamba_d_state"], n_groups=config["mamba_n_groups"],
        d_conv=config["mamba_d_conv"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        lm_head_multiplier=float(config["lm_head_multiplier"]),
        attn_in=float(config["attention_in_multiplier"]),
        attn_out=float(config["attention_out_multiplier"]),
        key_mult=float(config["key_multiplier"]),
        ssm_in=float(config["ssm_in_multiplier"]),
        ssm_out=float(config["ssm_out_multiplier"]),
        ssm_mults=tuple(float(m) for m in config["ssm_multipliers"]),
        mlp_mults=tuple(float(m) for m in config["mlp_multipliers"]),
        # what a slot row takes at the precisions the configuration states
        # (float32 state, bf16 convolution tail and K/V): the check holds
        # the program's live rows to these counts
        guaranteed={
            "state_bytes_per_row": flops_ssm.state_bytes_per_row(config),
            "kv_bytes_per_token": flops_ssm.kv_bytes_per_token(config)},
    )


def llm_arguments(config: dict) -> dict:
    """A configuration file's published keys as ``LLMConfig`` arguments: the
    family and its model arguments
    (``ray_tpu.models.falcon_h1.FalconH1Config``)."""
    _refuse_what_is_not_here(config)
    return dict(
        model_family="falcon_h1",
        model_kwargs=dict(
            vocab_size=config["vocab_size"],
            dim=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            intermediate=config["intermediate_size"],
            mamba_n_heads=config["mamba_n_heads"],
            mamba_d_head=config["mamba_d_head"],
            mamba_d_state=config["mamba_d_state"],
            mamba_n_groups=config["mamba_n_groups"],
            mamba_d_conv=config["mamba_d_conv"],
            mamba_chunk_size=config["mamba_chunk_size"],
            embedding_multiplier=config["embedding_multiplier"],
            lm_head_multiplier=config["lm_head_multiplier"],
            attention_in_multiplier=config["attention_in_multiplier"],
            attention_out_multiplier=config["attention_out_multiplier"],
            key_multiplier=config["key_multiplier"],
            ssm_in_multiplier=config["ssm_in_multiplier"],
            ssm_out_multiplier=config["ssm_out_multiplier"],
            ssm_multipliers=tuple(config["ssm_multipliers"]),
            mlp_multipliers=tuple(config["mlp_multipliers"]),
            rope_theta=float(config["rope_theta"]),
            norm_eps=config["rms_norm_eps"],
        ),
    )
