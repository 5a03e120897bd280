"""Motif-shaped transformer (``model_type`` Motif: Motif-3-Beta), TPU-first,
for the serving stack: grouped differential latent attention (GDLA) on
window and full layers, a residual of four streams mixed by learned,
Sinkhorn-normalised maps around every sub-layer (mHC), PolyNorm in every
feed-forward, two leading dense layers and then routed experts beside a
shared one, of which this process may hold one chip's share.

No reference analogue (the reference serves such models through vLLM).
What is shared: the latent down-projection, its norm and the cache leaves
are ``models/deepseek.latent_rows`` / ``latent_cache``; RoPE is
``ops/rope``, the step's cache write ``ops/kv_row_write`` and the step's
attention ``ops/decode_attention.latent_decode_attention`` in the absorbed
form, all as ``deepseek`` uses them; the routed experts are
``models/moe.MoEFFN`` under ``MoEConfig.experts_held`` and
``expert_activation="poly_norm"`` with the sigmoid router of
``parallel/expert.top_k_routing``. What is this family's own is written
out below, names as the published config's keys.

The residual (``mhc_expansion_rate`` ``n`` streams of ``hidden_size`` ``d``;
mHC, DeepSeek-AI 2025, on Hyper-Connections, arXiv:2409.19606). A token's
residual is ``X (n x d)``; the embedding row is copied into every stream
and the final norm reads the streams' sum. Each sub-layer ``F`` of a layer
(attention, feed-forward) has maps of its own, computed in float32::

    x~     = RMSNorm(vec(X))                       (n d values, no weight)
    H_pre  = sigmoid(a_pre (x~ Phi_pre) + b_pre)            (n)
    H_post = 2 sigmoid(a_post (x~ Phi_post) + b_post)       (n)
    H_res  = SK(exp(a_res mat(x~ Phi_res) + b_res))         (n x n)
    u  = H_pre X                  y = F(RMSNorm_w(u))
    X' = clip(H_res X + H_post^T y, -hidden_clamp, hidden_clamp)

``SK``: ``mhc_sinkhorn_iters`` times rows over their sums, then columns
over theirs, the sums adds of slices so that the twenty iterations are
elementwise and fuse (``sinkhorn``). The streams are ``n`` arrays.

Attention (GDLA: Grouped Differential Attention, Motif Technologies 2025,
on DeepSeek-V2's latent attention, arXiv:2405.04434, the subtraction in the
Differential Transformer's V2 form). ``num_attention_heads`` = ``G (S +
1)``: ``G = num_key_value_heads`` groups of ``S`` signal heads and one
noise head (head ``g (S + 1) + j``, the noise head ``j = S``)::

    c_q = RMSNorm(h W_dq);  q = c_q W_uq -> heads x (nope | rope)
    [c_raw | k_r] = h W_dkv;  c = RMSNorm(c_raw)
    RoPE on q's rope part and on k_r, one rotary row for all heads
    group g:  [k_nope_g | v_g] = c W_ukv,g;  k_g = [k_nope_g | k_r]
    A(q) = softmax(q k_g^T / sqrt(nope + rope) + mask) v_g
    lambda_g,j = sigmoid(h w_lambda,g,j)
    o_g,j = A(s_g,j) - lambda_g,j A(n_g)
    out = (o * sigmoid(h W_gate)) W_o

``mask`` is causal, and in a window layer (every layer ``i`` but those with
``i % sliding_window_period == sliding_window_period - 1``) also ``j > i -
sliding_window``. A whole sequence (a prefill into a fresh cache) takes
this published form in blocks of queries, a window layer's block against
the band of keys it can see. Against the cache the absorbed form: ``q_lat =
q_nope W_uk,g``, one call of ``latent_decode_attention`` with every head,
the subtraction on the latent outputs (the attention is linear in ``v``),
then ``W_uv,g``.

What a row keeps between steps (the ``cache`` collection): a full layer's
``cached_latent`` / ``cached_rope`` / ``cache_index`` as ``deepseek``'s; a
window layer's **ring** (``models.WINDOW``): ``window_latent (batch, 1,
ring, rank)`` and ``window_rope (batch, 1, ring, rope)``, position ``p`` at
slot ``p % ring``, ``ring`` = ``sliding_window``, beside a ``cache_index``
of its own. A cached ``c`` row carries no position and a cached ``k_rope``
row is already rotated, and a softmax over a set does not depend on its
order: the ring read with ``lengths = min(index + 1, ring)`` is the window,
by the same kernel. More than one new position a row against a cache (a
chunk behind a prefix) has no ring form and is refused
(``models.refusals``).

Feed-forward (``hidden_act`` poly_norm; arXiv:2411.03884): ``P(z) =
polynorm_output_scale (w1 N(z^3) + w2 N(z^2) + w3 N(z) + clip(b,
+-polynorm_bias_clamp))``, ``N(z) = z / sqrt(mean(z^2) + eps)`` over the
last axis; ``FFN(h) = (P(h W_gate) * (h W_up)) W_down``. The first
``n_dense_first_layers`` layers one FFN of ``intermediate_size``, every
later one ``sum_j w_j E_j(h) + Shared(h)``; ``s = sigmoid(h W_r)``, the
``experts_top_k`` largest, ``w = route_scale s_j / sum s`` (no selection
bias).

``init_params``: every weight drawn in float32 and rounded to
``param_dtype`` (``solar_open2.init_params`` says why); the maps' ``Phi`` a
fan-in normal, so ``x~ Phi`` is unit normal, ``a`` uniform in [0.5, 1],
``b`` unit normal: seeded maps that mix, gate and differ by token; the
mHC and PolyNorm parameters stay float32 whatever ``param_dtype``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..ops.decode_attention import latent_decode_attention
from ..ops.kv_row_write import write_rows
from ..ops.rmsnorm import rmsnorm
from ..ops.rope import apply_rope, rope_table
from . import ROUTING  # noqa: F401  (MoEFFN sows into it)
from .deepseek import latent_cache, latent_rows
from .llama import _dense, ring_of
from .moe import MoEConfig, MoEFFN, poly_coefficients, poly_init, poly_norm

F32 = jnp.float32
# the published-form attention's blocks (``_banded_attention``): 80 heads x
# 256 queries x 1024 keys of float32 scores are 84 MB. Measured on a v5e, a
# full layer of an 8192-token prompt, the attention alone: 256 x 1024 22.4
# ms, 512 x 512 26.3 (PERF.md, finding 50.2)
_QUERY_BLOCK = 256
_KEY_BLOCK = 1024
# a masked score: not -inf, which an online softmax turns into NaN where a
# whole block of keys is masked (exp(-inf - -inf))
_MASKED = -1e30

# a window layer keeps models.WINDOW leaves: the serving stack gives such a
# family no prefix reuse (models/__init__.py)
ROW_WINDOW = True


@dataclasses.dataclass(frozen=True)
class MotifConfig:
    """Motif-3-Beta's published sizes are the defaults."""

    vocab_size: int = 220160
    dim: int = 4096
    n_layers: int = 53
    n_heads: int = 80
    n_kv_heads: int = 16  # the KV groups, each with one noise head
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    sliding_window: int = 128
    sliding_window_period: int = 4
    mhc_streams: int = 4
    mhc_sinkhorn_iters: int = 20
    hidden_clamp: float = 1e6
    intermediate: int = 12288  # the dense layers' feed-forward
    moe_intermediate: int = 1280  # one routed expert's
    n_experts: int = 384  # routed over
    experts_per_token: int = 8
    n_shared_experts: int = 1
    first_dense_layers: int = 2
    norm_topk_prob: bool = True
    routed_scale: float = 2.0
    polynorm_scale: float = 0.5
    polynorm_bias_clamp: float = 0.5
    # one chip's share of every layer's experts (MoEConfig.experts_held)
    experts_held: Optional[Tuple[int, int]] = None
    max_seq_len: int = 8192
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads or self.n_heads == self.n_kv_heads:
            raise ValueError(
                f"MotifConfig: {self.n_heads} heads are not {self.n_kv_heads}"
                " groups of signal heads and one noise head"
            )
        if not 0 <= self.first_dense_layers <= self.n_layers:
            raise ValueError(
                f"MotifConfig: first_dense_layers {self.first_dense_layers}"
                f" of {self.n_layers} layers"
            )
        self.routed_config()  # refuse a bad share here

    @property
    def signal_heads(self) -> int:
        """Signal heads a group: the rest of its heads but the noise one."""
        return self.n_heads // self.n_kv_heads - 1

    def is_window(self, layer: int) -> bool:
        period = self.sliding_window_period
        return layer % period != period - 1

    @property
    def routed_layers(self) -> Tuple[int, ...]:
        """The layers with routed experts: what the engine's expert
        counters have a row for (``llm/engine.py``)."""
        return tuple(range(self.first_dense_layers, self.n_layers))

    def routed_config(self) -> MoEConfig:
        """The routed part of a layer as ``MoEFFN`` takes it."""
        return MoEConfig(
            dim=self.dim,
            intermediate=self.moe_intermediate,
            n_experts=self.n_experts,
            experts_per_token=self.experts_per_token,
            norm_topk_prob=self.norm_topk_prob,
            norm_eps=self.norm_eps,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            dropless=True,
            router_scoring="sigmoid",
            routed_scale=self.routed_scale,
            experts_held=self.experts_held,
            expert_activation="poly_norm",
            polynorm_scale=self.polynorm_scale,
            polynorm_bias_clamp=self.polynorm_bias_clamp,
        )

    @staticmethod
    def tiny(**kw) -> "MotifConfig":
        defaults = dict(
            vocab_size=256, dim=128, n_layers=8, n_heads=10, n_kv_heads=2,
            q_lora_rank=48, kv_lora_rank=64, qk_nope_head_dim=32,
            qk_rope_head_dim=16, v_head_dim=32, sliding_window=16,
            intermediate=256, moe_intermediate=128, n_experts=16,
            experts_per_token=2, max_seq_len=256,
        )
        defaults.update(kw)
        return MotifConfig(**defaults)


def sinkhorn(m, iters: int):
    """``m (n, n, ...)`` positive, a token's ``n x n`` map along the two
    leading axes: rows over their sums, then columns over theirs, ``iters``
    times. The sums are written as adds of slices, not as reductions, so
    that the whole of it is elementwise and one fusion on the TPU (a
    reduction ends a fusion: forty kernels a sub-layer, 640 a step)."""
    n = m.shape[0]
    for _ in range(iters):
        m = m / sum(m[:, j:j + 1] for j in range(n))
        m = m / sum(m[i:i + 1] for i in range(n))
    return m


class HyperConnection(nn.Module):
    """One sub-layer's mHC maps (module docstring): ``maps(streams)`` gives
    ``H_pre``, ``H_post`` (``n`` entries along the leading axis) and
    ``H_res`` (indexed ``[i, j]``), every entry ``(b, s, 1)`` float32."""

    config: MotifConfig

    @nn.compact
    def __call__(self, streams):
        cfg = self.config
        n = cfg.mhc_streams

        def vector(name, init, shape):
            return self.param(
                name, nn.with_logical_partitioning(init, (None,) * len(shape)),
                shape, F32)

        phi = vector(
            "phi", nn.initializers.variance_scaling(1.0, "fan_in", "normal"),
            (n * cfg.dim, 2 * n + n * n))
        alpha = vector(
            "alpha",
            lambda key, shape, dtype: jax.random.uniform(
                key, shape, dtype, 0.5, 1.0),
            (3,))
        bias = vector("bias", nn.initializers.normal(1.0), (2 * n + n * n,))
        with jax.named_scope("mhc.maps"):
            flat = jnp.concatenate(streams, axis=-1).astype(F32)
            flat = flat * jax.lax.rsqrt(
                jnp.mean(flat * flat, axis=-1, keepdims=True) + cfg.norm_eps)
            # full f32 products, as the router's: the maps feed an exp
            proj = jnp.einsum(
                "bsk,km->bsm", flat, phi,
                precision=jax.lax.Precision.HIGHEST)
            scale = jnp.concatenate([
                jnp.full((n,), alpha[0]), jnp.full((n,), alpha[1]),
                jnp.full((n * n,), alpha[2])])
            proj = proj * scale + bias
            # the maps' entries along the leading axis, the tokens minor: a
            # prompt's thousands of positions fill the lanes (a trailing
            # axis of one would leave each a tile of its own)
            proj = jnp.moveaxis(proj, -1, 0)  # (2n + n n, b, s)
            pre = jax.nn.sigmoid(proj[:n])
            post = 2.0 * jax.nn.sigmoid(proj[n:2 * n])
            res = sinkhorn(
                jnp.exp(proj[2 * n:]).reshape(n, n, *proj.shape[1:]),
                cfg.mhc_sinkhorn_iters)
        return pre[..., None], post[..., None], res[..., None]


def mix_in(streams, pre, dtype):
    """``u = H_pre X``."""
    with jax.named_scope("mhc.mix"):
        return sum(
            h * x.astype(F32) for h, x in zip(pre, streams)).astype(dtype)


def mix_out(streams, y, post, res, clamp: float):
    """``X' = clip(H_res X + H_post^T y)``."""
    with jax.named_scope("mhc.mix"):
        y = y.astype(F32)
        wide = [x.astype(F32) for x in streams]
        return tuple(
            jnp.clip(
                sum(res[i, j] * x for j, x in enumerate(wide)) + p * y,
                -clamp, clamp,
            ).astype(streams[0].dtype)
            for i, p in enumerate(post)
        )


def _banded_attention(q, k, v, scale: float, window: Optional[int]):
    """Causal softmax attention of a whole sequence, a group's heads
    against the group's keys: ``q (b, g, j, s, d)``, ``k (b, g, s, d)``,
    ``v (b, g, s, dv)``; with ``window`` a query at ``i`` sees the keys
    ``i - window < p <= i``. Stored values multiplied as they are, f32
    scores and softmax, probabilities rounded to the values' dtype for the
    second product.

    In blocks of ``_QUERY_BLOCK`` queries, a group's heads as more rows of
    one plain batched matmul. A window layer's block reads the band of keys
    it can see at once. A full layer's block walks the keys at or before
    its end ``_KEY_BLOCK`` at a time under an online softmax: one product
    against all 8192 keys of a long prompt and a softmax over its 42 MB of
    scores a group took 573 ms a layer on a v5e, the walk takes 22 (the
    kernel alone, PERF.md, finding 50.2)."""
    b, g, j, s, d = q.shape
    keys = -(-s // _KEY_BLOCK) * _KEY_BLOCK
    if window is None and keys > _KEY_BLOCK:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, keys - s), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, keys - s), (0, 0)))
    out = []
    for start in range(0, s, _QUERY_BLOCK):
        end = min(start + _QUERY_BLOCK, s)
        rows = q[:, :, :, start:end].reshape(b, g, j * (end - start), d)
        q_pos = jnp.tile(jnp.arange(start, end), j)[:, None]

        def scored(k_block, k_pos):
            scores = jnp.einsum(
                "bgmd,bgkd->bgmk", rows, k_block, preferred_element_type=F32,
            ) * scale
            visible = k_pos <= q_pos
            if window is not None:
                visible &= k_pos > q_pos - window
            return jnp.where(visible[None, None], scores, _MASKED)

        def weighted(probs, v_block):
            return jnp.einsum(
                "bgmk,bgkd->bgmd", probs.astype(v.dtype), v_block,
                preferred_element_type=F32)

        if window is not None or keys == _KEY_BLOCK:
            first = 0 if window is None else max(0, start - window + 1)
            probs = jax.nn.softmax(
                scored(k[:, :, first:end], jnp.arange(first, end)[None, :]),
                axis=-1)
            block = weighted(probs, v[:, :, first:end])
        else:
            def walk(i, carry):
                m, l, acc = carry
                at = i * _KEY_BLOCK
                scores = scored(
                    jax.lax.dynamic_slice_in_dim(k, at, _KEY_BLOCK, axis=2),
                    at + jnp.arange(_KEY_BLOCK)[None, :])
                m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
                p = jnp.exp(scores - m_new)
                alpha = jnp.exp(m - m_new)
                return (
                    m_new, l * alpha + p.sum(axis=-1, keepdims=True),
                    acc * alpha + weighted(
                        p, jax.lax.dynamic_slice_in_dim(
                            v, at, _KEY_BLOCK, axis=2)))

            m0 = jnp.full(rows.shape[:3] + (1,), _MASKED, F32)
            _, l, acc = jax.lax.fori_loop(
                0, -(-end // _KEY_BLOCK), walk,
                (m0, jnp.zeros_like(m0),
                 jnp.zeros(rows.shape[:3] + (v.shape[-1],), F32)))
            block = acc / l  # (a query sees itself: l is never 0)
        out.append(block.astype(v.dtype).reshape(b, g, j, end - start, -1))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=3)


class GDLA(nn.Module):
    config: MotifConfig
    window: bool

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        b, s, _ = x.shape
        h, g, sig = cfg.n_heads, cfg.n_kv_heads, cfg.signal_heads
        rank, nope, rope, dv = (
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim,
        )
        scale = 1.0 / math.sqrt(nope + rope)

        def dense(features, axes, name):
            return _dense(features, axes, name, cfg.param_dtype, cfg.dtype)

        def norm_weight(name, width):
            return self.param(
                name,
                nn.with_logical_partitioning(
                    nn.initializers.ones_init(), (None,)),
                (width,), cfg.param_dtype)

        c_q = rmsnorm(
            dense(cfg.q_lora_rank, ("embed", None), "wq_a")(x),
            norm_weight("q_norm", cfg.q_lora_rank).astype(x.dtype),
            cfg.norm_eps)
        q = dense(h * (nope + rope), (None, "heads"), "wq_b")(c_q)
        q = q.reshape(b, s, h, nope + rope).transpose(0, 2, 1, 3)
        q_nope, q_rope = q[..., :nope], q[..., nope:]
        c, k_r = latent_rows(self, cfg, x)
        # (rank, groups, nope | v): a group's up-projection of the latent
        # to its keys' nope part and to its values, kept whole so the
        # decode step can absorb either half
        wkv_b = self.param(
            "wkv_b",
            nn.with_logical_partitioning(
                nn.initializers.variance_scaling(
                    1.0, "fan_in", "truncated_normal", in_axis=0,
                    out_axis=(1, 2),
                ),
                (None, "heads", None),
            ),
            (rank, g, nope + dv),
            cfg.param_dtype,
        ).astype(cfg.dtype)
        # a token's lambda a signal head, group-major as the heads
        lam = jax.nn.sigmoid(
            dense(g * sig, ("embed", "heads"), "w_lambda")(x).astype(F32))
        gate = jax.nn.sigmoid(
            dense(g * sig * dv, ("embed", "heads"), "w_gate")(x).astype(F32))

        positions = cfg.sliding_window if self.window else cfg.max_seq_len
        cached_c, cached_r, idx_var, fresh = latent_cache(
            self, cfg, b, positions, "window" if self.window else "cached")
        idx = idx_var.value  # (b,): a row's position
        q_rope = apply_rope(q_rope, cos, sin, offset=idx)
        k_rope = apply_rope(k_r, cos, sin, offset=idx)
        new = (c[:, None].astype(cfg.dtype), k_rope.astype(cfg.dtype))

        if fresh:
            # published form over the sequence itself: a prefill into a
            # cache made in this very call (every row's position is 0)
            if self.window:
                cached_c.value, cached_r.value = (
                    ring_of(leaf, positions) for leaf in new)
            else:
                cached_c.value, cached_r.value = write_rows(
                    (cached_c.value, cached_r.value), new, idx)
            kv = jnp.einsum("bsr,rgd->bgsd", c, wkv_b)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_rope, (b, g, s, rope))],
                axis=-1,
            )
            attended = _banded_attention(
                jnp.concatenate([q_nope, q_rope], axis=-1).reshape(
                    b, g, sig + 1, s, nope + rope),
                k, kv[..., nope:], scale,
                cfg.sliding_window if self.window else None,
            )  # (b, g, sig + 1, s, dv)
            with jax.named_scope("gdla.diff"):
                weight = lam.reshape(b, s, g, sig, 1).transpose(0, 2, 3, 1, 4)
                out = (
                    attended[:, :, :sig].astype(F32)
                    - weight * attended[:, :, sig:].astype(F32)
                ).astype(cfg.dtype)
            out = out.transpose(0, 3, 1, 2, 4)  # (b, s, g, sig, dv)
        elif s != 1:
            raise NotImplementedError(
                "the motif family has no form for more than one new "
                "position a row against a cache (models.refusals)"
            )
        else:
            # absorbed form against the cache: the ring is written at the
            # position's slot and read up to its live slots, whatever
            # their order
            slot = idx % positions if self.window else idx
            cached_c.value, cached_r.value = write_rows(
                (cached_c.value, cached_r.value), new, slot)
            with jax.named_scope("gdla.absorb"):
                q_lat = jnp.einsum(
                    "bgjd,rgd->bgjr",
                    q_nope[:, :, 0].reshape(b, g, sig + 1, nope),
                    wkv_b[..., :nope],
                ).reshape(b, h, rank)
            o_lat = latent_decode_attention(
                q_lat, q_rope[:, :, 0], cached_c.value, cached_r.value,
                jnp.minimum(idx + 1, positions), sm_scale=scale,
            ).reshape(b, g, sig + 1, rank)
            with jax.named_scope("gdla.diff"):
                o_lat = (
                    o_lat[:, :, :sig].astype(F32)
                    - lam.reshape(b, g, sig, 1) * o_lat[:, :, sig:].astype(F32)
                ).astype(cfg.dtype)
            with jax.named_scope("gdla.absorb"):
                out = jnp.einsum(
                    "bgjr,rgd->bgjd", o_lat, wkv_b[..., nope:])[:, None]
        idx_var.value = idx + s
        out = (out.reshape(b, s, g * sig * dv).astype(F32) * gate).astype(
            cfg.dtype)
        return dense(cfg.dim, ("heads", "embed"), "wo")(out)


class PolyGLU(nn.Module):
    """``down(P(gate x) * up x)``: the dense layers' feed-forward, and the
    shared expert's."""

    config: MotifConfig
    features: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config

        def dense(features, axes, name):
            return _dense(features, axes, name, cfg.param_dtype, cfg.dtype)

        gate = dense(self.features, ("embed", "mlp"), "w_gate")(x)
        up = dense(self.features, ("embed", "mlp"), "w_up")(x)
        poly = self.param(
            "poly", nn.with_logical_partitioning(poly_init, (None,)), (4,),
            F32)
        hidden = poly_norm(
            gate.astype(F32),
            poly_coefficients(
                poly, cfg.polynorm_scale, cfg.polynorm_bias_clamp),
            cfg.norm_eps,
        ) * up.astype(F32)
        return dense(cfg.dim, ("mlp", "embed"), "w_down")(
            hidden.astype(cfg.dtype))


class Block(nn.Module):
    config: MotifConfig
    window: bool
    routed: bool
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, streams, cos, sin):
        cfg = self.config

        def sub_layer(name, streams, fn):
            pre, post, res = HyperConnection(cfg, name=f"{name}_mhc")(streams)
            w = self.param(
                f"{name}_norm",
                nn.with_logical_partitioning(
                    nn.initializers.ones_init(), ("embed",)),
                (cfg.dim,), cfg.param_dtype)
            u = mix_in(streams, pre, cfg.dtype)
            y = fn(rmsnorm(u, w.astype(u.dtype), cfg.norm_eps, self.mesh))
            return mix_out(streams, y, post, res, cfg.hidden_clamp)

        def feed_forward(y):
            if not self.routed:
                return PolyGLU(cfg, cfg.intermediate, name="mlp")(y)
            routed = MoEFFN(cfg.routed_config(), name="moe")(y)
            with jax.named_scope("moe.shared"):
                shared = PolyGLU(
                    cfg, cfg.n_shared_experts * cfg.moe_intermediate,
                    name="shared",
                )(y)
            return routed + shared

        streams = sub_layer(
            "attn", streams,
            lambda y: GDLA(cfg, self.window, name="attn")(y, cos, sin))
        return sub_layer("ffn", streams, feed_forward)


class Motif(nn.Module):
    config: MotifConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, tokens, adapters=None, adapter_slots=None):
        # tokens: (batch, seq) int32. The family has no adapter placement
        # (models.refusals): the two arguments are the engine's calling
        # convention and must stay None
        if adapters is not None:
            raise ValueError("the motif family takes no adapter bank")
        cfg = self.config
        embed = self.param(
            "embed",
            nn.with_logical_partitioning(
                nn.initializers.normal(1.0), ("vocab", "embed")
            ),
            (cfg.vocab_size, cfg.dim),
            cfg.param_dtype,
        )
        x = embed.astype(cfg.dtype)[tokens]
        streams = (x,) * cfg.mhc_streams
        cos, sin = rope_table(
            cfg.max_seq_len, cfg.qk_rope_head_dim, cfg.rope_theta)
        for i in range(cfg.n_layers):
            streams = Block(
                cfg, cfg.is_window(i), i in cfg.routed_layers, self.mesh,
                name=f"layer_{i}",
            )(streams, cos, sin)
        x = sum(s.astype(F32) for s in streams).astype(cfg.dtype)
        final_norm_w = self.param(
            "final_norm",
            nn.with_logical_partitioning(nn.initializers.ones_init(), ("embed",)),
            (cfg.dim,),
            cfg.param_dtype,
        )
        x = rmsnorm(x, final_norm_w.astype(x.dtype), cfg.norm_eps, self.mesh)
        head = self.param(
            "lm_head",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "vocab")
            ),
            (cfg.dim, cfg.vocab_size),
            cfg.param_dtype,
        )
        return x @ head.astype(x.dtype)


def build(config: MotifConfig, mesh: Optional[Mesh] = None,
          decode: bool = False):
    """What ``ray_tpu.models.build`` returns for this family: the serving
    module, which keeps a cache whenever it is applied (a whole sequence
    without one is a prefill into a fresh row)."""
    if not decode:
        raise NotImplementedError(
            "the motif family has a serving path only (decode=True)"
        )
    return Motif(config, mesh)


def init_params(config: MotifConfig, rng, mesh: Optional[Mesh] = None,
                seq: int = 8):
    """Seeded weights (module docstring), made by one compiled program: the
    forward pass that places them is traced and never run. Every weight is
    drawn in float32 and then cast to the dtype ``config`` gives it
    (``param_dtype``, but float32 for the maps and the PolyNorms):
    ``solar_open2.init_params`` says what a bf16 draw does (PERF.md,
    finding 36.5)."""
    tokens = jnp.zeros((1, seq), jnp.int32)
    wide = Motif(dataclasses.replace(config, param_dtype=F32), mesh)
    stored = jax.eval_shape(Motif(config, mesh).init, rng, tokens)["params"]

    def make(key):
        return jax.tree.map(
            lambda w, kept: w.astype(kept.dtype),
            wide.init(key, tokens)["params"], stored)

    return jax.jit(make)(rng)
