"""DeepSeek-V3-shaped transformer (``model_type`` deepseek_v3: Moonlight,
DeepSeek-V2/V3, Kimi, GLM-4.7+), TPU-first: multi-head *latent* attention,
a leading dense layer, then routed experts beside shared ones.

No reference analogue (the reference serves these through vLLM). What is
this family's own is the attention; the routed experts are
``models/moe.MoEFFN`` (dropless, ``ops/moe_experts.py``) with the sigmoid
router of ``parallel/expert.top_k_routing``.

Latent attention (no query low-rank path: ``q_lora_rank`` null). A layer:

- ``q = h W_q`` -> heads x (nope | rope); ``[c_raw | k_r] = h W_kva``
  (rank | rope); ``c = RMSNorm(c_raw)``; RoPE on ``q_rope`` and on ``k_r``,
  one rotary row shared by all heads
- the cache holds ``c`` and ``k_rope`` a position, after the norm and
  after RoPE: ``rank + rope`` values a token a layer (576 at Moonlight's
  sizes, where per-head keys and values would be 5120), in two leaves,
  ``cached_latent`` of ``(batch, 1, max_seq_len, rank)`` and
  ``cached_rope`` of ``(batch, 1, max_seq_len, rope)``
- *published form* (a whole sequence: training, and a prefill into a fresh
  cache): ``[k_nope | v] = c W_kvb`` a head, ``k = [k_nope | k_rope]``,
  causal ``softmax(q k^T / sqrt(nope + rope)) v``
- *absorbed form* (against the cache: a decode step, and a chunk behind a
  cached prefix): ``q_lat = q_nope W_kvb^K`` (nope -> rank) a head, scores
  ``(q_lat . c + q_rope . k_rope) / sqrt(nope + rope)``, ``o_lat = P c``,
  ``o = o_lat W_kvb^V``: the cached row is read as it is, once, and never
  up-projected (``ops/decode_attention.latent_decode_attention``). The same
  function as the published form in another order of rounding.

RoPE layout: the published code stores the rotary columns interleaved and
de-interleaves them before ``rotate_half``; this module rotates the half
split form (``ops/rope.apply_rope``). With weights of this layout that is
the same function; published checkpoints need the fixed permutation of
``W_q``'s and ``W_kva``'s rotary columns at load.

Feed-forward: the first ``first_dense_layers`` layers a dense SwiGLU of
``intermediate``; every later one ``sum_j w_j E_j(h) + Shared(h)``, the
routed part dropless, ``Shared`` one SwiGLU of ``n_shared_experts x
moe_intermediate`` every token passes.
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
from .llama import _dense
from .moe import MoEConfig, MoEFFN

# query rows of one block of the published-form attention: a block's f32
# scores against a 4096-token prompt are 16 heads x 512 x 4096 x 4 = 134 MB
_QUERY_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class DeepseekConfig:
    """Moonlight-16B-A3B's published sizes are the defaults."""

    vocab_size: int = 163840
    dim: int = 2048
    n_layers: int = 27
    n_heads: int = 16
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate: int = 11264  # the dense layers' SwiGLU
    moe_intermediate: int = 1408  # one routed expert's
    n_experts: int = 64  # routed
    experts_per_token: int = 6
    n_shared_experts: int = 2
    first_dense_layers: int = 1
    norm_topk_prob: bool = True
    routed_scale: float = 2.446
    max_seq_len: int = 8192
    rope_theta: float = 50000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False

    def __post_init__(self):
        if not 0 <= self.first_dense_layers <= self.n_layers:
            raise ValueError(
                f"DeepseekConfig: first_dense_layers {self.first_dense_layers}"
                f" of {self.n_layers} layers"
            )

    @property
    def n_kv_heads(self) -> int:
        """One latent row serves every head (what a tp plan would shard)."""
        return 1

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
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            dropless=True,
            router_scoring="sigmoid",
            router_bias=True,
            routed_scale=self.routed_scale,
        )

    @staticmethod
    def tiny(**kw) -> "DeepseekConfig":
        defaults = dict(
            vocab_size=256, dim=128, n_layers=3, n_heads=4, kv_lora_rank=64,
            qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
            intermediate=256, moe_intermediate=128, n_experts=8,
            experts_per_token=2, n_shared_experts=2, max_seq_len=512,
        )
        defaults.update(kw)
        return DeepseekConfig(**defaults)


def _causal_attention(q, k, v, scale: float):
    """Causal softmax attention of a whole sequence, ``q``/``k (b, h, s,
    d)``, ``v (b, h, s, dv)``, in query blocks against the keys at or
    before each block's end: stored values multiplied as they are, f32
    scores and softmax, probabilities rounded to the values' dtype for the
    second product."""
    s = q.shape[2]
    out = []
    for start in range(0, s, _QUERY_BLOCK):
        end = min(start + _QUERY_BLOCK, s)
        scores = jnp.einsum(
            "bhqd,bhkd->bhqk", q[:, :, start:end], k[:, :, :end],
            preferred_element_type=jnp.float32,
        ) * scale
        visible = (
            jnp.arange(end)[None, :] <= jnp.arange(start, end)[:, None]
        )
        probs = jax.nn.softmax(
            jnp.where(visible[None, None], scores, -jnp.inf), axis=-1
        )
        out.append(jnp.einsum(
            "bhqk,bhkd->bhqd", probs.astype(v.dtype), v[:, :, :end],
            preferred_element_type=jnp.float32,
        ).astype(v.dtype))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=2)


def latent_rows(module, cfg, x):
    """The latent down-projection of ``x (b, s, dim)`` inside ``module``'s
    compact call: ``[c_raw | k_r] = x W_kva``, ``c = RMSNorm(c_raw)``.
    Returns ``c (b, s, rank)`` and ``k_r (b, 1, s, rope)`` (one row for all
    heads, before RoPE). Shared by every family with latent attention
    (``models/motif.py``); ``cfg`` gives ``kv_lora_rank``,
    ``qk_rope_head_dim``, ``norm_eps`` and the two dtypes."""
    rank = cfg.kv_lora_rank
    kva = _dense(
        rank + cfg.qk_rope_head_dim, ("embed", None), "wkv_a",
        cfg.param_dtype, cfg.dtype,
    )(x)
    kv_norm_w = module.param(
        "kv_norm",
        nn.with_logical_partitioning(nn.initializers.ones_init(), (None,)),
        (rank,),
        cfg.param_dtype,
    )
    c = rmsnorm(kva[..., :rank], kv_norm_w.astype(x.dtype), cfg.norm_eps)
    return c, kva[..., None, :, rank:]


def latent_cache(module, cfg, batch: int, positions: int,
                 prefix: str = "cached"):
    """``module``'s latent cache of ``positions`` positions a row: the
    leaves ``<prefix>_latent (batch, 1, positions, rank)`` and
    ``<prefix>_rope (batch, 1, positions, rope)`` and the row's
    ``cache_index``, and whether this very call made them (a prefill into
    a fresh cache). Two leaves, not one of rank + rope columns: 576 is 4.5
    lane tiles, which the TPU stores sequence-minor and a kernel cannot
    read without a transpose (ops/decode_attention.py)."""
    fresh = not module.has_variable("cache", f"{prefix}_latent")
    cached_c = module.variable(
        "cache", f"{prefix}_latent",
        jnp.zeros, (batch, 1, positions, cfg.kv_lora_rank), cfg.dtype,
    )
    cached_r = module.variable(
        "cache", f"{prefix}_rope",
        jnp.zeros, (batch, 1, positions, cfg.qk_rope_head_dim), cfg.dtype,
    )
    idx_var = module.variable(
        "cache", "cache_index", lambda: jnp.zeros((batch,), jnp.int32)
    )
    return cached_c, cached_r, idx_var, fresh


class LatentAttention(nn.Module):
    config: DeepseekConfig
    decode: bool = False

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        b, s, _ = x.shape
        h = cfg.n_heads
        rank, nope, rope, dv = (
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim,
        )
        scale = 1.0 / math.sqrt(nope + rope)

        def dense(features, axes, name):
            return _dense(features, axes, name, cfg.param_dtype, cfg.dtype)

        q = dense(h * (nope + rope), ("embed", "heads"), "wq")(x)
        q = q.reshape(b, s, h, nope + rope).transpose(0, 2, 1, 3)
        q_nope, q_rope = q[..., :nope], q[..., nope:]
        c, k_r = latent_rows(self, cfg, x)
        # (rank, heads, nope | v): a head's up-projection of the latent to
        # its keys' nope part and to its values, kept whole so the decode
        # step can absorb either half
        wkv_b = self.param(
            "wkv_b",
            nn.with_logical_partitioning(
                nn.initializers.variance_scaling(
                    1.0, "fan_in", "truncated_normal", in_axis=0,
                    out_axis=(1, 2),
                ),
                (None, "heads", None),
            ),
            (rank, h, nope + dv),
            cfg.param_dtype,
        ).astype(cfg.dtype)

        if self.decode:
            cached_c, cached_r, idx_var, fresh = latent_cache(
                self, cfg, b, cfg.max_seq_len)
            idx = idx_var.value  # (b,): a row's write position
            q_rope = apply_rope(q_rope, cos, sin, offset=idx)
            k_rope = apply_rope(k_r, cos, sin, offset=idx)

            cached_c.value, cached_r.value = write_rows(
                (cached_c.value, cached_r.value),
                (c[:, None].astype(cfg.dtype), k_rope.astype(cfg.dtype)),
                idx,
            )
            idx_var.value = idx + s
        else:
            fresh = True
            q_rope = apply_rope(q_rope, cos, sin)
            k_rope = apply_rope(k_r, cos, sin)

        if fresh:
            # published form over the sequence itself: training, and a
            # prefill into a cache made in this very call (every row's
            # position is 0, nothing older to attend)
            kv = jnp.einsum("bsr,rhd->bhsd", c, wkv_b)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_rope, (b, h, s, rope))],
                axis=-1,
            )
            out = _causal_attention(
                jnp.concatenate([q_nope, q_rope], axis=-1), k, kv[..., nope:],
                scale,
            )
        else:
            # absorbed form against the cache
            with jax.named_scope("mla.absorb"):
                q_lat = jnp.einsum(
                    "bhsd,rhd->bhsr", q_nope, wkv_b[..., :nope]
                )
            if s == 1:
                o_lat = latent_decode_attention(
                    q_lat[:, :, 0], q_rope[:, :, 0], cached_c.value,
                    cached_r.value, jnp.minimum(idx + 1, cfg.max_seq_len),
                    sm_scale=scale,
                )[:, :, None]
            else:
                # a chunk behind a cached prefix (suffix and chunked
                # prefill): row r's query i sits at idx[r] + i and sees
                # the keys at or before it, all of them written
                rows_c, rows_r = cached_c.value[:, 0], cached_r.value[:, 0]
                scores = (
                    jnp.einsum(
                        "bhsr,bkr->bhsk", q_lat, rows_c,
                        preferred_element_type=jnp.float32,
                    ) + jnp.einsum(
                        "bhsd,bkd->bhsk", q_rope, rows_r,
                        preferred_element_type=jnp.float32,
                    )
                ) * scale
                q_pos = idx[:, None, None] + jnp.arange(s)[None, :, None]
                k_pos = jnp.arange(cfg.max_seq_len)[None, None, :]
                scores = jnp.where(
                    (k_pos <= q_pos)[:, None], scores, -jnp.inf
                )
                probs = jax.nn.softmax(scores, axis=-1)
                o_lat = jnp.einsum(
                    "bhsk,bkr->bhsr", probs.astype(cfg.dtype), rows_c,
                    preferred_element_type=jnp.float32,
                ).astype(cfg.dtype)
            with jax.named_scope("mla.absorb"):
                out = jnp.einsum("bhsr,rhd->bhsd", o_lat, wkv_b[..., nope:])
        out = out.transpose(0, 2, 1, 3).reshape(b, s, h * dv)
        return dense(cfg.dim, ("heads", "embed"), "wo")(out)


class SwiGLU(nn.Module):
    """``down(silu(gate x) * up x)``: the dense layers' feed-forward, and
    the shared experts' (all of a layer's as one matrix triple)."""

    config: DeepseekConfig
    features: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config

        def dense(features, axes, name):
            return _dense(features, axes, name, cfg.param_dtype, cfg.dtype)

        gate = dense(self.features, ("embed", "mlp"), "w_gate")(x)
        up = dense(self.features, ("embed", "mlp"), "w_up")(x)
        return dense(cfg.dim, ("mlp", "embed"), "w_down")(nn.silu(gate) * up)


class DeepseekBlock(nn.Module):
    config: DeepseekConfig
    routed: bool
    mesh: Optional[Mesh] = None
    decode: bool = False

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config

        def norm(y, name):
            w = self.param(
                name,
                nn.with_logical_partitioning(
                    nn.initializers.ones_init(), ("embed",)
                ),
                (cfg.dim,),
                cfg.param_dtype,
            )
            return rmsnorm(y, w.astype(y.dtype), cfg.norm_eps, self.mesh)

        h = x + LatentAttention(cfg, self.decode, name="attn")(
            norm(x, "attn_norm"), cos, sin
        )
        y = norm(h, "ffn_norm")
        if not self.routed:
            return h + SwiGLU(cfg, cfg.intermediate, name="mlp")(y)
        routed = MoEFFN(cfg.routed_config(), name="moe")(y)
        with jax.named_scope("moe.shared"):
            shared = SwiGLU(
                cfg, cfg.n_shared_experts * cfg.moe_intermediate,
                name="shared",
            )(y)
        return h + routed + shared


class DeepseekTransformer(nn.Module):
    config: DeepseekConfig
    mesh: Optional[Mesh] = None
    decode: bool = False

    @nn.compact
    def __call__(self, tokens, adapters=None, adapter_slots=None):
        # tokens: (batch, seq) int32. The family has no adapter placement
        # (models.refusals): the two arguments are the engine's calling
        # convention and must stay None
        if adapters is not None:
            raise ValueError("the deepseek family takes no adapter bank")
        cfg = self.config
        embed = self.param(
            "embed",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")
            ),
            (cfg.vocab_size, cfg.dim),
            cfg.param_dtype,
        )
        x = embed.astype(cfg.dtype)[tokens]
        cos, sin = rope_table(
            cfg.max_seq_len, cfg.qk_rope_head_dim, cfg.rope_theta
        )
        block = DeepseekBlock
        if cfg.remat:
            block = nn.remat(
                DeepseekBlock,
                policy=jax.checkpoint_policies.save_only_these_names(),
                prevent_cse=False,
            )
        for i in range(cfg.n_layers):
            x = block(
                cfg, i in cfg.routed_layers, self.mesh, self.decode,
                name=f"layer_{i}",
            )(x, cos, sin)
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
                nn.initializers.normal(0.02), ("embed", "vocab")
            ),
            (cfg.dim, cfg.vocab_size),
            cfg.param_dtype,
        )
        return x @ head.astype(x.dtype)


def build(config: DeepseekConfig, mesh: Optional[Mesh] = None,
          decode: bool = False):
    """What ``ray_tpu.models.build`` returns for this family."""
    return DeepseekTransformer(config, mesh, decode)


def init_params(config: DeepseekConfig, rng, mesh: Optional[Mesh] = None,
                seq: int = 8):
    model = DeepseekTransformer(config, mesh)
    tokens = jnp.zeros((1, seq), jnp.int32)
    return model.init(rng, tokens)["params"]
