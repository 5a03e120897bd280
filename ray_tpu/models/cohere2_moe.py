"""Cohere2-MoE-shaped transformer (``model_type`` cohere2_moe: Command A+),
TPU-first, for the serving stack: a **parallel block** whose attention and
feed-forward both read one LayerNorm of the residual stream and are added
to it together, window layers that keep plain K/V heads in a ring three
layers in four, routed experts as wide as the model beside four shared
experts whose outputs are averaged, and one matrix for the embedding and
the head.

No reference analogue (the reference serves such models through vLLM). What
is this family's own is the LayerNorm, the block and the order of layers;
the rest is shared: the attention is ``models/llama.Attention`` (its
``window`` ring and interleaved rotary pairs on the window layers,
``rope=False`` on the full ones; a whole prompt goes through
``ops/flash_attention.py``, under the band in a window layer), the routed
part ``models/moe.MoEFFN`` (sigmoid router, kept weights normalised over
the chosen, ``experts_held``: one chip's share), the shared experts
``models/deepseek.SwiGLU`` (all four as one matrix triple).

A layer, names as the published config's keys (``h`` the residual stream,
layer ``i`` a *window* layer when ``i % layer_switch != layer_switch - 1``,
else *full*: ``layer_types``)::

    n      = LayerNorm(h) = (h - mean(h)) / sqrt(var(h) + eps) * g   # float32, no bias
    q,k,v  = n W_q, n W_k, n W_v      # num_attention_heads / num_key_value_heads x head_dim
    window : q, k = rope(q, k), pairs (x[2i], x[2i+1]) (rope_gptj);
             key j visible to query t iff t - sliding_window < j <= t
    full   : no rotary embedding; key j visible iff j <= t
    attn   = concat(softmax(q k^T / sqrt(head_dim)) v) W_o
    s      = sigmoid(n W_r);  T = the num_experts_per_tok largest;  w_j = s_j / sum_T s
    ffn    = sum_{j in T, held here} w_j E_j(n) + (1 / num_shared_experts) sum_m S_m(n)
    h'     = h + attn + ffn                                          # use_parallel_block
    logits = logit_scale * LayerNorm(h_L) Emb^T                      # tie_word_embeddings

``E_j`` and ``S_m`` are SwiGLUs of ``intermediate_size`` on the model's
width. There is no bias, no q/k norm, no routed scale and no selection
bias; there are no leading dense layers (``first_k_dense_replace`` 0).

What a row keeps between steps (the ``cache`` collection): a window layer
``window_key`` / ``window_value`` ``(batch, kv_heads, sliding_window,
head_dim)`` (``models.WINDOW``: position ``p`` at slot ``p % ring``) beside
its ``cache_index``, a full layer ``llama``'s three. A family with a ring
in its rows gets no prefix reuse and no prefill chunk (``models.refusals``).

The one tied matrix is the parameter ``lm_head`` ``(vocab, dim)``, rows as
the published ``lm_head.weight`` has them: the embedding gathers its rows,
the head contracts its columns.

``init_params``: every weight drawn in float32 and rounded to
``param_dtype`` (a bf16 draw is biased: ``solar_open2.init_params``); every
projection a fan-in normal, the tied matrix a normal at ``1 / sqrt(dim)``
(the head's fan-in, and near the published 0.02 at 4096), the norms one.
The four shared experts' ``w_down`` is drawn at the fan-in of the four
together.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..ops.rope import rope_table
from .deepseek import SwiGLU
from .llama import Attention, LlamaConfig
from .moe import MoEConfig, MoEFFN

F32 = jnp.float32

# the window layers keep models.WINDOW leaves: the serving stack gives such
# a family no prefix reuse (models/__init__.py); index 0 is an empty ring,
# so a free row needs no zeroing
ROW_WINDOW = True


@dataclasses.dataclass(frozen=True)
class Cohere2MoEConfig:
    """command-a-plus-05-2026's published sizes are the defaults."""

    vocab_size: int = 262144
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 128
    n_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 4096
    # layer i is full when i % layer_switch == layer_switch - 1
    layer_switch: int = 4
    intermediate: int = 4096  # one expert's width, routed or shared
    n_experts: int = 128  # routed: the router's width
    experts_per_token: int = 8
    n_shared_experts: int = 4
    norm_topk_prob: bool = True
    # (first, stop) of the routed experts whose weights live here: one
    # chip's share of a layer (MoEConfig.experts_held); None is all
    experts_held: Optional[Tuple[int, int]] = None
    logit_scale: float = 1.0
    max_seq_len: int = 4096
    rope_theta: float = 50000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.experts_held is not None:
            object.__setattr__(
                self, "experts_held", tuple(self.experts_held))
        if self.layer_switch < 1 or self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"Cohere2MoEConfig: layer_switch {self.layer_switch} and "
                f"{self.n_heads} heads over {self.n_kv_heads} K/V heads"
            )
        # refuses what it cannot be built from
        self.routed_config()

    @property
    def routed_layers(self) -> Tuple[int, ...]:
        """The layers that sow their routing: all of them."""
        return tuple(range(self.n_layers))

    def is_window(self, i: int) -> bool:
        return i % self.layer_switch != self.layer_switch - 1

    def attention_config(self, window: bool) -> LlamaConfig:
        """A layer's attention as ``llama.Attention`` takes it: a ring and
        interleaved rotary pairs in a window layer, neither in a full one."""
        return LlamaConfig(
            vocab_size=self.vocab_size, dim=self.dim, n_layers=self.n_layers,
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            max_seq_len=self.max_seq_len, rope_theta=self.rope_theta,
            norm_eps=self.norm_eps, dtype=self.dtype,
            param_dtype=self.param_dtype, remat=False,
            attn_head_dim=self.head_dim, rope=window, rope_interleaved=True,
            window=self.sliding_window if window else None,
        )

    def routed_config(self) -> MoEConfig:
        """The routed part of a layer as ``MoEFFN`` takes it."""
        return MoEConfig(
            dim=self.dim, intermediate=self.intermediate,
            n_experts=self.n_experts,
            experts_per_token=self.experts_per_token,
            norm_topk_prob=self.norm_topk_prob, norm_eps=self.norm_eps,
            dtype=self.dtype, param_dtype=self.param_dtype, dropless=True,
            router_scoring="sigmoid", experts_held=self.experts_held,
        )

    @staticmethod
    def tiny(**kw) -> "Cohere2MoEConfig":
        """Test-scale config of the same shape: runs on the CPU in seconds."""
        defaults = dict(
            vocab_size=256, dim=64, n_layers=4, n_heads=8, n_kv_heads=2,
            head_dim=16, sliding_window=24, intermediate=48, n_experts=16,
            experts_per_token=4, n_shared_experts=2, max_seq_len=512,
        )
        defaults.update(kw)
        return Cohere2MoEConfig(**defaults)


def layer_norm(x, weight, eps: float):
    """Cohere's LayerNorm: the mean taken out, no bias, in float32."""
    x32 = x.astype(F32)
    centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    variance = jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
    return (centred * jax.lax.rsqrt(variance + eps)
            * weight.astype(F32)).astype(x.dtype)


def _norm_weight(module: nn.Module, name: str, cfg: Cohere2MoEConfig):
    return module.param(
        name,
        nn.with_logical_partitioning(nn.initializers.ones_init(), ("embed",)),
        (cfg.dim,),
        cfg.param_dtype,
    )


class Block(nn.Module):
    """``h + attn(n) + ffn(n)``, ``n`` one LayerNorm of ``h``."""

    config: Cohere2MoEConfig
    window: bool
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        with jax.named_scope("c2moe.norm"):
            n = layer_norm(x, _norm_weight(self, "norm", cfg), cfg.norm_eps)
        # a scope a kind of layer: the kernel's time is the trace's own
        # (decode_attention, flash_fwd), the rest of it q/k/v/o
        with jax.named_scope(
                "c2moe.attn_window" if self.window else "c2moe.attn_full"):
            attn = Attention(
                cfg.attention_config(self.window), self.mesh, True,
                name="attn",
            )(n, cos, sin)
        routed = MoEFFN(cfg.routed_config(), name="moe")(n)
        with jax.named_scope("moe.shared"):
            # the mean of the shared experts' outputs
            shared = SwiGLU(
                cfg, cfg.n_shared_experts * cfg.intermediate, name="shared",
            )(n) / cfg.n_shared_experts
        return x + attn + routed + shared


class Cohere2MoE(nn.Module):
    config: Cohere2MoEConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, tokens, adapters=None, adapter_slots=None):
        # tokens: (batch, seq) int32. The family has no adapter placement
        # (models.refusals): the two arguments are the engine's calling
        # convention and must stay None
        if adapters is not None:
            raise ValueError("the cohere2_moe family takes no adapter bank")
        cfg = self.config
        tied = self.param(
            "lm_head",
            nn.with_logical_partitioning(
                nn.initializers.normal(cfg.dim ** -0.5), ("vocab", "embed")
            ),
            (cfg.vocab_size, cfg.dim),
            cfg.param_dtype,
        ).astype(cfg.dtype)
        x = tied[tokens]
        cos, sin = rope_table(cfg.max_seq_len, cfg.head_dim, cfg.rope_theta)
        for i in range(cfg.n_layers):
            x = Block(cfg, cfg.is_window(i), self.mesh, name=f"layer_{i}")(
                x, cos, sin)
        x = layer_norm(
            x, _norm_weight(self, "final_norm", cfg), cfg.norm_eps)
        logits = jnp.einsum("bsd,vd->bsv", x, tied)
        return logits if cfg.logit_scale == 1.0 else logits * cfg.logit_scale


def build(config: Cohere2MoEConfig, mesh: Optional[Mesh] = None,
          decode: bool = False):
    """What ``ray_tpu.models.build`` returns for this family: the serving
    module, which keeps a cache whenever it is applied (a whole sequence
    without one is a prefill into a fresh row)."""
    if not decode:
        raise NotImplementedError(
            "the cohere2_moe family has a serving path only (decode=True)"
        )
    return Cohere2MoE(config, mesh)


def init_params(config: Cohere2MoEConfig, rng, mesh: Optional[Mesh] = None,
                seq: int = 8):
    """Seeded weights (module docstring), made by one compiled program: the
    forward pass that places them is traced and never run. Drawn in float32
    and then cast to ``param_dtype`` (``solar_open2.init_params`` says what
    a bf16 draw does to a router at these widths)."""
    model = Cohere2MoE(dataclasses.replace(config, param_dtype=F32), mesh)
    tokens = jnp.zeros((1, seq), jnp.int32)

    def make(key):
        return jax.tree.map(
            lambda w: w.astype(config.param_dtype),
            model.init(key, tokens)["params"])

    return jax.jit(make)(rng)
