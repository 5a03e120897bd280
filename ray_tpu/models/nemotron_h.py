"""Nemotron-H-shaped transformer (``model_type`` nemotron_h), TPU-first, for
the serving stack: a stack of **single-mixer layers**. Every layer is one
pre-norm sub-block, ``x = x + f_i(RMSNorm(x))``, and a character of
``hybrid_override_pattern`` says which ``f_i``: ``M`` a Mamba-2 mixer,
``*`` a grouped-query attention without positional embedding, ``E`` a
latent mixture of experts beside a shared expert. No layer has two of them.

No reference analogue (the reference serves such models through vLLM).
Nothing here is this family's own but the order of the layers and the
shared expert: the mixer is ``models/falcon_h1.Mixer`` at this family's
sizes (``MixerConfig``, every multiplier 1: 128 heads of 64, state 128, 8
groups), with its ``ssm_step`` / ``ssm_chunked`` and its float32 state; the
attention is ``models/llama.Attention`` with ``rope=False`` at 32 query
heads over 2 KV heads; the routed experts are ``models/moe.MoEFFN`` under
``MoEConfig.latent_dim`` (the experts work in a ``moe_latent`` wide space
between two projections the layer's experts share),
``expert_activation="relu2"`` (``down(relu(up l)^2)``, two matrices an
expert: ``ops/moe_experts.py``), the sigmoid router with a selection bias
of ``parallel/expert.top_k_routing`` and ``experts_held`` (one chip's share).

A layer, names as the published config's keys (``h = RMSNorm(x)``):

- ``M``: ``falcon_h1``'s module docstring, with ``mamba_num_heads`` heads of
  ``mamba_head_dim``, ``ssm_state_size``, ``n_groups``, ``conv_kernel`` taps
  with bias, no projection bias
- ``*``: ``q, k, v = h W_q, h W_k, h W_v``; causal softmax attention at
  ``1 / sqrt(head_dim)``; ``W_o``. No rotary embedding: the mixers carry
  position
- ``E``: ``s = sigmoid(h W_r)`` over ``n_routed_experts``; the
  ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``;
  weights ``s`` of the chosen over their sum, times
  ``routed_scaling_factor``; ``l = h W_lat_in``; ``r = sum_j w_j W_down_j
  relu(W_up_j l)^2``; ``out = r W_lat_out + W_sdown relu(W_sup h)^2``

What a row keeps between steps (the ``cache`` collection) **differs by
layer**: an ``M`` layer ``state_ssm`` ``(batch, heads, d_head, d_state)``
float32 and ``state_conv`` (``models.STATE``), a ``*`` layer ``llama``'s
``cached_key`` / ``cached_value`` / ``cache_index``, an ``E`` layer nothing.
The engine and the cache manager walk the tree by each leaf's kind and
never by layer, so a layer without leaves is simply absent from it.

``init_params``: every weight drawn in float32 and rounded to
``param_dtype`` (a bf16 draw is biased: ``solar_open2.init_params``); every
projection a fan-in normal, the embedding a unit normal; the mixer's
``dt_bias``, ``A_log``, ``D``, convolution and bias as Mamba-2 initialises
them (``falcon_h1``); the router's bias zero.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..ops.rmsnorm import rmsnorm
from . import ROUTING  # noqa: F401  (MoEFFN sows into it)
from .falcon_h1 import Mixer, MixerConfig
from .llama import Attention, LlamaConfig, _dense
from .moe import MoEConfig, MoEFFN

F32 = jnp.float32
MIXER, ATTENTION, EXPERTS = "M", "*", "E"

# the mixer layers keep models.STATE leaves: the serving stack gives such a
# family no prefix reuse (models/__init__.py); the engine zeroes a free
# row's state (falcon_h1's mixer reads what the leaf holds)
ROW_STATE = True

# NVIDIA-Nemotron-3-Super-120B-A12B's 88 layers
_PUBLISHED_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEMEM*EMEMEMEM*EMEMEMEME"
)


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """NVIDIA-Nemotron-3-Super-120B-A12B's published sizes are the
    defaults."""

    vocab_size: int = 131072
    dim: int = 4096
    # a character a layer (module docstring): ``hybrid_override_pattern``
    pattern: str = _PUBLISHED_PATTERN
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 8
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    moe_intermediate: int = 2688
    moe_latent: int = 1024
    shared_intermediate: int = 5376
    n_experts: int = 512  # routed: the router's width
    experts_per_token: int = 22
    norm_topk_prob: bool = True
    routed_scale: float = 5.0
    # (first, stop) of the routed experts whose weights live here: one
    # chip's share of a layer (MoEConfig.experts_held); None is all
    experts_held: Optional[Tuple[int, int]] = None
    max_seq_len: int = 4096
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        unknown = set(self.pattern) - {MIXER, ATTENTION, EXPERTS}
        if unknown or not self.pattern:
            raise ValueError(
                f"NemotronHConfig: pattern {self.pattern!r} has layers that "
                f"are none of {MIXER!r}, {ATTENTION!r}, {EXPERTS!r}"
            )
        if self.experts_held is not None:
            object.__setattr__(
                self, "experts_held", tuple(self.experts_held))
        # each refuses what it cannot be built from
        self.mixer_config(), self.routed_config()

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def routed_layers(self) -> Tuple[int, ...]:
        """The layers that sow their routing: the expert layers."""
        return tuple(
            i for i, kind in enumerate(self.pattern) if kind == EXPERTS)

    def mixer_config(self) -> MixerConfig:
        """An ``M`` layer as ``falcon_h1.Mixer`` takes it."""
        return MixerConfig(
            dim=self.dim, n_heads=self.mamba_n_heads,
            d_head=self.mamba_d_head, d_state=self.mamba_d_state,
            n_groups=self.mamba_n_groups, d_conv=self.mamba_d_conv,
            chunk_size=self.mamba_chunk_size, norm_eps=self.norm_eps,
            dtype=self.dtype, param_dtype=self.param_dtype,
        )

    def attention_config(self) -> LlamaConfig:
        """A ``*`` layer as ``llama.Attention`` takes it."""
        return LlamaConfig(
            vocab_size=self.vocab_size, dim=self.dim, n_layers=self.n_layers,
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            max_seq_len=self.max_seq_len, norm_eps=self.norm_eps,
            dtype=self.dtype, param_dtype=self.param_dtype, remat=False,
            rope=False, attn_head_dim=self.head_dim,
        )

    def routed_config(self) -> MoEConfig:
        """The routed part of an ``E`` layer as ``MoEFFN`` takes it."""
        return MoEConfig(
            dim=self.dim, intermediate=self.moe_intermediate,
            n_experts=self.n_experts,
            experts_per_token=self.experts_per_token,
            norm_topk_prob=self.norm_topk_prob, norm_eps=self.norm_eps,
            dtype=self.dtype, param_dtype=self.param_dtype, dropless=True,
            router_scoring="sigmoid", router_bias=True,
            routed_scale=self.routed_scale, experts_held=self.experts_held,
            expert_activation="relu2", latent_dim=self.moe_latent,
        )

    @staticmethod
    def tiny(**kw) -> "NemotronHConfig":
        """Test-scale config of the same shape: runs on the CPU in seconds."""
        defaults = dict(
            vocab_size=256, dim=64, pattern="ME*E", n_heads=4, n_kv_heads=2,
            head_dim=16, mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16,
            mamba_n_groups=2, mamba_chunk_size=8, moe_intermediate=48,
            moe_latent=32, shared_intermediate=96, n_experts=16,
            experts_per_token=4, max_seq_len=512,
        )
        defaults.update(kw)
        return NemotronHConfig(**defaults)


class SharedExpert(nn.Module):
    """``W_sdown relu(W_sup h)^2`` on the model's width: an ``E`` layer's
    shared expert, ungated like the routed ones."""

    config: NemotronHConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        up = _dense(
            cfg.shared_intermediate, ("embed", "mlp"), "w_up",
            cfg.param_dtype, cfg.dtype)(h)
        return _dense(
            cfg.dim, ("mlp", "embed"), "w_down", cfg.param_dtype, cfg.dtype,
        )(jnp.square(nn.relu(up)))


class Layer(nn.Module):
    config: NemotronHConfig
    kind: str
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        norm_w = self.param(
            "norm",
            nn.with_logical_partitioning(
                nn.initializers.ones_init(), ("embed",)),
            (cfg.dim,),
            cfg.param_dtype,
        )
        h = rmsnorm(x, norm_w.astype(x.dtype), cfg.norm_eps, self.mesh)
        if self.kind == MIXER:
            return x + Mixer(cfg.mixer_config(), name="mixer")(h)
        if self.kind == ATTENTION:
            # no rope: the tables are never read
            return x + Attention(
                cfg.attention_config(), self.mesh, True, name="attn",
            )(h, None, None)
        routed = MoEFFN(cfg.routed_config(), name="moe")(h)
        with jax.named_scope("moe.shared"):
            shared = SharedExpert(cfg, name="shared")(h)
        return x + routed + shared


class NemotronH(nn.Module):
    config: NemotronHConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, tokens, adapters=None, adapter_slots=None):
        # tokens: (batch, seq) int32. The family has no adapter placement
        # (models.refusals): the two arguments are the engine's calling
        # convention and must stay None
        if adapters is not None:
            raise ValueError("the nemotron_h family takes no adapter bank")
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
        for i, kind in enumerate(cfg.pattern):
            x = Layer(cfg, kind, self.mesh, name=f"layer_{i}")(x)
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


def build(config: NemotronHConfig, mesh: Optional[Mesh] = None,
          decode: bool = False):
    """What ``ray_tpu.models.build`` returns for this family: the serving
    module, which keeps a cache whenever it is applied (a whole sequence
    without one is a prefill into a fresh row)."""
    if not decode:
        raise NotImplementedError(
            "the nemotron_h family has a serving path only (decode=True)"
        )
    return NemotronH(config, mesh)


def init_params(config: NemotronHConfig, rng, mesh: Optional[Mesh] = None,
                seq: int = 8):
    """Seeded weights (module docstring), made by one compiled program: the
    forward pass that places them is traced and never run. Drawn in float32
    and then cast to ``param_dtype`` (``solar_open2.init_params`` says what
    a bf16 draw does to a router at these widths)."""
    model = NemotronH(dataclasses.replace(config, param_dtype=F32), mesh)
    tokens = jnp.zeros((1, seq), jnp.int32)

    def make(key):
        return jax.tree.map(
            lambda w: w.astype(config.param_dtype),
            model.init(key, tokens)["params"])

    return jax.jit(make)(rng)
