"""SmallThinker-shaped transformer (``model_name`` smallthinker_21b_instruct:
SmallThinker-21BA3B-Instruct), TPU-first, for the serving stack: a layer
whose **router reads the attention's input**, so its choice of experts is
known before attention starts, ReGLU experts every layer, a full layer
without positions followed by three rotary window layers a period, and an
untied head.

No reference analogue (the reference serves such models through vLLM). What
is this family's own is the order inside a layer and the order of layers;
the rest is shared: the attention is ``models/llama.Attention`` (its
``window`` ring and rotate-half rotary embedding on the window layers,
``rope=False`` on the full ones; a whole prompt goes through
``ops/flash_attention.py``, under the band in a window layer), the routed
part ``models/moe.MoEFFN`` in its two steps (``route`` on one tensor, the
experts on another; softmax router, kept weights normalised over the
chosen, ``expert_activation="reglu"``), the norms ``ops/rmsnorm.py``.

A layer, names as the published config's keys (``h`` the residual stream,
layer ``i`` *full* when ``i % layer_period == 0``, which is where both
``sliding_window_layout[i]`` and ``rope_layout[i]`` are 0, else *window*)::

    n1     = RMSNorm(h; input_layernorm)                   # float32
    s      = n1 W_r;  T = the moe_num_active_primary_experts largest of s
    w_j    = exp(s_j) / sum_{k in T} exp(s_k)              # softmax over the kept
    q,k,v  = n1 W_q, n1 W_k, n1 W_v    # num_attention_heads / num_key_value_heads x head_dim
    window : q, k = rope(q, k), halves (x[i], x[i + d/2]), whole head;
             key j visible to query t iff t - sliding_window_size < j <= t
    full   : no rotary embedding; key j visible iff j <= t
    a      = h + concat(softmax(q k^T / sqrt(head_dim)) v) W_o
    n2     = RMSNorm(a; post_attention_layernorm)
    h'     = a + sum_{j in T} w_j W_down,j (relu(n2 W_gate,j) * (n2 W_up,j))
    logits = RMSNorm(h_L; norm) W_head                     # untied

The softmax over the kept six is ``top_k_routing(scoring="softmax",
normalize=True)``: a softmax over all the experts divided by the kept ones'
sum. There is no bias, no q/k norm, no shared or secondary expert, no dense
layer and no routed scale.

What a row keeps between steps (the ``cache`` collection): a window layer
``window_key`` / ``window_value`` ``(batch, kv_heads, sliding_window,
head_dim)`` (``models.WINDOW``: position ``p`` at slot ``p % ring``) beside
its ``cache_index``, a full layer ``llama``'s three. A family with a ring
in its rows gets no prefix reuse and no prefill chunk (``models.refusals``).

``init_params``: every weight drawn in float32 and rounded to
``param_dtype`` (a bf16 draw is biased: ``solar_open2.init_params``); every
projection and the head a fan-in normal, an expert's matrices at one
expert's fan-in, the embedding a normal at 0.02, the norms one.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..ops.rmsnorm import rmsnorm
from ..ops.rope import rope_table
from .llama import Attention, LlamaConfig
from .moe import MoEConfig, MoEFFN

F32 = jnp.float32

# the window layers keep models.WINDOW leaves: the serving stack gives such
# a family no prefix reuse (models/__init__.py); index 0 is an empty ring,
# so a free row needs no zeroing
ROW_WINDOW = True


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    """SmallThinker-21BA3B-Instruct's published sizes are the defaults."""

    vocab_size: int = 151936
    dim: int = 2560
    n_layers: int = 52
    n_heads: int = 28
    n_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 4096
    # layer i is full (and without rotary embedding) when i % layer_period
    # == 0: ``sliding_window_layout`` = ``rope_layout`` = [0, 1, 1, 1] * 13
    layer_period: int = 4
    moe_intermediate: int = 768  # one expert's width
    n_experts: int = 64  # the router's width
    experts_per_token: int = 6
    norm_topk_prob: bool = True
    # (first, stop) of the experts whose weights live here
    # (MoEConfig.experts_held); None is all, and so is (0, n_experts),
    # which also makes the engine keep each row's last choice
    # (llm/engine.py ``_new_expert_counts``)
    experts_held: Optional[Tuple[int, int]] = None
    max_seq_len: int = 4096
    rope_theta: float = 1.5e6
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.experts_held is not None:
            object.__setattr__(
                self, "experts_held", tuple(self.experts_held))
        if self.layer_period < 1 or self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"SmallThinkerConfig: layer_period {self.layer_period} and "
                f"{self.n_heads} heads over {self.n_kv_heads} K/V heads"
            )
        # refuses what it cannot be built from
        self.routed_config()

    @property
    def routed_layers(self) -> Tuple[int, ...]:
        """The layers that sow their routing: all of them."""
        return tuple(range(self.n_layers))

    def is_window(self, i: int) -> bool:
        return i % self.layer_period != 0

    def attention_config(self, window: bool) -> LlamaConfig:
        """A layer's attention as ``llama.Attention`` takes it: a ring and
        rotate-half rotary embedding in a window layer, neither in a full
        one."""
        return LlamaConfig(
            vocab_size=self.vocab_size, dim=self.dim, n_layers=self.n_layers,
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            max_seq_len=self.max_seq_len, rope_theta=self.rope_theta,
            norm_eps=self.norm_eps, dtype=self.dtype,
            param_dtype=self.param_dtype, remat=False,
            attn_head_dim=self.head_dim, rope=window,
            window=self.sliding_window if window else None,
        )

    def routed_config(self) -> MoEConfig:
        """A layer's experts and their router as ``MoEFFN`` takes them."""
        return MoEConfig(
            dim=self.dim, intermediate=self.moe_intermediate,
            n_experts=self.n_experts,
            experts_per_token=self.experts_per_token,
            norm_topk_prob=self.norm_topk_prob, norm_eps=self.norm_eps,
            dtype=self.dtype, param_dtype=self.param_dtype, dropless=True,
            router_scoring="softmax", expert_activation="reglu",
            experts_held=self.experts_held,
        )

    @staticmethod
    def tiny(**kw) -> "SmallThinkerConfig":
        """Test-scale config of the same shape (a group of 7 query heads a
        K/V head included): runs on the CPU in seconds."""
        defaults = dict(
            vocab_size=256, dim=64, n_layers=4, n_heads=7, n_kv_heads=1,
            head_dim=16, sliding_window=24, moe_intermediate=48,
            n_experts=16, experts_per_token=4, max_seq_len=512,
        )
        defaults.update(kw)
        return SmallThinkerConfig(**defaults)


def _norm_weight(module: nn.Module, name: str, cfg: SmallThinkerConfig):
    return module.param(
        name,
        nn.with_logical_partitioning(nn.initializers.ones_init(), ("embed",)),
        (cfg.dim,),
        cfg.param_dtype,
    )


class Block(nn.Module):
    """``a = h + attn(n1)``, ``h' = a + experts(n2)`` under the routing of
    ``n1``: the router runs ahead of the attention, on its input."""

    config: SmallThinkerConfig
    window: bool
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config

        def normed(h, name):
            with jax.named_scope("sthink.norm"):
                return rmsnorm(
                    h, _norm_weight(self, name, cfg).astype(h.dtype),
                    cfg.norm_eps, self.mesh)

        n1 = normed(x, "attn_norm")
        moe = MoEFFN(cfg.routed_config(), name="moe")
        # the family's scope around MoEFFN's own ``moe.route``: one span,
        # the outer name the one this family's metrics read
        with jax.named_scope("sthink.route"):
            routing = moe.route(n1)
        # a scope a kind of layer: the kernel's time is the trace's own
        # (decode_attention, flash_fwd), the rest of it q/k/v/o
        with jax.named_scope(
                "sthink.attn_window" if self.window else "sthink.attn_full"):
            a = x + Attention(
                cfg.attention_config(self.window), self.mesh, True,
                name="attn",
            )(n1, cos, sin)
        return a + moe(normed(a, "ffn_norm"), routing)


class SmallThinker(nn.Module):
    config: SmallThinkerConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, tokens, adapters=None, adapter_slots=None):
        # tokens: (batch, seq) int32. The family has no adapter placement
        # (models.refusals): the two arguments are the engine's calling
        # convention and must stay None
        if adapters is not None:
            raise ValueError("the smallthinker family takes no adapter bank")
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
        cos, sin = rope_table(cfg.max_seq_len, cfg.head_dim, cfg.rope_theta)
        for i in range(cfg.n_layers):
            x = Block(cfg, cfg.is_window(i), self.mesh, name=f"layer_{i}")(
                x, cos, sin)
        with jax.named_scope("sthink.norm"):
            x = rmsnorm(
                x, _norm_weight(self, "final_norm", cfg).astype(x.dtype),
                cfg.norm_eps, self.mesh)
        head = self.param(
            "lm_head",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "vocab")
            ),
            (cfg.dim, cfg.vocab_size),
            cfg.param_dtype,
        )
        return x @ head.astype(x.dtype)


def build(config: SmallThinkerConfig, mesh: Optional[Mesh] = None,
          decode: bool = False):
    """What ``ray_tpu.models.build`` returns for this family: the serving
    module, which keeps a cache whenever it is applied (a whole sequence
    without one is a prefill into a fresh row)."""
    if not decode:
        raise NotImplementedError(
            "the smallthinker family has a serving path only (decode=True)"
        )
    return SmallThinker(config, mesh)


def init_params(config: SmallThinkerConfig, rng, mesh: Optional[Mesh] = None,
                seq: int = 8):
    """Seeded weights (module docstring), made by one compiled program: the
    forward pass that places them is traced and never run. Drawn in float32
    and then cast to ``param_dtype`` (``solar_open2.init_params`` says what
    a bf16 draw does to a router at these widths)."""
    model = SmallThinker(dataclasses.replace(config, param_dtype=F32), mesh)
    tokens = jnp.zeros((1, seq), jnp.int32)

    def make(key):
        return jax.tree.map(
            lambda w: w.astype(config.param_dtype),
            model.init(key, tokens)["params"])

    return jax.jit(make)(rng)
