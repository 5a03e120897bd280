"""Mixture-of-Experts transformer (Mixtral- and OLMoE-shaped), TPU-first.

No reference analogue (the reference serves MoE through vLLM engine kwargs
— SURVEY §2c "EP delegated"); here the framework owns the model layer.
LLaMA attention blocks (the shared ``models/llama.Attention``, decode cache
included) with the dense FFN replaced by a top-k routed expert FFN. Two
expert paths (parallel/expert.py):

- capacity (``dropless=False``, the training default): GShard dispatch and
  combine einsums with a static capacity; expert weights carry the
  ``expert`` logical axis and GSPMD lowers the einsums to all_to_alls over
  ``ep``. A token past an expert's capacity is dropped.
- dropless (``dropless=True``; serving always): the assignments sorted by
  expert through one grouped kernel (ops/moe_experts.py). Nothing is
  dropped, so a row's answer does not depend on its batch.

OLMoE (``modeling_olmoe.py``) is ``qk_norm=True, norm_topk_prob=False``:
RMSNorm over the whole q and k projections, and top-k weights that are not
renormalised.
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
from ..parallel.expert import (
    expert_capacity,
    moe_apply_dropless,
    moe_apply_gspmd,
    top_k_gating,
    top_k_routing,
)
from . import ROUTING
from .llama import Attention, LlamaConfig


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    intermediate: int = 14336
    n_experts: int = 8
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.02
    max_seq_len: int = 4096
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # no capacity: every assignment is computed (see the module docstring)
    dropless: bool = False
    # divide the kept top-k weights by their sum (Mixtral: yes, OLMoE: no)
    norm_topk_prob: bool = True
    # RMSNorm over the whole q and k projections (OLMoE)
    qk_norm: bool = False
    # the router of parallel/expert.top_k_routing: "softmax" over all
    # experts, or each expert's own "sigmoid" (DeepSeek-V3's noaux_tc)
    router_scoring: str = "softmax"
    # a learned per-expert bias added to the scores for the choice of
    # experts only (``router_bias`` parameter, zero-initialised)
    router_bias: bool = False
    # the kept weights times this (``routed_scaling_factor``)
    routed_scale: float = 1.0
    # ``(first, stop)``: the experts whose weights live here, one chip's
    # share of a layer whose ``n_experts`` lie on several; None is all of
    # them. The router keeps its ``n_experts`` outputs and its
    # ``experts_per_token``, the kept weights are normalised over the
    # experts chosen wherever they live, and the layer gives the part of
    # the result its own experts give (parallel/expert.moe_apply_dropless)
    experts_held: Optional[Tuple[int, int]] = None
    # an expert's activation: "swiglu" (``silu(gate) * up``), or Motif's
    # "poly_norm" (``P(gate) * up``, ops/moe_experts.py), an expert's four
    # coefficients in a ``poly`` parameter: three weights, and a bias
    # clipped to +-``polynorm_bias_clamp``, all times ``polynorm_scale``
    # or Nemotron-H's ungated "relu2" (``down(relu(up x)^2)``: an expert
    # has two matrices, no ``w_gate``), or SmallThinker's "reglu"
    # (``relu(gate) * up``: SwiGLU's three matrices under another gate)
    expert_activation: str = "swiglu"
    polynorm_scale: float = 0.5
    polynorm_bias_clamp: float = 0.5
    # the width the routed experts work in where it is not ``dim``
    # (Nemotron-H's ``moe_latent_size``): one ``dim -> latent_dim``
    # projection in front of a layer's experts and one back behind their
    # weighted sum, shared by them (``w_latent_in`` / ``w_latent_out``);
    # the router stays on the ``dim``-wide input
    latent_dim: Optional[int] = None

    def __post_init__(self):
        if self.expert_activation not in (
                "swiglu", "poly_norm", "relu2", "reglu"):
            raise ValueError(
                f"MoEConfig: unknown expert_activation "
                f"{self.expert_activation!r}"
            )
        if not self.dropless and (
                self.expert_activation not in ("swiglu", "reglu")
                or self.latent_dim):
            raise ValueError(
                f"MoEConfig: expert_activation {self.expert_activation!r}"
                f" with latent_dim {self.latent_dim} needs dropless=True: "
                "the capacity path's experts are gated (SwiGLU or ReGLU) on "
                "the model's width"
            )
        if self.experts_held is not None:
            first, stop = self.experts_held
            object.__setattr__(self, "experts_held", (first, stop))
            if not self.dropless or not 0 <= first < stop <= self.n_experts:
                raise ValueError(
                    f"MoEConfig: experts_held {self.experts_held} of "
                    f"{self.n_experts} experts needs dropless=True and a "
                    "range inside them: the capacity path dispatches to "
                    "every expert"
                )
        if not self.dropless and (
            self.router_scoring != "softmax" or self.router_bias
            or self.routed_scale != 1.0
        ):
            raise ValueError(
                "MoEConfig: a sigmoid router, a selection bias or a routed "
                "scale needs dropless=True: the capacity path "
                "(parallel/expert.top_k_gating) is a plain softmax"
            )
        if not self.dropless and not self.norm_topk_prob:
            raise ValueError(
                "MoEConfig(norm_topk_prob=False) needs dropless=True: the "
                "capacity path (parallel/expert.top_k_gating) always "
                "divides the kept weights by their sum, so it would train "
                "Mixtral's mathematics under OLMoE's setting"
            )

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def expert_dim(self) -> int:
        """The width a routed expert reads and writes."""
        return self.latent_dim or self.dim

    @property
    def n_experts_held(self) -> int:
        if self.experts_held is None:
            return self.n_experts
        return self.experts_held[1] - self.experts_held[0]

    def attention_config(self) -> LlamaConfig:
        return LlamaConfig(
            vocab_size=self.vocab_size,
            dim=self.dim,
            n_layers=self.n_layers,
            n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads,
            intermediate=self.intermediate,
            max_seq_len=self.max_seq_len,
            rope_theta=self.rope_theta,
            norm_eps=self.norm_eps,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            remat=self.remat,
            qk_norm=self.qk_norm,
        )

    @staticmethod
    def mixtral_8x7b(**kw) -> "MoEConfig":
        return MoEConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "MoEConfig":
        defaults = dict(
            vocab_size=256, dim=128, n_layers=2, n_heads=4, n_kv_heads=4,
            intermediate=256, n_experts=4, experts_per_token=2,
            max_seq_len=512, remat=False,
        )
        defaults.update(kw)
        return MoEConfig(**defaults)


def poly_init(key, shape, dtype=jnp.float32):
    """A PolyNorm's ``[w1, w2, w3, b]`` (last axis): the weights a third
    each, as published (arXiv:2411.03884), with a tenth of noise so that a
    seeded model's three terms differ; the bias normal at 0.4, so that the
    clamp at 0.5 bites on a fifth of them."""
    noise = jax.random.normal(key, shape, jnp.float32)
    centre = jnp.asarray([1 / 3, 1 / 3, 1 / 3, 0.0], jnp.float32)
    spread = jnp.asarray([0.1, 0.1, 0.1, 0.4], jnp.float32)
    return (centre + spread * noise).astype(dtype)


def poly_coefficients(poly, scale: float, bias_clamp: float):
    """``c1 .. c4`` of ``P(z) = c1 N(z^3) + c2 N(z^2) + c3 N(z) + c4`` from
    a ``poly`` parameter ``(..., 4)``: the output scale times the weights
    and times the bias clipped to +-``bias_clamp``."""
    poly = poly.astype(jnp.float32)
    return scale * jnp.concatenate(
        [poly[..., :3], jnp.clip(poly[..., 3:], -bias_clamp, bias_clamp)],
        axis=-1,
    )


def poly_norm(z, c, eps: float):
    """``P(z)`` over ``z``'s last axis in XLA, ``c`` the four coefficients
    (``poly_coefficients``): what ``ops/moe_experts.py`` computes a routed
    expert's rows with, for a dense feed-forward."""
    def normed(t):
        return t * jax.lax.rsqrt(
            jnp.mean(t * t, axis=-1, keepdims=True) + eps)

    z2 = z * z
    return c[0] * normed(z2 * z) + c[1] * normed(z2) + c[2] * normed(z) + c[3]


def _latent(cfg: MoEConfig, features: int):
    """One of a latent expert layer's two shared projections."""
    return nn.DenseGeneral(
        features=features, use_bias=False, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), ("embed", "mlp")),
    )


class MoEFFN(nn.Module):
    """Top-k routed expert FFN (SwiGLU experts on the model's width unless
    the config says otherwise). Router aux loss is emitted through the
    ``losses`` collection (sown) for the trainer to add.

    Two steps, ``route`` and the experts. ``__call__(x)`` takes both on
    ``x``. A family whose router reads another tensor than its experts
    (SmallThinker's reads the *attention's* input) calls ``route`` on that
    tensor itself, where in its layer it likes, and hands the result on:
    ``moe(x, moe.route(other))``."""

    config: MoEConfig

    def setup(self):
        cfg = self.config
        # (in the order they were always made: a parameter's key counts
        # the parameters made before it)
        self.router = self.param(
            "router",
            nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "expert")
            ),
            (cfg.dim, cfg.n_experts),
            cfg.param_dtype,
        )
        self.router_bias = self.param(
            "router_bias",
            nn.with_logical_partitioning(
                nn.initializers.zeros_init(), ("expert",)
            ),
            (cfg.n_experts,),
            jnp.float32,
        ) if cfg.dropless and cfg.router_bias else None

        # fan-in of one expert's matrix, not of all of them together: with
        # the expert axis counted in, every matrix is sqrt(n_experts) too
        # small and a random model's logits do not depend on its experts
        # (measured on the chip at OLMoE's widths, PERF.md finding 25.5)
        per_expert = nn.initializers.lecun_normal(batch_axis=(0,))
        width = cfg.expert_dim

        def expert_matrix(name, shape, axes):
            return self.param(
                name, nn.with_logical_partitioning(per_expert, axes),
                (cfg.n_experts_held,) + shape, cfg.param_dtype)

        # (an ungated expert has no gate matrix)
        self.w_gate = None if cfg.expert_activation == "relu2" else (
            expert_matrix(
                "w_gate", (width, cfg.intermediate), ("expert", "embed", "mlp")))
        self.w_up = expert_matrix(
            "w_up", (width, cfg.intermediate), ("expert", "embed", "mlp"))
        self.w_down = expert_matrix(
            "w_down", (cfg.intermediate, width), ("expert", "mlp", "embed"))
        self.poly = self.param(
            "poly",
            nn.with_logical_partitioning(poly_init, ("expert", None)),
            (cfg.n_experts_held, 4),
            jnp.float32,
        ) if cfg.expert_activation == "poly_norm" else None
        if cfg.latent_dim:
            self.w_latent_in = _latent(cfg, cfg.latent_dim)
            self.w_latent_out = _latent(cfg, cfg.dim)

    def route(self, x):  # (b, s, d)
        """Each token's experts and their weights, from ``x``: dropless
        ``(weights (b*s, k) f32, chosen (b*s, k) int32)``, sown into
        ``ROUTING``; with a capacity ``(dispatch, combine)``."""
        cfg = self.config
        b, s, d = x.shape
        with jax.named_scope("moe.route"):
            # full f32 products: on a TPU a default-precision f32 matmul
            # rounds its operands to bf16, which is enough to swap the
            # k-th and (k+1)-th expert; the product is tiny
            logits = jnp.dot(
                x.reshape(b * s, d).astype(jnp.float32),
                self.router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
            if cfg.dropless:
                weights, chosen, aux = top_k_routing(
                    logits, cfg.experts_per_token, cfg.norm_topk_prob,
                    cfg.router_scoring, self.router_bias, cfg.routed_scale,
                )
                self.sow(ROUTING, "experts", chosen)
                routing = weights, chosen
            else:
                capacity = expert_capacity(
                    b * s, cfg.n_experts, cfg.capacity_factor,
                    cfg.experts_per_token,
                )
                dispatch, combine, aux = top_k_gating(
                    logits, capacity, k=cfg.experts_per_token
                )
                routing = dispatch, combine
        self.sow("losses", "router_aux", cfg.router_aux_weight * aux)
        return routing

    def __call__(self, x, routing=None):  # (b, s, d)
        cfg = self.config
        b, s, d = x.shape
        if routing is None:
            routing = self.route(x)
        tokens = x.reshape(b * s, d)
        w_gate, w_up, w_down = self.w_gate, self.w_up, self.w_down

        def experts(inp):  # (E, C, d) -> (E, C, d)
            gate = jnp.einsum("ecd,edf->ecf", inp, w_gate.astype(inp.dtype))
            up = jnp.einsum("ecd,edf->ecf", inp, w_up.astype(inp.dtype))
            gated = nn.relu if cfg.expert_activation == "reglu" else nn.silu
            return jnp.einsum(
                "ecf,efd->ecd", gated(gate) * up, w_down.astype(inp.dtype)
            )

        activation = {}
        if cfg.expert_activation == "poly_norm":
            activation = dict(
                activation="poly_norm", eps=cfg.norm_eps,
                poly=poly_coefficients(
                    self.poly, cfg.polynorm_scale, cfg.polynorm_bias_clamp),
            )
        elif cfg.expert_activation != "swiglu":
            activation = dict(activation=cfg.expert_activation)

        if cfg.latent_dim:
            with jax.named_scope("moe.latent"):
                tokens = self.w_latent_in(tokens)
        with jax.named_scope("moe.experts"):
            if cfg.dropless:
                weights, chosen = routing
                out = moe_apply_dropless(
                    tokens, weights, chosen,
                    None if w_gate is None else w_gate.astype(tokens.dtype),
                    w_up.astype(tokens.dtype), w_down.astype(tokens.dtype),
                    held=cfg.experts_held, **activation,
                )
            else:
                out = moe_apply_gspmd(tokens, *routing, experts)
        if cfg.latent_dim:
            with jax.named_scope("moe.latent"):
                out = self.w_latent_out(out)
        return out.reshape(b, s, d)


class MoEBlock(nn.Module):
    config: MoEConfig
    mesh: Optional[Mesh] = None
    decode: bool = False

    @nn.compact
    def __call__(self, x, cos, sin, adapters=None, adapter_slots=None):
        cfg = self.config
        attn_cfg = cfg.attention_config()
        attn_norm_w = self.param(
            "attn_norm",
            nn.with_logical_partitioning(nn.initializers.ones_init(), ("embed",)),
            (cfg.dim,),
            cfg.param_dtype,
        )
        h = x + Attention(attn_cfg, self.mesh, self.decode, name="attn")(
            rmsnorm(x, attn_norm_w.astype(x.dtype), cfg.norm_eps, self.mesh),
            cos, sin,
            (adapters or {}).get("attn"), adapter_slots,
        )
        ffn_norm_w = self.param(
            "ffn_norm",
            nn.with_logical_partitioning(nn.initializers.ones_init(), ("embed",)),
            (cfg.dim,),
            cfg.param_dtype,
        )
        return h + MoEFFN(cfg, name="moe")(
            rmsnorm(h, ffn_norm_w.astype(h.dtype), cfg.norm_eps, self.mesh)
        )


class MoETransformer(nn.Module):
    config: MoEConfig
    mesh: Optional[Mesh] = None
    decode: bool = False

    @nn.compact
    def __call__(self, tokens, adapters=None, adapter_slots=None):
        # tokens: (batch, seq) int32; adapters / adapter_slots as in
        # models/llama.Llama (attention projections only)
        cfg = self.config
        if self.decode and not cfg.dropless:
            raise ValueError(
                "a decode cache needs MoEConfig.dropless: with a capacity, "
                "what a row is answered depends on the rows beside it"
            )
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
        block = MoEBlock
        if cfg.remat:
            block = nn.remat(
                MoEBlock,
                policy=jax.checkpoint_policies.save_only_these_names(),
                prevent_cse=False,
            )
        for i in range(cfg.n_layers):
            x = block(cfg, self.mesh, self.decode, name=f"layer_{i}")(
                x, cos, sin,
                (adapters or {}).get(f"layer_{i}"), adapter_slots,
            )
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


def build(config: MoEConfig, mesh: Optional[Mesh] = None, decode: bool = False):
    """What ``ray_tpu.models.build`` returns for this family."""
    return MoETransformer(config, mesh, decode)


def init_params(config: MoEConfig, rng, mesh: Optional[Mesh] = None, seq: int = 8):
    model = MoETransformer(config, mesh)
    tokens = jnp.zeros((1, seq), jnp.int32)
    return model.init(rng, tokens)["params"]


def next_token_loss(config: MoEConfig, mesh, params, tokens):
    """Causal LM loss + router load-balance aux losses."""
    model = MoETransformer(config, mesh)
    logits, aux = model.apply(
        {"params": params}, tokens, mutable=["losses"]
    )
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    targets = tokens[:, 1:]
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    loss = nll.mean()
    for leaf in jax.tree.leaves(aux.get("losses", {})):
        loss = loss + jnp.sum(leaf)
    return loss
