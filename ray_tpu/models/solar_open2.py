"""Solar-Open2-shaped transformer (``model_type`` solar_open2), TPU-first,
for the serving stack: three layers in four a Kimi Delta Attention (KDA)
mixer, a gated delta rule with a decay a channel; the fourth a gated
grouped-query attention without positional embedding; every layer's
feed-forward a routed expert layer beside a shared expert, of which this
process may hold one chip's share.

No reference analogue (the reference serves such models through vLLM).
What is shared: the GQA layers are ``models/llama.Attention`` with
``rope=False``, ``attn_gate=True`` and a head size of its own (the same
``ops/kv_row_write`` and ``ops/decode_attention``); the routed experts are
``models/moe.MoEFFN`` under ``MoEConfig.experts_held`` and the sigmoid
router of ``parallel/expert.top_k_routing``; the shared expert is
``models/deepseek.SwiGLU``. What is this family's own is the KDA mixer and
what it keeps between steps.

A block, names as the published config's keys: ``h = x +
Mixer_i(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``; layer ``i``'s mixer is
the GQA layer if ``i`` is in ``gqa_layers``, else KDA.

KDA, a head (``linear_attn_config``: ``num_heads`` heads, ``head_dim`` =
``d_k`` = ``d_v``), a position ``t``:

- ``[q | k | v] = silu(conv(x W_qkv))``: a causal depthwise convolution of
  ``short_conv_kernel_size`` taps a channel, no bias
- ``q_t = q / ||q|| * d_k^-1/2``, ``k_t = k / ||k||`` (the norm a head,
  ``rsqrt(sum of squares + 1e-6)``)
- ``g_t = -exp(A_log) * softplus(W_f2 (W_f1 x) + dt_bias)``, a channel of
  ``d_k``; ``A_log`` a head, ``dt_bias`` a channel; ``alpha_t = exp(g_t)``
- ``beta_t = 2 sigmoid(W_b x)`` a head (``kda_allow_neg_eigval``)
- state ``S (d_k, d_v)``: ``S' = Diag(alpha_t) S_{t-1}``; ``S_t = S' +
  beta_t k_t (v_t - S'^T k_t)^T``; ``o_t = S_t^T q_t``
- ``y = W_o concat_h(RMSNorm_{d_v}(o_t) * sigmoid(W_g2 (W_g1 x) + b_g))``

What a row keeps between steps (the ``cache`` collection): a GQA layer's
``cached_key`` / ``cached_value`` / ``cache_index`` as ``llama``'s, and a
KDA layer's **per-row state with no sequence axis** (``models.STATE``):
``state_kda`` ``(batch, heads, d_k, d_v)`` float32 and ``state_conv``
``(batch, taps - 1, 3 x heads x d_k)``, the convolution's last inputs
(channels minor, as ``falcon_h1``'s), beside a ``cache_index`` of its own.
The state is float32 and so is the recurrence. A row whose index is 0
starts from a zero state and tail, whatever the leaves hold
(``RESTARTS_OWN_STATE``): the select rides in the update that reads the
state anyway, where the engine's zeroing of a free row would be a pass over
every state in front of the kernel.

The recurrence has two forms, one function in two orders of rounding
(``tests/test_solar_open2.py`` holds them together and to the per-position
rule): ``seq == 1`` the update above (``ops/kda_step.py``, a Pallas kernel
that reads the state once; ``kda_step`` here is the same in XLA, which
reads it twice), the state written in the donated cache; ``seq > 1`` (a
prefill, and a chunk behind a row's state) the chunked form
(``kda_chunked``) at ``kda_chunk_size``, started from the row's state and
convolution tail.

``init_params``: every weight drawn in float32 and rounded to
``param_dtype`` (a bf16 draw is biased); every projection a fan-in normal;
``A_log`` the log of a uniform in [1, 4] and ``dt_bias`` the inverse
softplus of a log-uniform in [0.005, 0.2], under a unit-variance ``W_f2
W_f1 x``: a channel's ``alpha`` then lies between ~0.3 and ~0.99 (median
~0.9), memories of one to a hundred positions side by side; the router's
bias zero.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..ops.kda_step import kda_step as kda_step_kernel
from ..ops.rmsnorm import rmsnorm
from . import ROUTING  # noqa: F401  (MoEFFN sows into it)
from .deepseek import SwiGLU
from .llama import Attention, LlamaConfig, _dense
from .moe import MoEConfig, MoEFFN

F32 = jnp.float32
# what a row's recurrent state is stored in between steps. The benchmark's
# configuration guarantees float32 and its check refuses a row whose state
# takes other bytes (benchmarks/tests lowers this to show that it does)
STATE_DTYPE = jnp.float32
_L2_EPS = 1e-6

# the mixer keeps models.STATE leaves: the serving stack gives such a family
# no prefix reuse (models/__init__.py)
ROW_STATE = True
# ... and starts a row whose position is 0 from a zero state itself, inside
# the update that reads the state anyway (models/__init__.py): the engine
# leaves this family's state leaves alone
RESTARTS_OWN_STATE = True


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    """Solar-Open2-250B's published sizes are the defaults."""

    vocab_size: int = 196608
    dim: int = 4096
    n_layers: int = 48
    gqa_layers: Tuple[int, ...] = tuple(range(0, 48, 4))
    n_heads: int = 64
    n_kv_heads: int = 8
    head_dim: int = 128
    kda_heads: int = 64
    kda_head_dim: int = 128
    kda_conv: int = 4
    # the low rank of the decay's and the output gate's projections
    # (``kda_use_full_proj: false``; Kimi Linear's is the head size)
    kda_gate_rank: int = 128
    # positions of one chunk of the chunked form. Measured on a v5e, the
    # rule alone at these widths, ms a layer for 1024 positions: 16: 3.18,
    # 32: 3.42, 64: 5.36, 128: 12.2 (256 positions: 0.92 / 1.00 / 1.52 /
    # 3.22; PERF.md, finding 36.4): the pairwise decay sums grow with the
    # chunk, the scan's steps shrink with it
    kda_chunk_size: int = 16
    moe_intermediate: int = 1280
    n_experts: int = 320  # routed: the router's width
    experts_per_token: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scale: float = 1.0
    # (first, stop) of the routed experts whose weights live here: one
    # chip's share of a layer (MoEConfig.experts_held); None is all
    experts_held: Optional[Tuple[int, int]] = None
    max_seq_len: int = 4096
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        object.__setattr__(self, "gqa_layers", tuple(self.gqa_layers))
        if self.experts_held is not None:
            object.__setattr__(
                self, "experts_held", tuple(self.experts_held))
        self.routed_config()  # refuses a range outside the experts

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_head_dim

    def is_gqa(self, layer: int) -> bool:
        return layer in self.gqa_layers

    def attention_config(self) -> LlamaConfig:
        """A GQA layer as ``llama.Attention`` takes it."""
        return LlamaConfig(
            vocab_size=self.vocab_size, dim=self.dim, n_layers=self.n_layers,
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            max_seq_len=self.max_seq_len, norm_eps=self.norm_eps,
            dtype=self.dtype, param_dtype=self.param_dtype, remat=False,
            rope=False, attn_gate=True, attn_head_dim=self.head_dim,
        )

    def routed_config(self) -> MoEConfig:
        """The routed part of a layer as ``MoEFFN`` takes it."""
        return MoEConfig(
            dim=self.dim, intermediate=self.moe_intermediate,
            n_experts=self.n_experts,
            experts_per_token=self.experts_per_token,
            norm_topk_prob=self.norm_topk_prob, dtype=self.dtype,
            param_dtype=self.param_dtype, dropless=True,
            router_scoring="sigmoid", router_bias=True,
            routed_scale=self.routed_scale, experts_held=self.experts_held,
        )

    @staticmethod
    def tiny(**kw) -> "SolarOpen2Config":
        """Test-scale config of the same shape: runs on the CPU in seconds."""
        defaults = dict(
            vocab_size=256, dim=64, n_layers=4, gqa_layers=(0,), n_heads=4,
            n_kv_heads=2, head_dim=16, kda_heads=4, kda_head_dim=16,
            kda_gate_rank=16, kda_chunk_size=8, moe_intermediate=32,
            n_experts=16, experts_per_token=4, max_seq_len=512,
        )
        defaults.update(kw)
        return SolarOpen2Config(**defaults)


def kda_step(state, q, k, v, g, beta):
    """The delta rule for one position a row in plain XLA: what
    ``ops/kda_step.py`` computes, kept as what the tests hold the kernel
    and the chunked form to (the model's step is the kernel). ``state (b,
    h, dk, dv)`` f32, ``q`` / ``k`` / ``g (b, h, dk)`` (``g`` the log of the
    decay, <= 0), ``v (b, h, dv)``, ``beta (b, h)``. Returns the new state
    and ``o (b, h, dv)``. ``S'^T k`` has to be known before ``S_t`` can be
    written, so compiled this reads the state twice (once for the two
    reductions, once for the update) and writes it once: 54% of the
    roofline where the kernel reads 74 (PERF.md, finding 36.1)."""
    decayed = state * jnp.exp(g)[..., None]
    seen = jnp.sum(decayed * k[..., None], axis=-2)  # S'^T k
    asked = jnp.sum(decayed * q[..., None], axis=-2)  # S'^T q
    u = beta[..., None] * (v - seen)
    new = decayed + k[..., None] * u[..., None, :]
    # S_t^T q = S'^T q + (k . q) u
    o = asked + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return new, o


def kda_chunked(state, q, k, v, g, beta, chunk: int):
    """The same rule over ``s`` positions a row, started from ``state``:
    ``q`` / ``k`` / ``g (b, s, h, dk)``, ``v (b, s, h, dv)``, ``beta (b, s,
    h)``. Inside a chunk of ``chunk`` positions, with ``G_t`` the running
    sum of ``g`` from the chunk's start (a channel), ``S_t = Diag(e^{G_t})
    S_0 + sum_{i<=t} Diag(e^{G_t - G_i}) k_i w_i^T`` where the ``w_i`` solve
    the unit lower-triangular system ``(I + Diag(beta) A) W = Diag(beta) (V
    - (K e^G) S_0)``, ``A_{ti} = sum_c k_t k_i e^{G_t - G_i}`` for ``i <
    t``. Every exponent taken is of a difference ``G_t - G_i`` with ``i <=
    t``, so none overflows however strong the decay. A scan over the chunks
    carries the state; a sequence that is no multiple of ``chunk`` is
    padded with ``g = 0``, ``beta = 0`` positions, which leave it as it
    is."""
    bsz, s, h, dk = q.shape
    pad = -s % chunk
    if pad:
        def padded(t):
            return jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))

        q, k, v, g, beta = (padded(t) for t in (q, k, v, g, beta))
    nc = (s + pad) // chunk

    def chunks(t):  # (b, nc * L, h, ...) -> (nc, b, h, L, ...)
        t = t.reshape((bsz, nc, chunk) + t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 3, 2), 1, 0)

    at_or_before = jnp.tril(jnp.ones((chunk, chunk), bool))
    before = jnp.tril(jnp.ones((chunk, chunk), bool), -1)

    def one(carry, inputs):
        qc, kc, vc, gc, bc = inputs  # (b, h, L, dk) x2, (.., dv), (.., dk), (b, h, L)
        with jax.default_matmul_precision("highest"):
            run = jnp.cumsum(gc, axis=2)  # G_t, falling
            # e^{G_t - G_i} for i <= t, a channel: (b, h, t, i, dk)
            fade = jnp.exp(jnp.where(
                at_or_before[:, :, None],
                run[:, :, :, None] - run[:, :, None, :], -jnp.inf))
            faded_k = kc[:, :, None] * fade
            a = jnp.sum(kc[:, :, :, None] * faded_k, axis=-1)  # (b, h, t, i)
            b_ = jnp.sum(qc[:, :, :, None] * faded_k, axis=-1)
            system = jnp.where(before, a, 0.0) * bc[..., None] + jnp.eye(
                chunk, dtype=F32)
            entered = jnp.exp(run)  # e^{G_t}
            rhs = bc[..., None] * (
                vc - jnp.einsum("bhlk,bhkv->bhlv", kc * entered, carry))
            w = jax.scipy.linalg.solve_triangular(
                system, rhs, lower=True, unit_diagonal=True)
            o = jnp.einsum(
                "bhlk,bhkv->bhlv", qc * entered, carry
            ) + jnp.einsum("bhti,bhiv->bhtv", b_, w)
            to_end = jnp.exp(run[:, :, -1:] - run)  # e^{G_L - G_i}
            new = carry * entered[:, :, -1, :, None] + jnp.einsum(
                "bhlk,bhlv->bhkv", kc * to_end, w)
        return new, o

    state, o = jax.lax.scan(
        one, state, tuple(chunks(t) for t in (q, k, v, g, beta)))
    # (nc, b, h, L, dv) -> (b, s, h, dv)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)
    return state, o.reshape(bsz, nc * chunk, h, -1)[:, :s]


def _dt_bias(key, shape, dtype):
    """The inverse softplus of dt log-uniform in [0.005, 0.2]."""
    lo, hi = math.log(0.005), math.log(0.2)
    dt = jnp.exp(jax.random.uniform(key, shape, F32) * (hi - lo) + lo)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 4.0)).astype(dtype)


def _uniform(bound: float):
    def init(key, shape, dtype):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


class KDAMixer(nn.Module):
    """The KDA mixer and its per-row state (module docstring)."""

    config: SolarOpen2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, s, _ = x.shape
        h, d, taps = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv
        width, rank = cfg.kda_width, cfg.kda_gate_rank

        def dense(features, axes, name, use_bias=False):
            return _dense(
                features, axes, name, cfg.param_dtype, cfg.dtype, use_bias)

        def vector(name, init, shape, axes=(None,)):
            return self.param(
                name, nn.with_logical_partitioning(init, axes), shape,
                cfg.param_dtype,
            ).astype(F32)

        with jax.named_scope("kda.proj"):
            qkv = dense(3 * width, ("embed", "heads"), "wqkv")(x)
            decay = dense(width, (None, "heads"), "wf2")(
                dense(rank, ("embed", None), "wf1")(x))
            gate = dense(width, (None, "heads"), "wg2", use_bias=True)(
                dense(rank, ("embed", None), "wg1")(x))
            beta = dense(h, ("embed", None), "wb")(x)

        kda = self.variable(
            "cache", "state_kda", jnp.zeros, (b, h, d, d), STATE_DTYPE)
        tail = self.variable(
            "cache", "state_conv", jnp.zeros, (b, taps - 1, 3 * width),
            cfg.dtype,
        )
        # a row's position, as attention's: a row at 0 starts afresh, be
        # it a request's first token or a slot nobody holds (the engine
        # puts a free row's position back to 0 before every step)
        idx_var = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((b,), jnp.int32))
        fresh = idx_var.value == 0
        idx_var.value = idx_var.value + s
        with jax.named_scope("kda.conv"):
            conv_w = vector(
                "conv_weight", _uniform(1.0 / math.sqrt(taps)),
                (taps, 3 * width), (None, "heads"))
            # the row's last inputs, then this call's: position t of the
            # call reads window rows t .. t + taps - 1
            window = jnp.concatenate([
                jnp.where(fresh[:, None, None], 0, tail.value), qkv], axis=1)
            tail.value = window[:, s:]
            qkv = jax.nn.silu(sum(
                window[:, j:j + s].astype(F32) * conv_w[j]
                for j in range(taps)
            ))  # f32 from here to the gated norm
        q, k, v = (
            qkv[..., i * width:(i + 1) * width].reshape(b, s, h, d)
            for i in range(3))

        with jax.named_scope("kda.state"):
            q = q * jax.lax.rsqrt(
                jnp.sum(q * q, axis=-1, keepdims=True) + _L2_EPS
            ) * (d ** -0.5)
            k = k * jax.lax.rsqrt(
                jnp.sum(k * k, axis=-1, keepdims=True) + _L2_EPS)
            g = -jnp.exp(vector("A_log", _a_log, (h,)))[:, None] * (
                jax.nn.softplus(
                    decay.astype(F32) + vector("dt_bias", _dt_bias, (width,))
                ).reshape(b, s, h, d))
            beta = 2.0 * jax.nn.sigmoid(beta.astype(F32))
            state = kda.value.astype(F32)
            if s == 1:
                state, o = kda_step_kernel(
                    state, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                    fresh)
                o = o[:, None]
            else:
                state, o = kda_chunked(
                    jnp.where(fresh[:, None, None, None], 0.0, state),
                    q, k, v, g, beta, cfg.kda_chunk_size)
            kda.value = state.astype(STATE_DTYPE)

        with jax.named_scope("kda.proj"):
            # the norm over a head's d_v, one weight a channel of the head
            o = o * jax.lax.rsqrt(
                jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                + cfg.norm_eps)
            o = o * vector("norm", nn.initializers.ones_init(), (d,))
            o = o.reshape(b, s, width) * jax.nn.sigmoid(gate.astype(F32))
            return dense(cfg.dim, ("heads", "embed"), "wo")(
                o.astype(cfg.dtype))


class Block(nn.Module):
    config: SolarOpen2Config
    gqa: bool
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x):
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

        h = norm(x, "attn_norm")
        if self.gqa:
            # no rope: the tables are never read
            mixed = Attention(
                cfg.attention_config(), self.mesh, True, name="attn",
            )(h, None, None)
        else:
            mixed = KDAMixer(cfg, name="kda")(h)
        h = x + mixed
        y = norm(h, "ffn_norm")
        routed = MoEFFN(cfg.routed_config(), name="moe")(y)
        with jax.named_scope("moe.shared"):
            shared = SwiGLU(
                cfg, cfg.n_shared_experts * cfg.moe_intermediate,
                name="shared",
            )(y)
        return h + routed + shared


class SolarOpen2(nn.Module):
    config: SolarOpen2Config
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, tokens, adapters=None, adapter_slots=None):
        # tokens: (batch, seq) int32. The family has no adapter placement
        # (models.refusals): the two arguments are the engine's calling
        # convention and must stay None
        if adapters is not None:
            raise ValueError("the solar_open2 family takes no adapter bank")
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
        for i in range(cfg.n_layers):
            x = Block(cfg, cfg.is_gqa(i), self.mesh, name=f"layer_{i}")(x)
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


def build(config: SolarOpen2Config, mesh: Optional[Mesh] = None,
          decode: bool = False):
    """What ``ray_tpu.models.build`` returns for this family: the serving
    module, which keeps a cache whenever it is applied (a whole sequence
    without one is a prefill into a fresh row)."""
    if not decode:
        raise NotImplementedError(
            "the solar_open2 family has a serving path only (decode=True)"
        )
    return SolarOpen2(config, mesh)


def init_params(config: SolarOpen2Config, rng, mesh: Optional[Mesh] = None,
                seq: int = 8):
    """Seeded weights (module docstring), made by one compiled program: the
    forward pass that places them is traced and never run. Every weight is
    drawn in float32 and then cast to ``param_dtype``: ``jax.random.normal``
    in bfloat16 has 128 values and a mean of -0.012 sigma, which a
    projection of fan-in ``n`` turns into the same offset, ``sqrt(n)``
    times that, on every output wherever its inputs have a mean of their
    own (the gated norm in front of ``W_o``, 8192 wide). Every row's
    residual stream then shares a direction that grows with depth, and the
    router's top 8 with it: at these widths the busiest expert of the last
    layer took 12 x the mean (PERF.md, finding 36.5)."""
    model = SolarOpen2(dataclasses.replace(config, param_dtype=F32), mesh)
    tokens = jnp.zeros((1, seq), jnp.int32)

    def make(key):
        return jax.tree.map(
            lambda w: w.astype(config.param_dtype),
            model.init(key, tokens)["params"])

    return jax.jit(make)(rng)
