"""Falcon-H1-shaped transformer (``model_type`` falcon_h1), TPU-first, for
the serving stack: in *every* block a grouped-query attention and a Mamba-2
mixer side by side on one normed input, their outputs summed into the
residual, then a SwiGLU; a muP multiplier on every branch.

No reference analogue (the reference serves such models through vLLM). The
attention is ``models/llama.py``'s decode path at this family's head size
(``dim != n_heads * head_dim``, and the keys scaled before they are
cached): the same ``ops/kv_row_write``, ``ops/decode_attention``,
``ops/rope`` and ``ops/rmsnorm``. What is this family's own is the mixer
and what it keeps between steps.

A block, names as the published config's keys:

- ``h = RMSNorm(x)``; ``x = x + Attn(h) + Mixer(h)``; ``x = x +
  MLP(RMSNorm(x))``
- ``Attn``: ``h' = h * attention_in_multiplier``; ``q = h' W_q``, ``k = (h'
  W_k) * key_multiplier``, ``v = h' W_v``; rotate-half RoPE on q and k;
  causal softmax attention, ``n_heads / n_kv_heads`` query heads a KV head;
  ``W_o``; ``* attention_out_multiplier``
- ``Mixer``: ``u = (h * ssm_in_multiplier) W_in``, columns ``[z | x | B | C
  | dt]`` each scaled by its entry of ``ssm_multipliers``; ``[x | B | C] =
  silu(conv([x | B | C]))``, a causal depthwise convolution of
  ``mamba_d_conv`` taps with bias; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)`` a head. A head's state ``S`` is ``(mamba_d_head,
  mamba_d_state)``: ``S_t = exp(dt_t A) S_{t-1} + dt_t (x_t (x) B_t)``,
  ``y_t = S_t C_t + D x_t`` (``mamba_n_heads / mamba_n_groups`` heads share
  a group's ``B`` and ``C``). ``y = RMSNorm_group(y * silu(z))``, the mean
  square over a group's channels; ``W_out``; ``* ssm_out_multiplier``
- ``MLP = (silu(g W_gate * mlp_multipliers[0]) * g W_up) W_down *
  mlp_multipliers[1]``
- ``x_0 = E[token] * embedding_multiplier``; ``logits = RMSNorm(x) W_head *
  lm_head_multiplier``

What a row keeps between steps (the ``cache`` collection): the attention's
``cached_key`` / ``cached_value`` / ``cache_index`` as ``llama``'s, and the
mixer's **per-row state with no sequence axis** (``models.STATE``):
``state_ssm`` ``(batch, heads, d_head, d_state)`` float32, and
``state_conv`` ``(batch, d_conv - 1, conv channels)``, the convolution's
last inputs (channels minor: a minor axis of 3 would be padded to a lane
tile, 42 times its bytes). The state is float32 and so is the recurrence:
a bf16 state rounds the accumulator at every step of a request.

The recurrence has two forms, one function in two orders of rounding
(``tests/test_falcon_h1_family.py`` holds them together): ``seq == 1`` the
update above, a row a layer, read once and written once in the donated
cache; ``seq > 1`` (a prefill, and a suffix or chunk behind a row's state)
Mamba-2's chunked form at ``mamba_chunk_size``, matmuls inside a chunk and
a scan over chunks, started from the row's state and convolution tail
(zero for a fresh row).

``init_params`` (the benchmark's weights are these, from a seed): every
projection is a fan-in normal *divided by the multiplier that scales its
output* (``W_in``'s columns by ``ssm_in_multiplier`` and their group's
entry of ``ssm_multipliers``), the embedding has unit rms after
``embedding_multiplier``, so each of the three branches moves the residual
by a comparable rms and every multiplier is live; ``dt_bias``, ``A_log``,
``D``, the convolution and its bias as Mamba-2 initialises them (dt
log-uniform in [0.001, 0.1], ``A`` uniform in [1, 16], ``D`` 1). The
multipliers were published for trained weights: with a plain fan-in init
the mixer (``* 0.088``) and the attention (``* 0.0375``) would add nothing
a tolerance could see.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..ops.decode_attention import decode_attention
from ..ops.kv_row_write import write_rows
from ..ops.rmsnorm import rmsnorm
from ..ops.rope import apply_rope, rope_table

F32 = jnp.float32
# what a row's recurrent state is stored in between steps. The benchmark's
# configuration guarantees float32 and its check refuses a row whose state
# takes other bytes (benchmarks/tests lowers this to show that it does): a
# narrower state is another result, not a faster one
STATE_DTYPE = jnp.float32

# the mixer keeps models.STATE leaves: the serving stack gives such a family
# no prefix reuse (models/__init__.py)
ROW_STATE = True


@dataclasses.dataclass(frozen=True)
class MixerConfig:
    """What ``Mixer`` reads of its family's config: the Mamba-2 mixer's own
    sizes, which every family that has one builds from its published keys
    (``FalconH1Config.mixer_config``, ``NemotronHConfig.mixer_config``). A
    multiplier of 1 is no multiplication in the program."""

    dim: int
    n_heads: int
    d_head: int
    d_state: int
    n_groups: int
    d_conv: int = 4
    chunk_size: int = 128
    in_multiplier: float = 1.0
    out_multiplier: float = 1.0
    # W_in's column groups, in the order z, x, B, C, dt
    multipliers: Tuple[float, ...] = (1.0,) * 5
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        object.__setattr__(self, "multipliers", tuple(self.multipliers))
        if len(self.multipliers) != 5:
            raise ValueError(
                "MixerConfig: multipliers has five entries (z, x, B, C, dt)")
        if self.n_heads % self.n_groups:
            raise ValueError(
                f"MixerConfig: {self.n_heads} mixer heads in "
                f"{self.n_groups} groups"
            )

    @property
    def d_ssm(self) -> int:
        return self.n_heads * self.d_head

    @property
    def conv_channels(self) -> int:
        """x, B and C: what the convolution runs over."""
        return self.d_ssm + 2 * self.n_groups * self.d_state

    @property
    def in_proj_columns(self) -> Tuple[int, ...]:
        """``W_in``'s column groups, in the order of ``multipliers``."""
        gn = self.n_groups * self.d_state
        return (self.d_ssm, self.d_ssm, gn, gn, self.n_heads)


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    """Falcon-H1-34B-Instruct's published sizes are the defaults."""

    vocab_size: int = 261120
    dim: int = 5120
    n_layers: int = 72
    n_heads: int = 20
    n_kv_heads: int = 4
    head_dim: int = 128
    intermediate: int = 21504
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    # in the order z, x, B, C, dt
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738,
    )
    mlp_multipliers: Tuple[float, float] = (
        0.1767766952966369, 0.011160714285714284,
    )
    max_seq_len: int = 4096
    rope_theta: float = 1e11
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        object.__setattr__(
            self, "ssm_multipliers", tuple(self.ssm_multipliers))
        object.__setattr__(
            self, "mlp_multipliers", tuple(self.mlp_multipliers))
        if len(self.mlp_multipliers) != 2:
            raise ValueError(
                "FalconH1Config: mlp_multipliers has two entries")
        self.mixer_config()  # refuses what the mixer cannot be built from

    def mixer_config(self) -> MixerConfig:
        """The mixer of every block as ``Mixer`` takes it."""
        return MixerConfig(
            dim=self.dim, n_heads=self.mamba_n_heads,
            d_head=self.mamba_d_head, d_state=self.mamba_d_state,
            n_groups=self.mamba_n_groups, d_conv=self.mamba_d_conv,
            chunk_size=self.mamba_chunk_size,
            in_multiplier=self.ssm_in_multiplier,
            out_multiplier=self.ssm_out_multiplier,
            multipliers=self.ssm_multipliers, norm_eps=self.norm_eps,
            dtype=self.dtype, param_dtype=self.param_dtype,
        )

    @staticmethod
    def tiny(**kw) -> "FalconH1Config":
        """Test-scale config of the same shape: runs on the CPU in seconds."""
        defaults = dict(
            vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            head_dim=16, intermediate=128, mamba_n_heads=4, mamba_d_head=16,
            mamba_d_state=16, mamba_n_groups=2, mamba_chunk_size=8,
            max_seq_len=512,
        )
        defaults.update(kw)
        return FalconH1Config(**defaults)


def _fan_in(scale: float = 1.0):
    return nn.initializers.variance_scaling(
        scale * scale, "fan_in", "truncated_normal"
    )


def _dense(cfg, features, axes, name, scale=1.0, kernel_init=None):
    return nn.DenseGeneral(
        features=features, use_bias=False, name=name, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        kernel_init=nn.with_logical_partitioning(
            kernel_init or _fan_in(scale), axes
        ),
    )


class Attention(nn.Module):
    """``llama.Attention``'s decode path with a head size of its own and
    the keys scaled before the cache."""

    config: FalconH1Config
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        b, s, _ = x.shape
        h, hk, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

        def heads(y, n):
            return y.reshape(b, s, n, d).transpose(0, 2, 1, 3)

        x = x * cfg.attention_in_multiplier
        q = heads(_dense(cfg, h * d, ("embed", "heads"), "wq")(x), h)
        k = heads(
            _dense(cfg, hk * d, ("embed", "heads"), "wk",
                   1.0 / cfg.key_multiplier)(x) * cfg.key_multiplier,
            hk,
        )
        v = heads(_dense(cfg, hk * d, ("embed", "heads"), "wv")(x), hk)

        cached_k = self.variable(
            "cache", "cached_key",
            jnp.zeros, (b, hk, cfg.max_seq_len, d), cfg.dtype,
        )
        cached_v = self.variable(
            "cache", "cached_value",
            jnp.zeros, (b, hk, cfg.max_seq_len, d), cfg.dtype,
        )
        idx_var = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((b,), jnp.int32)
        )
        idx = idx_var.value  # (b,): a row's write position
        q = apply_rope(q, cos, sin, offset=idx)
        k = apply_rope(k, cos, sin, offset=idx)
        cached_k.value, cached_v.value = write_rows(
            (cached_k.value, cached_v.value),
            (k.astype(cfg.dtype), v.astype(cfg.dtype)), idx, self.mesh,
        )
        idx_var.value = idx + s
        if s == 1:
            out = decode_attention(
                q[:, :, 0], cached_k.value, cached_v.value,
                jnp.minimum(idx + 1, cfg.max_seq_len), self.mesh,
            )[:, :, None]
        else:
            # prefill, and a chunk behind a cached prefix: row r's query i
            # sits at idx[r] + i and sees the keys at or before it
            group = h // hk
            qg = q.reshape(b, hk, group * s, d)
            scores = jnp.einsum(
                "bgqd,bgkd->bgqk", qg, cached_k.value,
                preferred_element_type=F32,
            ).reshape(b, h, s, cfg.max_seq_len) / math.sqrt(d)
            q_pos = idx[:, None, None] + jnp.arange(s)[None, :, None]
            k_pos = jnp.arange(cfg.max_seq_len)[None, None, :]
            scores = jnp.where((k_pos <= q_pos)[:, None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum(
                "bgqk,bgkd->bgqd",
                probs.astype(cfg.dtype).reshape(
                    b, hk, group * s, cfg.max_seq_len),
                cached_v.value, preferred_element_type=F32,
            ).reshape(b, h, s, d).astype(cfg.dtype)
        out = out.transpose(0, 2, 1, 3).reshape(b, s, h * d)
        return _dense(
            cfg, cfg.dim, ("heads", "embed"), "wo",
            1.0 / cfg.attention_out_multiplier,
        )(out) * cfg.attention_out_multiplier


def ssm_step(state, x, dt, a, b_in, c_in, d_skip):
    """The recurrence for one position a row: ``state (b, h, p, n)`` f32,
    ``x (b, h, p)``, ``dt (b, h)`` (after the softplus), ``a (h,)``
    (negative), ``b_in`` / ``c_in (b, g, n)``, ``d_skip (h,)``. Returns the
    new state and ``y (b, h, p)``. Elementwise and one reduction, so the
    state is read once and written once."""
    bsz, h, p, n = state.shape
    g = b_in.shape[1]
    grouped = state.reshape(bsz, g, h // g, p, n)
    decay = jnp.exp(dt * a).reshape(bsz, g, h // g, 1, 1)
    dtx = (dt[..., None] * x).reshape(bsz, g, h // g, p, 1)
    new = grouped * decay + dtx * b_in[:, :, None, None, :]
    y = jnp.sum(new * c_in[:, :, None, None, :], axis=-1)  # (b, g, h/g, p)
    y = y.reshape(bsz, h, p) + d_skip[:, None] * x
    return new.reshape(state.shape), y


def ssm_chunked(state, x, dt, a, b_in, c_in, d_skip, chunk: int):
    """The same recurrence over ``s`` positions a row, started from
    ``state``: ``x (b, s, h, p)``, ``dt (b, s, h)``, ``b_in`` / ``c_in (b,
    s, g, n)``. Mamba-2's chunked form: inside a chunk of ``chunk``
    positions every output is a sum of matmuls (what the chunk's own inputs
    give, plus what the entering state gives), and a scan over the chunks
    carries the state. A sequence that is no multiple of ``chunk`` is padded
    with ``dt = 0`` positions, which leave the state as it is."""
    bsz, s, h, p = x.shape
    g, n = b_in.shape[2:]
    per = h // g
    pad = -s % chunk
    if pad:
        def padded(t):
            return jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))

        x, dt, b_in, c_in = padded(x), padded(dt), padded(b_in), padded(c_in)
    nc = (s + pad) // chunk

    def chunks(t):  # (b, nc * L, ...) -> (nc, b, L, ...)
        return jnp.moveaxis(
            t.reshape((bsz, nc, chunk) + t.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(carry, inputs):
        xc, dtc, bc, cc = inputs  # (b, L, h, p) (b, L, h) (b, L, g, n) x2
        with jax.default_matmul_precision("highest"):
            cum = jnp.cumsum(dtc * a, axis=1)  # (b, L, h), falling
            dtx = (dtc[..., None] * xc).reshape(bsz, chunk, g, per, p)
            # what position l takes of position m <= l of its own chunk
            cb = jnp.einsum("blgn,bmgn->bglm", cc, bc)
            seg = cum[:, :, None, :] - cum[:, None, :, :]  # (b, l, m, h)
            within = jnp.where(
                causal[None, :, :, None], jnp.exp(seg), 0.0
            ).reshape(bsz, chunk, chunk, g, per)
            y = jnp.einsum(
                "bglm,blmgk,bmgkp->blgkp", cb, within, dtx)
            # ... and of the state the chunk was entered with
            grouped = carry.reshape(bsz, g, per, p, n)
            entering = jnp.einsum("bgkpn,blgn->blgkp", grouped, cc)
            y = y + entering * jnp.exp(cum).reshape(
                bsz, chunk, g, per, 1)
            # the state the chunk leaves
            to_end = jnp.exp(cum[:, -1:, :] - cum).reshape(
                bsz, chunk, g, per, 1)
            left = jnp.einsum("blgkp,blgn->bgkpn", dtx * to_end, bc)
            new = grouped * jnp.exp(cum[:, -1, :]).reshape(
                bsz, g, per, 1, 1) + left
        return new.reshape(carry.shape), y.reshape(bsz, chunk, h, p)

    state, y = jax.lax.scan(
        one, state, (chunks(x), chunks(dt), chunks(b_in), chunks(c_in)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, nc * chunk, h, p)[:, :s]
    return state, y + d_skip[:, None] * x[:, :s]


def _mamba_dt_bias(key, shape, dtype):
    """The inverse softplus of dt log-uniform in [0.001, 0.1]."""
    dt = jnp.exp(
        jax.random.uniform(key, shape, F32)
        * (math.log(0.1) - math.log(0.001)) + math.log(0.001)
    )
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _mamba_a_log(key, shape, dtype):
    return jnp.log(
        jax.random.uniform(key, shape, F32, 1.0, 16.0)).astype(dtype)


def _uniform(bound: float):
    def init(key, shape, dtype):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


class Mixer(nn.Module):
    """The Mamba-2 mixer and its per-row state (module docstring), at the
    sizes a ``MixerConfig`` names: this family's, and
    ``models/nemotron_h.py``'s (every multiplier 1)."""

    config: MixerConfig

    @nn.compact
    def __call__(self, hidden):
        cfg = self.config
        b, s, _ = hidden.shape
        h, p, n, g = cfg.n_heads, cfg.d_head, cfg.d_state, cfg.n_groups
        taps, channels = cfg.d_conv, cfg.conv_channels
        columns = cfg.in_proj_columns
        scaled = any(m != 1.0 for m in cfg.multipliers)
        # one multiplier a column of W_in, by the column's group
        column_scale = jnp.concatenate([
            jnp.full((width,), m, F32)
            for width, m in zip(columns, cfg.multipliers)
        ])

        def in_proj_init(key, shape, dtype):
            kernel = _fan_in(1.0 / cfg.in_multiplier)(key, shape, F32)
            return (kernel / column_scale).astype(dtype)

        def vector(name, init, shape, axes=(None,)):
            return self.param(
                name, nn.with_logical_partitioning(init, axes), shape,
                cfg.param_dtype,
            ).astype(F32)

        with jax.named_scope("ssm.proj"):
            if cfg.in_multiplier != 1.0:
                hidden = hidden * cfg.in_multiplier
            u = _dense(
                cfg, sum(columns), ("embed", "mlp"), "in_proj",
                kernel_init=in_proj_init,
            )(hidden)
            if scaled:
                u = u * column_scale.astype(u.dtype)
        z = u[..., :columns[0]]
        xbc = u[..., columns[0]:columns[0] + channels]
        dt = u[..., columns[0] + channels:]

        ssm = self.variable(
            "cache", "state_ssm", jnp.zeros, (b, h, p, n), STATE_DTYPE)
        tail = self.variable(
            "cache", "state_conv", jnp.zeros, (b, taps - 1, channels),
            cfg.dtype,
        )
        with jax.named_scope("ssm.conv"):
            conv_w = vector(
                "conv_weight", _uniform(1.0 / math.sqrt(taps)),
                (taps, channels), (None, "mlp"))
            conv_b = vector(
                "conv_bias", _uniform(1.0 / math.sqrt(taps)), (channels,),
                ("mlp",))
            # the row's last inputs, then this call's: position t of the
            # call reads window rows t .. t + taps - 1
            window = jnp.concatenate([tail.value, xbc], axis=1)
            tail.value = window[:, s:]
            conv = conv_b + sum(
                window[:, j:j + s].astype(F32) * conv_w[j]
                for j in range(taps)
            )
            xbc = jax.nn.silu(conv)  # f32 from here to the gated norm
        x = xbc[..., :cfg.d_ssm].reshape(b, s, h, p)
        b_in = xbc[..., cfg.d_ssm:cfg.d_ssm + g * n].reshape(b, s, g, n)
        c_in = xbc[..., cfg.d_ssm + g * n:].reshape(b, s, g, n)

        with jax.named_scope("ssm.scan"):
            dt = jax.nn.softplus(
                dt.astype(F32) + vector("dt_bias", _mamba_dt_bias, (h,)))
            a = -jnp.exp(vector("A_log", _mamba_a_log, (h,)))
            d_skip = vector("D", nn.initializers.ones_init(), (h,))
            state = ssm.value.astype(F32)
            if s == 1:
                state, y = ssm_step(
                    state, x[:, 0], dt[:, 0], a, b_in[:, 0], c_in[:, 0],
                    d_skip)
                y = y[:, None]
            else:
                state, y = ssm_chunked(
                    state, x, dt, a, b_in, c_in, d_skip, cfg.chunk_size)
            ssm.value = state.astype(STATE_DTYPE)

        with jax.named_scope("ssm.proj"):
            # gate, then the norm over each group's channels
            y = y.reshape(b, s, g, cfg.d_ssm // g) * jax.nn.silu(
                z.astype(F32)).reshape(b, s, g, cfg.d_ssm // g)
            y = y * jax.lax.rsqrt(
                jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                + cfg.norm_eps)
            norm_w = vector(
                "norm", nn.initializers.ones_init(), (cfg.d_ssm,), ("mlp",))
            y = (y.reshape(b, s, cfg.d_ssm) * norm_w).astype(cfg.dtype)
            out = _dense(
                cfg, cfg.dim, ("mlp", "embed"), "out_proj",
                1.0 / cfg.out_multiplier,
            )(y)
            return out * cfg.out_multiplier if cfg.out_multiplier != 1.0 else out


class MLP(nn.Module):
    config: FalconH1Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        gate_m, down_m = cfg.mlp_multipliers
        gate = _dense(
            cfg, cfg.intermediate, ("embed", "mlp"), "w_gate", 1.0 / gate_m,
        )(x) * gate_m
        up = _dense(cfg, cfg.intermediate, ("embed", "mlp"), "w_up")(x)
        return _dense(
            cfg, cfg.dim, ("mlp", "embed"), "w_down", 1.0 / down_m,
        )(nn.silu(gate) * up) * down_m


class Block(nn.Module):
    config: FalconH1Config
    mesh: Optional[Mesh] = None

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

        h = norm(x, "in_norm")
        x = (
            x + Attention(cfg, self.mesh, name="attn")(h, cos, sin)
            + Mixer(cfg.mixer_config(), name="mixer")(h)
        )
        return x + MLP(cfg, name="mlp")(norm(x, "ff_norm"))


class FalconH1(nn.Module):
    config: FalconH1Config
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, tokens, adapters=None, adapter_slots=None):
        # tokens: (batch, seq) int32. The family has no adapter placement
        # (models.refusals): the two arguments are the engine's calling
        # convention and must stay None
        if adapters is not None:
            raise ValueError("the falcon_h1 family takes no adapter bank")
        cfg = self.config
        embed = self.param(
            "embed",
            nn.with_logical_partitioning(
                nn.initializers.normal(1.0 / cfg.embedding_multiplier),
                ("vocab", "embed"),
            ),
            (cfg.vocab_size, cfg.dim),
            cfg.param_dtype,
        )
        x = embed.astype(cfg.dtype)[tokens] * cfg.embedding_multiplier
        cos, sin = rope_table(cfg.max_seq_len, cfg.head_dim, cfg.rope_theta)
        for i in range(cfg.n_layers):
            x = Block(cfg, self.mesh, name=f"layer_{i}")(x, cos, sin)
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
                _fan_in(1.0 / cfg.lm_head_multiplier), ("embed", "vocab")
            ),
            (cfg.dim, cfg.vocab_size),
            cfg.param_dtype,
        )
        return (x @ head.astype(x.dtype)) * cfg.lm_head_multiplier


def build(config: FalconH1Config, mesh: Optional[Mesh] = None,
          decode: bool = False):
    """What ``ray_tpu.models.build`` returns for this family: the serving
    module, which keeps a cache whenever it is applied (a whole sequence
    without one is a prefill into a fresh row)."""
    if not decode:
        raise NotImplementedError(
            "the falcon_h1 family has a serving path only (decode=True)"
        )
    return FalconH1(config, mesh)


def init_params(config: FalconH1Config, rng, mesh: Optional[Mesh] = None,
                seq: int = 8):
    """Seeded weights (module docstring), made by one compiled program: the
    forward pass that places them is traced and never run (run eagerly, as
    the other families' is, it is hundreds of small compiles at these
    shapes)."""
    model = FalconH1(config, mesh)
    tokens = jnp.zeros((1, seq), jnp.int32)
    return jax.jit(lambda key: model.init(key, tokens)["params"])(rng)
