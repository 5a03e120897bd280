"""LLaMA-family transformer, TPU-first.

No reference analogue: the reference serves models through vLLM/torch
(SURVEY P19); this framework owns the model-execution layer. Design:

- flax.linen with *logical axis* annotations on every parameter
  (nn.with_logical_partitioning); parallel/sharding.py's rule table maps
  logical axes to mesh axes, XLA GSPMD inserts the collectives — TP/FSDP
  come from the sharding annotations, not model code changes
- attention runs the Pallas flash kernel; with a sequence-parallel mesh axis
  it runs ring attention under shard_map (parallel/ring_attention.py); a
  decode step over the KV cache runs ops/decode_attention.py
- bfloat16 activations, f32 params/optimizer by default; per-layer remat
  (jax.checkpoint) to trade FLOPs for HBM
- LoRA (q/k/v/o + optional mlp) for the Llama-2-7B fine-tune north-star
  (BASELINE.json config 3)
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax import traverse_util
from jax import shard_map
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.decode_attention import decode_attention
from ..ops.flash_attention import flash_attention
from ..ops.kv_row_write import write_rows
from ..ops.rmsnorm import rmsnorm
from ..ops.rope import apply_rope, rope_table
from ..parallel.ring_attention import ring_attention
from ..parallel.sharding import logical_to_spec, matrix_shards
from ..util import tracing
from . import remat_plan


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    intermediate: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # Wrap each layer in nn.remat (train path only): the backward pass gets
    # a layer's input and recomputes what does not fit. What fits is worked
    # out while the step is traced (``Llama._remat_policy``,
    # models/remat_plan.py) from the batch's tokens a device, the widths
    # and depth, the mesh, and the device kind's memory limit less the
    # parameters, the step's working set and a margin: the same choice on
    # every run of a job. A device that names no limit (the CPU) keeps
    # nothing.
    remat: bool = True
    # Stack the layers and run them with nn.scan (train path only). One
    # layer's buffers are live at a time — the python loop form lets XLA's
    # latency-hiding scheduler keep many layers' remat recomputations
    # resident at once (~7 GB of HLO temps at 7B/seq-2048, which OOMs a
    # 16 GB v5e next to 13.5 GB of bf16 params). Also ~L× faster compiles.
    scan_layers: bool = False
    lora_rank: int = 0
    lora_alpha: float = 16.0
    # RMSNorm over the whole q and k projections, before the split into
    # heads and before RoPE (OLMoE's q_norm / k_norm)
    qk_norm: bool = False
    # rotary position embedding on q and k; without it the causal mask is
    # all the order attention sees (a NoPE layer)
    rope: bool = True
    # an elementwise sigmoid gate on the attention's output before ``wo``,
    # from the layer's input (``w_gate``, as wide as the heads together)
    attn_gate: bool = False
    # a head's width where it is not ``dim / n_heads``
    attn_head_dim: Optional[int] = None
    # rotary pairs are a head's neighbours ``(x[2i], x[2i + 1])`` (GPT-J's
    # form, ``rope_gptj``), not its halves (ops/rope.py)
    rope_interleaved: bool = False
    # a window layer (serving only): a query sees its last ``window``
    # positions, itself included, and a row keeps those alone, in a ring
    # (``models.WINDOW``: ``window_key`` / ``window_value`` in the place of
    # ``cached_key`` / ``cached_value``)
    window: Optional[int] = None

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.dim // self.n_heads

    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        return LlamaConfig(**kw)

    @staticmethod
    def llama2_13b(**kw) -> "LlamaConfig":
        return LlamaConfig(
            dim=5120, n_layers=40, n_heads=40, n_kv_heads=40, intermediate=13824, **kw
        )

    @staticmethod
    def llama3_8b(**kw) -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=128256, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
            intermediate=14336, rope_theta=500000.0, **kw
        )

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        """Test-scale config: runs on CPU mesh in seconds."""
        defaults = dict(
            vocab_size=256, dim=128, n_layers=2, n_heads=4, n_kv_heads=4,
            intermediate=256, max_seq_len=512, remat=False,
        )
        defaults.update(kw)
        return LlamaConfig(**defaults)


# the float32 scores (batch x heads x seq x seq) a whole-prompt prefill may
# hold as one einsum's temporary (``prefills_through_kernel``)
_EINSUM_SCORE_BYTES = 1 << 30


def prefills_through_kernel(cfg: LlamaConfig, batch: int, seq: int) -> bool:
    """Which of its two forms a fresh whole prompt's attention takes, from
    the layer's configuration and the call's shapes alone: the float32
    einsum over the prompt's own keys, or ``ops/flash_attention.py``'s
    forward kernel. The kernel where the einsum has no form (a window
    layer: the band is the kernel's) or cannot hold its scores (more than
    ``_EINSUM_SCORE_BYTES`` of them: 128 heads at 2048 tokens are 2.1 GB,
    at 8192 34 GB); the einsum everywhere else, which is every prompt the
    benchmark's other cells send (32 heads x 2048 x 2048 are 0.5 GB) until
    ROADMAP S4(b)(i) moves them with a measured pair of its own. A suffix
    behind a cached prefix and a budgeted prefill's chunk never come here."""
    return (cfg.window is not None
            or 4 * batch * cfg.n_heads * seq * seq > _EINSUM_SCORE_BYTES)


# the smallest projection weight a decode step's program was seen to leave out
# of VMEM (``holds_projection``): half of the v5e's 128 MiB
_UNSTAGED_WEIGHT_BYTES = 64 << 20


def holds_projection(weight_bytes: int) -> bool:
    """Whether a decode step keeps its query projection's output as the
    matmul made it, ``(b, h * d)``, from the weight's bytes alone. The
    decode kernel wants the query as ``(b, kv_heads, group, d)``, and the
    TPU compiler gives the projection's output that order by re-laying the
    *weight*. That is free where it stages the weight in VMEM anyway (the
    copy is then the weight's one read: Mistral's and Nemotron's 32 MiB)
    and an HBM round trip of the whole weight every step where it does not
    (Solar-Open2's 64 MiB, 0.31 ms a step; Command A+'s 128 MiB, 0.41 ms a
    layer: PERF 6, 59.3 and PR 60). Held, the weight is read once as stored
    and the order is made on the activation, under a megabyte. Below the
    limit nothing is held: there the hold takes staged K/V reads out of
    VMEM (ROADMAP S11(e))."""
    return weight_bytes >= _UNSTAGED_WEIGHT_BYTES


def ring_of(rows, ring: int):
    """The ring a whole-prompt prefill leaves: ``rows (b, heads, s, width)``,
    position ``p`` to slot ``p % ring``, the last ``min(s, ring)`` of
    them; slots no position reached are zero (and never read: ``lengths``
    stops short of them)."""
    s = rows.shape[2]
    if s <= ring:
        return jnp.pad(rows, ((0, 0), (0, 0), (0, ring - s), (0, 0)))
    last = rows[:, :, s - ring:]  # entry k is position s - ring + k
    turn = ring - (s - ring) % ring  # ... and belongs at (k - turn) % ring
    return jnp.concatenate([last[:, :, turn:], last[:, :, :turn]], axis=2)


def _dense(features, logical_axes, name, param_dtype, dtype, use_bias=False):
    return nn.DenseGeneral(
        features=features,
        use_bias=use_bias,
        name=name,
        dtype=dtype,  # bf16 compute on the MXU; params stay f32
        param_dtype=param_dtype,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), logical_axes
        ),
    )


class LoRADense(nn.Module):
    """Dense with optional low-rank adapter: y = xW + (alpha/r)·xAB.

    The base kernel is annotated like a normal weight; A/B carry the
    ``lora_rank`` logical axis (replicated by default rules). Training
    freezes the base via an optimizer mask (train/lora.py).

    Multi-tenant serving path: ``adapter`` is a stacked slot bank
    ``{"lora_a": (num_slots, in_dim, r), "lora_b": (num_slots, r, out)}``
    (ray_tpu.lora.AdapterStore; lora_b pre-scaled by alpha/r at attach)
    and ``adapter_slots`` a per-row ``(batch,)`` int32 index vector —
    the delta is the batched gather ``x @ A[slot] @ B[slot]``, with slot
    -1 masked to zero (the base-only path), so ONE program serves a
    mixed-adapter batch."""

    features: int
    logical_axes: Tuple[str, ...]
    rank: int
    alpha: float
    param_dtype: Any
    dtype: Any

    @nn.compact
    def __call__(self, x, adapter=None, adapter_slots=None):
        y = _dense(
            self.features, self.logical_axes, "base", self.param_dtype, self.dtype
        )(x)
        if self.rank > 0:
            in_dim = x.shape[-1]
            a = self.param(
                "lora_a",
                nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), (self.logical_axes[0], "lora_rank")
                ),
                (in_dim, self.rank),
                self.param_dtype,
            )
            b = self.param(
                "lora_b",
                nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), ("lora_rank", self.logical_axes[-1])
                ),
                (self.rank, self.features),
                self.param_dtype,
            )
            scale = self.alpha / self.rank
            y = y + (x @ a.astype(x.dtype)) @ b.astype(x.dtype) * scale
        if adapter is not None and adapter_slots is not None:
            bank_a = adapter["lora_a"]
            bank_b = adapter["lora_b"]
            # clamp the gather index so slot -1 reads row 0 safely, then
            # mask its contribution to exactly zero
            idx = jnp.clip(adapter_slots, 0, bank_a.shape[0] - 1)
            ag = jnp.take(bank_a, idx, axis=0).astype(x.dtype)  # (b, in, r)
            bg = jnp.take(bank_b, idx, axis=0).astype(x.dtype)  # (b, r, out)
            delta = jnp.einsum("bsi,bir->bsr", x, ag)
            delta = jnp.einsum("bsr,bro->bso", delta, bg)
            live = (adapter_slots >= 0).astype(x.dtype)[:, None, None]
            y = y + delta * live
        return y


class Attention(nn.Module):
    config: LlamaConfig
    mesh: Optional[Mesh] = None
    decode: bool = False

    @nn.compact
    def __call__(self, x, cos, sin, adapters=None, adapter_slots=None):
        cfg = self.config
        b, s, _ = x.shape
        h, hk, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        adapters = adapters or {}

        def proj(n_out, name):
            return LoRADense(
                features=n_out,
                logical_axes=("embed", "heads"),
                rank=cfg.lora_rank,
                alpha=cfg.lora_alpha,
                param_dtype=cfg.param_dtype,
                dtype=cfg.dtype,
                name=name,
            )

        def run(mod, name):
            return mod(x, adapters.get(name), adapter_slots)

        def normed(y, name):
            # OLMoE's q_norm / k_norm: over the whole projection, before
            # the split into heads
            if not cfg.qk_norm:
                return y
            w = self.param(
                name,
                nn.with_logical_partitioning(
                    nn.initializers.ones_init(), ("heads",)
                ),
                (y.shape[-1],),
                cfg.param_dtype,
            )
            return rmsnorm(y, w.astype(y.dtype), cfg.norm_eps, self.mesh)

        def heads(y, n):
            return y.reshape(b, s, n, d).transpose(0, 2, 1, 3)

        q = run(proj(h * d, "wq"), "wq")
        if self.decode and s == 1 and holds_projection(
                x.shape[-1] * h * d * jnp.dtype(cfg.param_dtype).itemsize):
            # an identity the compiler does not look through: the heads'
            # layout below is made on q, and W_q is read as stored
            q = jax.lax.optimization_barrier(q)
        q = heads(normed(q, "q_norm"), h)
        # k and v are tagged for nn.remat's policy (the identity anywhere
        # else) here, at the width of the KV heads, before anything repeats
        # them to the query heads'; q, attention's output and its
        # log-sum-exp where the kernels' backward rules take them
        k = heads(normed(checkpoint_name(
            run(proj(hk * d, "wk"), "wk"), remat_plan.ATTN_K), "k_norm"), hk)
        v = heads(checkpoint_name(
            run(proj(hk * d, "wv"), "wv"), remat_plan.ATTN_V), hk)

        if self.decode:
            # KV-cache incremental path (serving; reference role: vLLM's
            # paged KV cache behind ray.llm — here a dense ring buffer per
            # layer in a flax "cache" collection). The cache index is
            # PER-ROW (b,): continuous batching interleaves requests at
            # different positions in one decode batch.
            # A window layer keeps a ring of its last ``window`` positions
            # under names of its own (models.WINDOW), position p at slot
            # p % window
            ring = cfg.window
            positions = ring or cfg.max_seq_len
            kept = "window" if ring else "cached"
            # No cache came in: this call makes it, so every position but
            # the s it writes is zero (a whole-prompt prefill)
            fresh = not self.has_variable("cache", f"{kept}_key")
            cached_k = self.variable(
                "cache", f"{kept}_key",
                jnp.zeros, (b, hk, positions, d), cfg.dtype,
            )
            cached_v = self.variable(
                "cache", f"{kept}_value",
                jnp.zeros, (b, hk, positions, d), cfg.dtype,
            )
            idx_var = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((b,), jnp.int32)
            )
            idx = idx_var.value  # (b,)
            if cfg.rope:
                q = apply_rope(q, cos, sin, offset=idx,
                               interleaved=cfg.rope_interleaved)
                k = apply_rope(k, cos, sin, offset=idx,
                               interleaved=cfg.rope_interleaved)
            new = (k.astype(cfg.dtype), v.astype(cfg.dtype))

            if ring and s > 1:
                if not fresh:
                    raise NotImplementedError(
                        "a window layer has no form for more than one new "
                        "position a row against its ring (models.refusals: "
                        "prefill_chunk)"
                    )
                # a fresh row is at position 0: the prompt's last
                # min(s, ring) positions, in ring order
                cached_k.value, cached_v.value = (
                    ring_of(leaf, ring) for leaf in new)
            else:
                # each row's new keys and values at its own position (its
                # position's slot in a ring)
                cached_k.value, cached_v.value = write_rows(
                    (cached_k.value, cached_v.value), new,
                    idx % ring if ring else idx, self.mesh,
                )
            idx_var.value = idx + s
            if s == 1:
                # a decode step: the kernel reads each row's keys and
                # values once, as stored, up to the row's length (an index
                # that ran past the cache still names at most all of it;
                # the keys are cached rotated and a softmax over a set
                # does not depend on its order, so a ring read up to its
                # live slots is the window)
                out = decode_attention(
                    q[:, :, 0], cached_k.value, cached_v.value,
                    jnp.minimum(idx + 1, positions), self.mesh,
                )[:, :, None]
            elif fresh and prefills_through_kernel(cfg, b, s):
                # a whole prompt on its own keys, K/V heads as they are,
                # under the band in a window layer
                out = flash_attention(
                    q, *new, causal=True, window=ring, forward_only=True)
            else:
                # prefill, whole or a chunk behind earlier keys. A whole
                # prompt's keys are the s it just wrote into its own
                # cache: it scores those and not the zeros behind them. A
                # suffix behind a prefix hit and a budgeted prefill's chunk
                # score all of a cache that holds earlier keys, each row from
                # its own offset (what a kernel here would have to take:
                # ROADMAP S4(b)); the arithmetic is one, so a hit answers
                # as the miss did
                n_keys = s if fresh else cfg.max_seq_len
                k_all = jnp.repeat(
                    cached_k.value[:, :, :n_keys], h // hk, axis=1
                )
                v_all = jnp.repeat(
                    cached_v.value[:, :, :n_keys], h // hk, axis=1
                )
                # row r's query i sits at absolute position idx[r]+i; key
                # j is visible iff j <= idx[r]+i (and thus has been written)
                scores = jnp.einsum(
                    "bhqd,bhkd->bhqk", q.astype(jnp.float32),
                    k_all.astype(jnp.float32),
                ) / math.sqrt(d)
                q_pos = idx[:, None, None] + jnp.arange(s)[None, :, None]
                k_pos = jnp.arange(n_keys)[None, None, :]
                mask = k_pos <= q_pos  # (b, s, n_keys)
                scores = jnp.where(mask[:, None], scores, -jnp.inf)
                probs = jax.nn.softmax(scores, axis=-1)
                out = jnp.einsum(
                    "bhqk,bhkd->bhqd", probs, v_all.astype(jnp.float32)
                ).astype(cfg.dtype)
        elif self.mesh is not None:
            if cfg.rope:
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)
            # ring attention under shard_map: batch over data axes, heads
            # over tp, sequence over sp (ICI neighbor exchanges)
            qkv_spec = P(("dcn", "dp", "fsdp"), "tp", "sp", None)
            attn = shard_map(
                partial(ring_attention, axis_name="sp"),
                mesh=self.mesh,
                in_specs=(qkv_spec, qkv_spec, qkv_spec),
                out_specs=qkv_spec,
                check_vma=False,
            )
            out = attn(q, k, v)
        else:
            if cfg.rope:
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)
            out = flash_attention(q, k, v, causal=True)
        out = out.transpose(0, 2, 1, 3).reshape(b, s, h * d)
        if cfg.attn_gate:
            with jax.named_scope("attn.gate"):
                gate = _dense(
                    h * d, ("embed", "heads"), "w_gate", cfg.param_dtype,
                    cfg.dtype,
                )(x)
                out = out * jax.nn.sigmoid(gate)
        return LoRADense(
            features=cfg.dim,
            logical_axes=("heads", "embed"),
            rank=cfg.lora_rank,
            alpha=cfg.lora_alpha,
            param_dtype=cfg.param_dtype,
            dtype=cfg.dtype,
            name="wo",
        )(out, adapters.get("wo"), adapter_slots)


class MLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        gate = checkpoint_name(_dense(
            cfg.intermediate, ("embed", "mlp"), "w_gate", cfg.param_dtype, cfg.dtype
        )(x), remat_plan.MLP_GATE)
        up = checkpoint_name(_dense(
            cfg.intermediate, ("embed", "mlp"), "w_up", cfg.param_dtype, cfg.dtype
        )(x), remat_plan.MLP_UP)
        fused = nn.silu(gate) * up
        return _dense(
            cfg.dim, ("mlp", "embed"), "w_down", cfg.param_dtype, cfg.dtype
        )(fused)


class Block(nn.Module):
    config: LlamaConfig
    mesh: Optional[Mesh] = None
    decode: bool = False

    @nn.compact
    def __call__(self, x, cos, sin, adapters=None, adapter_slots=None):
        cfg = self.config
        attn_norm_w = self.param(
            "attn_norm",
            nn.with_logical_partitioning(nn.initializers.ones_init(), ("embed",)),
            (cfg.dim,),
            cfg.param_dtype,
        )
        # the residual and not ``wo``'s output: the sum is written anyway,
        # and a kept value between the matmul and its add splits their
        # fusion (a step 1.8% longer for keeping it: PERF.md, PR 56)
        h = checkpoint_name(x + Attention(cfg, self.mesh, self.decode, name="attn")(
            rmsnorm(x, attn_norm_w.astype(x.dtype), cfg.norm_eps, self.mesh),
            cos, sin,
            (adapters or {}).get("attn"), adapter_slots,
        ), remat_plan.ATTN_RESID)
        mlp_norm_w = self.param(
            "mlp_norm",
            nn.with_logical_partitioning(nn.initializers.ones_init(), ("embed",)),
            (cfg.dim,),
            cfg.param_dtype,
        )
        return h + MLP(cfg, name="mlp")(
            rmsnorm(h, mlp_norm_w.astype(h.dtype), cfg.norm_eps, self.mesh)
        )


class BlockStep(nn.Module):
    """One scanned layer: Block adapted to the (carry, xs) -> (carry, ys)
    signature nn.scan requires; rope tables ride along as broadcast xs."""

    config: LlamaConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, cos_sin):
        cos, sin = cos_sin
        x = Block(self.config, self.mesh, False, name="block")(x, cos, sin)
        return x, None


class Llama(nn.Module):
    config: LlamaConfig
    mesh: Optional[Mesh] = None
    decode: bool = False

    def _remat_policy(self, tokens):
        """Keep, of what a layer's second forward would recompute, what the
        device's memory holds beside this job (``remat_plan.for_step``).
        Decided as the step is traced, from the traced shapes, the mesh and
        the device kind's limit, and written into the profiler's trace as
        ``train.remat_plan``. A decode model runs no backward pass and an
        ``init`` has no parameters to count: both keep nothing."""
        kept = ()
        if not self.decode and not self.is_initializing():
            flat = traverse_util.flatten_dict(
                nn.meta.unbox(self.variables["params"]))
            plan = remat_plan.for_step(
                self.config,
                dict(self.mesh.shape) if self.mesh is not None else {},
                matrix_shards(self.mesh),
                {path: x.size * x.dtype.itemsize for path, x in flat.items()},
                *tokens.shape, remat_plan.device_bytes_limit())
            tracing.program_fact(
                "train.remat_plan", kept="+".join(plan.kept),
                kept_bytes_per_device=plan.kept_bytes,
                budget_bytes=plan.budget_bytes,
                tokens_per_device=plan.tokens_per_device)
            kept = plan.tags
        return jax.checkpoint_policies.save_only_these_names(*kept)

    @nn.compact
    def __call__(self, tokens, adapters=None, adapter_slots=None):
        # tokens: (batch, seq) int32; adapters: nested AdapterStore bank
        # {"layer_i": {"attn": {"wq": {"lora_a": ..., "lora_b": ...}, ...}}};
        # adapter_slots: (batch,) int32 per-row slot index, -1 = base-only
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
        if cfg.scan_layers and not self.decode:
            # stacked layers under lax.scan: sequential structure the
            # scheduler can't flatten, one layer's working set at a time
            step = BlockStep
            if cfg.remat:
                step = nn.remat(
                    BlockStep,
                    policy=self._remat_policy(tokens),
                    prevent_cse=False,
                )
            x, _ = nn.scan(
                step,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                in_axes=nn.broadcast,
                length=cfg.n_layers,
                metadata_params={nn.meta.PARTITION_NAME: "layers"},
            )(cfg, self.mesh, name="layers")(x, (cos, sin))
        else:
            block = Block
            if cfg.remat:
                block = nn.remat(
                    Block,
                    policy=self._remat_policy(tokens),
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


def build(config: LlamaConfig, mesh: Optional[Mesh] = None, decode: bool = False):
    """What ``ray_tpu.models.build`` returns for this family."""
    return Llama(config, mesh, decode)


def init_params(config: LlamaConfig, rng, mesh: Optional[Mesh] = None, seq: int = 8):
    model = Llama(config, mesh)
    tokens = jnp.zeros((1, seq), jnp.int32)
    return model.init(rng, tokens)["params"]


def nll_from_logits(logits, tokens):
    """Next-token NLL from full-sequence logits: pairs logits[:, :-1] with
    tokens[:, 1:].

    nll = logsumexp(logits) - logits[target]: no [B, S, vocab] f32
    log-softmax intermediate (at bench shapes that tensor alone is ~1 GB of
    HBM traffic the fused form never writes)."""
    logits = logits[:, :-1]
    targets = tokens[:, 1:]
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    tgt = jnp.take_along_axis(
        logits, targets[..., None], axis=-1
    )[..., 0].astype(jnp.float32)
    return (lse - tgt).mean()


def next_token_loss(config: LlamaConfig, mesh, params, tokens):
    """Causal LM loss: model sees the full (sp-divisible) sequence; see
    nll_from_logits for the fused-NLL numerics."""
    model = Llama(config, mesh)
    return nll_from_logits(model.apply({"params": params}, tokens), tokens)
