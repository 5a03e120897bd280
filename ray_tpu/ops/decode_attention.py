"""Decode attention as a Pallas TPU kernel: one new token a row against the
dense slot cache, reading each row's keys and values once, in the dtype
they are stored in, per KV head and only up to that row's length.

The einsum it replaces (models/llama.py, still the ``s > 1`` path) repeats
both caches ``h // hk`` times, casts them to f32 and scores all
``max_seq_len`` positions of every row before masking: HBM traffic that
grows with the configured maximum, not with the tokens present.

Design:
- grid (rows, key blocks), key blocks innermost; one block holds all of a
  row's KV heads, so a grid step moves 1 MB each of K and V and a row costs
  ``max_seq_len / block_k`` steps however many heads it has
- ``lengths`` is the scalar-prefetch argument: the K/V index map clamps the
  block index to the row's last live block, so the index repeats past the
  length and Pallas elides the copy; ``pl.when`` skips the arithmetic
- the ``h // hk`` query heads of a group are the rows of one small matmul
  against the group's K/V block: nothing is repeated in HBM
- the einsum's arithmetic: exact products accumulated in f32 (stored values
  times stored values for q.k, f32 probabilities for p.v), f32 online
  softmax with running max, sum and accumulator in VMEM scratch

``latent_decode_attention`` is the same kernel for a latent cache (DeepSeek's
MLA in its absorbed form, models/deepseek.py): one shared row a position
(``rank`` latent columns and ``rope`` rotary ones, a cache each), every
query head a row of one matmul against it, and the values are the latent
columns themselves, so a block is read once and serves as K and as V. It shares the length clamp, the online softmax
(``_accumulate``) and ``_dot``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.plan import KV_SPEC

_NEG_INF = -1e30
# query heads of a group are padded to one f32 sublane tile, so every
# in-kernel slice of the group's rows is tile-aligned
_GROUP_ROWS = 8
# K (or V) bytes one grid step moves. Measured on a v5e at 16 rows x 8 KV
# heads x 4096 x 128 bf16, twelve layers: 0.5 / 1 / 2 MB take 1.9 / 1.8 / 2.1
# ms with 128-832 keys a row and 7.8 / 5.1 / 4.4 ms with every row full. A
# larger block amortises the ~0.35 us a grid step costs, live or skipped; a
# smaller one wastes less of a short row's last block. K and V,
# double-buffered, are four blocks of VMEM.
_BLOCK_BYTES = 1024 * 1024


def _use_interpret(kernel: str = "decode_attention") -> bool:
    from ray_tpu._internal.platform import pallas_interpret

    return pallas_interpret(kernel)


def block_k(max_seq_len: int, kv_heads: int, head_dim: int, dtype) -> int:
    """Key positions in one block: a power of two of about ``_BLOCK_BYTES``
    across the block's KV heads, at least 128 (the lane width of the scores)
    and at most the cache itself."""
    per_position = kv_heads * head_dim * jnp.dtype(dtype).itemsize
    fit = max(128, _BLOCK_BYTES // per_position)
    return min(max_seq_len, 1 << (fit.bit_length() - 1))


def _dot(a, b, dims):
    """a . b as the einsum multiplies: exact products of the operands upcast
    to f32, f32 accumulation. A product of two bf16 values is exact in f32,
    so two bf16 operands go to the MXU as they are (one pass, and measured:
    a quarter to a third of the kernel's time); f32 probabilities are not
    rounded on their way in."""
    if a.dtype == b.dtype == jnp.bfloat16:
        return jax.lax.dot_general(
            a, b, (dims, ((), ())), preferred_element_type=jnp.float32
        )
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32), (dims, ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _accumulate(s, v, acc_ref, m_ref, l_ref, j):
    """One block of the online softmax: masked scores ``s (rows, block)``
    and values ``v (block, d)`` into slot ``j`` of the running max, sum and
    accumulator."""
    m_prev = m_ref[j]  # (rows, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[j] = l_ref[j] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[j] = acc_ref[j] * alpha + _dot(p, v, ((1,), (0,)))
    m_ref[j] = m_new


def _last_live_block(lengths_ref, bi, ki, block: int):
    """The key block grid step ``ki`` of row ``bi`` reads: its own up to
    the row's last live one, which then repeats, so Pallas elides the copy
    of every block past the row's length."""
    last_live = jnp.maximum(lengths_ref[bi] - 1, 0) // block
    return jnp.minimum(ki, last_live)


def _kernel(
    lengths_ref, q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *, sm_scale: float, block: int, kv_heads: int,
):
    bi = pl.program_id(0)
    ki = pl.program_id(1)
    length = lengths_ref[bi]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ki * block < length)
    def _live_block():
        k_pos = ki * block + jax.lax.broadcasted_iota(
            jnp.int32, (_GROUP_ROWS, block), 1
        )
        k_row = ki * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, 1), 0
        )
        for j in range(kv_heads):
            q = q_ref[0, j]  # (_GROUP_ROWS, d)
            k = k_ref[0, j]  # (block, d)
            # rows past the length hold whatever the cache held before (or
            # nothing, past a partial last block): their probabilities are
            # 0, but 0 * NaN would still poison the matmul
            v = jnp.where(k_row < length, v_ref[0, j], 0)
            s = _dot(q, k, ((1,), (1,))) * sm_scale
            s = jnp.where(k_pos < length, s, _NEG_INF)
            _accumulate(s, v, acc_ref, m_ref, l_ref, j)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)  # a row of length 0 attends nothing
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _decode_attention(q, k_cache, v_cache, lengths):
    b, h, d = q.shape
    _, hk, max_seq_len, _ = k_cache.shape
    group = h // hk
    block = block_k(max_seq_len, hk, d, k_cache.dtype)
    rows = -(-group // _GROUP_ROWS) * _GROUP_ROWS
    qg = q.reshape(b, hk, group, d)
    if rows != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows - group), (0, 0)))

    def kv_index(bi, ki, lengths_ref):
        return (bi, 0, _last_live_block(lengths_ref, bi, ki, block), 0)

    q_spec = pl.BlockSpec((1, hk, rows, d), lambda bi, ki, _: (bi, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, hk, block, d), kv_index)
    out = pl.pallas_call(
        functools.partial(
            _kernel, sm_scale=1.0 / math.sqrt(d), block=block, kv_heads=hk
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, pl.cdiv(max_seq_len, block)),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((hk, rows, d), jnp.float32),
                pltpu.VMEM((hk, rows, 1), jnp.float32),
                pltpu.VMEM((hk, rows, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hk, rows, d), q.dtype),
        name="decode_attention",  # the op's name in a device trace
        interpret=_use_interpret(),
    )(lengths, qg, k_cache, v_cache)
    return out[:, :, :group].reshape(b, h, d)


def decode_attention(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, lengths: jax.Array,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """Attention of one query token a row over that row's cached keys and
    values: ``q (b, h, d)``, caches ``(b, hk, max_seq_len, d)``, ``lengths
    (b,) int32`` = positions of each row that hold a key (the new token's
    included). Returns ``(b, h, d)`` in q's dtype. Positions at or past a
    row's length are not read into the result.

    A pallas_call is opaque to the SPMD partitioner, so with a ``mesh`` the
    kernel runs per shard under shard_map on the layout the decode cache
    lives in (parallel/plan.py KV_SPEC: heads over tp), like ops/rmsnorm.py.
    """
    if mesh is None or mesh.size == 1:
        return _decode_attention(q, k_cache, v_cache, lengths)
    heads = P(*KV_SPEC[:2], None)  # q and the output: (b, h, d)
    return jax.shard_map(
        _decode_attention, mesh=mesh,
        in_specs=(heads, KV_SPEC, KV_SPEC, P()), out_specs=heads,
        check_vma=False,
    )(q, k_cache, v_cache, lengths)


# -- latent cache (MLA, absorbed form) ----------------------------------------


def latent_block_k(max_seq_len: int, width: int, dtype) -> int:
    """Positions in one block of a latent cache of ``width`` values a
    position: ``block_k``'s rule with twice the bytes, because the one
    block a grid step moves is its keys and its values together."""
    fit = max(128, 2 * _BLOCK_BYTES // (width * jnp.dtype(dtype).itemsize))
    return min(max_seq_len, 1 << (fit.bit_length() - 1))


def _latent_kernel(
    lengths_ref, q_ref, qr_ref, c_ref, r_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *, sm_scale: float, block: int,
):
    bi = pl.program_id(0)
    ki = pl.program_id(1)
    length = lengths_ref[bi]
    rows = q_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ki * block < length)
    def _live_block():
        k_pos = ki * block + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block), 1
        )
        k_row = ki * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, 1), 0
        )
        latent = c_ref[0, 0]  # (block, rank): the keys' latent part
        # ... and the values: read once, used twice; past the length they
        # are zeroed for the reason given in ``_kernel``
        v = jnp.where(k_row < length, latent, 0)
        s = (
            _dot(q_ref[0], latent, ((1,), (1,)))
            + _dot(qr_ref[0], r_ref[0, 0], ((1,), (0,)))  # (rope, block)
        ) * sm_scale
        s = jnp.where(k_pos < length, s, _NEG_INF)
        _accumulate(s, v, acc_ref, m_ref, l_ref, 0)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def latent_decode_attention(
    q_latent: jax.Array, q_rope: jax.Array, latent_cache: jax.Array,
    rope_cache: jax.Array, lengths: jax.Array, *, sm_scale: float,
) -> jax.Array:
    """Attention of one query token a row over that row's latent cache:
    ``q_latent (b, h, rank)`` (the absorbed queries ``q_nope W_kvb^K``),
    ``q_rope (b, h, rope)`` (rotated), ``latent_cache (b, 1, max_seq_len,
    rank)`` and ``rope_cache (b, 1, max_seq_len, rope)`` (``c`` and
    ``k_rope`` a position, shared by every head), ``lengths (b,) int32`` as
    in ``decode_attention``. Scores are ``(q_latent . c + q_rope . k_rope)
    * sm_scale``, values ``c`` itself. Returns ``(b, h, rank)`` in the
    queries' dtype: the caller projects it through ``W_kvb^V``.

    Two caches and not one row of ``rank + rope`` columns: 576 is 4.5 lane
    tiles, the TPU stores such an array sequence-minor, and a kernel's
    operand is row-major, so every step transposed every layer's rows
    first (PERF.md, PR 30). The rotary cache, 64 wide, is stored
    sequence-minor too: the kernel takes it as ``(b, 1, rope, seq)``, which
    is that array's own bytes, so the swap of axes below is a bitcast on
    the TPU (and the score a plain ``(rows, rope) x (rope, block)``). One device only: a latent row has no head axis
    to shard (``models.refusals("deepseek")["mesh"]``)."""
    b, h, rank = q_latent.shape
    rope = q_rope.shape[-1]
    max_seq_len = latent_cache.shape[2]
    if (latent_cache.shape != (b, 1, max_seq_len, rank)
            or rope_cache.shape != (b, 1, max_seq_len, rope)
            or q_rope.shape != (b, h, rope)):
        raise ValueError(
            f"latent caches {latent_cache.shape} / {rope_cache.shape} do "
            f"not match queries {q_latent.shape} / {q_rope.shape}"
        )
    block = latent_block_k(max_seq_len, rank + rope, latent_cache.dtype)
    rows = -(-h // _GROUP_ROWS) * _GROUP_ROWS
    if rows != h:
        pad = ((0, 0), (0, rows - h), (0, 0))
        q_latent, q_rope = jnp.pad(q_latent, pad), jnp.pad(q_rope, pad)

    def kv_index(bi, ki, lengths_ref):
        return (bi, 0, _last_live_block(lengths_ref, bi, ki, block), 0)

    def q_spec(width):
        return pl.BlockSpec((1, rows, width), lambda bi, ki, _: (bi, 0, 0))

    out = pl.pallas_call(
        functools.partial(_latent_kernel, sm_scale=sm_scale, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, pl.cdiv(max_seq_len, block)),
            in_specs=[
                q_spec(rank), q_spec(rope),
                pl.BlockSpec((1, 1, block, rank), kv_index),
                pl.BlockSpec(
                    (1, 1, rope, block),
                    lambda bi, ki, lengths_ref: (
                        bi, 0, 0, _last_live_block(lengths_ref, bi, ki, block)
                    ),
                ),
            ],
            out_specs=q_spec(rank),
            scratch_shapes=[
                pltpu.VMEM((1, rows, rank), jnp.float32),
                pltpu.VMEM((1, rows, 1), jnp.float32),
                pltpu.VMEM((1, rows, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, rows, rank), q_latent.dtype),
        name="latent_decode_attention",  # the op's name in a device trace
        interpret=_use_interpret("latent_decode_attention"),
    )(lengths, q_latent, q_rope, latent_cache, jnp.swapaxes(rope_cache, 2, 3))
    return out[:, :h]
