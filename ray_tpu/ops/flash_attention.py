"""Blockwise (flash) attention as a Pallas TPU kernel.

No reference analogue: the reference delegates attention math to torch/vLLM
(SURVEY §2c — SP/ring attention "must be built natively"). This kernel is the
single-chip building block; ring attention (parallel/ring_attention.py) calls
it per ring step and merges with the returned log-sum-exp.

Design (flash-attention-2 schedule):
- forward: grid (batch*heads, num_q_blocks, num_k_blocks), k innermost so the
  f32 accumulator/(m,l) scratch carries across k steps in VMEM; online
  softmax; causal blocks beyond the diagonal are predicated off
- backward: recompute P per block from the saved LSE (no S×S residuals);
  one kernel for dq (grid over q blocks) and one for dk/dv (grid over k
  blocks)
- everything MXU-shaped: 128-aligned blocks, matmuls in f32 accumulate
  (preferred_element_type), bf16-friendly inputs
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _use_interpret() -> bool:
    from ray_tpu._internal.platform import pallas_interpret

    return pallas_interpret("flash_attention")


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref,
    acc_ref, m_ref, l_ref,
    *, sm_scale: float, causal: bool, block_q: int, block_k: int,
    seq_q: int, seq_k: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _body():
        q = q_ref[0].astype(jnp.float32)  # (block_q, d)
        k = k_ref[0].astype(jnp.float32)  # (block_k, d)
        v = v_ref[0].astype(jnp.float32)  # (block_k, d)
        # zero padding rows: their probabilities are masked to 0, but the
        # uninitialized pad values would still poison matmuls via 0*NaN
        k_row = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0
        )
        v = jnp.where(k_row < seq_k, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (block_q, block_k)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        # padding rows/cols beyond the true lengths must not contribute
        valid = k_pos < seq_k
        if causal:
            valid = valid & (q_pos >= k_pos)
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_ref[...]  # (block_q, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (block_q, block_k)
        alpha = jnp.exp(m_prev - m_new)  # (block_q, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    if causal:
        # whole block above the diagonal: skip
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            _body()
    else:
        _body()

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        # log-sum-exp per q row, used by backward and ring merging
        lse_ref[0] = m_ref[...] + jnp.log(l_safe)


def _causal_kv_index(block_q: int, block_k: int):
    """Index map clamping the kv block to the q block's diagonal: iterations
    whose compute is predicated off (whole block above the diagonal) would
    otherwise still copy their K/V blocks HBM->VMEM; mapping them to the
    diagonal block makes the index repeat and Pallas elides the copy —
    ~1/3 less attention HBM traffic at seq=4*block."""

    def index_map(b, i, j):
        diag = (i * block_q + block_q - 1) // block_k
        return (b, jnp.minimum(j, diag), 0)

    return index_map


def _flash_forward(
    q, k, v, sm_scale: float, causal: bool, block_q: int, block_k: int
) -> Tuple[jax.Array, jax.Array]:
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    grid = (bh, pl.cdiv(sq, block_q), pl.cdiv(sk, block_k))
    out_shape = [
        jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
    ]
    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        seq_q=sq,
        seq_k=sk,
    )
    kv_index = (
        _causal_kv_index(block_q, block_k)
        if causal and sq == sk
        else (lambda b, i, j: (b, j, 0))
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        out_shape=out_shape,
        interpret=_use_interpret(),
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
    acc_ref,
    *, sm_scale: float, causal: bool, block_q: int, block_k: int,
    seq_q: int, seq_k: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        k_row = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0
        )
        k = jnp.where(k_row < seq_k, k, 0.0)
        v = jnp.where(k_row < seq_k, v, 0.0)
        lse = lse_ref[0]  # (block_q, 1)
        delta = delta_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        valid = (k_pos < seq_k) & (q_pos < seq_q)
        if causal:
            valid = valid & (q_pos >= k_pos)
        s = jnp.where(valid, s, _NEG_INF)
        p = jnp.exp(s - lse)  # (block_q, block_k)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * sm_scale
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            _body()
    else:
        _body()

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, sm_scale: float, causal: bool, block_q: int, block_k: int,
    seq_q: int, seq_k: int,
):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        q_row = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0
        )
        q = jnp.where(q_row < seq_q, q, 0.0)
        do = jnp.where(q_row < seq_q, do, 0.0)
        # padded lse/delta rows are uninitialized reads; exp(-inf - NaN)=NaN
        lse = jnp.where(q_row < seq_q, lse_ref[0], 0.0)
        delta = jnp.where(q_row < seq_q, delta_ref[0], 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        valid = (k_pos < seq_k) & (q_pos < seq_q)
        if causal:
            valid = valid & (q_pos >= k_pos)
        s = jnp.where(valid, s, _NEG_INF)
        p = jnp.exp(s - lse)  # (block_q, block_k)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * sm_scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            _body()
    else:
        _body()

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_bwd_dq(q, k, v, do, lse, delta, *, sm_scale, causal, block_q=256, block_k=256):
    """dq for one (q-block, kv-block) pairing; reused by ring attention.
    lse/delta: (bh, sq, 1) f32."""
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    kv_index = (
        _causal_kv_index(block_q, block_k)
        if causal and sq == sk
        else (lambda b, i, j: (b, j, 0))
    )
    return pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel,
            sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k,
            seq_q=sq, seq_k=sk,
        ),
        grid=(bh, pl.cdiv(sq, block_q), pl.cdiv(sk, block_k)),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=_use_interpret(),
    )(q, k, v, do, lse, delta)


def flash_bwd_dkv(q, k, v, do, lse, delta, *, sm_scale, causal, block_q=256, block_k=256):
    """dk/dv contribution of one q shard to one kv shard; reused by ring
    attention. lse/delta: (bh, sq, 1) f32."""
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if causal and sq == sk:
        # mirror of _causal_kv_index: early q blocks entirely above the
        # diagonal are compute-skipped; clamp their loads to the first
        # contributing q block so the repeated index elides the copy
        def q_index(b, j, i):
            first = (j * block_k) // block_q
            return (b, jnp.maximum(i, first), 0)
    else:
        def q_index(b, j, i):
            return (b, i, 0)

    return pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel,
            sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k,
            seq_q=sq, seq_k=sk,
        ),
        grid=(bh, pl.cdiv(sk, block_k), pl.cdiv(sq, block_q)),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_q, 1), q_index),
            pl.BlockSpec((1, block_q, 1), q_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=_use_interpret(),
    )(q, k, v, do, lse, delta)


def attention_delta(do, o):
    """delta = rowsum(dO * O), shape (bh, sq, 1) f32."""
    return jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )


def _flash_backward(sm_scale, causal, block_q, block_k, residuals, g):
    q, k, v, o, lse = residuals
    do, _ = g
    delta = attention_delta(do, o)
    dq = flash_bwd_dq(
        q, k, v, do, lse, delta,
        sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k,
    )
    dk, dv = flash_bwd_dkv(
        q, k, v, do, lse, delta,
        sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k,
    )
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_core(q, k, v, sm_scale, causal, block_q, block_k):
    o, lse = _flash_forward(q, k, v, sm_scale, causal, block_q, block_k)
    return o, lse


def _flash_core_fwd(q, k, v, sm_scale, causal, block_q, block_k):
    o, lse = _flash_forward(q, k, v, sm_scale, causal, block_q, block_k)
    return (o, lse), (q, k, v, o, lse)


def _flash_core_bwd(sm_scale, causal, block_q, block_k, residuals, g):
    return _flash_backward(sm_scale, causal, block_q, block_k, residuals, g)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = 256,
    block_k: int = 256,
) -> Tuple[jax.Array, jax.Array]:
    """Attention over (batch, heads, seq, head_dim); also returns per-row
    log-sum-exp (batch, heads, seq) for ring-step merging."""
    b, h, sq, d = q.shape
    _, hk, sk, _ = k.shape
    if h != hk:  # grouped-query attention: repeat kv heads
        k = jnp.repeat(k, h // hk, axis=1)
        v = jnp.repeat(v, h // hk, axis=1)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    o, lse = _flash_core(qf, kf, vf, sm_scale, causal, block_q, block_k)
    return o.reshape(b, h, sq, d), lse.reshape(b, h, sq)


def flash_attention(q, k, v, **kwargs) -> jax.Array:
    return flash_attention_with_lse(q, k, v, **kwargs)[0]


def reference_attention(q, k, v, *, causal: bool = True, sm_scale=None):
    """Plain XLA attention for correctness checks."""
    b, h, sq, d = q.shape
    _, hk, sk, _ = k.shape
    if h != hk:
        k = jnp.repeat(k, h // hk, axis=1)
        v = jnp.repeat(v, h // hk, axis=1)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
