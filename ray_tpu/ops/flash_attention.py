"""Blockwise (flash) attention as a Pallas TPU kernel.

No reference analogue: the reference delegates attention math to torch/vLLM
(SURVEY §2c — SP/ring attention "must be built natively"). This kernel is the
single-chip building block; ring attention (parallel/ring_attention.py) calls
it per ring step and merges with the returned log-sum-exp.

Design (flash-attention-2 schedule):
- forward: grid (batch*heads, num_q_blocks, num_k_blocks), k innermost so the
  f32 accumulator/(m,l) scratch carries across k steps in VMEM; online
  softmax; causal blocks beyond the diagonal are predicated off, and with
  a ``window`` (a query sees its last ``window`` positions, itself
  included: forward only) so are the blocks wholly beneath the band, while
  the blocks the band's lower edge crosses take the masked body
- backward: recompute P per block from the saved LSE (no S×S residuals);
  one kernel for dq (grid over q blocks) and one for dk/dv (grid over k
  blocks, scores computed transposed so no tile is ever transposed)
- tiles are large (``default_blocks``: a grid step costs what it costs
  whatever it does) and worked through ``_CHUNK`` columns at a time, so the
  f32 score temporaries stay small and one chunk's matmuls overlap
  another's softmax; in a square tile on the causal diagonal each chunk
  takes only the q rows at or below it
- what a tile gives the MXU: operands in the *input's* dtype (bf16 callers
  get single-pass bf16 matmuls, f32 callers keep f32 operands), f32
  accumulation (preferred_element_type). p and ds are cast to the input
  dtype just before their matmuls, as the activations they multiply are
- what a tile asks of the VPU: the softmax (max, exp, sum, lse, delta,
  accumulators, rescale) in f32, and nothing a tile does not need: the
  causal mask only in tiles the diagonal crosses, the padding mask only
  when a length is no multiple of its block (static), ``sm_scale`` on the
  accumulated dq and dk instead of on every ds, row statistics replicated
  over the 128 lanes
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128
# columns of a tile that one pass of a kernel's (unrolled) inner loop handles
_CHUNK = 256
# contract the last axis of both operands (a @ b.T), and the plain a @ b
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))


def _chunks(n: int):
    """(start, width) of the column chunks of a tile side of n: the f32
    score temporaries are (rows, _CHUNK) instead of (rows, n), and the
    matmuls of one chunk overlap the softmax of another. A side that is no
    multiple of _CHUNK (a whole short sequence) is one chunk."""
    w = _CHUNK if n % _CHUNK == 0 else n
    return [(c, w) for c in range(0, n, w)]


def _skips_above(masked: bool, causal: bool, pad: bool, block_q, block_k):
    """In a square tile on the diagonal (the only masked tiles of a causal
    call with block_q == block_k and no padding) chunk c needs only the q
    rows from c on: the rest of its columns is above the diagonal. With
    2048-wide tiles and 256-wide chunks that leaves 6% of the executed
    scores masked, as 256 x 256 tiles would, in 1/64 of the grid steps."""
    return masked and causal and not pad and block_q == block_k


def _use_interpret() -> bool:
    from ray_tpu._internal.platform import pallas_interpret

    return pallas_interpret("flash_attention")


def default_blocks(
    seq_q: int, seq_k: int, head_dim: int, dtype, causal: bool = True,
    window: Optional[int] = None,
) -> Tuple[int, int]:
    """(block_q, block_k) for a call nobody gave tiles: chosen from the shape
    alone, never by timing (set-up time is a judged metric).

    A grid step costs ~0.2 us whatever it does, and what a tile does once
    (rescale the accumulator, write it back, the pipeline's bookkeeping)
    is paid a step, so tiles are as large as VMEM allows and the work
    inside one is done ``_CHUNK`` columns at a time: 2048 x 2048 for a
    causal call, 1024 x 1024 for a call without a diagonal to skip above or
    with a ``window`` (their masked tiles keep every row in every chunk: a
    windowed 2048 x 2048 call asked the described chip for 19.5 MiB), both
    for a head row of at
    most 256 bytes (128 wide in bf16) and halved for each doubling of it,
    which keeps every call inside the 16 MiB of VMEM a kernel gets.
    A side of at most 1024 is one tile with no padding; a longer one takes
    the largest such tile that divides it (down to 512) and pads, at 1024,
    only when none does. Measured at (64, 4096, 128) bf16 causal
    on a v5e, a layer's four calls (PERF.md, PR 35): 256 x 256 31.3 ms,
    1024 x 1024 14.4, 2048 x 2048 in chunks 10.8.
    """
    row_bytes = max(head_dim, _LANES) * jnp.dtype(dtype).itemsize
    most = 2048 if causal and seq_q == seq_k and window is None else 1024
    most = max(512, most * 256 // max(256, row_bytes) // 512 * 512)

    def side(seq: int) -> int:
        if seq <= min(most, 1024):
            return seq
        for block in (most, most // 2, most // 4):
            if block >= 512 and seq % block == 0:
                return block
        return min(most, 1024)

    return side(seq_q), side(seq_k)


def _resolve_blocks(q, k, causal, block_q, block_k,
                    window=None) -> Tuple[int, int]:
    auto_q, auto_k = default_blocks(
        q.shape[1], k.shape[1], q.shape[2], q.dtype, causal, window
    )
    return (
        auto_q if block_q is None else min(block_q, q.shape[1]),
        auto_k if block_k is None else min(block_k, k.shape[1]),
    )


def _lanes(x, n: int):
    """(rows, _LANES) with every lane of a row equal -> (rows, n)."""
    if n == _LANES:
        return x
    if n % _LANES == 0:
        return jnp.concatenate([x] * (n // _LANES), axis=1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _mask_scores(s, q_axis: int, causal: bool, q0, k0, k_left=None,
                 window: Optional[int] = None):
    """Scores with q positions from ``q0`` along ``q_axis`` and k positions
    from ``k0`` along the other axis, -inf where the key comes after the
    query (``causal``), ``window`` or more positions before it, or lies
    past the sequence's end (``k_left``: the k positions of this tile that
    exist; None when none is padding). A subtract and a compare against a
    scalar, not two position tiles."""
    k_idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    valid = None
    if causal:  # q_pos >= k_pos
        q_idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
        ahead = k_idx - q_idx
        valid = ahead <= q0 - k0
        if window is not None:  # k_pos > q_pos - window
            valid &= ahead > q0 - k0 - window
    if k_left is not None:
        valid = k_idx < k_left if valid is None else valid & (k_idx < k_left)
    return s if valid is None else jnp.where(valid, s, _NEG_INF)


def _tile_kinds(causal: bool, q_idx, k_idx, block_q: int, block_k: int, edge,
                window: Optional[int] = None):
    """(plain, masked) predicates of grid tile (q_idx, k_idx): a tile runs
    the masked body iff the causal diagonal or a ``window``'s lower edge
    crosses it or it is an ``edge`` tile holding padding (``edge`` is None
    when there is no padding); a causal tile wholly above the diagonal, or
    wholly beneath the band, runs nothing. ``None`` for a predicate that is
    statically true/false."""
    if causal:
        runs = q_idx * block_q + block_q - 1 >= k_idx * block_k
        below = q_idx * block_q >= k_idx * block_k + block_k - 1
        if window is not None:
            # its last key is out of its first query's window: nothing to see
            runs &= k_idx * block_k + block_k - 1 > q_idx * block_q - window
            # its first key is in its last query's window: nothing to mask
            below &= k_idx * block_k > q_idx * block_q + block_q - 1 - window
        if edge is None:
            return below, runs & ~below
        return below & ~edge, runs & (~below | edge)
    if edge is None:
        return None, False
    return ~edge, edge


def _run_tiles(body, plain, masked):
    if plain is None:
        body(False)
        return
    pl.when(plain)(lambda: body(False))
    pl.when(masked)(lambda: body(True))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref,
    acc_ref, m_ref, l_ref,
    *, sm_scale: float, causal: bool, block_q: int, block_k: int,
    seq_q: int, seq_k: int, window: Optional[int] = None,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    pad_k = seq_k % block_k != 0
    d = acc_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _body(masked: bool):
        # (a masked tile of a window call may lie on the band's lower
        # edge, off the diagonal, where every chunk needs every row)
        tri = window is None and _skips_above(
            masked, causal, pad_k, block_q, block_k)
        for c, w in _chunks(block_k):
            r0 = c if tri else 0
            rows = slice(r0, block_q)
            k = k_ref[0, c:c + w, :]  # (w, d)
            v = v_ref[0, c:c + w, :]
            s = jax.lax.dot_general(
                q_ref[0, rows, :], k, _NT, preferred_element_type=jnp.float32
            ) * sm_scale  # (rows, w)
            if masked:
                k_left = seq_k - ki * block_k - c if pad_k else None
                s = _mask_scores(
                    s, 0, causal, qi * block_q + r0, ki * block_k + c, k_left,
                    window,
                )
                if pad_k:
                    # the pad rows of v are uninitialized, and 0 * NaN would
                    # poison the matmul
                    k_row = jax.lax.broadcasted_iota(jnp.int32, (w, 1), 0)
                    v = jnp.where(k_row < k_left, v, 0)
            m_prev = m_ref[rows, :]  # (rows, _LANES), lanes equal
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - _lanes(m_new, w))
            alpha = jnp.exp(m_prev - m_new)
            l_ref[rows, :] = l_ref[rows, :] * alpha + jnp.sum(
                p, axis=1, keepdims=True
            )
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32
            )
            acc_ref[rows, :] = acc_ref[rows, :] * _lanes(alpha, d) + pv
            m_ref[rows, :] = m_new

    edge = (ki == nk - 1) if pad_k else None
    _run_tiles(
        _body, *_tile_kinds(causal, qi, ki, block_q, block_k, edge, window))

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] * _lanes(1.0 / l_safe, d)).astype(o_ref.dtype)
        # log-sum-exp per q row, used by backward and ring merging
        lse_ref[0] = (m_ref[...] + jnp.log(l_safe))[:, :1]


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


def _kv_index(causal: bool, sq: int, sk: int, block_q: int, block_k: int,
              window: Optional[int] = None, group: int = 1):
    """Where grid step (b, i, j) finds its K/V block. ``group`` query heads
    read one K/V head (``b // group``: the K/V heads as they are, not
    repeated), and with a ``window`` the blocks beneath the band are clamped
    to the band's first as those above the diagonal are to its last."""
    if window is None and group == 1:
        if causal and sq == sk:
            return _causal_kv_index(block_q, block_k)
        return lambda b, i, j: (b, j, 0)

    def index_map(b, i, j):
        if causal and sq == sk:
            last = (i * block_q + block_q - 1) // block_k
            first = 0 if window is None else jnp.maximum(
                i * block_q - window + 1, 0) // block_k
            j = jnp.clip(j, first, last)
        return (b // group, j, 0)

    return index_map


# no vmem_limit_bytes: default_blocks keeps a call inside the compiler's 16
# MiB (a 2048 x 2048 bf16 call takes 11-15.6 of them, as much as it is left),
# and a call that reserves 20 or 32 MiB slows the step's own fusions around
# it by 0.8% of the step (PERF.md, PR 35)
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
)


def _flash_forward(
    q, k, v, sm_scale: float, causal: bool,
    block_q: Optional[int], block_k: Optional[int],
    window: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """``k`` and ``v`` may hold fewer rows than ``q``: row ``r`` of them is
    then read by q's rows ``r * group ..`` (grouped-query attention on the
    K/V heads as they are)."""
    bh, sq, d = q.shape
    bhk, sk, _ = k.shape
    block_q, block_k = _resolve_blocks(
        q, k, causal, block_q, block_k, window)
    kv_index = _kv_index(
        causal, sq, sk, block_q, block_k, window, bh // bhk)
    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel,
            sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k,
            seq_q=sq, seq_k=sk, window=window,
        ),
        grid=(bh, pl.cdiv(sq, block_q), pl.cdiv(sk, block_k)),
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
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=_use_interpret(),
        name="flash_fwd",  # the op's name in a device trace
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
    lse_s, delta_s, acc_ref,
    *, sm_scale: float, causal: bool, block_q: int, block_k: int,
    seq_q: int, seq_k: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    pad_k = seq_k % block_k != 0

    @pl.when(ki == 0)
    def _init():
        # resident for the whole k loop: the row statistics are spread
        # over the lanes once
        lse_s[...] = jnp.broadcast_to(lse_ref[0], lse_s.shape)
        delta_s[...] = jnp.broadcast_to(delta_ref[0], delta_s.shape)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _body(masked: bool):
        tri = _skips_above(masked, causal, pad_k, block_q, block_k)
        for c, w in _chunks(block_k):
            r0 = c if tri else 0
            rows = slice(r0, block_q)
            k = k_ref[0, c:c + w, :]
            v = v_ref[0, c:c + w, :]
            do = do_ref[0, rows, :]
            k_left = seq_k - ki * block_k - c if masked and pad_k else None
            if k_left is not None:
                k_row = jax.lax.broadcasted_iota(jnp.int32, (w, 1), 0)
                k = jnp.where(k_row < k_left, k, 0)
                v = jnp.where(k_row < k_left, v, 0)
            s = jax.lax.dot_general(
                q_ref[0, rows, :], k, _NT, preferred_element_type=jnp.float32
            ) * sm_scale
            if masked:
                s = _mask_scores(
                    s, 0, causal, qi * block_q + r0, ki * block_k + c, k_left
                )
            p = jnp.exp(s - _lanes(lse_s[rows, :], w))  # (rows, w)
            dp = jax.lax.dot_general(
                do, v, _NT, preferred_element_type=jnp.float32
            )
            ds = p * (dp - _lanes(delta_s[rows, :], w))  # sm_scale: at the end
            acc_ref[rows, :] += jax.lax.dot_general(
                ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32
            )

    edge = (ki == nk - 1) if pad_k else None
    _run_tiles(_body, *_tile_kinds(causal, qi, ki, block_q, block_k, edge))

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = (acc_ref[...] * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, sm_scale: float, causal: bool, block_q: int, block_k: int,
    seq_q: int, seq_k: int,
):
    """Scores are computed transposed, (block_k, block_q), so that p^T @ dO
    and ds^T @ q are plain matmuls; lse and delta arrive as rows."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    pad_q = seq_q % block_q != 0

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _body(masked: bool):
        tri = _skips_above(masked, causal, pad_q, block_q, block_k)
        for c, w in _chunks(block_q):
            r1 = c + w if tri else block_k  # k rows [0, r1) see q rows [c, c+w)
            rows = slice(0, r1)
            q = q_ref[0, c:c + w, :]
            do = do_ref[0, c:c + w, :]
            lse = lse_ref[0, :, c:c + w]
            delta = delta_ref[0, :, c:c + w]
            if masked and pad_q:
                # padded q rows are uninitialized reads: as zeros (lse and
                # delta too) they give p = 1 against dO = 0, and ds = 0
                q_left = seq_q - qi * block_q - c
                q_row = jax.lax.broadcasted_iota(jnp.int32, (w, 1), 0)
                q = jnp.where(q_row < q_left, q, 0)
                do = jnp.where(q_row < q_left, do, 0)
                q_col = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
                lse = jnp.where(q_col < q_left, lse, 0.0)
                delta = jnp.where(q_col < q_left, delta, 0.0)
            st = jax.lax.dot_general(
                k_ref[0, rows, :], q, _NT, preferred_element_type=jnp.float32
            ) * sm_scale  # (rows, w)
            if masked:
                st = _mask_scores(
                    st, 1, causal, qi * block_q + c, ki * block_k
                )
            pt = jnp.exp(st - lse)
            dv_acc[rows, :] += jax.lax.dot_general(
                pt.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32
            )
            dpt = jax.lax.dot_general(
                v_ref[0, rows, :], do, _NT, preferred_element_type=jnp.float32
            )
            dst = pt * (dpt - delta)  # sm_scale: at the end
            dk_acc[rows, :] += jax.lax.dot_general(
                dst.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32
            )

    edge = (qi == nq - 1) if pad_q else None
    _run_tiles(_body, *_tile_kinds(causal, qi, ki, block_q, block_k, edge))

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_bwd_dq(
    q, k, v, do, lse, delta, *, sm_scale, causal, block_q=None, block_k=None
):
    """dq for one (q-block, kv-block) pairing; reused by ring attention.
    lse/delta: (bh, sq, 1) f32."""
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    block_q, block_k = _resolve_blocks(q, k, causal, block_q, block_k)
    kv_index = _kv_index(causal, sq, sk, block_q, block_k)
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
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=_use_interpret(),
        name="flash_bwd_dq",  # the op's name in a device trace
    )(q, k, v, do, lse, delta)


def flash_bwd_dkv(
    q, k, v, do, lse, delta, *, sm_scale, causal, block_q=None, block_k=None
):
    """dk/dv contribution of one q shard to one kv shard; reused by ring
    attention. lse/delta: (bh, sq, 1) f32."""
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    block_q, block_k = _resolve_blocks(q, k, causal, block_q, block_k)
    if causal and sq == sk:
        # mirror of _causal_kv_index: early q blocks entirely above the
        # diagonal are compute-skipped; clamp their loads to the first
        # contributing q block so the repeated index elides the copy
        def first(j):
            return (j * block_k) // block_q
    else:
        def first(j):
            return 0

    def q_index(b, j, i):
        return (b, jnp.maximum(i, first(j)), 0)

    def row_index(b, j, i):
        return (b, 0, jnp.maximum(i, first(j)))

    # the kernel's scores have q along the lanes: hand it lse and delta as
    # rows (a reshape of (bh, sq, 1), no data moves)
    lse = lse.reshape(bh, 1, sq)
    delta = delta.reshape(bh, 1, sq)
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
            pl.BlockSpec((1, 1, block_q), row_index),
            pl.BlockSpec((1, 1, block_q), row_index),
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
        compiler_params=_COMPILER_PARAMS,
        interpret=_use_interpret(),
        name="flash_bwd_dkv",  # the op's name in a device trace
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


# ``checkpoint_name`` tags of what a backward rule keeps of its forward
# (models/remat_plan.py says which of them a rematerialised layer saves)
ATTN_Q, ATTN_OUT, ATTN_LSE = "attn_q", "attn_out", "attn_lse"


def tag_residuals(q, o, lse):
    """Tag q, the output and its log-sum-exp where a backward rule's
    residuals are made (the identity outside ``jax.checkpoint``): a remat
    policy that keeps ``o`` and ``lse`` runs no forward kernel a second
    time, where a tag on the caller's output would keep a copy and still
    run it; one that keeps ``q`` too skips its projection, RoPE and layout."""
    return (checkpoint_name(q, ATTN_Q), checkpoint_name(o, ATTN_OUT),
            checkpoint_name(lse, ATTN_LSE))


def _flash_core_fwd(q, k, v, sm_scale, causal, block_q, block_k):
    o, lse = _flash_forward(q, k, v, sm_scale, causal, block_q, block_k)
    q, o, lse = tag_residuals(q, o, lse)
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
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    window: Optional[int] = None,
    forward_only: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Attention over (batch, heads, seq, head_dim); also returns per-row
    log-sum-exp (batch, heads, seq) for ring-step merging. Tiles nobody
    names are ``default_blocks`` of the shape.

    ``forward_only``: the forward kernel alone, with no backward rule, on
    the K/V heads as they come (a group of query heads reads its K/V head
    where it lies; nothing is repeated): a serving prefill. ``window``
    (causal calls; implies ``forward_only``, the backward kernels have no
    band): query ``t`` sees the keys ``t - window < j <= t``."""
    b, h, sq, d = q.shape
    _, hk, sk, _ = k.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if window is not None or forward_only:
        if window is not None and not causal:
            raise ValueError("flash_attention: a window is a causal band")
        o, lse = _flash_forward(
            q.reshape(b * h, sq, d), k.reshape(b * hk, sk, d),
            v.reshape(b * hk, sk, d), sm_scale, causal, block_q, block_k,
            window)
        return o.reshape(b, h, sq, d), lse.reshape(b, h, sq)
    if h != hk:  # grouped-query attention: repeat kv heads
        k = jnp.repeat(k, h // hk, axis=1)
        v = jnp.repeat(v, h // hk, axis=1)
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    o, lse = _flash_core(qf, kf, vf, sm_scale, causal, block_q, block_k)
    return o.reshape(b, h, sq, d), lse.reshape(b, h, sq)


def flash_attention(q, k, v, **kwargs) -> jax.Array:
    return flash_attention_with_lse(q, k, v, **kwargs)[0]


def reference_attention(q, k, v, *, causal: bool = True, sm_scale=None,
                        window: Optional[int] = None):
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
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq - window)
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
