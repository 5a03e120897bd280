"""Decode attention as a Pallas TPU kernel: one new token a row against the
dense slot cache, reading each row's keys and values once, in the dtype
they are stored in, per KV head and only up to that row's length.

The einsum it replaces (models/llama.py, still the ``s > 1`` path) repeats
both caches ``h // hk`` times, casts them to f32 and scores all
``max_seq_len`` positions of every row before masking: HBM traffic that
grows with the configured maximum, not with the tokens present.

Design:
- one call of the kernel walks the whole step (``_walk``): a loop over
  *visits*, one chunk of ``block_k`` key positions of one row, all of the
  row's KV heads. Its extent is the step's live chunks, ``sum(cdiv(max(
  length, 1), chunk))`` over the rows, counted in the kernel from
  ``lengths``: nothing of it is a function of ``max_seq_len``, and no XLA
  op prepares it. (Until PR 48 a grid of rows x ``cdiv(max_seq_len,
  block)`` steps, ~0.35 us each, live or dead. A grid of that dynamic
  extent needs one reduction a layer in XLA, whose operand is fetched a
  second time behind the weights' prefetches: 0.08-0.57 ms of a 12-layer
  step, PERF.md.) A row of length 0 keeps one visit, in which every
  position is masked, so its output is written (as zeros) like any other's
- ``lengths`` is the scalar-prefetch argument and the whole schedule
- K and V stay in HBM; a visit waits for its own chunk's copy, and the
  copies of the next ``_SLOTS - 1`` visits are on their way, so only chunks
  that hold a key are ever copied. Queries and outputs of all rows are
  resident in VMEM (a few hundred KB)
- a visit follows the bytes a position holds (``block_k``): 128 keys where
  8 K/V heads or more make that 256 KB a cache, 512 KB a cache where fewer
  heads make a 128-key copy too small to pay for the ~0.3 us a visit costs
  besides it (4 heads: 512 keys, 2: 1024). The arithmetic goes 128 keys
  (``_PIECE``) at a time whatever the visit copied
- the ``h // hk`` query heads of a group are the rows of one small matmul
  against a piece of the group's K/V chunk: nothing is repeated in HBM
- the einsum's arithmetic: exact products accumulated in f32 (stored values
  times stored values for q.k, f32 probabilities for p.v), f32 online
  softmax with running max, sum and accumulator in VMEM scratch
- the Pallas call sits under a ``jax.jit`` of its own (``_attend``,
  ``_latent_attend``): a program of twelve layers traces and lowers the
  kernel once a shape, not twelve times

``latent_decode_attention`` is the same kernel for a latent cache (DeepSeek's
MLA in its absorbed form, models/deepseek.py): one shared row a position
(``rank`` latent columns and ``rope`` rotary ones, a cache each), every
query head a row of one matmul against it, and the values are the latent
columns themselves, so a chunk is read once and serves as K and as V. It
shares the schedule (``_walk``), the online softmax (``_init``,
``_accumulate``, ``_normalised``) and ``_dot``.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence, Tuple

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
# K (or V) bytes one visit copies. Measured on a v5e, the kernel alone, a
# program of the cell's layers, ms (PERF.md, PR 48; before: a grid of rows x
# cdiv(max_seq_len, block) steps over blocks of 1 MB, 512 / 256 / 1024 / 2048
# keys; a visit's matmuls then contracted over all the keys it copied):
#   16 rows x 8 heads x 4096, 12 layers   128 keys  256 keys  512 keys  before
#     15 rows of one key + one of 480       0.261     0.600       -      1.436
#     128-832 keys a row                    0.766     1.341       -      1.810
#     every row full                        4.619     7.673       -      5.141
#   8 x 16 heads x 4096, 8 layers, 128-832  0.379     0.687       -      0.969
#     every row full                        2.897     4.958       -      5.011
#   64 x 4 heads x 1024, 6 layers, 128-832  1.091     1.367     1.043    1.173
#     every row full                        1.861     2.110     1.380    1.172
#   32 x 8 heads x 2048, 2 layers, 200-2048 0.479     0.816     0.575    0.752
#   16 x 2 heads x 4096 (a tp=4 shard)      0.502     0.459     0.370    1.061
#     every row full                        2.918     2.407     1.483    1.139
# A visit costs ~0.25-0.35 us besides its copy (0.58 us a 128-key visit of 4
# heads, 0.16 of it the copy's bytes at the HBM's peak), so a visit wants
# bytes; a row's last visit is copied and multiplied whole, so short rows
# want it small. Since PR 62 the copy's extent and a matmul's contraction
# are two things: a visit copies ``block_k`` keys and works them ``_PIECE``
# at a time (``_kernel``). The kernel alone again, the cells' layers and
# lengths (SmallThinker's and Command A+'s rows mid-flight in their closed
# loops, 0.5k-5k and 2.5k-9.7k), parent (128 keys; 256 at 2 heads) and keys
# a visit in pieces of 128, ms; * is what the rule below gives the shape:
#                                          parent    128     256     512    1024
#   64 x 28/4 x 4096 ring, 6 layers, cell   3.774   3.772   2.570   2.459*  2.613
#     every row full                        7.099   7.095   4.665   4.302*  4.311
#   64 x 28/4 x 5120 row, 2 layers, cell    1.316   1.317   0.909   0.879*  0.969
#     every row full                        2.967   2.965   1.950   1.804*  1.807
#   24 x 128/8 x 4096 ring, 3 layers, cell  1.723   1.724*  1.481   1.897   2.287
#   24 x 128/8 x 10240 row, 1 layer, cell   0.836   0.834*  0.726   0.943   1.181
#   16 x 32/8 x 4096, 12 layers, 15 + 480   0.246   0.242*  0.343   0.598   1.148
#     128-832 keys a row                    0.653   0.654*  0.658   0.763   1.145
#     every row full                        4.599   4.601*  4.314   4.326   4.339
#   8 x 16/16 x 4096, 8 layers, 128-832     0.330   0.330*  0.381   0.460     -
#     every row full                        2.882   2.883*  2.887   2.901     -
#   64 x 20/4 x 1024, 6 layers, 128-832     1.066   1.069   0.788   0.877*  1.113
#     every row full                        1.844   1.844   1.239   1.111*  1.116
#   64 x 32/2 x 4096, 1 layer, 0.3k-4k      0.314   0.375   0.245   0.198   0.207*
#     every row full                        0.826   1.052   0.645   0.450   0.378*
#   32 x 64/8 x 2048, 2 layers, 200-2048    0.421   0.420*  0.415   0.467   0.541
# Full rows of 4 heads go from 55% to 91% of the HBM's peak, the two cells'
# own lengths from 53% to 82 / 79% (10-13% more keys copied than live). The
# other forms of the arithmetic, same ring, cell's lengths / every row full:
# one q.k and one softmax update a visit with p.v in pieces, or all of it
# over the visit's keys (PR 48's form), 4.44 / 8.12 at 256 keys and 2.99 /
# 5.19 at 512; pieces with a ``pl.when`` around each one past the first,
# 3.66 / 6.85 at 512 (the branch costs what the visit was extended for). A
# true group of 8 for the 7 padded to 8: 3.769 -> 2.456 (nothing); 24 rows
# of 4096 against 64 of 1536, every row full: 2.678 / 2.719 -> 1.637 /
# 1.644 (nothing). At 8 heads a second piece gains 13-14% on rows of
# 2.5k-9.7k and costs 40% on the steady cell's one-key rows (its copy is
# twice the bytes and nothing hides it), at one shape of cache: a piece that
# is ``_LONE_PIECE_BYTES`` already stays a visit by itself, and those
# shapes' programs are what they were.
_VISIT_BYTES = 512 * 1024
_LONE_PIECE_BYTES = 256 * 1024
# key positions a matmul of the kernel contracts over (the lane width of the
# scores), and the most of them a visit's straight-line code holds (measured
# up to 8: 2 heads x 1024 keys; 8 heads x 4 pieces were slower than x 2)
_PIECE = 128
_MAX_PIECES = 8
# ... and of a latent cache, keys and values in one: 1024 positions of 576
# values. Its rows are long where it is served (1024-5120 keys of 8192, 24
# rows, 7 layers): 512 / 1024 / 2048 positions took 1.229 / 1.120 / 1.192 ms
# (before: 1.552), every row full 3.085 / 2.625 / 2.430 (2.743), 23 rows of
# one key beside one of 3000 0.298 / 0.421 / 0.699 (0.866).
_LATENT_BLOCK_BYTES = 2 * 1024 * 1024
# visits whose chunks are in VMEM or on their way there at once. With two, a
# 0.5 MB copy started one visit ahead has not landed when its visit comes
# (0.945 / 5.923 ms for the 128-832 and the full rows of the first shape);
# with three it has (0.766 / 4.619); four give nothing more (0.759 / 4.597).
_SLOTS = 3

# cache shape -> key positions a visit of the kernel traced for it covers:
# what the engine counts a step's visits in (``traced_chunk``)
_traced_chunks: Dict[Tuple[int, ...], int] = {}


def traced_chunk(cache_shape: Sequence[int]) -> Optional[int]:
    """Key positions in one visit of the kernel a program of this process
    traced for a cache (K, or the latent leaf) of this shape; None if none
    was."""
    return _traced_chunks.get(tuple(cache_shape))


def _use_interpret(kernel: str = "decode_attention") -> bool:
    from ray_tpu._internal.platform import pallas_interpret

    return pallas_interpret(kernel)


def _chunk(max_seq_len: int, per_position: int, fit_bytes: int) -> int:
    """A power of two of positions of about ``fit_bytes``, at least 128
    (the lane width of the scores), that divides the cache: a chunk never
    hangs over the cache's end, where a copy could not follow it. (A cache
    whose length is no multiple of 128 gets the largest power of two that
    divides it.)"""
    fit = max(128, fit_bytes // per_position)
    return math.gcd(max_seq_len, 1 << (fit.bit_length() - 1))


def block_k(max_seq_len: int, kv_heads: int, head_dim: int, dtype) -> int:
    """Key positions in one visit: a piece of ``_PIECE`` keys where that is
    ``_LONE_PIECE_BYTES`` across the visit's KV heads already, else about
    ``_VISIT_BYTES`` (``_chunk``'s rule) in ``_MAX_PIECES`` pieces at
    most."""
    per_position = kv_heads * head_dim * jnp.dtype(dtype).itemsize
    piece_bytes = _PIECE * per_position
    fit = 0 if piece_bytes >= _LONE_PIECE_BYTES else min(
        _VISIT_BYTES, _MAX_PIECES * piece_bytes)
    return _chunk(max_seq_len, per_position, fit)


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


def _init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _accumulate(s, v, acc_ref, m_ref, l_ref, j):
    """One chunk of the online softmax: masked scores ``s (rows, chunk)``
    and values ``v (chunk, d)`` into slot ``j`` of the running max, sum and
    accumulator."""
    m_prev = m_ref[j]  # (rows, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[j] = l_ref[j] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[j] = acc_ref[j] * alpha + _dot(p, v, ((1,), (0,)))
    m_ref[j] = m_new


def _normalised(acc_ref, l_ref):
    l = l_ref[...]
    l = jnp.where(l == 0.0, 1.0, l)  # a row of length 0 attends nothing
    return acc_ref[...] / l


def visits(lengths, chunk: int):
    """Chunks of a step that hold a key, a row of length 0 counted as one:
    what the kernels walk (``_walk`` counts the same in SMEM), of NumPy
    ``lengths``, for the engine's counter."""
    return ((lengths.clip(1) + (chunk - 1)) // chunk).sum()


def _after(lengths_ref, row, ci, chunk: int):
    """The visit that follows ``(row, ci)``, and whether the row ends at
    ``ci``. (Past the last row there is nothing to name: the clamp only
    keeps the read inside ``lengths``.)"""
    last_row = lengths_ref.shape[0] - 1
    ends = (ci + 1) * chunk >= lengths_ref[jnp.minimum(row, last_row)]
    return jnp.where(ends, row + 1, row), jnp.where(ends, 0, ci + 1), ends


def _walk(lengths_ref, chunk: int, copies, visit):
    """The schedule both kernels share: ``visit(row, ci, slot, ends)`` once
    for every chunk ``ci`` of every row that holds a key (a row of length
    0 has one, all masked), rows in order, with that chunk's copies landed
    in buffer ``slot``; ``ends`` says the row's last chunk.

    ``copies(row, ci, slot)`` describes a visit's HBM -> VMEM copies. Up
    to ``_SLOTS - 1`` visits' copies are in flight ahead of the one being
    worked on: each iteration starts one more before it waits for its
    own, so a copy runs under the arithmetic of the visits before it."""
    ahead = _SLOTS - 1
    n_visits = jax.lax.fori_loop(
        0, lengths_ref.shape[0],
        lambda row, n: n + (
            jnp.maximum(lengths_ref[row], 1) + (chunk - 1)) // chunk,
        jnp.int32(0),
    )

    def start(row, ci, nth):
        for copy in copies(row, ci, nth % _SLOTS):
            copy.start()

    fetch_row = fetch_ci = jnp.int32(0)
    for nth in range(ahead):
        pl.when(nth < n_visits)(
            functools.partial(start, fetch_row, fetch_ci, nth))
        fetch_row, fetch_ci, _ = _after(lengths_ref, fetch_row, fetch_ci, chunk)

    def step(nth, at):
        row, ci, fetch_row, fetch_ci = at
        pl.when(nth + ahead < n_visits)(
            functools.partial(start, fetch_row, fetch_ci, nth + ahead))
        slot = nth % _SLOTS
        for copy in copies(row, ci, slot):
            copy.wait()
        next_row, next_ci, ends = _after(lengths_ref, row, ci, chunk)
        visit(row, ci, slot, ends)
        return (next_row, next_ci,
                *_after(lengths_ref, fetch_row, fetch_ci, chunk)[:2])

    jax.lax.fori_loop(
        0, n_visits, step, (jnp.int32(0), jnp.int32(0), fetch_row, fetch_ci))


def _live(ci, chunk: int, length, rows: int):
    """Masks of the chunk's positions that lie inside the row's length:
    ``(rows, chunk)`` for the scores, ``(chunk, 1)`` for the values."""
    first = ci * chunk
    k_pos = first + jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 1)
    k_row = first + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    return k_pos < length, k_row < length


def _kernel(
    lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
    k_buf, v_buf, sems, acc_ref, m_ref, l_ref,
    *, sm_scale: float, chunk: int, kv_heads: int,
):
    # what a visit copies and what a matmul contracts are two things: the
    # arithmetic goes ``piece`` keys at a time however many the visit holds
    piece = min(chunk, _PIECE)
    pieces = chunk // piece

    def copies(row, ci, slot):
        keys = pl.ds(pl.multiple_of(ci * chunk, chunk), chunk)
        return (
            pltpu.make_async_copy(
                k_hbm.at[row, :, keys], k_buf.at[slot], sems.at[0, slot]),
            pltpu.make_async_copy(
                v_hbm.at[row, :, keys], v_buf.at[slot], sems.at[1, slot]),
        )

    def visit(row, ci, slot, ends):
        @pl.when(ci == 0)
        def _start_row():
            _init(acc_ref, m_ref, l_ref)

        # straight-line code, pieces past the row's length included (all
        # masked): a branch a piece costs the visit what it was extended
        # for (the table above, "a `pl.when` a piece")
        for i in range(pieces):
            at = pl.ds(i * piece, piece)
            # (a group's padded rows: _GROUP_ROWS up to 8 query heads a KV
            # head, two tiles at Nemotron-H's 16; one piece a visit: the
            # program as it was before a visit could hold several)
            in_scores, in_values = _live(
                ci * pieces + i if pieces > 1 else ci, piece,
                lengths_ref[row], q_ref.shape[2])
            for j in range(kv_heads):
                q = q_ref[row, j]  # (rows, d)
                k = k_buf[slot, j, at]  # (piece, d)
                # positions past the length hold whatever the cache held
                # before: their probabilities are 0, but 0 * NaN would
                # still poison the matmul
                v = jnp.where(in_values, v_buf[slot, j, at], 0)
                s = _dot(q, k, ((1,), (1,))) * sm_scale
                s = jnp.where(in_scores, s, _NEG_INF)
                _accumulate(s, v, acc_ref, m_ref, l_ref, j)

        @pl.when(ends)
        def _finish_row():
            o_ref[row] = _normalised(acc_ref, l_ref).astype(o_ref.dtype)

    _walk(lengths_ref, chunk, copies, visit)


# a whole operand in VMEM for the length of the call, and one that stays in
# HBM for the kernel to copy from itself
_RESIDENT = pl.BlockSpec(memory_space=pltpu.VMEM)
_IN_HBM = pl.BlockSpec(memory_space=pl.ANY)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _attend(q, k_cache, v_cache, lengths, *, chunk: int, interpret: bool):
    b, h, d = q.shape
    hk = k_cache.shape[1]
    group = h // hk
    rows = -(-group // _GROUP_ROWS) * _GROUP_ROWS
    qg = q.reshape(b, hk, group, d)
    if rows != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows - group), (0, 0)))
    out = pl.pallas_call(
        functools.partial(
            _kernel, sm_scale=1.0 / math.sqrt(d), chunk=chunk, kv_heads=hk
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(),
            in_specs=[_RESIDENT, _IN_HBM, _IN_HBM],
            out_specs=_RESIDENT,
            scratch_shapes=[
                pltpu.VMEM((_SLOTS, hk, chunk, d), k_cache.dtype),
                pltpu.VMEM((_SLOTS, hk, chunk, d), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, _SLOTS)),
                pltpu.VMEM((hk, rows, d), jnp.float32),
                pltpu.VMEM((hk, rows, 1), jnp.float32),
                pltpu.VMEM((hk, rows, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hk, rows, d), q.dtype),
        name="decode_attention",  # the op's name in a device trace
        interpret=interpret,
    )(lengths, qg, k_cache, v_cache)
    return out[:, :, :group].reshape(b, h, d)


def _decode_attention(q, k_cache, v_cache, lengths):
    _, hk, max_seq_len, d = k_cache.shape
    chunk = block_k(max_seq_len, hk, d, k_cache.dtype)
    # (under a mesh the shape is a shard's, which no engine asks for)
    _traced_chunks[k_cache.shape] = chunk
    return _attend(
        q, k_cache, v_cache, lengths, chunk=chunk, interpret=_use_interpret()
    )


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
    """Positions in one chunk of a latent cache of ``width`` values a
    position: ``_chunk``'s rule at ``_LATENT_BLOCK_BYTES``."""
    per_position = width * jnp.dtype(dtype).itemsize
    return _chunk(max_seq_len, per_position, _LATENT_BLOCK_BYTES)


def _latent_kernel(
    lengths_ref, q_ref, qr_ref, c_hbm, r_hbm, o_ref,
    c_buf, r_buf, sems, acc_ref, m_ref, l_ref,
    *, sm_scale: float, chunk: int,
):
    def copies(row, ci, slot):
        keys = pl.ds(pl.multiple_of(ci * chunk, chunk), chunk)
        return (
            pltpu.make_async_copy(
                c_hbm.at[row, 0, keys], c_buf.at[slot], sems.at[0, slot]),
            pltpu.make_async_copy(
                r_hbm.at[row, 0, :, keys], r_buf.at[slot], sems.at[1, slot]),
        )

    def visit(row, ci, slot, ends):
        @pl.when(ci == 0)
        def _start_row():
            _init(acc_ref, m_ref, l_ref)

        in_scores, in_values = _live(
            ci, chunk, lengths_ref[row], q_ref.shape[1])
        latent = c_buf[slot]  # (chunk, rank): the keys' latent part
        # ... and the values: read once, used twice; past the length they
        # are zeroed for the reason given in ``_kernel``
        v = jnp.where(in_values, latent, 0)
        s = (
            _dot(q_ref[row], latent, ((1,), (1,)))
            + _dot(qr_ref[row], r_buf[slot], ((1,), (0,)))  # (rope, chunk)
        ) * sm_scale
        s = jnp.where(in_scores, s, _NEG_INF)
        _accumulate(s, v, acc_ref, m_ref, l_ref, 0)

        @pl.when(ends)
        def _finish_row():
            o_ref[row] = _normalised(acc_ref, l_ref)[0].astype(o_ref.dtype)

    _walk(lengths_ref, chunk, copies, visit)


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "chunk", "interpret"))
def _latent_attend(
    q_latent, q_rope, latent_cache, rope_cache, lengths,
    *, sm_scale: float, chunk: int, interpret: bool,
):
    b, h, rank = q_latent.shape
    rope = q_rope.shape[-1]
    rows = -(-h // _GROUP_ROWS) * _GROUP_ROWS
    if rows != h:
        pad = ((0, 0), (0, rows - h), (0, 0))
        q_latent, q_rope = jnp.pad(q_latent, pad), jnp.pad(q_rope, pad)
    out = pl.pallas_call(
        functools.partial(_latent_kernel, sm_scale=sm_scale, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(),
            in_specs=[_RESIDENT, _RESIDENT, _IN_HBM, _IN_HBM],
            out_specs=_RESIDENT,
            scratch_shapes=[
                pltpu.VMEM((_SLOTS, chunk, rank), latent_cache.dtype),
                pltpu.VMEM((_SLOTS, rope, chunk), rope_cache.dtype),
                pltpu.SemaphoreType.DMA((2, _SLOTS)),
                pltpu.VMEM((1, rows, rank), jnp.float32),
                pltpu.VMEM((1, rows, 1), jnp.float32),
                pltpu.VMEM((1, rows, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, rows, rank), q_latent.dtype),
        name="latent_decode_attention",  # the op's name in a device trace
        interpret=interpret,
    )(lengths, q_latent, q_rope, latent_cache, jnp.swapaxes(rope_cache, 2, 3))
    return out[:, :h]


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
    is that array's own bytes, so the swap of axes is a bitcast on the TPU
    (and the score a plain ``(rows, rope) x (rope, chunk)``). One device
    only: a latent row has no head axis to shard
    (``models.refusals("deepseek")["mesh"]``)."""
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
    chunk = latent_block_k(max_seq_len, rank + rope, latent_cache.dtype)
    _traced_chunks[latent_cache.shape] = chunk
    return _latent_attend(
        q_latent, q_rope, latent_cache, rope_cache, lengths,
        sm_scale=float(sm_scale), chunk=chunk,
        interpret=_use_interpret("latent_decode_attention"),
    )
