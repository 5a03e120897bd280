"""Routed experts as one grouped Pallas TPU kernel: rows sorted by expert
in, each row through its own expert's SwiGLU out.

``y[r] = down_e(silu(gate_e x[r]) * up_e x[r])`` for the expert ``e`` that
owns row ``r``, where the rows of one expert are contiguous
(``group_sizes`` says how many each has). Every row of a group is computed
whatever the imbalance: there is no capacity and no ``(tokens, experts,
capacity)`` tensor. Rows behind the last group are no expert's here and
are left alone.

Design:
- grid (visits, f blocks). A visit is one (row tile, expert) pair that
  share a row; the schedule (megablox's ``make_group_metadata``, which
  ships with JAX) lists them in row order as two scalar-prefetch arrays,
  and the number of visits is the grid's own (dynamic) extent. An expert
  no row chose has no visit, so its weights are never read: a decode step
  of 8 rows x 8 experts reads the ~41 experts it touches, not all 64
- the three matrices of a visit stream through VMEM in blocks of
  ``_BLOCK_BYTES`` along the expert's inner width f: gate and up columns,
  the matching down rows; ``silu(gate) * up`` never leaves VMEM and the
  down product accumulates in an f32 scratch over the f blocks
- a row tile that several experts share is visited once by each, in
  succession; each stores only its own rows (the output block stays
  resident between visits of one tile)
- the arithmetic of the einsum it stands for: operands as stored (bf16
  products are exact in f32), f32 accumulation, the hidden activation
  rounded to the weights' dtype before the down product
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

# bytes of one matrix's block a grid step moves (three matrices, double
# buffered: six of these in VMEM beside the row tile and the accumulator).
# Measured on a v5e at OLMoE's shapes (64 experts of 2048 x 1024 bf16, top
# 8, eight layers chained): 1 / 2 / 4 MB take 6.14 / 6.30 / 5.72 ms for a
# decode step's 64 assignments (~41 experts a layer touched: 5.0 ms at the
# HBM's peak) and 10.7 / 10.2 / 9.8 ms for a 128-token prefill's 1024. At
# 4 MB an OLMoE expert's matrix is one block.
_BLOCK_BYTES = 4 * 1024 * 1024
# rows of a tile: a decode step's 64 assignments are one tile, so each
# touched expert is visited (and read) exactly once; a prefill's thousands
# of rows take tiles of 128, the MXU's height (256 measured the same)
_TILE_ROWS = 128
_ROW_ALIGN = 16  # bf16 sublane packing
_VMEM_LIMIT = 64 * 1024 * 1024


def _use_interpret() -> bool:
    from ray_tpu._internal.platform import pallas_interpret

    return pallas_interpret("moe_experts")


def tile_rows(rows: int) -> int:
    """Rows of one tile for ``rows`` assignments."""
    return min(_TILE_ROWS, -(-rows // _ROW_ALIGN) * _ROW_ALIGN)


def block_f(dim: int, inner: int, dtype) -> int:
    """Columns of the expert's inner width in one block: the largest
    divisor of ``inner`` that is a multiple of 128 and keeps a (dim, block)
    slab within ``_BLOCK_BYTES``; the whole width where there is none.
    Moonlight's 1408 = 11 x 128 has no such divisor but 128 (0.5 MB slabs,
    11 grid steps a matrix), which measured the same as the whole width in
    one 5.8 MB block: 8.295 against 8.299 ms for a decode step's 144
    assignments over 57.8 touched experts a layer, six layers chained, 88%
    of the HBM's peak either way (PERF.md, PR 30). So the rule stands."""
    fit = _BLOCK_BYTES // (dim * jnp.dtype(dtype).itemsize)
    for block in range(min(fit, inner) // 128 * 128, 0, -128):
        if inner % block == 0:
            return block
    return inner


def _dot(a, b):
    """a @ b with exact products and f32 accumulation: bf16 operands go to
    the MXU as they are, f32 ones at full precision."""
    if a.dtype == b.dtype == jnp.bfloat16:
        return jnp.dot(a, b, preferred_element_type=jnp.float32)
    return jnp.dot(
        a.astype(jnp.float32), b.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _kernel(
    offsets_ref, group_ids_ref, tile_ids_ref,
    x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *, tm: int,
):
    visit = pl.program_id(0)
    fi = pl.program_id(1)

    @pl.when(fi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]  # (tm, d)
    hidden = jax.nn.silu(_dot(x, wg_ref[...])) * _dot(x, wu_ref[...])
    acc_ref[...] += _dot(hidden.astype(wd_ref.dtype), wd_ref[...])

    @pl.when(fi == pl.num_programs(1) - 1)
    def _store():
        # only this expert's rows of the tile: the others belong to the
        # visits before and after
        group = group_ids_ref[visit]
        row = tile_ids_ref[visit] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0
        )
        mine = (row >= offsets_ref[group]) & (row < offsets_ref[group + 1])
        o_ref[...] = jnp.where(mine, acc_ref[...], o_ref[...])


def moe_experts(x, w_gate, w_up, w_down, group_sizes):
    """``x (m, d)`` rows sorted by expert, ``m`` a multiple of
    ``tile_rows(m)``; ``w_gate``/``w_up (E, d, f)``, ``w_down (E, f, d)``;
    ``group_sizes (E,) int32`` summing to ``m`` or to less: rows past the
    last group's end belong to no visit, are not computed and come back as
    whatever the output buffer held (``moe_apply_dropless`` puts the
    assignments to experts held elsewhere there). Returns ``(m, d)``
    f32."""
    m, d = x.shape
    n_experts, _, f = w_gate.shape
    tm = tile_rows(m)
    if m % tm:
        raise ValueError(f"{m} rows are not whole tiles of {tm}")
    tf = block_f(d, f, w_gate.dtype)
    (offsets, group_ids, tile_ids), visits = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=m, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=n_experts,
        visit_empty_groups=False,
    )

    def rows(v, fi, offsets, group_ids, tile_ids):
        return tile_ids[v], 0

    def columns(v, fi, offsets, group_ids, tile_ids):
        return group_ids[v], 0, fi

    def down_rows(v, fi, offsets, group_ids, tile_ids):
        return group_ids[v], fi, 0

    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(visits, f // tf),
            in_specs=[
                pl.BlockSpec((tm, d), rows),
                pl.BlockSpec((None, d, tf), columns),
                pl.BlockSpec((None, d, tf), columns),
                pl.BlockSpec((None, tf, d), down_rows),
            ],
            out_specs=pl.BlockSpec((tm, d), rows),
            scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        name="moe_experts",  # the op's name in a device trace
        interpret=_use_interpret(),
    )(offsets, group_ids, tile_ids, x, w_gate, w_up, w_down)
