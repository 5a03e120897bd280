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

The activation is an argument (``activation=``). ``"swiglu"``, the
default, is the kernel above and nothing of it changes. ``"poly_norm"``
(Motif: ``y[r] = down_e(P_e(gate_e x[r]) * up_e x[r])``, ``P(z) = c1 N(z^3)
+ c2 N(z^2) + c3 N(z) + c4``, ``N(z) = z / sqrt(mean(z^2) + eps)`` over the
expert's whole inner row) normalises over all of f, so a block of gate
columns cannot be activated when it arrives: a visit takes two sweeps of
the f blocks in one grid axis of ``2 x f // block`` steps. The first sweep
multiplies the gate columns, keeps them in VMEM (f32, ``(tile, f)``) and
adds up the row sums of ``z^2``, ``z^4`` and ``z^6``; the second multiplies
the up columns against the finished activation and accumulates the down
product. Each matrix is still read once: the gate's block index stands
still through the second sweep and the other two's through the first, and
a block whose index does not change is not fetched again.

``"relu2"`` (Nemotron-H: ``y[r] = down_e(relu(up_e x[r])^2)``, no gate) is
the SwiGLU kernel with two matrices a visit instead of three: one sweep of
the f blocks, ``relu(.)^2`` of an up block in VMEM, the matching down rows,
each matrix read once. Two matrices in flight where SwiGLU has three, so a
block may be half as large again in the same VMEM (``block_f(matrices=2)``).
``w_gate`` is None.

``"reglu"`` (SmallThinker: ``y[r] = down_e(relu(gate_e x[r]) * up_e
x[r])``) is the SwiGLU kernel under another gate function and nothing else:
three matrices a visit, one sweep, the same blocks.

**Past one row tile** a touched expert is no longer read once. The visits
are (row tile, expert) pairs, so an expert whose rows straddle the edge of
a tile has a visit in each and its three matrices are fetched for each: at
64 rows of a 6-of-64 router a step sorts 384 assignments into three tiles
of 128 and, with ~64 groups of ~6 rows, two experts a layer lie on an edge
and are read twice (~3% more bytes than the touched experts'). A roofline
that counts each touched expert once counts the least work, not this
kernel's: its share falls by what the second reads cost.
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
# rows of a tile: a decode step of up to 128 assignments (OLMoE's 64) is
# one tile, so each touched expert is visited (and read) exactly once;
# more rows take tiles of 128, the MXU's height (256 measured the same),
# and an expert whose rows lie on a tile's edge is visited, and read, once
# a tile: a prefill's thousands of rows, and a decode step of 64 rows x 6
# experts (384 assignments, three tiles: module docstring)
_TILE_ROWS = 128
_ROW_ALIGN = 16  # bf16 sublane packing
# the gate function of a three-matrix expert, by ``activation``
_GATES = {"swiglu": jax.nn.silu, "reglu": jax.nn.relu}
_VMEM_LIMIT = 64 * 1024 * 1024


def _use_interpret() -> bool:
    from ray_tpu._internal.platform import pallas_interpret

    return pallas_interpret("moe_experts")


def tile_rows(rows: int) -> int:
    """Rows of one tile for ``rows`` assignments."""
    return min(_TILE_ROWS, -(-rows // _ROW_ALIGN) * _ROW_ALIGN)


def block_f(dim: int, inner: int, dtype, matrices: int = 3) -> int:
    """Columns of the expert's inner width in one block: the largest
    divisor of ``inner`` that is a multiple of 128 and keeps a (dim, block)
    slab within ``_BLOCK_BYTES`` (with ``matrices`` in flight instead of
    three, within the same VMEM in all); the whole width where there is
    none.
    Moonlight's 1408 = 11 x 128 has no such divisor but 128 (0.5 MB slabs,
    11 grid steps a matrix), which measured the same as the whole width in
    one 5.8 MB block: 8.295 against 8.299 ms for a decode step's 144
    assignments over 57.8 touched experts a layer, six layers chained, 88%
    of the HBM's peak either way (PERF.md, PR 30). So the rule stands."""
    fit = _BLOCK_BYTES * 3 // matrices // (dim * jnp.dtype(dtype).itemsize)
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


def _store_own_rows(offsets_ref, group_ids_ref, tile_ids_ref, acc_ref,
                    o_ref, visit, tm: int):
    """A visit's result into its tile: only this expert's rows, the others
    belong to the visits before and after."""
    group = group_ids_ref[visit]
    row = tile_ids_ref[visit] * tm + jax.lax.broadcasted_iota(
        jnp.int32, (tm, 1), 0
    )
    mine = (row >= offsets_ref[group]) & (row < offsets_ref[group + 1])
    o_ref[...] = jnp.where(mine, acc_ref[...], o_ref[...])


def _sweep(offsets_ref, group_ids_ref, tile_ids_ref, o_ref, acc_ref, tm,
           down_product):
    """One step of a visit's single sweep of the f blocks: the accumulator
    zeroed at the first block, this block's ``down_product()`` added, the
    visit's own rows stored behind the last."""
    visit = pl.program_id(0)
    fi = pl.program_id(1)

    @pl.when(fi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += down_product()

    @pl.when(fi == pl.num_programs(1) - 1)
    def _store():
        _store_own_rows(
            offsets_ref, group_ids_ref, tile_ids_ref, acc_ref, o_ref, visit, tm)


def _kernel(
    offsets_ref, group_ids_ref, tile_ids_ref,
    x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *, tm: int, gate,
):
    def down_product():
        x = x_ref[...]  # (tm, d)
        hidden = gate(_dot(x, wg_ref[...])) * _dot(x, wu_ref[...])
        return _dot(hidden.astype(wd_ref.dtype), wd_ref[...])

    _sweep(offsets_ref, group_ids_ref, tile_ids_ref, o_ref, acc_ref, tm,
           down_product)


def _relu2_kernel(
    offsets_ref, group_ids_ref, tile_ids_ref,
    x_ref, wu_ref, wd_ref, o_ref, acc_ref, *, tm: int,
):
    def down_product():
        hidden = jnp.square(jax.nn.relu(_dot(x_ref[...], wu_ref[...])))
        return _dot(hidden.astype(wd_ref.dtype), wd_ref[...])

    _sweep(offsets_ref, group_ids_ref, tile_ids_ref, o_ref, acc_ref, tm,
           down_product)


def _poly_kernel(
    offsets_ref, group_ids_ref, tile_ids_ref,
    x_ref, wg_ref, wu_ref, wd_ref, poly_ref, o_ref, acc_ref, gate_ref,
    sums_ref, *, tm: int, nf: int, inner: int, eps: float,
):
    visit = pl.program_id(0)
    fi = pl.program_id(1)
    x = x_ref[...]  # (tm, d)

    @pl.when(fi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        sums_ref[...] = jnp.zeros_like(sums_ref)

    @pl.when(fi < nf)
    def _gate_sweep():
        z = _dot(x, wg_ref[...])  # (tm, tf) f32
        gate_ref[fi] = z
        z2 = z * z
        for power, term in enumerate((z2, z2 * z2, z2 * z2 * z2)):
            sums_ref[power] += jnp.sum(term, axis=1, keepdims=True)

    @pl.when(fi >= nf)
    def _up_sweep():
        coeff = poly_ref[0]  # (4, tf): a coefficient a sublane row
        z = gate_ref[fi - nf]
        z2 = z * z
        # N(z^p) = z^p / sqrt(mean(z^2p) + eps), the mean over all of f
        scale = [
            jax.lax.rsqrt(sums_ref[power][:, :1] / inner + eps)
            for power in range(3)
        ]
        activated = (
            coeff[0:1] * (z2 * z) * scale[2]
            + coeff[1:2] * z2 * scale[1]
            + coeff[2:3] * z * scale[0]
            + coeff[3:4]
        )
        hidden = activated * _dot(x, wu_ref[...])
        acc_ref[...] += _dot(hidden.astype(wd_ref.dtype), wd_ref[...])

    @pl.when(fi == 2 * nf - 1)
    def _store():
        _store_own_rows(
            offsets_ref, group_ids_ref, tile_ids_ref, acc_ref, o_ref, visit, tm)


def _poly_norm_experts(x, w_gate, w_up, w_down, schedule, visits, tm, tf,
                       rows, poly, eps):
    """``moe_experts``' PolyNorm form on its schedule, row tile ``tm`` and
    f block ``tf``."""
    m, d = x.shape
    n_experts, _, f = w_gate.shape
    nf = f // tf

    def gate_columns(v, fi, offsets, group_ids, tile_ids):
        return group_ids[v], 0, jnp.minimum(fi, nf - 1)

    def up_columns(v, fi, offsets, group_ids, tile_ids):
        return group_ids[v], 0, jnp.maximum(fi - nf, 0)

    def down_rows(v, fi, offsets, group_ids, tile_ids):
        return group_ids[v], jnp.maximum(fi - nf, 0), 0

    def coefficients(v, fi, offsets, group_ids, tile_ids):
        return group_ids[v], 0, 0

    # an expert's four coefficients as a (4, tf) f32 tile, the value
    # repeated along the lanes (Mosaic broadcasts along sublanes or along
    # lanes, not a scalar along both)
    poly = jnp.broadcast_to(
        poly.astype(jnp.float32)[:, :, None], (n_experts, 4, tf))
    return pl.pallas_call(
        functools.partial(
            _poly_kernel, tm=tm, nf=nf, inner=f, eps=float(eps)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(visits, 2 * nf),
            in_specs=[
                pl.BlockSpec((tm, d), rows),
                pl.BlockSpec((None, d, tf), gate_columns),
                pl.BlockSpec((None, d, tf), up_columns),
                pl.BlockSpec((None, tf, d), down_rows),
                pl.BlockSpec((1, 4, tf), coefficients),
            ],
            out_specs=pl.BlockSpec((tm, d), rows),
            scratch_shapes=[
                pltpu.VMEM((tm, d), jnp.float32),
                pltpu.VMEM((nf, tm, tf), jnp.float32),
                pltpu.VMEM((3, tm, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        name="moe_experts",  # the op's name in a device trace
        interpret=_use_interpret(),
    )(*schedule, x, w_gate, w_up, w_down, poly)


def moe_experts(x, w_gate, w_up, w_down, group_sizes,
                activation: str = "swiglu", poly=None, eps: float = 1e-5):
    """``x (m, d)`` rows sorted by expert, ``m`` a multiple of
    ``tile_rows(m)``; ``w_gate``/``w_up (E, d, f)``, ``w_down (E, f, d)``;
    ``group_sizes (E,) int32`` summing to ``m`` or to less: rows past the
    last group's end belong to no visit, are not computed and come back as
    whatever the output buffer held (``moe_apply_dropless`` puts the
    assignments to experts held elsewhere there). Returns ``(m, d)``
    f32. ``activation="poly_norm"`` takes ``poly (E, 4)``, an expert's
    ``c1 .. c4`` (module docstring), and ``eps``; ``activation="relu2"``
    takes no gate (``w_gate`` None); ``activation="reglu"`` is SwiGLU's
    call under ``relu``."""
    m, d = x.shape
    n_experts, _, f = w_up.shape
    tm = tile_rows(m)
    if m % tm:
        raise ValueError(f"{m} rows are not whole tiles of {tm}")
    if activation not in ("swiglu", "poly_norm", "relu2", "reglu"):
        raise ValueError(f"moe_experts: unknown activation {activation!r}")
    if (w_gate is None) != (activation == "relu2"):
        raise ValueError(
            f"moe_experts: activation {activation!r} "
            f"{'takes no' if w_gate is not None else 'needs a'} gate matrix")
    ungated = activation == "relu2"
    tf = block_f(d, f, w_up.dtype, matrices=2 if ungated else 3)
    (offsets, group_ids, tile_ids), visits = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=m, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=n_experts,
        visit_empty_groups=False,
    )

    def rows(v, fi, offsets, group_ids, tile_ids):
        return tile_ids[v], 0

    if activation == "poly_norm":
        return _poly_norm_experts(
            x, w_gate, w_up, w_down, (offsets, group_ids, tile_ids), visits,
            tm, tf, rows, poly, eps)

    def columns(v, fi, offsets, group_ids, tile_ids):
        return group_ids[v], 0, fi

    def down_rows(v, fi, offsets, group_ids, tile_ids):
        return group_ids[v], fi, 0

    # (the gate's matrix and its block are what "relu2" lacks)
    weights = (w_up, w_down) if ungated else (w_gate, w_up, w_down)
    kernel = functools.partial(_relu2_kernel, tm=tm) if ungated else (
        functools.partial(_kernel, tm=tm, gate=_GATES[activation]))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(visits, f // tf),
            in_specs=[
                pl.BlockSpec((tm, d), rows),
                *[pl.BlockSpec((None, d, tf), columns)] * (len(weights) - 1),
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
    )(offsets, group_ids, tile_ids, x, *weights)
