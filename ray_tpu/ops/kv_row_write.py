"""The cache write of the dense slot cache: new keys and values (or latent
and rotary rows) stored at each row's own position.

A decode step stores one position a row, at positions that differ by row.
Written as ``jax.vmap(dynamic_update_slice_in_dim)`` that is a scatter,
and the TPU compiler expands a scatter into a ``while`` loop over its
indices: a bounds test, a select and an in-place ``dynamic-update-slice``
of one ``(1, heads, 1, width)`` sliver an iteration, 3.9 us each to move
2 KB (PERF.md, PR 31: 384 iterations and 1.49 ms of a 10.2 ms Mistral
step; it grows with slots x layers x leaves). It is launch overhead, not
bandwidth.

Here the step's write is one Pallas call a layer over all of that layer's
leaves: grid ``(rows,)``, the rows' lengths as the scalar-prefetch argument,
every leaf an input aliased to its output. A grid step reads the one
aligned tile of its row that holds the position, selects the new values in
on an iota, and writes the tile back; nothing else of the leaf moves and a
donated cache stays where it is. A tile is the dtype's sublane tile of
positions (16 for bf16, 8 for f32) by the leaf's width, ``heads x 4 KB``.
Measured on a v5e (PERF.md, PR 31): 7-12 us a call, about 5 us a launch
and 0.2-0.3 us a row: 0.10 ms of a Mistral step (16 rows, 12 layers).

A leaf whose width is no multiple of the 128 lanes is stored
sequence-minor on the TPU (PERF.md, finding 30.1), so it is written in
that view, ``(rows, heads, width, positions)``, which is its own bytes: a
tile of 128 positions in the lanes, selected on a lane iota.

More than one new position a row (a prefill, a suffix behind a prefix hit, a
budgeted chunk) is one ``dynamic_update_slice`` a row already and stays that.
Positions clamp as ``dynamic_update_slice`` clamps them: the bytes a step
leaves in the cache are the bytes the vmapped form left.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.plan import KV_SPEC

_LANES = 128


# leaf shape -> how a traced program of this process writes one position a
# row into it: the proof that a decode program took the kernel
_traced_forms: Dict[Tuple[int, ...], str] = {}


def traced_form(leaf_shape: Sequence[int]) -> Optional[str]:
    """``"tile"`` once a program that writes one position a row into a
    cache leaf of this shape was traced here through the kernel; None if
    none was. (No leaf needs another form: the sequence-minor view of a
    narrow leaf is a bitcast both ways, tests/test_chip_compile.py.)"""
    return _traced_forms.get(tuple(leaf_shape))


def _use_interpret() -> bool:
    from ray_tpu._internal.platform import pallas_interpret

    return pallas_interpret("kv_row_write")


def _tile(axis: int, dtype) -> int:
    """Positions in one aligned tile along ``axis`` of a leaf's view: the
    lanes, or the dtype's sublane tile (a 32-bit sublane packs 2 bf16)."""
    return _LANES if axis == 3 else 8 * (4 // jnp.dtype(dtype).itemsize)


def _last_position(lengths_ref, r):
    return jnp.maximum(lengths_ref[r] - 1, 0)


def _kernel(lengths_ref, *refs, axes: Tuple[int, ...]):
    n = len(axes)
    position = _last_position(lengths_ref, pl.program_id(0))
    for axis, new_ref, old_ref, out_ref in zip(
        axes, refs[:n], refs[n:2 * n], refs[2 * n:]
    ):
        at = position % old_ref.shape[axis]
        here = jax.lax.broadcasted_iota(jnp.int32, old_ref.shape, axis) == at
        out_ref[...] = jnp.where(here, new_ref[...], old_ref[...])


@jax.jit  # one lowering of the kernel a program, not one a layer
def _write_last_position(lengths, new_rows, leaves):
    """``leaves[i][r, :, lengths[r] - 1] = new_rows[i][r, :, 0]`` for every
    row ``r``, one kernel over all the leaves."""
    views, news, axes, leaf_specs, new_specs = [], [], [], [], []
    for leaf, new in zip(leaves, new_rows):
        # the positions' axis in the view the TPU stores the leaf in:
        # sequence-minor when the width is no multiple of the lanes (the
        # swap of axes is then a bitcast)
        axis = 3 if leaf.shape[3] % _LANES else 2
        if axis == 3:
            leaf, new = jnp.swapaxes(leaf, 2, 3), jnp.swapaxes(new, 2, 3)
        tile = min(_tile(axis, leaf.dtype), leaf.shape[axis])

        def block(size, axis=axis):
            shape = list(leaf.shape)
            shape[0], shape[axis] = 1, size
            return tuple(shape)

        def tile_of_row(r, lengths_ref, axis=axis, tile=tile):
            index = [r, 0, 0, 0]
            index[axis] = _last_position(lengths_ref, r) // tile
            return tuple(index)

        views.append(leaf)
        news.append(new)
        axes.append(axis)
        leaf_specs.append(pl.BlockSpec(block(tile), tile_of_row))
        new_specs.append(
            pl.BlockSpec(block(1), lambda r, _: (r, 0, 0, 0))
        )
    n = len(views)
    written = pl.pallas_call(
        functools.partial(_kernel, axes=tuple(axes)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(lengths.shape[0],),
            in_specs=new_specs + leaf_specs,
            out_specs=leaf_specs,
        ),
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype) for v in views],
        # operand 0 is the lengths, then the new rows, then the leaves
        input_output_aliases={1 + n + i: i for i in range(n)},
        name="kv_row_write",  # the op's name in a device trace
        interpret=_use_interpret(),
    )(lengths, *news, *views)
    return tuple(
        jnp.swapaxes(w, 2, 3) if axis == 3 else w
        for w, axis in zip(written, axes)
    )


def write_rows(
    leaves: Sequence[jax.Array], new_rows: Sequence[jax.Array],
    positions: jax.Array, mesh: Optional[Mesh] = None,
) -> Tuple[jax.Array, ...]:
    """The cache leaves ``(b, heads, max_seq_len, width)`` with ``new_rows
    (b, heads, s, width)`` stored at ``positions (b,)``: leaf ``i``'s row
    ``r`` holds ``new_rows[i][r]`` at ``positions[r] .. positions[r] + s``,
    clamped to fit as ``dynamic_update_slice`` clamps. The leaves of one
    call share ``b`` and ``max_seq_len``.

    With ``mesh`` the one-position kernel runs per shard under shard_map on
    the layout the decode cache lives in (KV_SPEC), as ``decode_attention``
    does."""
    if new_rows[0].shape[2] != 1:

        def insert(cache_row, new_row, pos):
            return jax.lax.dynamic_update_slice_in_dim(
                cache_row, new_row, pos, axis=1
            )

        return tuple(
            jax.vmap(insert)(leaf, new, positions)
            for leaf, new in zip(leaves, new_rows)
        )
    leaves, new_rows = tuple(leaves), tuple(new_rows)
    for leaf in leaves:
        _traced_forms[leaf.shape] = "tile"
    # the kernel's scalar argument is each row's length with the new
    # position in it, which the attention kernel takes next: written as
    # the models write it, so the compiler hands both kernels one operand
    # and fetches it once a layer (a second fetch queues behind the
    # weights' prefetches: 0.05 ms a layer). A row ends at its last
    # position at most, which is how dynamic_update_slice clamps
    lengths = jnp.minimum(positions + 1, leaves[0].shape[2])
    if mesh is None or mesh.size == 1:
        return _write_last_position(lengths, new_rows, leaves)
    each = (KV_SPEC,) * len(leaves)
    return jax.shard_map(
        _write_last_position, mesh=mesh,
        in_specs=(P(), each, each), out_specs=each, check_vma=False,
    )(lengths, new_rows, leaves)
