"""The gated delta rule for one position a row as a Pallas TPU kernel: a
decode step's update of a KDA layer's state, the state read once and
written once.

``S' = Diag(alpha) S``; ``S_t = S' + beta k (v - S'^T k)^T``; ``o = S_t^T
q``, a head's ``S (d_k, d_v)`` float32. ``S'^T k`` has to be known before
``S_t`` can be written, so the XLA form (``models/solar_open2.kda_step``)
takes two passes over the state: one fusion reduces ``S'^T k`` and ``S'^T
q``, a second reads the state again and writes it (three state-sized
transfers for the two the rule needs; on a described v5e the compiler
merges the second passes of all layers into one fusion at the end of the
step, which changes nothing of that count). Here a head's ``128 x 128``
state is 64 KB: it sits in VMEM between the reductions and the write.

Design:
- grid ``(rows, heads / _HEADS)``: a grid step holds ``_HEADS`` heads'
  states of one row (2 MB at 32 heads of 128 x 128; in and out double
  buffered is 8 MB, inside the compiler's default 16 MB of scoped VMEM),
  the state an input aliased to its output, so a donated cache stays where
  it is
- per head, on the VPU: ``S' = S * alpha`` with ``alpha`` a column (a
  channel of ``d_k`` a sublane row); the two reductions over ``d_k`` are
  sublane sums of ``S' * k`` and ``S' * q``; ``u = beta (v - S'^T k)`` a
  row over ``d_v``; ``S_t = S' + k u``; ``o = S'^T q + (k . q) u``. About
  130 vector operations a 64 KB tile: a fifth of the time its two
  transfers take at the HBM's peak
- what is a column in the kernel (``alpha``, ``k``, ``q``: indexed by
  ``d_k``, broadcast along ``d_v``'s lanes) arrives as a column: the
  wrapper transposes the three ``(rows, heads, d_k)`` vectors (1 MB each)
  into one ``(rows, heads / _HEADS, d_k, 3 x _HEADS)`` array, so no
  relayout happens in the kernel; ``v`` and ``beta`` arrive as rows
- a row that starts afresh (``fresh``: a slot nobody holds, which the
  engine restarts at position 0 before every step, or a request's first
  position) reads its state as zero, here, on a scalar a row: as a select
  before the call it is a pass over every state of its own, which the
  compiler does not fuse into a custom call's aliased operand
  (``allow_input_fusion`` tried on a described v5e: a
  ``broadcast_select_fusion`` of all layers' states stayed in front)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# heads of one grid step (module docstring)
_HEADS = 32


def _use_interpret() -> bool:
    from ray_tpu._internal.platform import pallas_interpret

    return pallas_interpret("kda_step")


def _kernel(fresh_ref, s_ref, cols_ref, rows_ref, new_ref, o_ref, *, hb: int):
    carried = fresh_ref[pl.program_id(0)] == 0
    for h in range(hb):
        # (dk, dv); a select, not a product: what a free row holds may be
        # anything
        state = jnp.where(carried, s_ref[0, h], 0.0)
        alpha = cols_ref[0, 0, :, h:h + 1]  # (dk, 1)
        k = cols_ref[0, 0, :, hb + h:hb + h + 1]
        q = cols_ref[0, 0, :, 2 * hb + h:2 * hb + h + 1]
        v = rows_ref[0, 0, h:h + 1, :]  # (1, dv)
        beta = rows_ref[0, 0, hb + h:hb + h + 1, :]
        decayed = state * alpha
        seen = jnp.sum(decayed * k, axis=0, keepdims=True)  # S'^T k
        asked = jnp.sum(decayed * q, axis=0, keepdims=True)  # S'^T q
        u = beta * (v - seen)
        new_ref[0, h] = decayed + k * u
        o_ref[0, 0, h:h + 1, :] = asked + jnp.sum(
            k * q, axis=0, keepdims=True) * u


def kda_step(state, q, k, v, g, beta, fresh):
    """``state (b, h, dk, dv)`` f32, ``q`` / ``k`` / ``g (b, h, dk)`` (``g``
    the log of the decay, <= 0), ``v (b, h, dv)``, ``beta (b, h)``, all
    f32; ``fresh (b,)`` bool, the rows whose state counts as zero. Returns
    the new state (in ``state``'s buffer where it was donated) and ``o (b,
    h, dv)``: ``models/solar_open2.kda_step``'s function from
    ``where(fresh, 0, state)``."""
    b, h, dk, dv = state.shape
    hb = _HEADS if h % _HEADS == 0 else h
    blocks = h // hb

    def columns(t):  # (b, h, dk) -> (b, blocks, dk, hb)
        return t.reshape(b, blocks, hb, dk).transpose(0, 1, 3, 2)

    cols = jnp.concatenate(
        [columns(jnp.exp(g)), columns(k), columns(q)], axis=-1)
    rows = jnp.concatenate([
        v.reshape(b, blocks, hb, dv),
        jnp.broadcast_to(beta[..., None], (b, h, dv)).reshape(
            b, blocks, hb, dv),
    ], axis=2)
    new, o = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, blocks),
            in_specs=[
                pl.BlockSpec((1, hb, dk, dv), lambda r, j, fresh: (r, j, 0, 0)),
                pl.BlockSpec((1, 1, dk, 3 * hb), lambda r, j, fresh: (r, j, 0, 0)),
                pl.BlockSpec((1, 1, 2 * hb, dv), lambda r, j, fresh: (r, j, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, hb, dk, dv), lambda r, j, fresh: (r, j, 0, 0)),
                pl.BlockSpec((1, 1, hb, dv), lambda r, j, fresh: (r, j, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((b, blocks, hb, dv), jnp.float32),
        ],
        input_output_aliases={1: 0},  # (the scalars are operand 0)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        name="kda_step",  # the op's name in a device trace
        interpret=_use_interpret(),
    )(fresh.astype(jnp.int32), state, cols, rows)
    return new, o.reshape(b, h, dv)
