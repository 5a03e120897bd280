"""Rotary position embeddings (RoPE).

Pure jnp: RoPE is elementwise mul/add on (seq, head_dim) — XLA fuses it into
the surrounding projections, so a hand kernel buys nothing; the win is the
precomputed frequency table and an offset argument for sequence-parallel
shards (each sp rank applies its absolute positions).

Two published forms, the same angles (``rope_table``) on different pairs of
a head's values:

- *rotate-half* (GPT-NeoX, Llama; the default): frequency ``i`` turns the
  pair ``(x[i], x[i + head_dim / 2])``
- *interleaved* (GPT-J, ``rope_gptj``; ``interleaved=True``): frequency
  ``i`` turns the neighbours ``(x[2i], x[2i + 1])``, which is the complex
  number ``x[2i] + 1j x[2i + 1]`` times ``exp(1j angle_i)``

One is the other under a fixed permutation of a head's columns, so weights
trained under one are wrong under the other; a model says which it is."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rope_table(max_len: int, head_dim: int, theta: float = 10000.0):
    """Returns (cos, sin) tables of shape (max_len, head_dim // 2), f32."""
    freqs = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    pos = jnp.arange(max_len, dtype=jnp.float32)
    angles = jnp.outer(pos, freqs)
    return jnp.cos(angles), jnp.sin(angles)


def _turn_neighbours(x, cos, sin, offset):
    """``x * cos + partner * sin`` at the rows' positions, a lane's partner
    its neighbour and both lanes of a pair under the pair's angle (the
    tables' rows are sliced first and widened after: nothing of
    ``max_len`` is materialised)."""
    seq = x.shape[-2]
    even = jnp.arange(x.shape[-1]) % 2 == 0
    partner = jnp.where(
        even, -jnp.roll(x, -1, axis=-1), jnp.roll(x, 1, axis=-1))

    def at(table, off):
        rows = jax.lax.dynamic_slice_in_dim(table, off, seq, axis=0)
        return jnp.repeat(rows, 2, axis=-1)

    if hasattr(offset, "ndim") and offset.ndim == 1:
        def per_row(x_row, p_row, off):  # (heads, seq, head_dim)
            return x_row * at(cos, off)[None] + p_row * at(sin, off)[None]

        return jax.vmap(per_row)(x, partner, offset).astype(x.dtype)
    return (x * at(cos, offset)[None, None]
            + partner * at(sin, offset)[None, None]).astype(x.dtype)


def apply_rope(
    x: jax.Array,  # (batch, heads, seq, head_dim)
    cos: jax.Array,
    sin: jax.Array,
    offset: int | jax.Array = 0,
    interleaved: bool = False,
) -> jax.Array:
    """Rotate a head's pairs, the halves ``(x[..., i], x[..., i + half])``
    or with ``interleaved`` the neighbours ``(x[..., 2i], x[..., 2i + 1])``
    (module docstring); ``offset`` is the absolute position of x's first
    token (nonzero on sp shards and in decode). A vector offset of shape
    (batch,) applies a different position per row — the
    continuous-batching decode case."""
    seq = x.shape[-2]
    half = x.shape[-1] // 2
    if interleaved:
        # no reshape into pairs: the TPU compiler folds one into the
        # projection in front of it as a relayout of that projection's
        # *weight*, every step (PERF.md, finding 59.2)
        return _turn_neighbours(x, cos, sin, offset)
    if hasattr(offset, "ndim") and offset.ndim == 1:
        def per_row(x_row, off):  # (heads, seq, head_dim)
            c = jax.lax.dynamic_slice_in_dim(cos, off, seq, axis=0)[None]
            s = jax.lax.dynamic_slice_in_dim(sin, off, seq, axis=0)[None]
            x1 = x_row[..., :half]
            x2 = x_row[..., half:]
            return jnp.concatenate(
                [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
            )

        return jax.vmap(per_row)(x, offset).astype(x.dtype)
    c = jax.lax.dynamic_slice_in_dim(cos, offset, seq, axis=0)[None, None]
    s = jax.lax.dynamic_slice_in_dim(sin, offset, seq, axis=0)[None, None]
    x1 = x[..., :half]
    x2 = x[..., half:]
    out1 = x1 * c - x2 * s
    out2 = x2 * c + x1 * s
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)
