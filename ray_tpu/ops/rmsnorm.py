"""Fused RMSNorm as a Pallas TPU kernel with custom VJP.

One HBM round-trip for x (vs separate mean-square, rsqrt, scale ops when XLA
doesn't fuse); f32 statistics regardless of input dtype, matching the
numerics LLaMA-family models expect.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import Mesh, PartitionSpec as P


def _use_interpret() -> bool:
    from ray_tpu._internal.platform import pallas_interpret

    return pallas_interpret("rmsnorm")


def _fwd_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    o_ref[...] = (x * inv * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _rmsnorm_fwd_impl(x2, w, eps, block_rows):
    n, d = x2.shape
    grid = (pl.cdiv(n, block_rows),)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x2.dtype),
        interpret=_use_interpret(),
    )(x2, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rmsnorm(x2, w, eps):
    return _rmsnorm_fwd_impl(x2, w, eps, block_rows=256)


def _rmsnorm_fwd(x2, w, eps):
    return _rmsnorm(x2, w, eps), (x2, w)


def _rmsnorm_bwd(eps, res, g):
    # backward in plain XLA: elementwise chains fuse well, and the extra
    # rematerialized rsqrt is cheap relative to an extra pallas kernel here
    x2, w = res
    x = x2.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    d = x.shape[-1]
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    xhat = x * inv
    dw = jnp.sum(gf * xhat, axis=0).astype(w.dtype)
    gw = gf * wf
    dx = inv * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    return dx.astype(x2.dtype), dw


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def _rmsnorm_any_shape(x, weight, eps):
    shape = x.shape
    out = _rmsnorm(x.reshape(-1, shape[-1]), weight, eps)
    return out.reshape(shape)


def activation_spec(mesh: Mesh, shape) -> P:
    """How the models lay an activation of ``shape`` out on ``mesh``
    (parallel/sharding.py DEFAULT_RULES): batch over the data axes, the
    sequence axis (second to last, when there is one) over sp, features
    whole — so under tp every rank holds the full residual stream. An axis
    its mesh axes do not divide (a decode batch of 1 on a dp mesh) stays
    whole."""
    def fit(axes, size):
        axes = tuple(a for a in axes if a in mesh.axis_names)
        extent = math.prod(mesh.shape[a] for a in axes)
        return axes if extent > 1 and size % extent == 0 else None

    if len(shape) < 2:
        return P(None)
    batch = fit(("dcn", "dp", "fsdp"), shape[0])
    if len(shape) == 2:
        return P(batch, None)
    return P(batch, *([None] * (len(shape) - 3)), fit(("sp",), shape[-2]), None)


def rmsnorm(
    x: jax.Array, weight: jax.Array, eps: float = 1e-6,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """RMSNorm over the last axis; any leading shape.

    A pallas_call is opaque to the SPMD partitioner: left alone under a
    multi-device mesh it is replicated — every chip all-gathers the whole
    batch, runs every row, and slices its share back. Rows are independent,
    so with a ``mesh`` the kernel runs per shard under shard_map, on the
    layout the models already keep their activations in
    (``activation_spec``), weight replicated. (custom_partitioning, the
    declarative way to say the same, never reaches libtpu's partitioner on
    this installation: the TPU compiler rejects the program with "Custom
    emitter for CustomSPMDPartitioning not found".)"""
    if mesh is None or mesh.size == 1:
        return _rmsnorm_any_shape(x, weight, eps)
    spec = activation_spec(mesh, x.shape)
    return jax.shard_map(
        functools.partial(_rmsnorm_any_shape, eps=eps),
        mesh=mesh, in_specs=(spec, P()), out_specs=spec, check_vma=False,
    )(x, weight)
