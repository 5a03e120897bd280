"""Ring attention: causal attention over a sequence-parallel mesh axis.

Not present in the reference (SURVEY §2c: sequence/context parallelism "must
be built natively" — Ray itself only gangs the workers). Design:

- the global sequence is sharded over the ``sp`` mesh axis; each rank holds
  contiguous positions [rank*s_local, (rank+1)*s_local)
- forward: the diagonal block is causal flash attention on local K/V; then
  K/V rotate around the ring via ``jax.lax.ppermute`` (neighbor exchanges on
  the ICI torus) and every arriving earlier-rank block is merged with the
  running output by log-sum-exp reweighting — blockwise softmax never
  materializes the full S×S matrix
- backward: custom VJP. The (q, dO, lse, delta, dq_acc) packet rotates while
  K/V stay resident; each rank accumulates its local dK/dV from visiting
  query shards and adds the matching dq contribution into the traveling
  packet, which arrives home after a full loop. Compute reuses the same
  Pallas block kernels as single-chip flash attention.

Communication per step is one neighbor ppermute of the K/V (or packet) shard
— bandwidth-optimal on an ICI ring; compute of step i overlaps XLA-scheduled
transfer of step i+1.

Call inside shard_map with q, k, v already sharded over ``axis_name``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.flash_attention import (
    attention_delta,
    flash_attention_with_lse,
    flash_bwd_dkv,
    flash_bwd_dq,
    tag_residuals,
)


def _merge(o1, lse1, o2, lse2):
    """Combine two partial attention results via log-sum-exp weights.
    o: (b,h,s,d); lse: (b,h,s) f32."""
    lse_max = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - lse_max)
    w2 = jnp.exp(lse2 - lse_max)
    denom = w1 + w2
    lse_new = lse_max + jnp.log(denom)
    o = (
        o1.astype(jnp.float32) * (w1 / denom)[..., None]
        + o2.astype(jnp.float32) * (w2 / denom)[..., None]
    )
    return o.astype(o1.dtype), lse_new


def _shift(x, axis_name: str, n: int):
    """Rotate shards one step around the ring: rank i -> rank (i+1) % n."""
    perm = [(i, (i + 1) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ring_core(q, k, v, axis_name: str, sm_scale: float):
    o, _ = _ring_forward(q, k, v, axis_name, sm_scale)
    return o


def _ring_forward(q, k, v, axis_name, sm_scale):
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    # diagonal block: local causal attention
    o, lse = flash_attention_with_lse(q, k, v, causal=True, sm_scale=sm_scale)
    kv = (k, v)
    for s in range(1, n):
        kv = _shift(kv, axis_name, n)  # now holding kv of rank (me - s) % n
        k_s, v_s = kv
        visible = me >= s  # that rank is strictly earlier -> full attention

        def _attend(args):
            q_, k_, v_ = args
            return flash_attention_with_lse(
                q_, k_, v_, causal=False, sm_scale=sm_scale
            )

        def _skip(args):
            q_, _, _ = args
            b, h, sq, d = q_.shape
            return (
                jnp.zeros_like(q_),
                jnp.full((b, h, sq), -jnp.inf, jnp.float32),
            )

        o_s, lse_s = lax.cond(visible, _attend, _skip, (q, k_s, v_s))
        o, lse = _merge(o, lse, o_s, lse_s)
    return o, lse


def _ring_fwd(q, k, v, axis_name, sm_scale):
    o, lse = _ring_forward(q, k, v, axis_name, sm_scale)
    q, o, lse = tag_residuals(q, o, lse)
    return o, (q, k, v, o, lse)


def _ring_bwd(axis_name, sm_scale, res, do):
    q, k, v, o, lse = res
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    bh = b * h

    def flat(x):
        return x.reshape(bh, x.shape[2], x.shape[3])

    def flat_l(x):  # (b,h,s) -> (bh,s,1)
        return x.reshape(bh, x.shape[2], 1)

    qf, kf, vf, dof = flat(q), flat(k), flat(v), flat(do)
    of = flat(o)
    lsef = flat_l(lse)
    deltaf = attention_delta(dof, of)

    # diagonal contributions (local, causal)
    dq = flash_bwd_dq(
        qf, kf, vf, dof, lsef, deltaf, sm_scale=sm_scale, causal=True
    )
    dk, dv = flash_bwd_dkv(
        qf, kf, vf, dof, lsef, deltaf, sm_scale=sm_scale, causal=True
    )

    # rotate the query packet around the ring; kv stays resident
    packet = (qf, dof, lsef, deltaf, dq)
    for s in range(1, n):
        packet = _shift(packet, axis_name, n)
        q_s, do_s, lse_s, delta_s, dq_s = packet
        # we now host the packet of rank qr = (me - s) % n; that query shard
        # attends OUR kv iff qr > me, i.e. s > me
        visible = s > me

        def _contrib(args):
            q_, do_, lse_, delta_, dq_, k_, v_ = args
            dk_c, dv_c = flash_bwd_dkv(
                q_, k_, v_, do_, lse_, delta_, sm_scale=sm_scale, causal=False
            )
            dq_c = flash_bwd_dq(
                q_, k_, v_, do_, lse_, delta_, sm_scale=sm_scale, causal=False
            )
            return dk_c.astype(k_.dtype), dv_c.astype(v_.dtype), dq_c

        def _zero(args):
            q_, _, _, _, _, k_, v_ = args
            return jnp.zeros_like(k_), jnp.zeros_like(v_), jnp.zeros_like(q_)

        dk_c, dv_c, dq_c = lax.cond(
            visible, _contrib, _zero, (q_s, do_s, lse_s, delta_s, dq_s, kf, vf)
        )
        dk = dk + dk_c
        dv = dv + dv_c
        packet = (q_s, do_s, lse_s, delta_s, dq_s + dq_c)

    # one more rotation brings every packet home (total n shifts)
    packet = _shift(packet, axis_name, n)
    _, _, _, _, dq_home = packet

    unflat = lambda x: x.reshape(b, h, x.shape[1], x.shape[2])
    return unflat(dq_home).astype(q.dtype), unflat(dk), unflat(dv)


_ring_core.defvjp(_ring_fwd, _ring_bwd)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "sp",
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Causal attention with the sequence sharded over ``axis_name``.

    Must be called inside shard_map with (batch, heads, seq_local, head_dim)
    shards. With axis size 1 this degrades to plain flash attention.
    GQA: kv heads are repeated to match q heads before ringing.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if k.shape[1] != q.shape[1]:
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    return _ring_core(q, k, v, axis_name, sm_scale)
