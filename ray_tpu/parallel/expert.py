"""Expert parallelism: MoE gating, dispatch, and combine.

No reference analogue (SURVEY §2c: EP is "delegated" to engines in the
reference; here the framework owns it). GShard/Switch-style top-k routing
with static capacity so every shape is compile-time constant (XLA/TPU needs
static shapes — no gather/scatter of ragged expert batches):

- ``top_k_gating`` builds dispatch/combine tensors (tokens, experts,
  capacity) plus the load-balancing auxiliary loss
- ``moe_apply_gspmd`` runs the experts with einsums and lets GSPMD insert
  the all-to-alls from the ``expert`` logical-axis sharding (the pjit path
  used by models/moe.py)
- ``moe_dispatch`` / ``moe_combine`` are the explicit shard_map path: a
  ``lax.all_to_all`` over the ``ep`` axis moves (expert, capacity, dim)
  slabs so each rank runs only its local experts — for hand-scheduled
  kernels and tests of the comm pattern itself

The dropless path (serving, and any model with ``MoEConfig.dropless``) has
no capacity: ``top_k_routing`` picks each token's experts and
``moe_apply_dropless`` sorts the ``tokens x k`` assignments by expert and
runs them as one grouped matmul (ops/moe_experts.py), so every assignment
is computed whatever the imbalance and an answer does not depend on which
other rows share the batch.
"""

from __future__ import annotations

import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def expert_capacity(tokens: int, n_experts: int, capacity_factor: float,
                    k: int = 2) -> int:
    """Static per-expert token capacity (reference pattern: GShard cap)."""
    return max(1, int(math.ceil(tokens * k * capacity_factor / n_experts)))


def top_k_gating(
    router_logits: jax.Array,  # (tokens, experts) f32
    capacity: int,
    k: int = 2,
):
    """Build dispatch/combine tensors with static capacity.

    Returns:
      dispatch: (tokens, experts, capacity) bool-ish f32 — token t goes to
        expert e at slot c
      combine:  (tokens, experts, capacity) f32 — gate weight for the same
      aux_loss: load-balance loss (Switch-style: E * sum(frac_tokens * frac_prob))
    """
    t, e = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)

    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    # running per-expert fill count, updated between the k passes
    position_in_expert = jnp.zeros((e,), jnp.int32)
    masked = probs
    for _ in range(k):
        gate = jnp.max(masked, axis=-1)  # (t,)
        idx = jnp.argmax(masked, axis=-1)  # (t,)
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # (t, e)
        # slot index for each token within its chosen expert: running count
        # of earlier tokens choosing the same expert, offset by prior passes
        pos = jnp.cumsum(onehot, axis=0) - 1.0 + position_in_expert[None, :]
        pos_tok = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)  # (t,)
        keep = pos_tok < capacity
        slot = jax.nn.one_hot(pos_tok, capacity, dtype=jnp.float32)  # (t, c)
        sel = onehot * keep[:, None].astype(jnp.float32)
        dispatch = dispatch + sel[:, :, None] * slot[:, None, :]
        combine = combine + (gate * keep)[:, None, None] * (
            sel[:, :, None] * slot[:, None, :]
        )
        position_in_expert = position_in_expert + jnp.sum(
            onehot * keep[:, None], axis=0
        ).astype(jnp.int32)
        masked = masked * (1.0 - onehot)  # exclude chosen expert next pass

    # renormalize combine weights over the k selected experts
    denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
    combine = combine / jnp.maximum(denom, 1e-9)

    frac_tokens = jnp.mean(
        (jnp.sum(dispatch, axis=-1) > 0).astype(jnp.float32), axis=0
    )
    frac_probs = jnp.mean(probs, axis=0)
    aux_loss = e * jnp.sum(frac_tokens * frac_probs)
    return dispatch, combine, aux_loss


def moe_apply_gspmd(
    x: jax.Array,  # (tokens, dim)
    dispatch: jax.Array,  # (tokens, E, C)
    combine: jax.Array,  # (tokens, E, C)
    expert_fn: Callable[[jax.Array], jax.Array],  # (E, C, dim) -> (E, C, dim_out)
) -> jax.Array:
    """pjit path: einsum dispatch -> per-expert compute -> einsum combine.
    With expert weights annotated on the ``expert`` logical axis, GSPMD
    lowers the einsums to all_to_alls over the ep mesh axis."""
    expert_inputs = jnp.einsum(
        "td,tec->ecd", x.astype(jnp.float32), dispatch
    ).astype(x.dtype)
    expert_outputs = expert_fn(expert_inputs)  # (E, C, d_out)
    return jnp.einsum(
        "ecd,tec->td", expert_outputs.astype(jnp.float32), combine
    ).astype(x.dtype)


# -- explicit shard_map path -------------------------------------------------


def moe_dispatch(x, dispatch, axis_name: str = "ep"):
    """Inside shard_map: local tokens -> this rank's local experts' slabs.

    x: (tokens_local, d); dispatch: (tokens_local, E_global, C).
    Returns (E_local, n * C, d): every rank's contribution to our experts.
    """
    n = lax.psum(1, axis_name)
    slabs = jnp.einsum("td,tec->ecd", x.astype(jnp.float32), dispatch).astype(
        x.dtype
    )  # (E_global, C, d)
    e_global, c, d = slabs.shape
    if e_global % n != 0:
        raise ValueError(f"experts ({e_global}) not divisible by ep axis ({n})")
    # split expert dim across ranks, gather source-rank dim in its place
    slabs = slabs.reshape(n, e_global // n, c, d)
    recv = lax.all_to_all(slabs, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)  # (n, E_local, C, d), dim0 = source rank
    n_, e_local, c_, d_ = recv.shape
    return recv.transpose(1, 0, 2, 3).reshape(e_local, n_ * c_, d_)


def moe_combine(y_local, combine, axis_name: str = "ep"):
    """Inverse of moe_dispatch: local expert outputs -> local tokens.

    y_local: (E_local, n * C, d_out); combine: (tokens_local, E_global, C).
    """
    n = lax.psum(1, axis_name)
    e_local, nc, d = y_local.shape
    c = nc // n
    slabs = y_local.reshape(e_local, n, c, d).transpose(1, 0, 2, 3)
    # send each source-rank slab home: (n, E_local, C, d) -> full expert dim
    back = lax.all_to_all(slabs, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)  # (n, E_local, C, d), dim0 = expert group
    slabs_home = back.reshape(n * e_local, c, d)  # (E_global, C, d)
    return jnp.einsum(
        "ecd,tec->td", slabs_home.astype(jnp.float32), combine
    ).astype(y_local.dtype)


# -- dropless path -----------------------------------------------------------


def top_k_routing(
    router_logits: jax.Array,  # (tokens, experts)
    k: int,
    normalize: bool = True,
    scoring: str = "softmax",
    selection_bias: Optional[jax.Array] = None,  # (experts,)
    scale: float = 1.0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Each token's ``k`` experts and their weights, f32.

    ``scoring="softmax"``: a softmax over all experts, the ``k`` largest
    probabilities and their experts; ``normalize`` divides the kept weights
    by their sum (Mixtral), without it they sum to less than 1 (OLMoE's
    ``norm_topk_prob: false``).

    ``scoring="sigmoid"`` (DeepSeek-V3's ``noaux_tc`` router without a
    group limit): each expert's score is its own sigmoid; the experts are
    the ``k`` largest of ``score + selection_bias``, a learned per-expert
    correction that enters the *choice* only; the weights are the chosen
    experts' unbiased scores, divided by ``sum + 1e-20`` with ``normalize``,
    then times ``scale`` (``routed_scaling_factor``).

    Returns ``(weights (tokens, k) f32, experts (tokens, k) int32,
    aux_loss)`` with the same Switch-style load-balance loss as
    ``top_k_gating`` (over the scores, whichever they are)."""
    n_experts = router_logits.shape[-1]
    if scoring == "softmax":
        probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    elif scoring == "sigmoid":
        probs = jax.nn.sigmoid(router_logits.astype(jnp.float32))
    else:
        raise ValueError(f"top_k_routing: unknown scoring {scoring!r}")
    if selection_bias is None:
        weights, experts = lax.top_k(probs, k)
    else:
        _, experts = lax.top_k(
            probs + selection_bias.astype(jnp.float32)[None, :], k
        )
        weights = jnp.take_along_axis(probs, experts, axis=-1)
    if normalize:
        total = jnp.sum(weights, axis=-1, keepdims=True)
        # the published guard of the sigmoid router; a softmax's kept
        # weights divide as they always have
        weights = weights / (total + 1e-20 if scoring == "sigmoid" else total)
    if scale != 1.0:
        weights = weights * scale
    chosen = jnp.zeros_like(probs).at[
        jnp.arange(probs.shape[0])[:, None], experts
    ].set(1.0)
    aux_loss = n_experts * jnp.sum(
        jnp.mean(chosen, axis=0) * jnp.mean(probs, axis=0)
    )
    return weights, experts.astype(jnp.int32), aux_loss


def moe_apply_dropless(
    x: jax.Array,  # (tokens, dim)
    weights: jax.Array,  # (tokens, k) f32
    experts: jax.Array,  # (tokens, k) int32
    w_gate: Optional[jax.Array],  # (E, dim, f); None with "relu2"
    w_up: jax.Array,  # (E, dim, f)
    w_down: jax.Array,  # (E, f, dim)
    held: Optional[Tuple[int, int]] = None,
    activation: str = "swiglu",
    poly: Optional[jax.Array] = None,  # (E, 4) with "poly_norm"
    eps: float = 1e-5,
) -> jax.Array:
    """``sum_j weights[t, j] * swiglu_{experts[t, j]}(x[t])`` for every
    token, no assignment dropped: the ``tokens x k`` assignments sorted by
    expert, one grouped matmul over the sorted rows, then back in token
    order and summed. Static shapes: the rows are padded to whole tiles,
    and the padding rides with the last expert at weight zero.

    ``held = (first, stop)``: the weights are those of experts ``first ..
    stop - 1`` of the ``experts`` routed over (one chip's share of a layer
    whose experts lie on several), and the result is the part of the sum
    those experts give. The assignments to them are sorted first and are
    the only rows the grouped matmul visits; an assignment to an expert
    held elsewhere, like the padding, sorts behind them, belongs to no
    group and adds nothing: the work follows the held assignments.

    ``activation`` / ``poly`` / ``eps``: the expert's activation as
    ``ops/moe_experts.moe_experts`` takes it (SwiGLU unless said; an
    ungated ``"relu2"`` expert has no ``w_gate``; ``"reglu"`` is SwiGLU's
    three matrices under ``relu``)."""
    from ..ops.moe_experts import moe_experts, tile_rows

    tokens, k = experts.shape
    n_experts = w_up.shape[0]
    n = tokens * k
    tm = tile_rows(n)
    padded = -(-n // tm) * tm
    if held is not None and held[1] - held[0] != n_experts:
        raise ValueError(
            f"moe_apply_dropless: {n_experts} experts' weights for the "
            f"held range {held}"
        )
    # what follows from the routing alone, whatever the rows hold (a
    # scope of its own inside the caller's: a trace can tell it from the
    # rows' gather and the kernel)
    with jax.named_scope("moe.sort"):
        flat = experts.reshape(n)
        if held is not None:
            first, stop = held
            here = (flat >= first) & (flat < stop)
            # a key past the last held expert: behind every held assignment
            flat = jnp.where(here, flat - first, n_experts)
            outside = n_experts
        else:
            outside = n_experts - 1
        if padded != n:
            flat = jnp.concatenate(
                [flat, jnp.full((padded - n,), outside, jnp.int32)]
            )
        order = jnp.argsort(flat, stable=True)
        # an assignment's token: row i of ``flat`` belongs to token i // k
        # (padding rows read the last token; nothing reads their result)
        source = jnp.minimum(order // k, tokens - 1)
        # (a key past the last expert is out of bounds here, and dropped)
        group_sizes = jnp.zeros((n_experts,), jnp.int32).at[flat].add(1)
    # (SwiGLU's call is the one it always was: no argument it does not take)
    extra = {} if activation == "swiglu" else dict(
        activation=activation, poly=poly, eps=eps)
    y = moe_experts(x[source], w_gate, w_up, w_down, group_sizes, **extra)
    with jax.named_scope("moe.sort"):
        back = jnp.argsort(order)[:n]  # sorted row of each assignment
    y = y[back].reshape(tokens, k, -1)
    if held is not None:
        # a row no group owns was never written: whatever the buffer held
        y = jnp.where(here.reshape(tokens, k, 1), y, 0.0)
    return jnp.sum(y * weights[..., None], axis=1).astype(x.dtype)
