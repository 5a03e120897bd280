"""What a rematerialised layer stack keeps for its backward pass.

``nn.remat`` around a layer saves the layer's input and runs the layer's
forward a second time inside the backward pass. That second forward is a
third of the FLOPs the model needs, and it buys memory only a job at its
limit has a use for. The models tag the values the second forward would
recompute (``jax.ad_checkpoint.checkpoint_name``; a tag is the identity
outside ``nn.remat``), and ``plan`` says which tags the policy keeps: as
many as the device's memory holds beside the parameters and the step's own
working set, none where that is nothing or cannot be known.

The choice is arithmetic on what a trace can see (shapes, the mesh, the
device kind's memory limit), never on how full the device happens to be:
a job's shapes, mesh and device give the same program on every run, so the
compile cache answers it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.flash_attention import ATTN_LSE, ATTN_OUT, ATTN_Q

# ``checkpoint_name`` tags: these as ``models/llama.py`` writes them, q and
# attention's output as the kernels' forward rules do. A candidate is a tag;
# attention's output carries its log-sum-exp with it (the backward kernels
# read both, and one without the other still runs the forward kernel).
ATTN_K, ATTN_V, ATTN_RESID = "attn_k", "attn_v", "attn_resid"
MLP_GATE, MLP_UP = "mlp_gate", "mlp_up"

# Of the device's limit, what no plan spends: other programs' buffers, the
# allocator's fragments, and what the working set's reckoning misses.
MARGIN = 1 / 16


@dataclasses.dataclass(frozen=True)
class Candidate:
    name: str
    bytes: int  # a layer, a device
    flops: int  # of the second forward that keeping it takes away


@dataclasses.dataclass(frozen=True)
class Plan:
    kept: Tuple[str, ...]  # candidates' names, in the order taken
    kept_bytes: int  # a device, all layers
    budget_bytes: int
    tokens_per_device: int

    @property
    def tags(self) -> Tuple[str, ...]:
        return self.kept + ((ATTN_LSE,) if ATTN_OUT in self.kept else ())


def candidates(cfg, tokens: int, seq: int) -> List[Candidate]:
    """A layer's taggable values at ``tokens`` a device, in the order ``plan``
    walks them: by FLOPs saved a byte kept, to the nearest power of two (a
    fusion's or a kernel's distance from the MXU's peak differs by more than
    anything finer would tell), and within one such class in the order of
    the list below.

    A projection's output saves its contraction width a byte in bf16
    (``dim``; heads x head_dim for ``wo``) and attention's output the
    sequence length (causal: half of 4 x seq x width FLOPs a token), so at
    4096 wide and 4096 long everything ties and the list decides. It goes
    from small to large, since the walk stops at the first value that does
    not fit and so leaves unused less than that value's size; attention's
    output stands before q because it takes a kernel call out of the
    backward pass, and q after k and v because alone it saves nothing: the
    projections share their input and the compiler runs them as one.
    Measured a GB kept on a v5e at Mistral's widths (PERF.md, PR 56): k 28
    ms, attention's output 23, gate 19, the residual 19, q 0 alone and 25
    with k and v kept."""
    a = jnp.dtype(cfg.dtype).itemsize
    q_w, kv_w = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim

    def product(name, k, n):
        return Candidate(name, tokens * n * a, 2 * tokens * k * n)

    found = [
        product(ATTN_K, cfg.dim, kv_w),
        product(ATTN_V, cfg.dim, kv_w),
        Candidate(ATTN_OUT, tokens * q_w * a + tokens * cfg.n_heads * 4,
                  2 * tokens * seq * q_w),
        product(ATTN_Q, cfg.dim, q_w),
        product(ATTN_RESID, q_w, cfg.dim),
        product(MLP_GATE, cfg.dim, cfg.intermediate),
        product(MLP_UP, cfg.dim, cfg.intermediate),
    ]
    return sorted(found, key=lambda c: -round(math.log2(c.flops / c.bytes)))


def working_set_bytes(cfg, tokens: int, seq: int, layer_param_bytes: int,
                      head_param_bytes: int) -> int:
    """What a step holds on a device at its fullest besides the parameters and
    whatever a plan keeps, reckoned from shapes: every layer's input (remat
    keeps those under any policy); then the larger of the loss (the logits in
    the activations' type, their float32 copy and its gradient) and one
    layer's backward (each recomputed value, the gated product, and a
    gradient for each); two layers' parameters whole, the one at work and
    the one being gathered; the embedding and the head whole.

    Held to the TPU compiler's own count for Mistral-7B on fsdp=4 at 8192
    and 16384 tokens a device (6.05 and 10.88 GB of temporaries; this gives
    6.24 and 11.08: PERF.md, PR 56)."""
    a = jnp.dtype(cfg.dtype).itemsize
    inputs = cfg.n_layers * tokens * cfg.dim * a
    loss = tokens * cfg.vocab_size * (a + 4 + 4)
    layer = 2 * (sum(c.bytes for c in candidates(cfg, tokens, seq))
                 + tokens * cfg.intermediate * a)
    return inputs + max(loss, layer) + 2 * layer_param_bytes + head_param_bytes


def plan(cfg, tokens: int, seq: int, budget_bytes: Optional[int]) -> Plan:
    """The longest run of ``candidates`` whose bytes over all layers fit
    ``budget_bytes``. A walk that stops, not a knapsack: a larger budget
    keeps what a smaller one kept and more, so a job that grows loses names
    from the end and never trades one for another. No budget (None, or
    nothing left) keeps nothing, which is ``save_only_these_names()``."""
    budget = max(0, budget_bytes or 0)
    kept, kept_bytes = [], 0
    for c in candidates(cfg, tokens, seq):
        if kept_bytes + c.bytes * cfg.n_layers > budget:
            break
        kept.append(c.name)
        kept_bytes += c.bytes * cfg.n_layers
    return Plan(tuple(kept), kept_bytes, budget, tokens)



DATA_AXES = ("dcn", "dp", "fsdp", "sp")  # what cuts a batch's tokens


def for_step(cfg, extent: Mapping[str, int], shards: int,
             param_bytes: Mapping[tuple, int], batch: int, seq: int,
             bytes_limit: Optional[int]) -> Plan:
    """The plan for one traced step: ``batch`` x ``seq`` tokens over a mesh
    of ``extent`` (axis -> size, empty without one) that cuts a weight
    matrix ``shards`` ways, parameters of ``param_bytes`` (path -> bytes,
    whole), on a device that allows ``bytes_limit``.

    The budget is the limit less ``MARGIN`` of it, less what stays on the
    device through the step (its share of the parameters; a gradient and
    AdamW's two moments for each that trains: the adapters over a frozen
    base, else all), less ``working_set_bytes``. No limit (the CPU, a
    described chip) is no budget."""
    tokens = batch * seq // math.prod(extent.get(a, 1) for a in DATA_AXES)
    if not bytes_limit:
        return plan(cfg, tokens, seq, None)
    trained = sum(n for path, n in param_bytes.items()
                  if not cfg.lora_rank or path[-1].startswith("lora_"))
    resident = (sum(param_bytes.values()) + 3 * trained) // shards
    fsdp = extent.get("fsdp", 1)

    def gathered(n):  # a gather over fsdp holds matrices whole but for tp's cut
        return n * fsdp // shards if fsdp > 1 else 0

    stack = sum(n for path, n in param_bytes.items() if path[0].startswith("layer"))
    ends = sum(param_bytes.get((name,), 0) for name in ("embed", "lm_head"))
    working = working_set_bytes(
        cfg, tokens, seq, gathered(stack // cfg.n_layers), gathered(ends))
    return plan(cfg, tokens, seq,
                int(bytes_limit * (1 - MARGIN)) - resident - working)


def device_bytes_limit() -> Optional[int]:
    """``bytes_limit`` of this process's first device: a property of the
    device kind, the same on every run. Not ``bytes_in_use``: a choice that
    moved with it would miss the compile cache."""
    stats = jax.local_devices()[0].memory_stats()
    return (stats or {}).get("bytes_limit")
