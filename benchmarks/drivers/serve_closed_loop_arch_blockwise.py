"""``serve_closed_loop_arch`` for a cell whose check does not fit whole,
and whose decode step attends in another form than its prefill: the same
cluster, request path, warm-up, window and result, with another comparison
against the plain reference on the replica.

Why a kind of its own. ``serve_arch_common.ArchReplica.bench_reference``
holds, for a routed architecture, the program's logits over prompt + answer
(bf16), the reference's (float32), and in ``routed_facts`` two more float32
arrays of that size. At OLMoE's 50304-column head and 527 positions that is
0.4 GB. At Moonlight's 163840 columns and a 4096-token prompt it is 1.3 +
2.7 + 2.7 + 2.7 = 9.4 GB beside 12 GB of weights, slot rows and pool on a
16 GB chip (PR 30's first run of the cell ended there). That file may not
be edited by the PR that adds the cell, so the cell names this kind instead;
a ``benchmark`` PR folds both differences into ``serve_arch_common`` and
deletes this file (PERF.md, Open questions).

What differs (``BlockwiseReplica.bench_reference``):

- *In blocks.* The reference returns its final hidden states
  (``hidden_states``: positions x width, 34 MB); its head (``head``) is
  applied to ``POSITION_BLOCK`` positions at a time and each block is
  reduced against the program's logits to three numbers on the device.
  What is still held whole is the program's logits over the prompt
  (``serve_arch_common.forward_routed``: 1.3 GB at 4096), but not while
  the reference's pass runs.
- *Through the engine's cache and its decode program.* ``ArchReplica``
  compares one whole-sequence pass of the program's model, which for a
  latent-attention model is the published form throughout and touches
  neither the cache nor the decode kernel. Here the answer's positions go
  the way the request went. The engine's own jitted ``_prefill`` runs on
  the prompt (its last-position logits are ``prefill_max_abs_logit_diff``,
  as in ``ArchReplica``); its row is put into the engine's own pool of slot
  rows with ``_insert_row``; and the engine's own jitted ``_decode``, at the
  pool's shape with that one row live, is fed the tokens the engine
  generated, one step each (``replay``). It is the program and the input
  the request's steps had, so its logits are theirs. The experts each step
  chose are read from the program's own counters (zeroed before a step,
  they are the live row's choice), the reference follows them, and every
  position of prompt + answer is compared: ``max_abs_logit_diff`` is the
  largest difference anywhere, ``rms_logit_diff`` the larger of the prompt
  positions' and the decoded positions' (``prefill_rms_logit_diff`` /
  ``decode_rms_logit_diff``: a fault of the cache alone moves only the
  latter). ``token_gap_max`` so holds the tokens the request path returned
  against a reference that followed the steps that made them.
- *What the request left in the block pool.* The request committed its
  prompt's blocks at admission and, at its end, the blocks its steps filled
  (``_extract_row``, ``KVCacheManager.commit``). They are assembled again
  (``KVCacheManager.assemble``) and compared with the replay's row, which
  holds the same positions from the same programs: any difference is an
  ``error`` (``pool_row``).

The facts' names and ``within`` are ``serve_arch_common``'s, so a mix
file's ``tolerance`` means what it means there. A reference used with this
kind has ``hidden_states`` and ``head`` beside what ``serve_arch_common``
lists, and ``program_routing``; it may name ``ATTENTION_SCOPES``, those of
its ``TRACE_SCOPES`` that are no part of the expert layers: a traced run's
``scopes["scope_s"]`` keeps the others (``moe_experts_busy_share`` sums
them) and these go to ``scopes["attention_scope_s"]``.

Mix keys: those of ``serve_closed_loop``.
"""

from __future__ import annotations

import functools
from typing import List

from . import serve_arch_common as common
from . import serve_closed_loop_arch

POSITION_BLOCK = 512


def replay(engine, row, fed, slot: int = 0):
    """The engine's own decode program over its own pool of slot rows, as a
    request's steps ran it: ``row`` (a prefill's) inserted at ``slot``, no
    other row live, the tokens ``fed`` one step each. Returns the steps'
    logits (len(fed), vocab), each routed layer's experts (len(fed), k), and
    the row as the steps left it. For an idle engine, under its lock: the
    pool's other rows are free, and this one is again afterwards."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    slots = engine._num_slots
    active = np.zeros(slots, bool)
    active[slot] = True
    at = jnp.asarray(slot, jnp.int32)
    nobody = np.full(slots, -1)
    zeroed = jax.tree.map(jnp.zeros_like, engine._expert_counts)
    engine._cache = engine._insert_row(engine._cache, row, at)
    rows, chosen = [], []
    for token in fed:
        last = np.zeros((slots, 1), np.int32)
        last[slot] = token
        logits, engine._cache, counts = engine._decode(
            engine._params, engine._cache, jnp.asarray(last),
            *engine._adapter_args(nobody), active=active, expert_counts=zeroed)
        rows.append(logits[slot])
        chosen.append(counts["assignments"])  # (routed layers, experts) of 0 / 1
    chosen = jnp.stack(chosen)
    k = int(chosen[0, 0].sum())
    experts = jax.lax.top_k(chosen, k)[1].astype(jnp.int32)  # (steps, layers, k)
    return (jnp.stack(rows), [experts[:, layer] for layer in range(experts.shape[1])],
            engine._extract_row(engine._cache, at))


def pool_row(engine, tokens, row) -> dict:
    """The blocks the block pool holds for ``tokens`` (what a request of
    them committed), assembled, against ``row`` at the same positions."""
    import jax
    import jax.numpy as jnp

    kv = engine._kv
    lease = kv.acquire(tokens)
    try:
        held = lease.num_cached_tokens
        if held < (len(tokens) - 1) // kv.block_size * kv.block_size:
            return {"error": f"the pool holds {held} of {len(tokens)} positions"}
        worst = max(
            float(jnp.max(jnp.abs(a[..., :held, :].astype(jnp.float32)
                                  - b[..., :held, :].astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(kv.assemble(lease)), jax.tree.leaves(row))
            if a.ndim >= 3)
    finally:
        kv.release(lease)
    if worst:
        return {"error": f"the pool's blocks differ from the replayed row by {worst}"}
    return {"pool_positions": held}


@functools.lru_cache(maxsize=None)
def _block_reducer():
    """One block's (largest difference, sum of squares, all finite)."""
    import jax
    import jax.numpy as jnp

    def bench_block_reduce(ref, eng):
        diff = jnp.abs(eng.astype(jnp.float32) - ref)
        return jnp.max(diff), jnp.sum(diff * diff), jnp.all(jnp.isfinite(diff))

    return jax.jit(bench_block_reduce)


def blockwise_facts(arch, params, hidden, eng_all, eps: float) -> dict:
    """``routed_facts``' logit numbers from the reference's final hidden
    states ``hidden (seq, dim)`` and the program's logits ``eng_all (seq,
    vocab)`` or ``(1, seq, vocab)``, a block of positions at a time (an
    eager slice is a copy: only blocks are ever sliced)."""
    reduce = _block_reducer()
    positions = hidden.shape[0]
    batch = (0,) * (eng_all.ndim - 2)
    largest, squares, finite = 0.0, 0.0, True
    for start in range(0, positions, POSITION_BLOCK):
        stop = min(start + POSITION_BLOCK, positions)
        ref = arch.head(
            hidden[None, start:stop], params["final_norm"], params["lm_head"],
            eps=eps)[0]
        block_max, block_squares, block_finite = reduce(
            ref, eng_all[batch + (slice(start, stop),)])
        largest = max(largest, float(block_max))
        squares += float(block_squares)
        finite = finite and bool(block_finite)
    return {
        "positions": int(positions),
        "max_abs_logit_diff": largest,
        "rms_logit_diff": (squares / (positions * eng_all.shape[-1])) ** 0.5,
        "finite": finite,
    }


class BlockwiseReplica(common.ArchReplica):
    def bench_reference(self, architecture: str, sizes: dict,
                        prompt: List[int], generated: List[int]) -> dict:
        """``ArchReplica.bench_reference``'s facts for a routed
        architecture, the answer's positions through the engine's cache and
        decode program and the comparison in blocks (module docstring)."""
        import jax.numpy as jnp

        arch = common.reference_of(architecture)
        engine = self._engine
        params = engine._params
        n, plen, eps = len(generated), len(prompt), sizes["eps"]
        fed = list(generated[:-1])
        tokens = jnp.asarray([list(prompt) + fed], jnp.int32)
        with engine._lock:
            if engine._slots or engine._inflight is not None or not fed:
                return {"error": "the engine is not idle, or no step to replay"}
            engine_last, row = engine._prefill(
                params, tokens[:, :plen], *engine._adapter_args([-1]))
            # run twice, for its routing here and for its logits below: they
            # are 1.3 GB at 4096 positions, which the reference's pass between
            # the two needs (the same program on the same input both times)
            forward = common.forward_routed(engine._model, arch.ROUTING_COLLECTION)
            routing = arch.program_routing(
                forward(params, tokens[:, :plen])[1], sizes["n_layers"])
            eng_steps, stepped, row = replay(engine, row, fed)
            routing = [jnp.concatenate(pair) for pair in zip(routing, stepped)]
            pooled = pool_row(engine, list(prompt) + fed, row)
            if "error" in pooled:
                return pooled
            slack: list = []
            hidden = arch.hidden_states(
                params, tokens, follow=routing, slack=slack, **sizes)[0]
            ref_last = arch.head(
                hidden[None, -n:], params["final_norm"], params["lm_head"], eps=eps)[0]
            facts = common.reference_facts(ref_last, engine_last[0], generated)
            before = blockwise_facts(
                arch, params, hidden[:plen], forward(params, tokens[:, :plen])[0], eps)
            after = blockwise_facts(arch, params, hidden[plen:], eng_steps, eps)
            slack = jnp.stack(slack)  # (routed layers, seq)
            replayed = jnp.concatenate([engine_last, eng_steps])
            facts.update(
                pooled,
                positions=before["positions"] + after["positions"],
                max_abs_logit_diff=max(
                    before["max_abs_logit_diff"], after["max_abs_logit_diff"]),
                rms_logit_diff=max(before["rms_logit_diff"], after["rms_logit_diff"]),
                prefill_rms_logit_diff=before["rms_logit_diff"],
                decode_rms_logit_diff=after["rms_logit_diff"],
                decode_max_abs_logit_diff=after["max_abs_logit_diff"],
                replayed_tokens_equal=int(jnp.sum(
                    jnp.argmax(replayed, axis=-1) == jnp.asarray(generated, jnp.int32))),
                routing_agree_share=float(jnp.mean(jnp.all(slack == 0, axis=0))),
                routing_slack_max=float(jnp.max(slack)),
                finite=facts["finite"] and before["finite"] and after["finite"])
        return facts


def run(run):
    # ``serve_arch_common.serving`` deploys the class this name is bound to
    # (it takes no other: the fold named in the module docstring ends this)
    accepted = common.ArchReplica
    common.ArchReplica = BlockwiseReplica
    try:
        result = serve_closed_loop_arch.run(run)
    finally:
        common.ArchReplica = accepted
    scopes = result.get("scopes")
    apart = getattr(common.reference_of(
        run.cell["config_file"]["architecture"]), "ATTENTION_SCOPES", ())
    if scopes:
        scopes["attention_scope_s"] = {
            name: scopes["scope_s"].pop(name) for name in apart if name in scopes["scope_s"]}
    return result
