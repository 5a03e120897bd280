"""``serve_closed_loop_arch`` for a family whose rows carry state with no
sequence axis (a recurrent layer's): the same cluster, request path,
warm-up, window and result, with the comparison against the plain reference
made the way a request goes.

Why a kind of its own. ``serve_arch_common.ArchReplica.bench_reference``
compares one whole-sequence pass of the program's model, which never runs
the step that carries a state from one token to the next.
``serve_closed_loop_arch_blockwise`` does go through the engine's programs,
but reads each step's experts from the program's counters and compares the
blocks a request left in the pool; this family routes nothing and commits
nothing (no prefix reuse: ``ray_tpu.models``). Neither file may be edited
by the PR that adds the cell; the ``benchmark`` PR that folds the blockwise
kind into ``serve_arch_common`` folds this one with it (PERF.md, Open
questions).

What is compared (``StatefulReplica.bench_reference``), for one request
sent alone through the request path. The only programs run are the engine's
own, the ones the window times: its jitted ``_prefill``, ``_insert_row``
and ``_decode`` at the pool's shape.

- ``_prefill`` on the prompt: its last position's logits
  (``prefill_max_abs_logit_diff``), and its row, state leaves and all.
- ``replay``: the pool stepped ``len(prompt) + len(answer) - 1`` times with
  *two rows live and one of them admitted into a slot another row left*,
  because a free row's state is zeroed by the step's ``active`` mask and a
  mask that zeroes, or keeps, the wrong row's state shows only when rows
  come and go beside a live one:

  - the last slot holds a fresh row (``_empty_row``) fed the prompt and
    then the answer, a token a step, from position 0: every position of
    prompt + answer through the decode program, the state carried by the
    program itself from zero (``stepped_*``);
  - slot 0 first holds the prefill's row as some other request (fed the
    answer's tokens for ``DECOY_STEPS`` steps), is then free for
    ``FREE_STEPS`` steps, and is then given the prefill's row again by
    ``_insert_row`` and fed the tokens the request returned: the program
    and the input the request's steps had (``decode_*``: a state or a
    convolution tail lost, rounded or left over between prefill and decode
    moves only these).

- every one of those positions against the float32 reference (the
  recurrence a position at a time), ``POSITIONS`` at a time because the head
  is 261120 columns wide: ``max_abs_logit_diff`` the largest difference
  anywhere, ``rms_logit_diff`` the larger of the two rows'.
- ``token_gap_max``: how far the tokens the request path returned lie under
  the reference's best at their positions.
- *what the configuration guarantees and no logit shows* (``unkept``): the
  live slot rows hold the bytes a row of float32 state, bf16 convolution
  tail and bf16 K/V takes (``sizes["guaranteed"]``, counted by the
  reference from the published keys), no weight is narrower than bf16, and
  nothing was served from, or left in, the block pool. A breach is an
  ``error``: at these lengths a bf16 state or fp8 K/V moves the logits by
  less than bf16 activations do (mix file, ``tolerance_why``), so a
  tolerance cannot hold them and a count of bytes does.

``within`` holds every one of these to the mix's ``tolerance``
(``prefill_logit`` the largest difference, ``rms_logit``, ``token_gap``).

A reference used with this kind has ``hidden_states`` and ``head(...,
multiplier=)`` beside what ``serve_arch_common`` lists, and
``lm_head_multiplier`` and ``guaranteed`` (``state_bytes_per_row``,
``kv_bytes_per_token``) among its sizes.

Mix keys: those of ``serve_closed_loop``.
"""

from __future__ import annotations

import functools
import types
from typing import List, Optional

from . import serve_arch_common as common
from . import serve_closed_loop_arch
from .serve_closed_loop_arch_blockwise import blockwise_facts

POSITIONS = 256
DECOY_STEPS, FREE_STEPS = 8, 2


def replay(engine, row, prompt, fed):
    """The engine's own decode program over its own pool of slot rows, two
    rows live (module docstring). Returns the logits of the fresh row fed
    ``prompt + fed`` (a position each) and of ``row`` (a prefill's of
    ``prompt``) fed ``fed`` after its slot was another row's and then free.
    For an idle engine, under its lock: every row is free again afterwards."""
    import jax.numpy as jnp
    import numpy as np

    slots = engine._num_slots
    stepped, taken = slots - 1, 0
    at = {s: jnp.asarray(s, jnp.int32) for s in (stepped, taken)}
    nobody = np.full(slots, -1)
    everything = list(prompt) + list(fed)
    admitted = DECOY_STEPS + FREE_STEPS  # the step the request's row enters at
    if engine._cache is None:
        engine._cache = engine._empty_cache(row)
    engine._cache = engine._insert_row(engine._cache, engine._empty_row(), at[stepped])
    engine._cache = engine._insert_row(engine._cache, row, at[taken])
    whole, answered = [], []
    for step, token in enumerate(everything):
        if step == admitted:
            engine._cache = engine._insert_row(engine._cache, row, at[taken])
        since = step - admitted
        active = np.zeros(slots, bool)
        last = np.zeros((slots, 1), np.int32)
        active[stepped], last[stepped] = True, token
        if step < DECOY_STEPS:  # some other request's steps
            active[taken], last[taken] = True, fed[step % len(fed)]
        elif 0 <= since < len(fed):  # the request's own
            active[taken], last[taken] = True, fed[since]
        logits, engine._cache = engine._decode(
            engine._params, engine._cache, jnp.asarray(last),
            *engine._adapter_args(nobody), active=active)
        whole.append(logits[stepped])
        if 0 <= since < len(fed):
            answered.append(logits[taken])
    return jnp.stack(whole), jnp.stack(answered)


def compared(headed, params, hidden, logits, eps: float) -> dict:
    """``blockwise_facts`` over ``POSITIONS`` positions at a time, as one."""
    parts = [blockwise_facts(headed, params, hidden[start:start + POSITIONS],
                             logits[start:start + POSITIONS], eps)
             for start in range(0, hidden.shape[0], POSITIONS)]
    positions = sum(p["positions"] for p in parts)
    return {
        "positions": positions,
        "max_abs_logit_diff": max(p["max_abs_logit_diff"] for p in parts),
        "rms_logit_diff": (sum(p["rms_logit_diff"] ** 2 * p["positions"]
                               for p in parts) / positions) ** 0.5,
        "finite": all(p["finite"] for p in parts),
    }


def unkept(engine, guaranteed: dict) -> Optional[str]:
    """The first of the configuration's guarantees that the live engine
    does not keep and no logit would show (module docstring); None if it
    keeps them all."""
    import jax

    pooled = engine._kv.stats()
    if pooled["hits"] or pooled["blocks_in_use"] or engine._kv.ready:
        return ("a family with per-row state was served from, or left "
                f"blocks in, the pool: {pooled}")
    held = {"state_bytes_per_row": engine.state_bytes_per_row(),
            "kv_bytes_per_token": engine.cache_bytes_per_token()}
    if held != guaranteed:
        return (f"a slot row holds {held}, and float32 state with bf16 "
                f"convolution tail and K/V is {guaranteed}")
    narrow = {str(leaf.dtype) for leaf in jax.tree.leaves(engine._params)
              if leaf.dtype.itemsize < 2}
    if narrow:
        return f"weights narrower than bf16: {sorted(narrow)}"
    return None


def within(facts: dict, tolerance: dict) -> bool:
    """``StatefulReplica.bench_reference``'s facts against a mix file's
    ``tolerance``."""
    if "error" in facts or not facts["finite"]:
        return False
    return (
        facts["max_abs_logit_diff"] <= tolerance["prefill_logit"]
        and facts["prefill_max_abs_logit_diff"] <= tolerance["prefill_logit"]
        and facts["rms_logit_diff"] <= tolerance["rms_logit"]
        and facts["token_gap_max"] <= tolerance["token_gap"]
    )


class StatefulReplica(common.ArchReplica):
    def bench_reference(self, architecture: str, sizes: dict,
                        prompt: List[int], generated: List[int]) -> dict:
        """``ArchReplica.bench_reference``'s facts with every position
        through the engine's slot rows and decode program, the comparison
        in blocks, and the guarantees no logit shows (module docstring)."""
        import jax.numpy as jnp

        arch = common.reference_of(architecture)
        engine = self._engine
        params = engine._params
        sizes = dict(sizes)
        guaranteed = sizes.pop("guaranteed")
        n, plen, eps = len(generated), len(prompt), sizes["eps"]
        fed = list(generated[:-1])
        tokens = jnp.asarray([list(prompt) + fed], jnp.int32)
        # the reference's head as ``blockwise_facts`` calls it
        headed = types.SimpleNamespace(head=functools.partial(
            arch.head, multiplier=sizes["lm_head_multiplier"]))
        with engine._lock:
            if engine._slots or engine._inflight is not None:
                return {"error": "the engine is not idle"}
            if (engine._num_slots < 2 or len(fed) < 1
                    or DECOY_STEPS + FREE_STEPS > plen):
                return {"error": "no room to replay two rows in"}
            # on the rows as the request path left them
            broken = unkept(engine, guaranteed)
            if broken:
                return {"error": broken}
            engine_last, row = engine._prefill(
                params, tokens[:, :plen], *engine._adapter_args([-1]))
            whole, answered = replay(engine, row, prompt, fed)
            hidden = arch.hidden_states(params, tokens, **sizes)[0]
            ref_last = headed.head(
                hidden[None, -n:], params["final_norm"], params["lm_head"],
                eps=eps)[0]
            facts = common.reference_facts(ref_last, engine_last[0], generated)
            stepped = compared(headed, params, hidden, whole, eps)
            decoded = compared(headed, params, hidden[plen:], answered, eps)
            replayed = jnp.concatenate([engine_last, answered])
            facts.update(
                positions=stepped["positions"] + decoded["positions"],
                max_abs_logit_diff=max(
                    stepped["max_abs_logit_diff"], decoded["max_abs_logit_diff"]),
                rms_logit_diff=max(
                    stepped["rms_logit_diff"], decoded["rms_logit_diff"]),
                stepped_rms_logit_diff=stepped["rms_logit_diff"],
                stepped_max_abs_logit_diff=stepped["max_abs_logit_diff"],
                decode_rms_logit_diff=decoded["rms_logit_diff"],
                decode_max_abs_logit_diff=decoded["max_abs_logit_diff"],
                replayed_tokens_equal=int(jnp.sum(
                    jnp.argmax(replayed, axis=-1) == jnp.asarray(generated, jnp.int32))),
                finite=facts["finite"] and stepped["finite"] and decoded["finite"])
        return facts


def run(run):
    # ``serve_arch_common`` deploys the class, and judges by the function,
    # these names are bound to (as the blockwise kind does: the fold named
    # in the module docstring ends both)
    accepted = common.ArchReplica, common.within
    common.ArchReplica, common.within = StatefulReplica, within
    try:
        return serve_closed_loop_arch.run(run)
    finally:
        common.ArchReplica, common.within = accepted
