"""``serve_closed_loop_arch_stateful`` for a family whose rows carry state
with no sequence axis *and* whose layers route: the same cluster, request
path, warm-up, window and result, the comparison made the way a request
goes **and under the program's routing**.

Why a kind of its own. ``serve_closed_loop_arch_stateful`` replays two live
rows through the engine's own programs and counts a live row's bytes, but
its reference chooses nothing; with top-k experts a bf16 hidden state swaps
the k-th and (k+1)-th expert against a float32 reference and the logits
jump (``serve_arch_common.routed_facts``). ``serve_closed_loop_arch_blockwise``
follows the program's routing, but reads it from the engine's ``assignments``
counter with one row live (a sum over rows names no row), compares what a
request left in a block pool this family has none of, and never carries a
state from a step to the next with a neighbour coming and going. Neither
file may be edited by the PR that adds the cell; the ``benchmark`` PR that
folds those two into ``serve_arch_common`` folds this one with them
(PERF.md, Open questions).

What is compared (``RoutedStatefulReplica.bench_reference``), for one
request sent alone through the request path. The only programs run are the
engine's own, the ones the window times, but for one whole-prompt pass of
the engine's model that says which experts the *prefill* chose
(``serve_arch_common.forward_routed``, as the blockwise kind).

- ``_prefill`` on the prompt: its last position's logits
  (``prefill_max_abs_logit_diff``), and its row, state leaves and all.
- ``replay``: ``serve_closed_loop_arch_stateful.replay``'s schedule (the
  last slot a fresh row fed prompt + answer a token a step from position 0;
  slot 0 the prefill's row as some other request for ``DECOY_STEPS`` steps,
  free for ``FREE_STEPS``, then given the prefill's row again and fed the
  tokens the request returned), and after every step the experts that step
  chose for each of the two rows, read from the step's own counters
  (``choice``: the engine keeps the last step's choice a row where the
  family holds a share of its experts).
- two passes of the float32 reference (the recurrence a position at a
  time, the share of experts the configuration holds), each **following**
  one row's experts: the fresh row's (every position chosen by a decode
  step), and the request's (the prompt's by the whole-prompt pass, the
  answer's by its decode steps). Every position of both rows is compared,
  ``POSITIONS`` at a time: ``max_abs_logit_diff``, ``rms_logit_diff`` (the
  larger of the two rows'), and ``routing_agree_share`` /
  ``routing_slack_max`` over both passes (how often the program chose the
  reference's own top-k in every layer, and how unfair its choice was
  where not).
- ``token_gap_max``: how far the tokens the request path returned lie under
  the request's reference's best at their positions.
- what no logit shows (``unkept``): the live slot rows hold the bytes
  ``sizes["guaranteed"]`` counts, no weight is narrower than bf16, nothing
  was served from or left in the pool; and, this kind's own, the program's
  expert counters are over ``n_routed_experts`` experts *held* of
  ``sizes["n_routed"]`` routed over, and every live assignment is in them
  or in ``assignments_absent``.

``within`` holds all of it to the mix's ``tolerance`` (``prefill_logit``,
``rms_logit``, ``token_gap``, ``routing_agree_share``, ``routing_slack``,
``unfollowed_logit``: ``serve_arch_common.within``'s names, with their
meaning there).

A reference used with this kind has what the stateful and the blockwise
kinds ask (``hidden_states(follow=, slack=)``, ``head``,
``program_routing``, ``ROUTING_COLLECTION``, ``guaranteed`` and
``n_held`` / ``n_routed`` among its sizes); it may name
``ATTENTION_SCOPES`` as for the blockwise kind.

The load (``_load``) is ``serve_closed_loop``'s closed loop, as many clients
with no think time, but the clients take their requests from **one**
sequence of ``harness/traffic.py`` as they become free, where that kind gives
each client a sequence of its own. The generator holds every group of 20
consecutive requests to the mix's proportions; with a sequence a client, a
client sends 4-5 requests of its own 20 in a window, and what the 32 send
together is hardly held at all: over seven seeds of the cell the answers
completed in a window averaged 608-678 tokens, so a window took 138-150
admissions and their prefills cost a decoding row 2.06-2.23 ms a token,
which was the whole of ``tpot_p50_ms``'s spread (a step alone: 15.01-15.07
ms; PERF.md, finding 36.7). From one sequence every window's ~145
admissions are seven whole groups.

Mix keys: those of ``serve_closed_loop``.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional

from ..harness import traffic
from . import serve_arch_common as common
from . import serve_closed_loop_arch_stateful as stateful
from .serve_closed_loop_arch_stateful import DECOY_STEPS, FREE_STEPS, compared


def replay(engine, row, prompt, fed):
    """``serve_closed_loop_arch_stateful.replay`` with each step's choice of
    experts: returns the fresh row's logits and the request's row's, and
    for each of the two a list, a routed layer an entry, of the experts its
    steps chose (steps, k)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    slots = engine._num_slots
    stepped, taken = slots - 1, 0
    at = {s: jnp.asarray(s, jnp.int32) for s in (stepped, taken)}
    nobody = np.full(slots, -1)
    everything = list(prompt) + list(fed)
    admitted = DECOY_STEPS + FREE_STEPS
    zeroed = jax.tree.map(jnp.zeros_like, engine._expert_counts)
    if engine._cache is None:
        engine._cache = engine._empty_cache(row)
    engine._cache = engine._insert_row(engine._cache, engine._empty_row(), at[stepped])
    engine._cache = engine._insert_row(engine._cache, row, at[taken])
    whole, answered, whole_chose, answered_chose = [], [], [], []
    counted, live = [], []
    for step, token in enumerate(everything):
        if step == admitted:
            engine._cache = engine._insert_row(engine._cache, row, at[taken])
        since = step - admitted
        active = np.zeros(slots, bool)
        last = np.zeros((slots, 1), np.int32)
        active[stepped], last[stepped] = True, token
        if step < DECOY_STEPS:
            active[taken], last[taken] = True, fed[step % len(fed)]
        elif 0 <= since < len(fed):
            active[taken], last[taken] = True, fed[since]
        logits, engine._cache, counts = engine._decode(
            engine._params, engine._cache, jnp.asarray(last),
            *engine._adapter_args(nobody), active=active, expert_counts=zeroed)
        whole.append(logits[stepped])
        whole_chose.append(counts["choice"][:, stepped])
        # live assignments = those counted on held experts + those absent
        counted.append(counts["assignments"].sum(axis=1) + counts["absent"])
        live.append(int(active.sum()))
        if 0 <= since < len(fed):
            answered.append(logits[taken])
            answered_chose.append(counts["choice"][:, taken])

    def by_layer(chose):  # [(layers, k)] * steps -> [(steps, k)] * layers
        chose = jnp.stack(chose)
        return [chose[:, layer] for layer in range(chose.shape[1])]

    k = int(whole_chose[0].shape[-1])
    lost = np.nonzero(np.any(
        np.asarray(jnp.stack(counted)) != np.asarray(live)[:, None] * k, axis=1))[0]
    return (jnp.stack(whole), jnp.stack(answered), by_layer(whole_chose),
            by_layer(answered_chose), lost)


def unkept(engine, guaranteed: dict, held: int, routed: int) -> Optional[str]:
    """``serve_closed_loop_arch_stateful.unkept``, and the share of its
    experts the program says it holds."""
    broken = stateful.unkept(engine, guaranteed)
    if broken:
        return broken
    said = engine.expert_stats()
    if not said:
        return "the program keeps no expert counters"
    everyone = len(said["assignments"][0])
    says = (said.get("experts_held", everyone), said.get("experts_routed", everyone))
    if says != (held, routed):
        return (f"the program counts over {says[0]} experts held of {says[1]} "
                f"routed over, the configuration holds {held} of {routed}")
    return None


_accepted_within = common.within  # (``run`` rebinds the name around a run)


def within(facts: dict, tolerance: dict) -> bool:
    """``RoutedStatefulReplica.bench_reference``'s facts against a mix
    file's ``tolerance``: ``serve_arch_common.within``'s routed form."""
    return "routing_agree_share" in facts and _accepted_within(facts, tolerance)


class RoutedStatefulReplica(common.ArchReplica):
    def bench_reference(self, architecture: str, sizes: dict,
                        prompt: List[int], generated: List[int]) -> dict:
        import jax.numpy as jnp

        arch = common.reference_of(architecture)
        engine = self._engine
        params = engine._params
        sizes = dict(sizes)
        guaranteed = sizes.pop("guaranteed")
        held, routed = sizes.pop("n_held"), sizes.pop("n_routed")
        n, plen, eps = len(generated), len(prompt), sizes["eps"]
        fed = list(generated[:-1])
        tokens = jnp.asarray([list(prompt) + fed], jnp.int32)
        with engine._lock:
            if engine._slots or engine._inflight is not None:
                return {"error": "the engine is not idle"}
            if (engine._num_slots < 2 or len(fed) < 1
                    or DECOY_STEPS + FREE_STEPS > plen):
                return {"error": "no room to replay two rows in"}
            broken = unkept(engine, guaranteed, held, routed)
            if broken:
                return {"error": broken}
            engine_last, row = engine._prefill(
                params, tokens[:, :plen], *engine._adapter_args([-1]))
            prefilled = arch.program_routing(
                common.forward_routed(engine._model, arch.ROUTING_COLLECTION)(
                    params, tokens[:, :plen])[1], sizes["n_layers"])
            whole, answered, whole_chose, answered_chose, lost = replay(
                engine, row, prompt, fed)
            if len(lost):
                return {"error": "live assignments that are neither counted on a "
                                 f"held expert nor absent, at steps {lost[:8].tolist()}"}
            slack_whole: list = []
            slack_own: list = []
            stepped_hidden = arch.hidden_states(
                params, tokens, follow=whole_chose, slack=slack_whole, **sizes)[0]
            own_hidden = arch.hidden_states(
                params, tokens,
                follow=[jnp.concatenate(pair) for pair in zip(prefilled, answered_chose)],
                slack=slack_own, **sizes)[0]
            ref_last = arch.head(
                own_hidden[None, -n:], params["final_norm"], params["lm_head"],
                eps=eps)[0]
            facts = common.reference_facts(ref_last, engine_last[0], generated)
            stepped = compared(arch, params, stepped_hidden, whole, eps)
            decoded = compared(arch, params, own_hidden[plen:], answered, eps)
            slack = jnp.concatenate(
                [jnp.stack(slack_whole), jnp.stack(slack_own)], axis=1)
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
                routing_agree_share=float(jnp.mean(jnp.all(slack == 0, axis=0))),
                routing_slack_max=float(jnp.max(slack)),
                finite=facts["finite"] and stepped["finite"] and decoded["finite"])
        return facts


def _load(clients, mix, vocab, seed, start, end, clock):
    """``serve_closed_loop._load`` with one sequence of requests for all the
    clients: whoever is free takes the next."""
    sequence = traffic.requests(mix, vocab, seed)
    taking = threading.Lock()

    def client():
        while True:
            with taking:
                request = next(sequence)
            if clock() >= end or clients.cut.is_set():
                return
            clients.one(request, due=clock())

    threads = [
        threading.Thread(target=client, daemon=True)
        for _ in range(mix["_clients"])
    ]
    for t in threads:
        t.start()
    time.sleep(max(end - clock(), 0))
    return threads


def run(run):
    mix = run.cell["traffic_file"]
    mix["_clients"] = int(
        mix["clients_per_slot"] * run.cell["config_file"]["serving"]["max_batch_size"])
    # ``serve_arch_common`` deploys the class, and judges by the function,
    # these names are bound to (as the two kinds this one joins do)
    accepted = common.ArchReplica, common.within
    common.ArchReplica, common.within = RoutedStatefulReplica, within
    try:
        result = common.run_serving(run, _load)
    finally:
        common.ArchReplica, common.within = accepted
    scopes = result.get("scopes")
    apart = getattr(common.reference_of(
        run.cell["config_file"]["architecture"]), "ATTENTION_SCOPES", ())
    if scopes:
        scopes["attention_scope_s"] = {
            name: scopes["scope_s"].pop(name) for name in apart if name in scopes["scope_s"]}
    return result
