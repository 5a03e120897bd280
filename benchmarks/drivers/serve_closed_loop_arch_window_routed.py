"""``serve_closed_loop_arch_stateful_routed`` for a family whose rows keep a
*ring* of a window layer's last positions beside full-length rows, whose
layers route, and whose prompts are too long to feed a token a step: the
same cluster, request path, warm-up, one-sequence load, window and result,
the comparison made the way a request goes and under the program's routing.

Why a kind of its own. ``serve_closed_loop_arch_stateful_routed`` replays
two live rows with no pool and follows each row's ``choice``, which is what
this family needs, but it feeds a fresh row the whole prompt a token a step
from position 0: 8192 decode steps of ~10 ms are 80 s a row of set-up. And
it holds a live row to ``state_bytes_per_row``, which this family has none
of. ``serve_closed_loop_arch_blockwise`` compares a long prompt's positions
in blocks, but with one row live, and against a block pool this family has
none of. Neither file may be edited by the PR that adds the cell; the
``benchmark`` PR that folds those kinds into ``serve_arch_common`` folds
this one with them (PERF.md, Open questions).

What is compared (``WindowRoutedReplica.bench_reference``), for one request
sent alone through the request path, by the engine's own timed programs
(``_prefill``, ``_insert_row``, ``_decode`` at the pool's shape), against
the float32 reference (published form, a banded mask for a window layer, no
cache) **following** the experts those programs chose:

- a prompt of at most ``sizes["step_from_zero"]`` tokens (256): the stateful-routed kind's
  schedule whole (``replay`` there): the last slot a fresh row fed prompt +
  answer a token a step from position 0, so every position goes through
  the ring's write, its wrap and the decode kernel; slot 0 the prefill's
  row as another request, then free, then the request's own.
- a longer prompt (``replay_behind``): slot 0 as above (the prefill's row
  as another request for ``DECOY_STEPS`` steps, free for ``FREE_STEPS``,
  then given the prefill's row again by ``_insert_row`` and fed the tokens
  the request returned); the last slot holds a *shorter* row all the
  while, a prefill of the prompt's first 256 tokens fed the
  prompt's next ones, so that two rows of different lengths share every
  step (a full layer reads 8192 positions of one and ~300 of the other, a
  window layer 128 of each). The prompt's own positions are compared from
  one whole-prompt pass of the engine's model (``forward_routed``: the
  published form under the band, which also says which experts the prefill
  chose), in blocks of positions.
- ``max_abs_logit_diff`` the largest difference anywhere, ``rms_logit_diff``
  the largest of the parts' rms, ``routing_agree_share`` /
  ``routing_slack_max`` over every followed position, ``token_gap_max`` how
  far the tokens the request path returned lie under the reference's best.
- what no logit shows (``unkept``): a live row holds
  ``window_bytes_per_row`` of rings and ``kv_bytes_per_token`` a position
  as the configuration's precision takes (``sizes["guaranteed"]``) and no
  state besides, no weight is narrower than bf16, nothing was served from
  or left in the pool, the expert counters are over the experts *held* of
  those routed over, and every live assignment of a replay is counted on a
  held expert or as absent.

``within`` is the stateful-routed kind's. A reference used with this kind
has what that kind asks, ``guaranteed`` with ``window_bytes_per_row`` and
``kv_bytes_per_token``.

Mix keys: those of ``serve_closed_loop``.
"""

from __future__ import annotations

from typing import List, Optional

from . import serve_arch_common as common
from . import serve_closed_loop_arch_stateful_routed as routed
from .serve_closed_loop_arch_blockwise import blockwise_facts
from .serve_closed_loop_arch_stateful import DECOY_STEPS, FREE_STEPS, compared


def replay_behind(engine, row, short_row, begun, prompt, fed):
    """The engine's decode program over its own pool, two rows of different
    lengths live (module docstring): ``short_row`` a prefill's of
    ``prompt[:begun]``. Returns the logits of ``row`` (a prefill's of
    ``prompt``) fed ``fed`` after its slot was another row's
    and then free, the experts those steps chose, a routed layer an entry
    (steps, k), and the steps whose live assignments were not all counted.
    For an idle engine, under its lock: every row is free again afterwards."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    slots = engine._num_slots
    beside, taken = slots - 1, 0
    at = {s: jnp.asarray(s, jnp.int32) for s in (beside, taken)}
    nobody = np.full(slots, -1)
    admitted = DECOY_STEPS + FREE_STEPS
    zeroed = jax.tree.map(jnp.zeros_like, engine._expert_counts)
    if engine._cache is None:
        engine._cache = engine._empty_cache(row)
    engine._cache = engine._insert_row(engine._cache, short_row, at[beside])
    engine._cache = engine._insert_row(engine._cache, row, at[taken])
    answered, chose, counted, live = [], [], [], []
    for step in range(admitted + len(fed)):
        if step == admitted:
            engine._cache = engine._insert_row(engine._cache, row, at[taken])
        since = step - admitted
        active = np.zeros(slots, bool)
        last = np.zeros((slots, 1), np.int32)
        active[beside], last[beside] = True, prompt[begun + step]
        if step < DECOY_STEPS:
            active[taken], last[taken] = True, fed[step % len(fed)]
        elif since >= 0:
            active[taken], last[taken] = True, fed[since]
        logits, engine._cache, counts = engine._decode(
            engine._params, engine._cache, jnp.asarray(last),
            *engine._adapter_args(nobody), active=active, expert_counts=zeroed)
        counted.append(counts["assignments"].sum(axis=1) + counts["absent"])
        live.append(int(active.sum()))
        if since >= 0:
            answered.append(logits[taken])
            chose.append(counts["choice"][:, taken])
    chose = jnp.stack(chose)  # (steps, routed layers, k)
    k = int(chose.shape[-1])
    lost = np.nonzero(np.any(
        np.asarray(jnp.stack(counted)) != np.asarray(live)[:, None] * k, axis=1))[0]
    return (jnp.stack(answered),
            [chose[:, layer] for layer in range(chose.shape[1])], lost)


def unkept(engine, guaranteed: dict, held: int, routed_over: int) -> Optional[str]:
    """The first of the configuration's guarantees that the live engine
    does not keep and no logit would show (module docstring); None if it
    keeps them all."""
    import jax

    pooled = engine._kv.stats()
    if pooled["hits"] or pooled["blocks_in_use"] or engine._kv.ready:
        return ("a family with a ring in its rows was served from, or left "
                f"blocks in, the pool: {pooled}")
    holds = {"window_bytes_per_row": engine.window_bytes_per_row(),
             "kv_bytes_per_token": engine.cache_bytes_per_token()}
    if holds != guaranteed or engine.state_bytes_per_row():
        return (f"a slot row holds {holds} and "
                f"{engine.state_bytes_per_row()} B of state, and bf16 rings "
                f"and latent rows and nothing wider are {guaranteed}")
    narrow = {str(leaf.dtype) for leaf in jax.tree.leaves(engine._params)
              if leaf.dtype.itemsize < 2}
    if narrow:
        return f"weights narrower than bf16: {sorted(narrow)}"
    said = engine.expert_stats()
    if not said:
        return "the program keeps no expert counters"
    everyone = len(said["assignments"][0])
    says = (said.get("experts_held", everyone), said.get("experts_routed", everyone))
    if says != (held, routed_over):
        return (f"the program counts over {says[0]} experts held of {says[1]} "
                f"routed over, the configuration holds {held} of {routed_over}")
    return None


def _joined(parts: List[dict]) -> dict:
    return {
        "positions": sum(p["positions"] for p in parts),
        "max_abs_logit_diff": max(p["max_abs_logit_diff"] for p in parts),
        "rms_logit_diff": max(p["rms_logit_diff"] for p in parts),
        "finite": all(p["finite"] for p in parts),
    }


class WindowRoutedReplica(common.ArchReplica):
    def bench_reference(self, architecture: str, sizes: dict,
                        prompt: List[int], generated: List[int]) -> dict:
        import jax.numpy as jnp

        arch = common.reference_of(architecture)
        engine = self._engine
        params = engine._params
        sizes = dict(sizes)
        guaranteed = sizes.pop("guaranteed")
        held, routed_over = sizes.pop("n_held"), sizes.pop("n_routed")
        step_from_zero = sizes.pop("step_from_zero")
        n, plen, eps = len(generated), len(prompt), sizes["eps"]
        fed = list(generated[:-1])
        tokens = jnp.asarray([list(prompt) + fed], jnp.int32)
        from_zero = plen <= step_from_zero
        with engine._lock:
            if engine._slots or engine._inflight is not None:
                return {"error": "the engine is not idle"}
            if (engine._num_slots < 2 or len(fed) < 1
                    or DECOY_STEPS + FREE_STEPS > plen or (
                        not from_zero and plen < step_from_zero
                        + DECOY_STEPS + FREE_STEPS + len(fed))):
                return {"error": "no room to replay two rows in"}
            broken = unkept(engine, guaranteed, held, routed_over)
            if broken:
                return {"error": broken}
            engine_last, row = engine._prefill(
                params, tokens[:, :plen], *engine._adapter_args([-1]))
            forward = common.forward_routed(engine._model, arch.ROUTING_COLLECTION)
            prefilled = arch.program_routing(
                forward(params, tokens[:, :plen])[1], sizes["n_layers"])
            slack: list = []
            parts = []
            if from_zero:
                whole, answered, whole_chose, answered_chose, lost = routed.replay(
                    engine, row, prompt, fed)
                slack_whole: list = []
                stepped_hidden = arch.hidden_states(
                    params, tokens, follow=whole_chose, slack=slack_whole, **sizes)[0]
                parts.append(compared(arch, params, stepped_hidden, whole, eps))
                slack.extend(slack_whole)
            else:
                short_row = engine._prefill(
                    params, tokens[:, :step_from_zero], *engine._adapter_args([-1]))[1]
                answered, answered_chose, lost = replay_behind(
                    engine, row, short_row, step_from_zero, prompt, fed)
            if len(lost):
                return {"error": "live assignments that are neither counted on a "
                                 f"held expert nor absent, at steps {lost[:8].tolist()}"}
            slack_own: list = []
            own_hidden = arch.hidden_states(
                params, tokens,
                follow=[jnp.concatenate(pair) for pair in zip(prefilled, answered_chose)],
                slack=slack_own, **sizes)[0]
            ref_last = arch.head(
                own_hidden[None, -n:], params["final_norm"], params["lm_head"],
                eps=eps)[0]
            facts = common.reference_facts(ref_last, engine_last[0], generated)
            if not from_zero:
                # the prompt's own positions, from the whole-prompt pass whose
                # routing the reference followed (run again: its logits are
                # 0.45 GB at 8192 positions, which the reference's pass needs)
                parts.append(blockwise_facts(
                    arch, params, own_hidden[:plen],
                    forward(params, tokens[:, :plen])[0], eps))
            decoded = compared(arch, params, own_hidden[plen:], answered, eps)
            parts.append(decoded)
            both = _joined(parts)
            slack = jnp.concatenate(
                [jnp.stack(s) for s in (slack, slack_own) if s], axis=1)
            replayed = jnp.concatenate([engine_last, answered])
            facts.update(
                both,
                from_zero=from_zero,
                decode_rms_logit_diff=decoded["rms_logit_diff"],
                decode_max_abs_logit_diff=decoded["max_abs_logit_diff"],
                first_part_rms_logit_diff=parts[0]["rms_logit_diff"],
                first_part_max_abs_logit_diff=parts[0]["max_abs_logit_diff"],
                replayed_tokens_equal=int(jnp.sum(
                    jnp.argmax(replayed, axis=-1) == jnp.asarray(generated, jnp.int32))),
                routing_agree_share=float(jnp.mean(jnp.all(slack == 0, axis=0))),
                routing_slack_max=float(jnp.max(slack)),
                finite=facts["finite"] and both["finite"])
        return facts


def run(run):
    mix = run.cell["traffic_file"]
    mix["_clients"] = int(
        mix["clients_per_slot"] * run.cell["config_file"]["serving"]["max_batch_size"])
    # ``serve_arch_common`` deploys the class, and judges by the function,
    # these names are bound to (as the kinds this one joins do)
    accepted = common.ArchReplica, common.within
    common.ArchReplica, common.within = WindowRoutedReplica, routed.within
    try:
        result = common.run_serving(run, routed._load)
    finally:
        common.ArchReplica, common.within = accepted
    scopes = result.get("scopes")
    apart = getattr(common.reference_of(
        run.cell["config_file"]["architecture"]), "ATTENTION_SCOPES", ())
    if scopes:
        scopes["attention_scope_s"] = {
            name: scopes["scope_s"].pop(name) for name in apart if name in scopes["scope_s"]}
    return result
