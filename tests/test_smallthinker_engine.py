"""The SmallThinker-shaped family (``tests/test_smallthinker_family.py``
says what it is and holds the toy) through ``ContinuousBatchingEngine``:
admission, the slot cache with rings in it, the pool's decode step and the
counters. A file of its own so that the family's two halves are two
workers' jobs."""

import jax
import jax.numpy as jnp
import numpy as np

from test_smallthinker_family import (  # noqa: F401  (fixtures by name)
    PUBLISHED, RING, TOL, _diff, _engine, _followed,
    _is_the_references_greedy, _request, _tokens, apply, tiny,
)

from benchmarks.harness import flops_stmoe
from ray_tpu import models


def test_engine_tokens_through_the_slot_cache(tiny):
    """Four requests on three slots, one shorter than the ring, one as long
    and two longer, through admission, the slot cache (row insert, the
    pool's decode step one ahead), retirement and a slot freed and taken
    again: each gets the reference's own greedy tokens."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompts = [_tokens((n,), seed=10 + n) for n in (9, 24, 33, 41)]
    results = engine.generate(
        [_request(p, n) for p, n in zip(prompts, (14, 6, 14, 9))])
    for prompt, result, n in zip(prompts, results, (14, 6, 14, 9)):
        assert len(result.token_ids) == n
        assert _is_the_references_greedy(params, prompt, result.token_ids)
    kinds = jax.tree.leaves(models.cache_kinds(engine._cache))
    assert kinds.count("window") == 3 * 2 and kinds.count("sequence") == 2
    stats = engine._kv.stats()
    assert stats["prefix_reuse"] is False and stats["hits"] == 0
    engine.close()


def test_engine_steps_match_the_reference_logits_two_rows_live(tiny, apply):
    """The engine's own jitted prefill, row insert and decode at the pool's
    shape, two rows live, one past the ring and one younger than it, one of
    them in a slot another row left; every step's logits under the step's
    own choice of experts (the counters' ``choice``, kept because the
    configuration names the experts held as a range): what the benchmark's
    check does at the cell's size."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompt = _tokens((29,), seed=21)
    young = _tokens((40,), seed=22)
    solo = _engine(cfg, params)
    tokens = solo.generate([_request(prompt, 12)])[0].token_ids
    solo.close()
    logits, row = engine._prefill(params, jnp.asarray([prompt], jnp.int32))
    other = engine._prefill(params, jnp.asarray([young[:15]], jnp.int32))[1]
    cache = engine._empty_cache(row)
    cache = engine._insert_row(cache, other, jnp.asarray(0, jnp.int32))
    cache = engine._insert_row(cache, row, jnp.asarray(2, jnp.int32))
    zeroed = jax.tree.map(jnp.zeros_like, engine._expert_counts)
    assert zeroed["assignments"].shape == (4, 16)  # layers x held
    # slot 2: someone else for 3 steps, then free for 2, then the request;
    # slot 0: a row 15 positions old fed its prompt's next tokens, through
    # the ring's wrap at 24
    got, chose, beside, beside_chose = [], [], [], []
    for step in range(5 + len(tokens) - 1):
        active = np.array([True, False, step < 3 or step >= 5])
        last = np.full((3, 1), 7, np.int32)
        last[0] = int(young[15 + step])
        if step == 5:
            cache = engine._insert_row(cache, row, jnp.asarray(2, jnp.int32))
        if step >= 5:
            last[2] = tokens[step - 5]
        out, cache, counts = engine._decode(
            params, cache, jnp.asarray(last), active=active,
            expert_counts=zeroed)
        assert bool(jnp.all(jnp.isfinite(out)))
        live = int(active.sum()) * cfg.experts_per_token
        assert [int(n) for n in counts["assignments"].sum(1)] == [live] * 4
        assert not counts["absent"].any()  # every expert is held here
        beside.append(out[0])
        beside_chose.append(counts["choice"][:, 0])
        if step >= 5:
            got.append(out[2])
            chose.append(counts["choice"][:, 2])
    chose, beside_chose = jnp.stack(chose), jnp.stack(beside_chose)
    fed = jnp.asarray([list(map(int, prompt)) + tokens[:-1]], jnp.int32)
    prefilled = apply(params, fed[:, :29])[2]
    follow = [jnp.concatenate([prefilled[layer], chose[:, layer]])
              for layer in range(4)]
    want = _followed(params, fed, follow)[0]
    assert _diff(jnp.stack(got), want[29:]) < TOL
    assert _diff(logits[0], want[28]) < TOL
    # the row that was younger than the ring, through its wrap at 24
    steps = len(beside)
    fed = jnp.asarray([list(map(int, young[:15 + steps]))], jnp.int32)
    prefilled = apply(params, fed[:, :15])[2]
    follow = [jnp.concatenate([prefilled[layer], beside_chose[:, layer]])
              for layer in range(4)]
    want = _followed(params, fed, follow)[0]
    assert _diff(jnp.stack(beside), want[15:]) < TOL
    engine.close()


def test_the_counters_count_rings_rows_and_every_expert(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    assert engine.window_bytes_per_row() is None
    engine.generate([_request(_tokens((9,)), 6), _request(_tokens((35,), 4), 6)])
    # one full layer of four: K and V of 1 head x 16 x 4 B
    assert engine.cache_bytes_per_token() == 2 * 1 * 16 * 4
    # three rings of 24 positions of the same
    assert engine.window_bytes_per_row() == 3 * RING * 2 * 1 * 16 * 4
    assert engine.state_bytes_per_row() == 0
    assert flops_stmoe.kv_bytes_per_token(PUBLISHED, 4) == 128
    assert flops_stmoe.window_bytes_per_row(PUBLISHED, 4) == 3 * RING * 128
    stats = engine.expert_stats()
    assert (stats["experts_routed"], stats["experts_held"]) == (16, 16)
    assert np.asarray(stats["assignments"]).shape == (4, 16)
    live = np.asarray(stats["assignments"]).sum(1)
    assert len(set(live)) == 1 and live[0] % cfg.experts_per_token == 0
    assert stats["assignments_absent"] == [0] * 4
    # a step's live rows touch at most their choices, at least one's
    touched = np.asarray(stats["touched"]) / stats["decode_steps"]
    assert (touched >= cfg.experts_per_token).all() and (touched <= 8).all()
    engine.close()
