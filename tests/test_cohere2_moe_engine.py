"""The Cohere2-MoE-shaped family (``tests/test_cohere2_moe_family.py`` says
what it is and holds the toy) through ``ContinuousBatchingEngine``: admission,
the slot cache with rings in it, the pool's decode step and the counters. A
file of its own so that the family's two halves are two workers' jobs."""

import jax
import jax.numpy as jnp
import numpy as np

from test_cohere2_moe_family import (  # noqa: F401  (fixtures by name)
    PUBLISHED, RING, TOL, _diff, _engine, _followed,
    _is_the_references_greedy, _request, _tokens, apply, tiny,
)

from benchmarks.harness import flops_c2moe
from ray_tpu import models


def test_engine_tokens_through_the_slot_cache(tiny):
    """Three requests, one shorter than the ring and two longer, through
    admission, the slot cache (row insert, the pool's decode step one
    ahead) and retirement: each gets the reference's own greedy tokens."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompts = [_tokens((n,), seed=10 + n) for n in (9, 16, 21)]
    results = engine.generate([_request(p, 14) for p in prompts])
    for prompt, result in zip(prompts, results):
        assert len(result.token_ids) == 14
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
    own choice of experts (the counters' ``choice``): what the benchmark's
    check does at the cell's size."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompt = _tokens((17,), seed=21)
    young = _tokens((24,), seed=22)
    solo = _engine(cfg, params)
    tokens = solo.generate([_request(prompt, 12)])[0].token_ids
    solo.close()
    logits, row = engine._prefill(params, jnp.asarray([prompt], jnp.int32))
    other = engine._prefill(params, jnp.asarray([young[:5]], jnp.int32))[1]
    cache = engine._empty_cache(row)
    cache = engine._insert_row(cache, other, jnp.asarray(0, jnp.int32))
    cache = engine._insert_row(cache, row, jnp.asarray(2, jnp.int32))
    zeroed = jax.tree.map(jnp.zeros_like, engine._expert_counts)
    assert zeroed["assignments"].shape == (4, 4)  # layers x held
    # slot 2: someone else for 3 steps, then free for 2, then the request;
    # slot 0: a row 5 positions old fed its prompt's next tokens
    got, chose, beside, beside_chose = [], [], [], []
    for step in range(5 + len(tokens) - 1):
        active = np.array([True, False, step < 3 or step >= 5])
        last = np.full((3, 1), 7, np.int32)
        last[0] = int(young[5 + step])
        if step == 5:
            cache = engine._insert_row(cache, row, jnp.asarray(2, jnp.int32))
        if step >= 5:
            last[2] = tokens[step - 5]
        out, cache, counts = engine._decode(
            params, cache, jnp.asarray(last), active=active,
            expert_counts=zeroed)
        assert bool(jnp.all(jnp.isfinite(out)))
        live = int(active.sum()) * cfg.experts_per_token
        assert [int(n) for n in counts["assignments"].sum(1) + counts["absent"]
                ] == [live] * 4
        beside.append(out[0])
        beside_chose.append(counts["choice"][:, 0])
        if step >= 5:
            got.append(out[2])
            chose.append(counts["choice"][:, 2])
    chose, beside_chose = jnp.stack(chose), jnp.stack(beside_chose)
    fed = jnp.asarray([list(map(int, prompt)) + tokens[:-1]], jnp.int32)
    prefilled = apply(params, fed[:, :17])[2]
    follow = [jnp.concatenate([prefilled[layer], chose[:, layer]])
              for layer in range(4)]
    want = _followed(params, fed, follow)[0]
    assert _diff(jnp.stack(got), want[17:]) < TOL
    assert _diff(logits[0], want[16]) < TOL
    # the row that was younger than the ring, through its wrap at 12
    steps = len(beside)
    fed = jnp.asarray([list(map(int, young[:5 + steps]))], jnp.int32)
    prefilled = apply(params, fed[:, :5])[2]
    follow = [jnp.concatenate([prefilled[layer], beside_chose[:, layer]])
              for layer in range(4)]
    want = _followed(params, fed, follow)[0]
    assert _diff(jnp.stack(beside), want[5:]) < TOL
    engine.close()


def test_the_counters_count_rings_rows_and_the_experts_held(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    assert engine.window_bytes_per_row() is None
    engine.generate([_request(_tokens((9,)), 6), _request(_tokens((15,), 4), 6)])
    # one full layer of four: K and V of 2 heads x 16 x 4 B
    assert engine.cache_bytes_per_token() == 2 * 2 * 16 * 4
    # three rings of 12 positions of the same
    assert engine.window_bytes_per_row() == 3 * RING * 2 * 2 * 16 * 4
    assert engine.state_bytes_per_row() == 0
    assert flops_c2moe.kv_bytes_per_token(PUBLISHED, 4) == 256
    assert flops_c2moe.window_bytes_per_row(PUBLISHED, 4) == 3 * RING * 256
    stats = engine.expert_stats()
    assert (stats["experts_routed"], stats["experts_held"]) == (16, 4)
    assert np.asarray(stats["assignments"]).shape == (4, 4)
    live = (np.asarray(stats["assignments"]).sum(1)
            + np.asarray(stats["assignments_absent"]))
    assert len(set(live)) == 1 and live[0] % cfg.experts_per_token == 0
    assert all(0 < gone < total for gone, total
               in zip(stats["assignments_absent"], live))
    engine.close()


