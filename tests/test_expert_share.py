"""An expert layer that is told which experts it holds
(``MoEConfig.experts_held``): the router keeps its width and its experts a
token, the kept weights are normalised over the experts chosen wherever
they live, and the layer gives the part of the result its own experts give.

``model-configs`` section 4's test ties the share to the model: the parts
that all the shares give, with what every chip computes alike (the shared
expert) counted once, add up to what the uncut layer gives. With all the
experts held the layer is the one it was, bit for bit, and the work of the
grouped kernel follows the held assignments.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu import models  # noqa: E402
from ray_tpu.llm import LLMConfig  # noqa: E402
from ray_tpu.llm.engine import _count_experts, _new_expert_counts  # noqa: E402
from ray_tpu.models.deepseek import SwiGLU  # noqa: E402
from ray_tpu.models.moe import MoEConfig, MoEFFN  # noqa: E402
from ray_tpu.models.solar_open2 import SolarOpen2Config  # noqa: E402
from ray_tpu.ops import moe_experts as kernel  # noqa: E402
from ray_tpu.parallel import expert  # noqa: E402
from ray_tpu.parallel.sharding import unbox_params  # noqa: E402

LAYER = dict(dim=64, intermediate=32, n_experts=32, experts_per_token=4,
             dropless=True, router_scoring="sigmoid", router_bias=True,
             norm_topk_prob=True, dtype=jnp.float32, param_dtype=jnp.float32)


def _layer(held=None, seed=0, **changed):
    cfg = MoEConfig(**dict(LAYER, experts_held=held, **changed))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 9, cfg.dim))
    params = unbox_params(
        MoEFFN(cfg).init(jax.random.PRNGKey(seed), x)["params"])
    return cfg, params, x


def _share(params, first, stop):
    """The uncut layer's parameters cut to the experts ``first .. stop-1``:
    the router and its bias whole."""
    return {name: leaf[first:stop] if name.startswith("w_") else leaf
            for name, leaf in params.items()}


def test_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    cfg, params, x = _layer()
    params["router_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(3), params["router_bias"].shape)
    whole = MoEFFN(cfg).apply({"params": params}, x)
    shared_cfg = SolarOpen2Config.tiny(dtype=jnp.float32)
    shared = SwiGLU(shared_cfg, 32)
    shared_params = shared.init(jax.random.PRNGKey(5), x)["params"]
    alike = shared.apply({"params": shared_params}, x)
    parts = []
    for chip in range(8):
        held = (4 * chip, 4 * chip + 4)
        part_cfg = MoEConfig(**dict(LAYER, experts_held=held))
        part = MoEFFN(part_cfg).apply({"params": _share(params, *held)}, x)
        assert MoEFFN(part_cfg).init(jax.random.PRNGKey(0), x)["params"][
            "w_gate"].value.shape == (4, 64, 32)
        parts.append(part)
    assert float(jnp.max(jnp.abs(sum(parts) + alike - (whole + alike)))) < 1e-5
    # a share is a part, not the whole: no chip's part is nothing or all
    assert all(1e-3 < float(jnp.max(jnp.abs(p))) for p in parts)
    assert float(jnp.max(jnp.abs(parts[0] - whole))) > 1e-3


@pytest.mark.parametrize("preset", ["olmoe", "moonlight"])
def test_all_experts_held_is_todays_layer_bit_for_bit(preset):
    """OLMoE's and Moonlight's routed layers at their tiny presets:
    ``experts_held`` None, and the whole range, against each other; None
    traces the program it always did."""
    changed = {"olmoe": dict(router_scoring="softmax", router_bias=False,
                             norm_topk_prob=False, n_experts=8),
               "moonlight": dict(routed_scale=2.446, n_experts=8)}[preset]
    cfg, params, x = _layer(**changed)
    today = MoEFFN(cfg).apply({"params": params}, x)
    all_held = MoEConfig(**dict(LAYER, experts_held=(0, 8), **changed))
    assert bool(jnp.all(MoEFFN(all_held).apply({"params": params}, x) == today))
    lowered = [
        jax.jit(lambda p, c=c: MoEFFN(c).apply({"params": p}, x)).lower(
            params).as_text()
        for c in (cfg, MoEConfig(**dict(LAYER, **changed)))]
    assert lowered[0] == lowered[1]
    assert "experts_held" not in lowered[0]


def test_the_served_families_configs_hold_everything():
    for family in ("moe", "deepseek"):
        cfg = LLMConfig(model_id=f"{family}-tiny", model_family=family,
                        max_seq_len=32).build_model_config()
        routed = cfg if family == "moe" else cfg.routed_config()
        assert routed.experts_held is None
        assert routed.n_experts_held == routed.n_experts
        counts = _new_expert_counts(cfg, 4)
        assert set(counts) == {"steps", "assignments", "touched"}


def test_refused_outside_the_experts_and_on_the_capacity_path():
    with pytest.raises(ValueError, match="experts_held"):
        MoEConfig(**dict(LAYER, experts_held=(30, 34)))
    with pytest.raises(ValueError, match="experts_held"):
        MoEConfig(**dict(LAYER, dropless=False, router_scoring="softmax",
                         router_bias=False, experts_held=(0, 4)))
    x = jnp.zeros((3, 8))
    with pytest.raises(ValueError, match="held range"):
        expert.moe_apply_dropless(
            x, jnp.ones((3, 2)), jnp.zeros((3, 2), jnp.int32),
            jnp.zeros((4, 8, 8)), jnp.zeros((4, 8, 8)), jnp.zeros((4, 8, 8)),
            held=(0, 8))


def test_the_grouped_kernel_is_handed_the_held_assignments_alone(monkeypatch):
    """A prefill's 512 tokens x 4 choices over 32 experts of which 4 are
    held: the groups the kernel is given sum to the held assignments (~256
    of 2048), its schedule visits the tiles those rows fill and no other,
    and the rows behind them (which it never computes) add nothing."""
    seen = {}
    grouped = kernel.moe_experts

    def recorded(x, w_gate, w_up, w_down, group_sizes):
        seen.update(rows=x.shape[0], groups=np.asarray(group_sizes))
        return jnp.full((x.shape[0], w_down.shape[-1]), jnp.nan).at[
            :int(group_sizes.sum())].set(
                grouped(x, w_gate, w_up, w_down, group_sizes)[
                    :int(group_sizes.sum())])

    monkeypatch.setattr(kernel, "moe_experts", recorded)
    keys = jax.random.split(jax.random.PRNGKey(1), 6)
    tokens, k, n, held = 512, 4, 32, (8, 12)
    x = jax.random.normal(keys[0], (tokens, 16))
    chosen = jnp.stack([jax.random.permutation(key, n)[:k]
                        for key in jax.random.split(keys[1], tokens)]).astype(jnp.int32)
    weights = jax.random.uniform(keys[2], (tokens, k))
    w = [jax.random.normal(key, shape) * 0.2 for key, shape in zip(
        keys[3:], [(n, 16, 8), (n, 16, 8), (n, 8, 16)])]
    got = expert.moe_apply_dropless(
        x, weights, chosen, *(m[held[0]:held[1]] for m in w), held=held)
    here = int(((chosen >= held[0]) & (chosen < held[1])).sum())
    assert 0 < here < tokens * k // 4
    assert seen["rows"] == tokens * k and int(seen["groups"].sum()) == here
    assert seen["groups"].shape == (4,)
    # the schedule: one visit a (tile, expert) pair that shares a row
    tile = kernel.tile_rows(tokens * k)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata
    _, visits = make_group_metadata(
        group_sizes=jnp.asarray(seen["groups"]), m=tokens * k, tm=tile,
        start_group=jnp.int32(0), num_nonzero_groups=4, visit_empty_groups=False)
    # (3 tiles of the 16, and a visit more for each expert that starts
    # inside a tile another began)
    assert int(visits) <= -(-here // tile) + 3 < tokens * k // tile
    # the rows never computed came back as NaN here and are not in the sum
    assert bool(jnp.all(jnp.isfinite(got)))
    want = expert.moe_apply_dropless(
        x, jnp.where((chosen >= held[0]) & (chosen < held[1]), weights, 0.0),
        chosen, *w)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    # no row of a step chose a held expert: nothing is visited, nothing added
    nobody = expert.moe_apply_dropless(
        x[:4], weights[:4], jnp.full((4, k), 20, jnp.int32),
        *(m[held[0]:held[1]] for m in w), held=held)
    assert float(jnp.max(jnp.abs(nobody))) == 0.0 and int(seen["groups"].sum()) == 0


def test_absent_plus_held_counts_are_rows_times_k():
    cfg = SolarOpen2Config.tiny(experts_held=(4, 8))
    counts = _new_expert_counts(cfg, rows=5)
    assert counts["assignments"].shape == (4, 4) and counts["choice"].shape == (4, 5, 4)
    keys = jax.random.split(jax.random.PRNGKey(0), 4 * 5)
    step = jnp.stack([jax.random.permutation(key, 16)[:4] for key in keys]
                     ).reshape(4, 5, 4).astype(jnp.int32)
    routing = {f"layer_{i}": {"moe": {"experts": (step[i],)}} for i in range(4)}
    active = np.array([True, False, True, True, False])
    for _ in range(3):
        counts = _count_experts(counts, routing, active, 4)
    live = step[:, active]
    here = (live >= 4) & (live < 8)
    assert [int(n) for n in counts["assignments"].sum(1)] == [
        3 * int(h.sum()) for h in here]
    assert [int(n) for n in counts["absent"]] == [
        3 * int((~h).sum()) for h in here]
    assert [int(n) for n in counts["assignments"].sum(1) + counts["absent"]] == [
        3 * 3 * 4] * 4
    assert int(counts["assignments"][0, 1]) == 3 * int((live[0] == 5).sum())
    assert bool(jnp.all(counts["choice"] == step)) and int(counts["steps"]) == 3
    assert [int(t) for t in counts["touched"]] == [
        3 * len(set(np.asarray(l[h]).tolist())) for l, h in zip(live, here)]
    # with every row live: rows x k a layer
    every = _count_experts(_new_expert_counts(cfg, rows=5), routing, None, 4)
    assert [int(n) for n in every["assignments"].sum(1) + every["absent"]] == [20] * 4
