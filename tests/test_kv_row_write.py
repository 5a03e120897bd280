"""The cache write helper (ops/kv_row_write.py) against the form it
replaced, ``jax.vmap(dynamic_update_slice_in_dim)``, bit for bit: the
decode step's one position a row through the tile kernel (interpreted
here), several positions a row through the slice it always was.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import GenerationRequest
from ray_tpu.llm.engine import ContinuousBatchingEngine
from ray_tpu.ops import kv_row_write
from ray_tpu.ops.kv_row_write import write_rows
from ray_tpu.parallel.sharding import unbox_params

S = 48  # three bf16 tiles of positions, six f32 ones; no whole lane tile

# (rows, heads, width): the cells' leaves at a small max_seq_len
LEAVES = {
    "mistral": (16, 8, 128),
    "olmoe": (8, 16, 128),
    "moonlight-latent": (24, 1, 512),
    "moonlight-rope": (24, 1, 64),
}


def _vmapped(leaf, new, positions):
    return jax.vmap(
        lambda row, n, p: jax.lax.dynamic_update_slice_in_dim(row, n, p, axis=1)
    )(leaf, new, positions)


def _positions(kind, rows, tile):
    if kind == "different":  # every tile, rows in no order, some past S
        return (np.arange(rows) * 7 + 3) % (S + 4)
    return np.full(rows, {
        "zero": 0, "tile-last": tile - 1, "tile-first": tile,
        "last": S - 1, "past": S + 5,
    }[kind])


def _bits(x):
    return np.asarray(x).view(np.uint16 if x.dtype == jnp.bfloat16 else np.uint32)


@pytest.mark.parametrize(
    "kind", ["zero", "tile-last", "tile-first", "last", "past", "different"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("leaf", list(LEAVES))
def test_one_position_a_row_equals_the_vmapped_slice(leaf, dtype, kind):
    rows, heads, width = LEAVES[leaf]
    keys = jax.random.split(jax.random.PRNGKey(rows + width), 4)
    # two leaves a call, as a layer has
    old = [jax.random.normal(k, (rows, heads, S, width), dtype) for k in keys[:2]]
    new = [jax.random.normal(k, (rows, heads, 1, width), dtype) for k in keys[2:]]
    tile = 16 if dtype == jnp.bfloat16 else 8
    positions = jnp.asarray(_positions(kind, rows, tile), jnp.int32)
    got = jax.jit(write_rows)(old, new, positions)
    for g, o, n in zip(got, old, new):
        want = _vmapped(o, n, positions)
        assert g.dtype == want.dtype and g.shape == want.shape
        assert (_bits(g) == _bits(want)).all()
        # the position it says, and the clamp dynamic_update_slice applies
        at = np.minimum(np.asarray(positions), S - 1)
        assert (_bits(g)[np.arange(rows), :, at] == _bits(n)[:, :, 0]).all()


@pytest.mark.parametrize("leaf", list(LEAVES))
def test_several_positions_a_row_stay_the_slice(leaf):
    """Prefill, chunked prefill and speculative verify: the same helper,
    the program it always was."""
    rows, heads, width = LEAVES[leaf]
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    old = jax.random.normal(keys[0], (rows, heads, S, width), jnp.bfloat16)
    new = jax.random.normal(keys[1], (rows, heads, 5, width), jnp.bfloat16)
    positions = jnp.asarray((np.arange(rows) * 5) % (S + 2), jnp.int32)
    (got,) = jax.jit(write_rows)([old], [new], positions)
    assert (_bits(got) == _bits(_vmapped(old, new, positions))).all()
    # (a vmapped update at batched positions is a scatter)
    several = str(jax.make_jaxpr(write_rows)([old], [new], positions))
    assert "pallas_call" not in several and "scatter" in several
    one = str(jax.make_jaxpr(write_rows)([old], [new[:, :, :1]], positions))
    assert "pallas_call" in one and "scatter" not in one


def test_a_donated_cache_is_written_where_it_is():
    old = [jnp.zeros((4, 2, S, 128), jnp.bfloat16),
           jnp.zeros((4, 2, S, 64), jnp.bfloat16)]
    new = [jnp.ones((4, 2, 1, 128), jnp.bfloat16),
           jnp.ones((4, 2, 1, 64), jnp.bfloat16)]
    where = [leaf.unsafe_buffer_pointer() for leaf in old]
    step = jax.jit(write_rows, donate_argnums=(0,))
    got = step(old, new, jnp.asarray([0, 17, 47, 60], jnp.int32))
    assert all(leaf.is_deleted() for leaf in old)
    assert [leaf.unsafe_buffer_pointer() for leaf in got] == where
    for leaf in got:
        assert np.asarray(leaf, np.float32).sum() == 4 * 2 * leaf.shape[-1]


def test_heads_sharded_over_tp_write_the_same_bytes():
    from ray_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(4, tp=2, fsdp=2)
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    old = jax.random.normal(keys[0], (3, 4, S, 32), jnp.bfloat16)
    new = jax.random.normal(keys[1], (3, 4, 1, 32), jnp.bfloat16)
    positions = jnp.asarray([0, 31, 99], jnp.int32)
    with mesh:
        (sharded,) = jax.jit(
            lambda *a: write_rows(*a, mesh=mesh))([old], [new], positions)
    assert (_bits(sharded) == _bits(_vmapped(old, new, positions))).all()


def _toy(family):
    from ray_tpu import models

    if family == "llama":
        from ray_tpu.models.llama import LlamaConfig

        cfg = LlamaConfig.tiny(n_layers=2, max_seq_len=64)
        leaves = {"cached_key", "cached_value"}
    elif family == "moe":
        from ray_tpu.models.moe import MoEConfig

        cfg = MoEConfig(
            vocab_size=96, dim=64, n_layers=2, n_heads=4, n_kv_heads=4,
            intermediate=32, n_experts=8, experts_per_token=4, max_seq_len=64,
            dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
            dropless=True, qk_norm=True,
        )
        leaves = {"cached_key", "cached_value"}
    else:
        from ray_tpu.models.deepseek import DeepseekConfig

        cfg = DeepseekConfig(
            vocab_size=96, dim=64, n_layers=2, n_heads=4, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            intermediate=96, moe_intermediate=32, n_experts=8,
            experts_per_token=3, n_shared_experts=2, first_dense_layers=1,
            max_seq_len=64, dtype=jnp.float32, param_dtype=jnp.float32,
        )
        leaves = {"cached_latent", "cached_rope"}
    params = unbox_params(models.init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, params, leaves


@pytest.mark.parametrize("family", ["llama", "moe", "deepseek"])
def test_the_engine_reports_how_its_step_writes_each_leaf(family, monkeypatch):
    """``runtime_info()["kv"]["row_write"]`` is the engine's ``row_write()``:
    every 4-d leaf of the live slot cache by name, ``"tile"`` once the
    decode step that writes it was traced through the kernel."""
    cfg, params, leaves = _toy(family)
    monkeypatch.setattr(kv_row_write, "_traced_forms", {})
    eng = ContinuousBatchingEngine(cfg, params, num_slots=3, seed=0)
    assert eng.row_write() is None  # no cache yet
    (result,) = eng.generate(
        [GenerationRequest(token_ids=[5, 9, 2, 7], max_new_tokens=3)])
    assert len(result.token_ids) == 3
    assert eng.row_write() == {name: "tile" for name in leaves}
    # the prefill's write (b = 1, s > 1) is not the kernel's
    assert kv_row_write.traced_form((1,) + jax.tree.leaves(
        eng._cache)[-1].shape[1:]) is None
