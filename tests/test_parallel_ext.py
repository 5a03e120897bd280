"""Ulysses SP, expert parallelism, pipeline parallelism, MoE model — on the
virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.models.moe import MoEConfig, next_token_loss
from ray_tpu.models.moe import init_params as moe_init_params
from ray_tpu.ops.flash_attention import reference_attention
from ray_tpu.parallel.expert import (
    expert_capacity,
    moe_apply_gspmd,
    moe_combine,
    moe_dispatch,
    top_k_gating,
)
from ray_tpu.parallel.mesh import make_mesh
from ray_tpu.parallel.pipeline import pipeline_apply, select_stage_params
from ray_tpu.parallel.sharding import param_shardings, unbox_params
from ray_tpu.parallel.ulysses import ulysses_attention

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)


def _ulysses(mesh):
    spec = P(None, None, "sp", None)
    return jax.jit(
        shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp"),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
    )


# bf16: the flash kernels' MXU operands are the input's dtype; the reference
# is float32 math on the same inputs (tolerance: tests/test_ops.py)
DTYPES = pytest.mark.parametrize(
    "dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"]
)


@DTYPES
def test_ulysses_matches_reference(dtype):
    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("sp",))
    b, h, s, d = 2, 4, 128, 16
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), (b, h, s, d)).astype(dtype)
        for i in range(3)
    )
    out = _ulysses(mesh)(q, k, v)
    assert out.dtype == dtype
    ref = reference_attention(
        *(x.astype(jnp.float32) for x in (q, k, v)), causal=True
    )
    np.testing.assert_allclose(
        np.asarray(out.astype(jnp.float32)), np.asarray(ref), atol=2e-2
    )


@DTYPES
def test_ulysses_grads_match(dtype):
    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("sp",))
    b, h, s, d = 1, 4, 128, 16
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), (b, h, s, d)).astype(dtype)
        for i in range(3)
    )
    attn = _ulysses(mesh)
    g1 = jax.grad(
        lambda q, k, v: (attn(q, k, v).astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    g2 = jax.grad(
        lambda q, k, v: (reference_attention(q, k, v, causal=True) ** 2).sum(),
        argnums=(0, 1, 2),
    )(*(x.astype(jnp.float32) for x in (q, k, v)))
    for a, b_ in zip(g1, g2):
        a = a.astype(jnp.float32)
        rel = float(jnp.abs(a - b_).max()) / (float(jnp.abs(b_).max()) + 1e-9)
        assert rel < 2e-2, rel


def test_ulysses_gqa():
    devs = np.array(jax.devices()[:2]).reshape(2)
    mesh = Mesh(devs, ("sp",))
    b, h, hk, s, d = 1, 4, 2, 64, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, s, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, hk, s, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, hk, s, d), jnp.float32)
    spec = P(None, None, "sp", None)
    out = jax.jit(
        shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp"),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
    )(q, k, v)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)


class TestExpertParallel:
    def test_gating_respects_capacity(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (32, 4))
        cap = 4
        dispatch, combine, aux = top_k_gating(logits, cap, k=2)
        # no expert slot is used twice
        per_slot = np.asarray(dispatch).sum(axis=0)  # (E, C)
        assert per_slot.max() <= 1.0 + 1e-6
        # combine weights normalized per token (for non-dropped tokens)
        w = np.asarray(combine).sum(axis=(1, 2))
        assert np.all((np.abs(w - 1.0) < 1e-5) | (w < 1e-6))
        assert float(aux) > 0

    def test_gspmd_apply_identity_experts(self):
        t, d, e = 16, 8, 4
        x = jax.random.normal(jax.random.PRNGKey(0), (t, d))
        logits = jax.random.normal(jax.random.PRNGKey(1), (t, e))
        cap = expert_capacity(t, e, capacity_factor=2.0, k=1)
        dispatch, combine, _ = top_k_gating(logits, cap, k=1)
        out = moe_apply_gspmd(x, dispatch, combine, lambda inp: inp)
        # identity experts + top-1 routing with ample capacity => y == x
        np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=1e-5)

    def test_shard_map_dispatch_matches_gspmd(self):
        devs = np.array(jax.devices()[:4]).reshape(4)
        mesh = Mesh(devs, ("ep",))
        t, d, e = 32, 8, 4  # 8 tokens per rank
        x = jax.random.normal(jax.random.PRNGKey(0), (t, d))
        logits = jax.random.normal(jax.random.PRNGKey(1), (t, e))
        cap = expert_capacity(t // 4, e, capacity_factor=2.0, k=1)

        w = jax.random.normal(jax.random.PRNGKey(2), (e, d, d)) * 0.1

        def local(x_local, w_full):
            lg = x_local @ jax.random.normal(jax.random.PRNGKey(1), (d, e)) * 0
            # deterministic local routing from the global logits is awkward
            # inside shard_map; recompute from x to keep shards independent
            lg = x_local[:, :e]
            dispatch, combine, _ = top_k_gating(lg, cap, k=1)
            slabs = moe_dispatch(x_local, dispatch, axis_name="ep")  # (E_l, n*C, d)
            me = jax.lax.axis_index("ep")
            w_local = jax.lax.dynamic_index_in_dim(w_full, me, 0, keepdims=False)
            y = slabs @ w_local  # this rank's single expert
            return moe_combine(y, combine, axis_name="ep")

        sharded = jax.jit(
            shard_map(
                local,
                mesh=mesh,
                in_specs=(P("ep", None), P(None, None, None)),
                out_specs=P("ep", None),
                check_vma=False,
            )
        )(x, w)

        # single-device reference with identical routing
        outs = []
        for r in range(4):
            xl = x[r * 8:(r + 1) * 8]
            lg = xl[:, :e]
            dispatch, combine, _ = top_k_gating(lg, cap, k=1)
            y = moe_apply_gspmd(
                xl, dispatch, combine,
                lambda inp: jnp.einsum("ecd,edf->ecf", inp, w),
            )
            outs.append(y)
        ref = jnp.concatenate(outs, axis=0)
        np.testing.assert_allclose(
            np.asarray(sharded), np.asarray(ref), atol=1e-4
        )


def test_pipeline_apply_4_stages():
    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("pp",))
    n_micro, mb = 6, 8
    xs = jax.random.normal(jax.random.PRNGKey(0), (n_micro, mb))
    stage_scales = jnp.array([2.0, 3.0, 5.0, 7.0])  # product 210

    def run(xs, scales):
        params = select_stage_params(scales, axis_name="pp")
        out = pipeline_apply(
            lambda p, x: x * p, params, xs, axis_name="pp"
        )
        # only the last rank holds real outputs (zeros elsewhere): psum home
        return jax.lax.psum(out, "pp")

    out = jax.jit(
        shard_map(
            run,
            mesh=mesh,
            in_specs=(P(), P()),
            out_specs=P(),
            check_vma=False,
        )
    )(xs, stage_scales)
    np.testing.assert_allclose(np.asarray(out), np.asarray(xs) * 210.0, rtol=1e-5)


@pytest.mark.slow
class TestMoEModel:
    def test_loss_and_grads_finite(self):
        cfg = MoEConfig.tiny()
        params = unbox_params(moe_init_params(cfg, jax.random.PRNGKey(0)))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 256)
        loss, grads = jax.value_and_grad(
            lambda p: next_token_loss(cfg, None, p, tokens)
        )(params)
        assert np.isfinite(float(loss))
        flat = jax.tree.leaves(grads)
        assert all(np.all(np.isfinite(np.asarray(g))) for g in flat)
        # router gradients flow
        assert any(
            "router" in "/".join(map(str, path))
            and float(jnp.abs(leaf).sum()) > 0
            for path, leaf in jax.tree_util.tree_flatten_with_path(grads)[0]
            for path in [tuple(getattr(p, "key", p) for p in path)]
        )

    def test_sharded_loss_matches_single_device(self):
        cfg = MoEConfig.tiny()
        boxed = moe_init_params(cfg, jax.random.PRNGKey(0))
        params = unbox_params(boxed)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 256)
        base = float(next_token_loss(cfg, None, params, tokens))

        mesh = make_mesh(8, fsdp=2, ep=2, tp=2)
        shardings = param_shardings(mesh, boxed)
        params_sharded = jax.device_put(params, shardings)
        with mesh:
            sharded = float(
                jax.jit(lambda p, t: next_token_loss(cfg, None, p, t))(
                    params_sharded, tokens
                )
            )
        assert abs(base - sharded) < 5e-2, (base, sharded)
