"""Mesh, sharding rules, ring attention, sharded model parity — on the
virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.models.llama import Llama, LlamaConfig, init_params, next_token_loss
from ray_tpu.ops.flash_attention import reference_attention
from ray_tpu.parallel.mesh import MeshSpec, make_mesh, mesh_axis_size
from ray_tpu.parallel.ring_attention import ring_attention
from ray_tpu.parallel.sharding import logical_to_spec, param_shardings, unbox_params

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)


def test_mesh_spec_resolution():
    spec = MeshSpec(dp=2, fsdp=-1, tp=2)
    sizes = spec.resolved_sizes(8)
    assert sizes == {
        "dcn": 1, "pp": 1, "dp": 2, "fsdp": 2, "ep": 1, "sp": 1, "tp": 2,
    }
    with pytest.raises(ValueError):
        MeshSpec(dp=3).resolved_sizes(8)


def test_make_mesh_and_axis_sizes():
    mesh = make_mesh(dp=1, fsdp=2, sp=2, tp=2)
    assert mesh_axis_size(mesh, "fsdp") == 2
    assert mesh_axis_size(mesh, "tp") == 2


def test_logical_to_spec():
    assert logical_to_spec(("batch", "embed")) == P(("dcn", "dp", "fsdp"), "fsdp")
    assert logical_to_spec((None, "mlp")) == P(None, "tp")


def _ring(mesh):
    return jax.jit(
        shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
            mesh=mesh,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None),
            check_vma=False,
        )
    )


# bf16: the kernels hand the MXU bf16 operands (p and ds rounded to 2^-9
# relative, tests/test_ops.py), the reference is float32 math on the same
# inputs; sp = 4 runs the non-causal body under lax.cond three times a rank
DTYPES = pytest.mark.parametrize(
    "dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"]
)


@DTYPES
def test_ring_attention_matches_reference(dtype):
    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("sp",))
    b, h, s, d = 2, 2, 256, 32
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), (b, h, s, d)).astype(dtype)
        for i in range(3)
    )
    out = _ring(mesh)(q, k, v)
    assert out.dtype == dtype
    ref = reference_attention(
        *(x.astype(jnp.float32) for x in (q, k, v)), causal=True
    )
    assert float(jnp.abs(out - ref).max()) < 2e-2


@DTYPES
def test_ring_attention_grads_match(dtype):
    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("sp",))
    b, h, s, d = 1, 2, 256, 32
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), (b, h, s, d)).astype(dtype)
        for i in range(3)
    )
    ring = _ring(mesh)
    g1 = jax.grad(
        lambda q, k, v: (ring(q, k, v).astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    g2 = jax.grad(
        lambda q, k, v: (reference_attention(q, k, v, causal=True) ** 2).sum(),
        argnums=(0, 1, 2),
    )(*(x.astype(jnp.float32) for x in (q, k, v)))
    for a, b_ in zip(g1, g2):
        assert a.dtype == dtype
        a = a.astype(jnp.float32)
        rel = float(jnp.abs(a - b_).max()) / (float(jnp.abs(b_).max()) + 1e-9)
        assert rel < 2e-2, rel


@pytest.mark.slow
def test_llama_sharded_matches_single_device():
    cfg = LlamaConfig.tiny()
    mesh = make_mesh(dp=1, fsdp=2, sp=2, tp=2)
    boxed = init_params(cfg, jax.random.PRNGKey(0))
    raw = unbox_params(boxed)
    shardings = param_shardings(mesh, boxed)
    sharded = jax.jit(lambda p: p, out_shardings=shardings)(raw)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 128), 0, cfg.vocab_size)
    loss_sharded = jax.jit(lambda p, t: next_token_loss(cfg, mesh, p, t))(
        sharded, tokens
    )
    loss_single = jax.jit(lambda p, t: next_token_loss(cfg, None, p, t))(raw, tokens)
    assert abs(float(loss_sharded) - float(loss_single)) < 2e-2


@pytest.mark.slow
def test_llama_lora_params_exist():
    cfg = LlamaConfig.tiny(lora_rank=4)
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
    attn = params["layer_0"]["attn"]["wq"]
    assert "lora_a" in attn and "lora_b" in attn
    assert attn["lora_a"].shape == (cfg.dim, 4)
    # lora_b starts at zero: output identical to base model
    base = unbox_params(init_params(LlamaConfig.tiny(), jax.random.PRNGKey(0)))
    tokens = jnp.zeros((1, 16), jnp.int32)
    out_lora = Llama(cfg, None).apply({"params": params}, tokens)
    out_base = Llama(LlamaConfig.tiny(), None).apply({"params": base}, tokens)
    assert float(jnp.abs(out_lora - out_base).max()) < 1e-3


@pytest.mark.slow
def test_graft_entry_dryrun():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "graft",
        os.path.join(os.path.dirname(os.path.dirname(__file__)), "__graft_entry__.py"),
    )
    g = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(g)
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[0] == args[1].shape[0]
    g.dryrun_multichip(8)
