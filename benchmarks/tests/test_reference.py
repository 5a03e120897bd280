"""The plain reference against ``ray_tpu.models.llama`` at a tiny size: the
same float32 weights must give the same logits and the same loss, in the
per-layer tree (serving) and in the stacked one (scanned training)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import llama_arch

SIZES = dict(n_layers=2, n_heads=4, n_kv_heads=2, theta=1e6, eps=1e-5)


def _program(scan_layers):
    from ray_tpu.models.llama import Llama, LlamaConfig, init_params, next_token_loss
    from ray_tpu.parallel.sharding import unbox_params

    cfg = LlamaConfig(
        vocab_size=256, dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
        intermediate=256, max_seq_len=64, rope_theta=1e6, norm_eps=1e-5,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        scan_layers=scan_layers)
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(7)))
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 48), 0, 256)
    logits = Llama(cfg, None).apply({"params": params}, tokens)
    return params, tokens, logits, next_token_loss(cfg, None, params, tokens)


@pytest.mark.parametrize("scan_layers", [False, True])
def test_reference_matches_the_program_in_float32(scan_layers):
    params, tokens, logits, loss = _program(scan_layers)
    ref = llama_arch.logits(params, tokens, **SIZES)
    # float32 on both sides: what is left is the order of summation
    np.testing.assert_allclose(np.asarray(ref), np.asarray(logits), atol=2e-4)
    last = llama_arch.logits(params, tokens, last=5, **SIZES)
    np.testing.assert_allclose(np.asarray(last), np.asarray(ref[:, -5:]), atol=1e-5)
    ref_loss = llama_arch.next_token_loss(params, tokens, **SIZES)
    assert abs(float(ref_loss) - float(loss)) < 1e-4


def test_reference_is_causal_and_position_aware():
    params, tokens, _, _ = _program(False)
    full = llama_arch.logits(params, tokens, **SIZES)
    changed = tokens.at[:, -1].set((tokens[:, -1] + 1) % 256)
    again = llama_arch.logits(params, changed, **SIZES)
    np.testing.assert_allclose(
        np.asarray(full[:, :-1]), np.asarray(again[:, :-1]), atol=1e-6)
    rolled = llama_arch.logits(params, jnp.roll(tokens, 1, axis=1), **SIZES)
    assert float(jnp.max(jnp.abs(rolled[:, 1:] - full[:, :-1]))) > 1e-3
