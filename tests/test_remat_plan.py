"""What ``llama``'s rematerialised layer stack keeps (models/remat_plan.py):
the sizing as arithmetic, the kept values as the bits they would have been
recomputed to, and the programs that keep nothing (no memory limit to read,
a decode model) as the ones they were before there were tags."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, remat_plan
from ray_tpu.models.llama import Llama, LlamaConfig, init_params, next_token_loss
from ray_tpu.ops import flash_attention
from ray_tpu.parallel.mesh import make_mesh
from ray_tpu.parallel.sharding import matrix_shards, unbox_params
from ray_tpu.util import tracing

V5E_LIMIT = 16_909_336_064  # bytes_limit of a v5e chip's memory_stats()
GB = 10**9

MISTRAL = LlamaConfig(
    vocab_size=32768, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
    intermediate=14336, max_seq_len=4096, lora_rank=16,
    param_dtype=jnp.bfloat16)
# Mistral-7B's parameters by path, in bytes (bf16), as the scanned stack
# holds them; the adapters are a rounding error and left out
MISTRAL_BYTES = {
    ("embed",): 2 * 32768 * 4096,
    ("lm_head",): 2 * 32768 * 4096,
    ("layers", "block", "attn", "wq", "base", "kernel"): 32 * 2 * 4096 * 4096,
    ("layers", "block", "attn", "wk", "base", "kernel"): 32 * 2 * 4096 * 1024,
    ("layers", "block", "attn", "wv", "base", "kernel"): 32 * 2 * 4096 * 1024,
    ("layers", "block", "attn", "wo", "base", "kernel"): 32 * 2 * 4096 * 4096,
    ("layers", "block", "mlp", "w_gate", "kernel"): 32 * 2 * 4096 * 14336,
    ("layers", "block", "mlp", "w_up", "kernel"): 32 * 2 * 4096 * 14336,
    ("layers", "block", "mlp", "w_down", "kernel"): 32 * 2 * 14336 * 4096,
}


def _cell(sequences_per_chip, limit=V5E_LIMIT):
    """The training cells' step: fsdp=4, sequences of 4096."""
    return remat_plan.for_step(
        MISTRAL, {"fsdp": 4}, 4, MISTRAL_BYTES, 4 * sequences_per_chip, 4096,
        limit)


def _no_limit():
    assert _cell(2, limit=None).kept == ()
    assert _cell(2, limit=None).budget_bytes == 0


def _no_budget():
    for budget in (0, -5 * GB, None):
        plan = remat_plan.plan(MISTRAL, 8192, 4096, budget)
        assert plan.kept == () and plan.tags == () and plan.kept_bytes == 0
    # a device too small for the job itself leaves nothing to spend
    assert _cell(4, limit=8 * GB).kept == ()


def _rising_budgets():
    last = ()
    for budget in range(0, 26 * GB, GB // 4):
        kept = remat_plan.plan(MISTRAL, 8192, 4096, budget).kept
        assert kept[:len(last)] == last, (budget, last, kept)
        last = kept
    assert set(last) == {c.name for c in remat_plan.candidates(MISTRAL, 8192, 4096)}


def _cell_fits(sequences_per_chip):
    def check():
        plan = _cell(sequences_per_chip)
        assert plan.kept and plan.kept[0] == remat_plan.ATTN_K
        assert 0 < plan.kept_bytes <= plan.budget_bytes
        # under the limit by the margin, with the parameters a device and
        # the compiler's own count of the step's temporaries (PERF.md, PR
        # 56: 6.05 GB at 8192 tokens a device, 10.88 at 16384)
        counted = 3.693 * GB + {2: 6.047, 4: 10.882}[sequences_per_chip] * GB
        assert counted + plan.kept_bytes <= V5E_LIMIT * (1 - remat_plan.MARGIN)
    return check


def _same_inputs_same_names():
    assert _cell(2) == _cell(2) and _cell(4) == _cell(4)
    assert _cell(2).kept == (
        remat_plan.ATTN_K, remat_plan.ATTN_V, remat_plan.ATTN_OUT,
        remat_plan.ATTN_Q)
    assert remat_plan.ATTN_LSE in _cell(2).tags
    assert _cell(4).kept == (remat_plan.ATTN_K,)


def _order_follows_the_shapes():
    # at a short sequence attention's output saves few FLOPs a byte: last
    short = [c.name for c in remat_plan.candidates(MISTRAL, 8192, 256)]
    assert short[-1] == remat_plan.ATTN_OUT
    full = [c.name for c in remat_plan.candidates(MISTRAL, 8192, 4096)]
    assert full.index(remat_plan.ATTN_OUT) < full.index(remat_plan.ATTN_Q)
    assert full[:2] == [remat_plan.ATTN_K, remat_plan.ATTN_V]


@pytest.mark.parametrize("check", [
    pytest.param(_no_limit, id="no-memory-stats-keeps-nothing"),
    pytest.param(_no_budget, id="no-budget-keeps-nothing"),
    pytest.param(_rising_budgets, id="rising-budgets-are-supersets"),
    pytest.param(_cell_fits(2), id="cell-2-a-chip-fits-under-the-margin"),
    pytest.param(_cell_fits(4), id="cell-4-a-chip-fits-under-the-margin"),
    pytest.param(_same_inputs_same_names, id="same-inputs-same-names"),
    pytest.param(_order_follows_the_shapes, id="order-follows-the-shapes"),
])
def test_policy(check):
    check()


def test_matrix_shards_counts_the_axes_that_cut_a_matrix():
    assert matrix_shards(None) == 1
    assert matrix_shards(make_mesh(num_devices=4, fsdp=4)) == 4
    assert matrix_shards(make_mesh(num_devices=4, fsdp=2, tp=2)) == 4
    assert matrix_shards(make_mesh(num_devices=4, dp=4)) == 1


# -- the program ------------------------------------------------------------


def _toy(scan):
    cfg = LlamaConfig.tiny(
        remat=True, scan_layers=scan, lora_rank=4, n_kv_heads=2, max_seq_len=64)
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, cfg.vocab_size)
    return cfg, params, tokens


def _loss_and_grads(cfg, mesh, tokens):
    # a new function object a call: jit answers the same one from its cache
    return jax.value_and_grad(lambda p: next_token_loss(cfg, mesh, p, tokens))


def _flash_forwards(jaxpr) -> int:
    """``flash_fwd`` kernel calls in a jaxpr, through every sub-jaxpr."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found += eqn.params.get("name") == "flash_fwd"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _flash_forwards(sub)
    return found


@pytest.fixture
def limit(monkeypatch):
    def set_limit(value):
        monkeypatch.setattr(remat_plan, "device_bytes_limit", lambda: value)
    return set_limit


@pytest.mark.parametrize("meshed", [False, True], ids=["one-device", "fsdp4"])
@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "unrolled"])
def test_keeping_everything_changes_no_bit(scan, meshed, limit):
    cfg, params, tokens = _toy(scan)
    mesh = make_mesh(num_devices=4, fsdp=4) if meshed else None
    limit(None)
    nothing = _loss_and_grads(cfg, mesh, tokens)
    loss0, grads0 = jax.jit(nothing)(params)
    recomputing = _flash_forwards(jax.make_jaxpr(nothing)(params).jaxpr)
    limit(10**12)
    everything = _loss_and_grads(cfg, mesh, tokens)
    loss1, grads1 = jax.jit(everything)(params)
    keeping = _flash_forwards(jax.make_jaxpr(everything)(params).jaxpr)
    assert np.array_equal(loss0, loss1)
    for a, b in zip(jax.tree.leaves(grads0), jax.tree.leaves(grads1), strict=True):
        assert np.array_equal(a, b)
    # the forward kernel runs once a layer when its output is kept, twice
    # when the backward pass makes it again (a scan's body stands once)
    layers = 1 if scan else cfg.n_layers
    assert (recomputing, keeping) == (2 * layers, layers)


def _text(lowered) -> str:
    """A lowered program's text, without the counter the lowering appends to
    a private function's name (``@_where_68``): it counts call sites of the
    whole process, not of the program."""
    return re.sub(r"(@[A-Za-z_]+)_\d+", r"\1", lowered.as_text())


def _untagged(monkeypatch):
    for module in (llama, flash_attention):  # the ring's rule tags through flash's
        monkeypatch.setattr(module, "checkpoint_name", lambda x, name: x)


@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "unrolled"])
def test_nothing_kept_is_the_program_without_tags(scan, limit, monkeypatch):
    """No limit to read: the gradient's program is the one
    ``save_only_these_names()`` gave before there was a tag."""
    cfg, params, tokens = _toy(scan)
    limit(None)
    tagged = _loss_and_grads(cfg, None, tokens)
    assert " name[" in str(jax.make_jaxpr(tagged)(params))
    tagged_text = _text(jax.jit(tagged).lower(params))
    _untagged(monkeypatch)
    monkeypatch.setattr(
        Llama, "_remat_policy",
        lambda self, tokens: jax.checkpoint_policies.save_only_these_names())
    plain = _loss_and_grads(cfg, None, tokens)
    assert " name[" not in str(jax.make_jaxpr(plain)(params))
    assert _text(jax.jit(plain).lower(params)) == tagged_text


def test_decode_model_lowers_as_without_tags(limit, monkeypatch):
    """The serving programs share ``Attention`` and ``MLP``: a prefill and a
    decode step lower to the same text with the tags as without, whatever
    memory the device has."""
    cfg = LlamaConfig.tiny(n_kv_heads=2, max_seq_len=64, remat=True)
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
    limit(10**12)
    before = dict(tracing._program_facts)

    def lowered():
        model = Llama(cfg, None, decode=True)

        def prefill(p, tokens):
            return model.apply({"params": p}, tokens, mutable=["cache"])

        def decode(p, cache, token):
            return model.apply({"params": p, "cache": cache}, token, mutable=["cache"])

        prompt = jnp.zeros((2, 8), jnp.int32)
        _, state = jax.eval_shape(prefill, params, prompt)
        return (_text(jax.jit(prefill).lower(params, prompt)),
                _text(jax.jit(decode).lower(
                    params, state["cache"], jnp.zeros((2, 1), jnp.int32))))

    tagged = lowered()
    assert tracing._program_facts == before  # a decode model plans nothing
    _untagged(monkeypatch)
    assert lowered() == tagged


def test_traced_step_writes_its_plan(limit):
    cfg, params, tokens = _toy(scan=True)
    limit(10**12)
    tracing._program_facts.clear()
    jax.make_jaxpr(_loss_and_grads(cfg, None, tokens))(params)
    (fact,) = tracing._program_facts.values()
    assert fact["tokens_per_device"] == 4 * 64
    assert fact["kept"].split("+")[:2] == [remat_plan.ATTN_K, remat_plan.ATTN_V]
    assert 0 < fact["kept_bytes_per_device"] <= fact["budget_bytes"]
    # the same step traced again is the same fact, kept once; a report
    # writes it into whatever profiler session is open by then
    jax.make_jaxpr(_loss_and_grads(cfg, None, tokens))(params)
    assert list(tracing._program_facts.values()) == [fact]
    tracing.replay_program_facts()
