"""Pallas kernels vs XLA references (CPU interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.flash_attention import (
    default_blocks,
    flash_attention,
    flash_attention_with_lse,
    reference_attention,
)
from ray_tpu.ops.rmsnorm import rmsnorm
from ray_tpu.ops.rope import apply_rope, rope_table

# The kernels hand the MXU operands in the input's dtype. The reference is
# always float32 math on the *same* inputs, so what a bf16 case measures is
# what the kernel rounds: p and ds to bf16 just before their matmuls (half
# an ulp, 2^-9 relative, as the bf16 activations they multiply already are)
# and the bf16 result itself (2^-9 of |o| <= ~4: 8e-3). Both stay under the
# 2e-2 the float32 cases have always had (they read 1e-6 here: float32
# inputs keep float32 operands), so one tolerance serves both.
TOL = 2e-2
DTYPES = pytest.mark.parametrize(
    "dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"]
)


@pytest.fixture(scope="module")
def qkv():
    b, h, s, d = 2, 2, 256, 64
    ks = [jax.random.PRNGKey(i) for i in range(3)]
    return tuple(jax.random.normal(k, (b, h, s, d), jnp.float32) for k in ks)


def _f32(*xs):
    return tuple(x.astype(jnp.float32) for x in xs)


def _grads(fn, q, k, v):
    """Gradients of sum(fn(q, k, v)^2), as float32."""
    loss = lambda *a: (fn(*a).astype(jnp.float32) ** 2).sum()
    return _f32(*jax.grad(loss, argnums=(0, 1, 2))(q, k, v))


def _assert_grads_close(got, want):
    for name, a, b in zip("qkv", got, want):
        rel = float(jnp.abs(a - b).max()) / (float(jnp.abs(b).max()) + 1e-9)
        assert rel < TOL, (name, rel)


@DTYPES
@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward(qkv, causal, dtype):
    q, k, v = (x.astype(dtype) for x in qkv)
    out = flash_attention(q, k, v, causal=causal)
    assert out.dtype == dtype
    ref = reference_attention(*_f32(q, k, v), causal=causal)
    assert float(jnp.abs(out - ref).max()) < TOL


@DTYPES
def test_flash_lse_consistency(qkv, dtype):
    q, k, v = (x.astype(dtype) for x in qkv)
    out, lse = flash_attention_with_lse(q, k, v, causal=False)
    assert lse.dtype == jnp.float32  # whatever the inputs are
    # direct lse computation
    qf, kf = _f32(q, k)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) / jnp.sqrt(q.shape[-1])
    ref_lse = jax.scipy.special.logsumexp(s, axis=-1)
    assert float(jnp.abs(lse - ref_lse).max()) < TOL


@DTYPES
def test_flash_grads(qkv, dtype):
    q, k, v = (x.astype(dtype) for x in qkv)
    g1 = _grads(lambda *a: flash_attention(*a, causal=True), q, k, v)
    g2 = _grads(
        lambda *a: reference_attention(*a, causal=True), *_f32(q, k, v)
    )
    _assert_grads_close(g1, g2)


@DTYPES
def test_flash_gqa(qkv, dtype):
    q = qkv[0].astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(7), (2, 1, 256, 64)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(8), (2, 1, 256, 64)).astype(dtype)
    out = flash_attention(q, k, v, causal=True)
    ref = reference_attention(*_f32(q, k, v), causal=True)
    assert float(jnp.abs(out - ref).max()) < TOL
    g1 = _grads(lambda *a: flash_attention(*a, causal=True), q, k, v)
    g2 = _grads(
        lambda *a: reference_attention(*a, causal=True), *_f32(q, k, v)
    )
    _assert_grads_close(g1, g2)


# (seq, tile, causal). 197: ViT-B/16's length, shorter than a tile, so one
# unaligned tile and no padding. 320 at 128: the last tile of a side is half
# padding (the static padding path, causal and not). 512 at 128: four tiles a
# side, so tiles wholly below the diagonal (unmasked body), tiles the
# diagonal crosses (masked body) and skipped tiles above it all occur.
# 1024 at 512: every tile is two 256-column chunks, and in the two tiles on
# the diagonal the second chunk runs on the lower half of the rows only.
# 768 at its default: one tile of three chunks, causal (rows skipped above
# each chunk) and not (every row in every chunk).
TILINGS = [(197, None, False), (320, 128, False), (320, 128, True),
           (512, 128, True), (1024, 512, True), (768, None, True),
           (768, None, False)]


@DTYPES
@pytest.mark.parametrize("seq,tile,causal", TILINGS)
def test_flash_tilings_forward_and_grads(seq, tile, causal, dtype):
    shape = (1, 2, seq, 64)
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), shape).astype(dtype)
        for i in range(3)
    )
    attn = lambda *a: flash_attention(
        *a, causal=causal, block_q=tile, block_k=tile
    )
    ref = lambda *a: reference_attention(*a, causal=causal)
    out = attn(q, k, v)
    assert not bool(jnp.isnan(out.astype(jnp.float32)).any())
    assert float(jnp.abs(out - ref(*_f32(q, k, v))).max()) < TOL
    _assert_grads_close(_grads(attn, q, k, v), _grads(ref, *_f32(q, k, v)))


def test_flash_rectangular_tiles_match_square_ones():
    """block_q != block_k moves the diagonal through tiles off their
    corners; the masked / unmasked / skipped split must still cover it."""
    shape = (1, 1, 512, 64)
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), shape) for i in range(3)
    )
    want = _grads(lambda *a: flash_attention(*a, causal=True), q, k, v)
    for bq, bk in [(256, 128), (128, 256)]:
        got = _grads(
            lambda *a: flash_attention(
                *a, causal=True, block_q=bq, block_k=bk
            ), q, k, v,
        )
        for a, b in zip(got, want):
            assert float(jnp.abs(a - b).max()) < 1e-4


def _eqns(jaxpr, kernel=None):
    """(equation, name of the pallas_call it is in, or None) all the way
    down ``jaxpr``."""
    for eqn in jaxpr.eqns:
        name = eqn.params["name"] if eqn.primitive.name == "pallas_call" else kernel
        yield eqn, name
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, name)


@DTYPES
def test_flash_mxu_operands_follow_the_input(dtype):
    """The mechanism engages where it should and only there: every matmul
    of the three kernels takes operands of the input's dtype (bf16 inputs:
    single-pass bf16 tiles; f32 inputs: f32 operands) and accumulates in
    float32."""
    x = jnp.zeros((1, 2, 256, 128), dtype)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: flash_attention(*a, causal=True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2),
    ))(x, x, x)
    dots = [
        (kernel, *(x.aval.dtype for x in eqn.invars), eqn.outvars[0].aval.dtype)
        for eqn, kernel in _eqns(jaxpr.jaxpr)
        if eqn.primitive.name == "dot_general" and kernel is not None
    ]
    # per body: forward q.kT and p.v; dq q.kT, dO.vT, ds.k; dk/dv k.qT,
    # pT.dO, v.dOT, dsT.q -- each in an unmasked and a masked body
    assert sorted(n for n, *_ in dots) == (
        ["flash_bwd_dkv"] * 8 + ["flash_bwd_dq"] * 6 + ["flash_fwd"] * 4
    )  # (256 long: one chunk a tile; a longer tile repeats them a chunk)
    for name, lhs, rhs, out in dots:
        assert (lhs, rhs, out) == (dtype, dtype, jnp.float32), name


def test_flash_default_tiles():
    """Chosen from the shape, statically (PERF.md, PR 35): the training
    cell's causal (4096, 128) bf16 gets 2048 x 2048, a call without a
    diagonal 1024 x 1024, a wider row fewer rows, a short sequence one tile,
    a length no tile divides the padded 1024; an explicit tile is honoured."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert default_blocks(4096, 4096, 128, bf16) == (2048, 2048)
    assert default_blocks(4096, 4096, 128, bf16, causal=False) == (1024, 1024)
    assert default_blocks(4096, 4096, 128, f32) == (1024, 1024)
    assert default_blocks(4096, 4096, 256, f32) == (512, 512)
    assert default_blocks(197, 197, 64, bf16) == (197, 197)
    assert default_blocks(1536, 1536, 128, bf16) == (512, 512)
    assert default_blocks(1500, 1500, 128, bf16) == (1024, 1024)
    assert default_blocks(256, 8192, 128, bf16) == (256, 1024)
    x = jnp.zeros((1, 1, 512, 128), bf16)
    jaxpr = jax.make_jaxpr(
        lambda *a: flash_attention(*a, block_q=256, block_k=128)
    )(x, x, x)
    (call,) = [
        e for e, _ in _eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"
    ]
    assert call.params["grid_mapping"].grid == (1, 2, 4)


def _masked_softmax_attention(q, k, v, window):
    """The band written out: query t sees keys t - window < j <= t, K/V
    head u // group for query head u, one softmax over a masked row."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    seq = q.shape[2]
    t, j = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    seen = (j <= t) & (j > t - window)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


@pytest.mark.parametrize("seq,window,tile", [
    (512, 200, 128),   # seq > window: tiles beneath the band run nothing
    (512, 130, 256),   # ... and the band's lower edge crosses a q tile twice
    (384, 384, 128),   # seq == window: the band is the causal triangle
    (256, 1000, 128),  # seq < window
    (300, 77, 128),    # a padded last tile under the band
    (96, 40, None),    # one tile, both edges in it
], ids=["over", "edge", "equal", "under", "padded", "one_tile"])
def test_flash_window_is_the_banded_softmax(seq, window, tile):
    """The forward kernel under a window, 4 query heads on 2 K/V heads as
    they are, against a masked softmax."""
    keys = jax.random.split(jax.random.PRNGKey(seq + window), 3)
    q = jax.random.normal(keys[0], (2, 4, seq, 32))
    k, v = (jax.random.normal(key, (2, 2, seq, 32)) for key in keys[1:])
    got = flash_attention(
        q, k, v, causal=True, window=window, block_q=tile, block_k=tile)
    assert float(jnp.abs(
        got - _masked_softmax_attention(q, k, v, window)).max()) < 1e-5
    # the plain reference knows the band too
    assert float(jnp.abs(got - reference_attention(
        q, k, v, causal=True, window=window)).max()) < 1e-5
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=window)


@DTYPES
def test_flash_without_a_window_is_the_kernel_it_was(qkv, dtype):
    """``window=None`` changes nothing: the forward-only entry on K/V heads
    as they are is bit-equal to the path with a backward rule on repeated
    heads, and a window that covers the sequence is the causal call."""
    q, k, v = (x.astype(dtype) for x in qkv)
    q4 = jnp.concatenate([q, q[:, ::-1]], axis=1)  # 4 query heads on 2
    trained = flash_attention(q4, k, v, causal=True, block_q=128, block_k=128)
    served = flash_attention(
        q4, k, v, causal=True, block_q=128, block_k=128, forward_only=True)
    assert bool(jnp.all(trained == served))
    covered = flash_attention(
        q4, k, v, causal=True, block_q=128, block_k=128, window=256)
    assert float(jnp.abs(_f32(covered)[0] - _f32(trained)[0]).max()) < TOL
    # a window has no backward kernel: it says so, it does not mis-train
    with pytest.raises(Exception):
        jax.grad(lambda x: flash_attention(
            x, k, v, window=64).astype(jnp.float32).sum())(q4)
    assert default_blocks(8192, 8192, 128, jnp.bfloat16, window=4096) == (
        1024, 1024)


def test_rmsnorm_matches_reference():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 96, 128))
    w = jax.random.normal(jax.random.PRNGKey(1), (128,)) * 0.1 + 1.0
    out = rmsnorm(x, w)
    ref = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w
    assert float(jnp.abs(out - ref).max()) < 1e-4

    def loss_a(x, w):
        return (rmsnorm(x, w) ** 2).sum()

    def loss_b(x, w):
        return ((x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w) ** 2).sum()

    ga = jax.grad(loss_a, argnums=(0, 1))(x, w)
    gb = jax.grad(loss_b, argnums=(0, 1))(x, w)
    for a, b in zip(ga, gb):
        assert float(jnp.abs(a - b).max()) < 1e-2


def test_rope_properties():
    cos, sin = rope_table(128, 64)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 16, 64))
    rotated = apply_rope(x, cos, sin)
    # norms preserved per pair rotation
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(rotated), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        rtol=1e-5,
    )
    # offset slicing equals slicing the table
    shifted = apply_rope(x, cos, sin, offset=32)
    pad = jnp.zeros((1, 2, 32, 64), x.dtype)
    full = apply_rope(jnp.concatenate([pad, x], axis=2), cos, sin)[:, :, 32:]
    np.testing.assert_allclose(np.asarray(shifted), np.asarray(full), atol=1e-5)


@pytest.mark.parametrize("offset", [0, 37, "rows"])
def test_interleaved_rope_is_the_complex_rotation(offset):
    """``interleaved=True`` turns the neighbours ``(x[2i], x[2i + 1])``:
    the complex number ``x[2i] + 1j x[2i + 1]`` times ``exp(1j p theta_i)``
    (GPT-J's form), where the default turns a head's halves; one is the
    other under a fixed permutation of the columns."""
    d, theta = 16, 50000.0
    cos, sin = rope_table(128, d, theta)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 5, d))
    at = jnp.asarray([3, 40]) if offset == "rows" else offset
    got = np.asarray(apply_rope(x, cos, sin, at, interleaved=True))
    freqs = 1.0 / theta ** (np.arange(0, d, 2) / d)
    pairs = np.asarray(x).reshape(2, 3, 5, d // 2, 2)
    for row in range(2):
        first = int(at[row]) if offset == "rows" else offset
        angles = (first + np.arange(5))[:, None] * freqs[None, :]
        turned = (pairs[row, ..., 0] + 1j * pairs[row, ..., 1]) * np.exp(
            1j * angles)[None]
        want = np.stack([turned.real, turned.imag], axis=-1).reshape(3, 5, d)
        np.testing.assert_allclose(got[row], want, atol=2e-6)
    # the two forms are one rotation on permuted columns, not one function
    halves = np.asarray(apply_rope(x, cos, sin, at))
    assert np.abs(halves - got).max() > 0.1
    order = np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])
    np.testing.assert_allclose(
        np.asarray(apply_rope(x[..., order], cos, sin, at)), got[..., order],
        atol=2e-6)


# -- the grouped expert kernel's ungated form (ops/moe_experts.py) ------------

def _relu2_einsum(x, w_up, w_down, group_sizes):
    """``down_e(relu(up_e x[r])^2)`` for the expert that owns row ``r``."""
    owner = jnp.repeat(jnp.arange(len(group_sizes)), group_sizes,
                       total_repeat_length=x.shape[0])
    hidden = jnp.square(jax.nn.relu(jnp.einsum("md,mdf->mf", x, w_up[owner])))
    return jnp.einsum("mf,mfd->md", hidden, w_down[owner])


def _relu2_operands(rows, inner, experts=5, d=64):
    keys = jax.random.split(jax.random.PRNGKey(rows + inner), 4)
    x = jax.random.normal(keys[0], (rows, d))
    w_up = jax.random.normal(keys[1], (experts, d, inner)) / 8
    w_down = jax.random.normal(keys[2], (experts, inner, d)) / 8
    return x, w_up, w_down, keys[3]


@pytest.mark.parametrize("rows,inner", [(16, 32), (48, 256), (256, 384)])
def test_the_relu2_kernel_is_the_einsum(rows, inner, monkeypatch):
    """The two-matrix form against the einsum it stands for, with an inner
    width of one block and of several, tiles that experts share, and an
    expert nobody chose."""
    from ray_tpu.ops import moe_experts as kernel

    monkeypatch.setattr(kernel, "_BLOCK_BYTES", 64 * 128 * 4 * 2 // 3 + 1)
    x, w_up, w_down, key = _relu2_operands(rows, inner)
    cut = jnp.sort(jax.random.randint(key, (3,), 0, rows + 1))
    sizes = jnp.diff(jnp.concatenate(
        [jnp.zeros(1, jnp.int32), cut, jnp.asarray([rows])]))
    sizes = jnp.concatenate([sizes[:2], jnp.zeros(1, jnp.int32), sizes[2:]])
    got = kernel.moe_experts(x, None, w_up, w_down, sizes, activation="relu2")
    want = _relu2_einsum(x, w_up, w_down, sizes)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * float(
        jnp.max(jnp.abs(want)))
    # two matrices in flight: a block half as large again as SwiGLU's three
    assert kernel.block_f(64, 384, jnp.float32, matrices=2) == 128
    monkeypatch.undo()
    assert kernel.block_f(1024, 2688, jnp.bfloat16, matrices=2) == 2688
    assert kernel.block_f(1024, 2688, jnp.bfloat16) == 896


def test_the_relu2_kernel_leaves_rows_behind_the_last_group_alone():
    """Groups that sum to fewer rows than handed (the assignments to
    experts held elsewhere sort behind the held ones): the rows of the
    groups are the einsum's, and no tile behind them is visited."""
    from ray_tpu.ops.moe_experts import moe_experts

    x, w_up, w_down, _ = _relu2_operands(256, 128)
    sizes = jnp.asarray([40, 0, 30, 7, 3], jnp.int32)  # 80 of 256 rows
    got = moe_experts(x, None, w_up, w_down, sizes, activation="relu2")
    want = _relu2_einsum(x[:80], w_up, w_down, sizes)
    assert float(jnp.max(jnp.abs(got[:80] - want))) < 2e-5 * float(
        jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("held", [None, (4, 12)], ids=["all", "held"])
def test_dropless_relu2_is_the_weighted_sum_over_the_experts_held(held):
    """``moe_apply_dropless`` in the ungated form, with and without a held
    share: every token's weighted sum over its chosen experts that are
    held, no assignment dropped."""
    from ray_tpu.parallel.expert import moe_apply_dropless

    tokens, k, experts, d, inner = 37, 4, 16, 32, 48
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(keys[0], (tokens, d))
    w_up = jax.random.normal(keys[1], (experts, d, inner)) / 6
    w_down = jax.random.normal(keys[2], (experts, inner, d)) / 6
    weights = jax.random.uniform(keys[3], (tokens, k))
    chosen = jnp.argsort(
        jax.random.uniform(keys[4], (tokens, experts)), axis=-1)[:, :k]
    first, stop = held or (0, experts)
    got = moe_apply_dropless(
        x, weights, chosen.astype(jnp.int32), None, w_up[first:stop],
        w_down[first:stop], held=held, activation="relu2")
    every = jnp.einsum(
        "tef,efd->ted",
        jnp.square(jax.nn.relu(jnp.einsum("td,edf->tef", x, w_up))), w_down)
    kept = jnp.where((chosen >= first) & (chosen < stop), weights, 0.0)
    want = jnp.einsum(
        "tk,tkd->td", kept,
        jnp.take_along_axis(every, chosen[..., None], axis=1))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * float(
        jnp.max(jnp.abs(want)))
    with pytest.raises(ValueError, match="gate"):
        moe_apply_dropless(
            x, weights, chosen.astype(jnp.int32), w_up, w_up, w_down,
            activation="relu2")


# -- the grouped expert kernel past one row tile (ops/moe_experts.py) ---------

_GATES = {"swiglu": jax.nn.silu, "reglu": jax.nn.relu}


def _experts_einsum(x, w_gate, w_up, w_down, group_sizes, activation):
    """Each row through the expert that owns it, by the activation's own
    formula: the einsum the kernel stands for."""
    if activation == "relu2":
        return _relu2_einsum(x, w_up, w_down, group_sizes)
    owner = jnp.repeat(jnp.arange(len(group_sizes)), group_sizes,
                       total_repeat_length=x.shape[0])
    hidden = _GATES[activation](
        jnp.einsum("md,mdf->mf", x, w_gate[owner])
    ) * jnp.einsum("md,mdf->mf", x, w_up[owner])
    return jnp.einsum("mf,mfd->md", hidden, w_down[owner])


@pytest.mark.parametrize("tiles", [1, 2, 3])
@pytest.mark.parametrize("activation", ["swiglu", "reglu", "relu2"])
def test_an_expert_on_a_tile_edge_is_the_einsum(activation, tiles, monkeypatch):
    """One, two and three row tiles of 128 (a decode step of 64 rows x 6
    experts sorts 384 assignments into three), ~6 rows an expert, an expert
    nobody chose, and past one tile an expert whose rows straddle each
    tile's edge: it is visited once a tile and each visit stores its own
    rows alone. The same case for each activation the one-sweep kernel
    has."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

    from ray_tpu.ops import moe_experts as kernel

    # an inner width of three blocks
    monkeypatch.setattr(kernel, "_BLOCK_BYTES", 64 * 128 * 4)
    rows, experts, d, inner = 128 * tiles, 21 * tiles + 1, 64, 384
    keys = jax.random.split(jax.random.PRNGKey(tiles), 5)
    x = jax.random.normal(keys[0], (rows, d))
    w_gate, w_up = (jax.random.normal(k, (experts, d, inner)) / 8
                    for k in keys[1:3])
    w_down = jax.random.normal(keys[3], (experts, inner, d)) / 8
    # groups of 6 rows, one of them empty, whose ends (multiples of 6)
    # miss 128 and 256; the last takes what is left
    sizes = [6] * experts
    sizes[3] = 0
    sizes[-1] += rows - sum(sizes)
    sizes = jnp.asarray(sizes, jnp.int32)
    assert int(sizes.sum()) == rows and int(sizes.min()) == 0
    ends = np.cumsum(np.asarray(sizes))
    assert all(128 * t not in ends for t in range(1, tiles))
    (_, group_ids, _), visits = make_group_metadata(
        group_sizes=sizes, m=rows, tm=128, start_group=jnp.int32(0),
        num_nonzero_groups=experts, visit_empty_groups=False)
    # every touched expert once, and once more for each that straddles
    assert int(visits) == experts - 1 + (tiles - 1)
    assert kernel.tile_rows(rows) == 128
    got = kernel.moe_experts(
        x, None if activation == "relu2" else w_gate, w_up, w_down, sizes,
        **({} if activation == "swiglu" else {"activation": activation}))
    want = _experts_einsum(x, w_gate, w_up, w_down, sizes, activation)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * float(
        jnp.max(jnp.abs(want)))


def test_reglu_is_not_swiglu_and_needs_its_gate():
    from ray_tpu.ops.moe_experts import moe_experts

    x, w_up, w_down, key = _relu2_operands(32, 128)
    w_gate = jax.random.normal(key, w_up.shape) / 8
    sizes = jnp.asarray([10, 0, 12, 7, 3], jnp.int32)
    reglu = moe_experts(x, w_gate, w_up, w_down, sizes, activation="reglu")
    swiglu = moe_experts(x, w_gate, w_up, w_down, sizes)
    assert float(jnp.max(jnp.abs(reglu - swiglu))) > 1e-2
    with pytest.raises(ValueError, match="gate"):
        moe_experts(x, None, w_up, w_down, sizes, activation="reglu")
    with pytest.raises(ValueError, match="unknown activation"):
        moe_experts(x, w_gate, w_up, w_down, sizes, activation="geglu")


@pytest.mark.parametrize("held", [None, (0, 16), (4, 12)],
                         ids=["all", "whole_range", "held"])
def test_dropless_reglu_is_the_weighted_sum_over_the_experts_held(held):
    """``moe_apply_dropless`` under ReGLU: every token's weighted sum over
    its chosen experts that are held, no assignment dropped; the whole
    range named as a share is all of them."""
    from ray_tpu.parallel.expert import moe_apply_dropless

    tokens, k, experts, d, inner = 37, 4, 16, 32, 48
    keys = jax.random.split(jax.random.PRNGKey(9), 6)
    x = jax.random.normal(keys[0], (tokens, d))
    w_gate, w_up = (jax.random.normal(key, (experts, d, inner)) / 6
                    for key in keys[1:3])
    w_down = jax.random.normal(keys[3], (experts, inner, d)) / 6
    weights = jax.random.uniform(keys[4], (tokens, k))
    chosen = jnp.argsort(
        jax.random.uniform(keys[5], (tokens, experts)), axis=-1)[:, :k]
    first, stop = held or (0, experts)
    got = moe_apply_dropless(
        x, weights, chosen.astype(jnp.int32), w_gate[first:stop],
        w_up[first:stop], w_down[first:stop], held=held, activation="reglu")
    every = jnp.einsum(
        "tef,efd->ted",
        jax.nn.relu(jnp.einsum("td,edf->tef", x, w_gate))
        * jnp.einsum("td,edf->tef", x, w_up), w_down)
    kept = jnp.where((chosen >= first) & (chosen < stop), weights, 0.0)
    want = jnp.einsum(
        "tk,tkd->td", kept,
        jnp.take_along_axis(every, chosen[..., None], axis=1))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * float(
        jnp.max(jnp.abs(want)))
