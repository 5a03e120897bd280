"""What the chip path needs, checked without the chip.

1. The main path's kernels and step programs compile for a *described* TPU
   v5e at Llama-2-7B widths (the TPU compiler is installed here; the chip
   is not attached). A kernel that only ever ran in interpret mode can be
   refused by the real compiler; these catch that at no chip time. Skipped
   where the topology cannot be described.
2. One process per chip: a TPU lease makes its worker the owner of exactly
   the granted chips, every other process stays on the CPU platform, and
   nothing that is not a chip run prints a result.
"""

import collections
import math
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

import ray_tpu
from ray_tpu._internal import accelerators, platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# 1. compiles for a described v5e chip
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def v5e_host():
    """The four devices of a described v5e 2x2 host, with the persistent
    compile cache off: such a compile would be written to it and could
    never be read back without a chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def v5e_chip(v5e_host):
    """SingleDeviceSharding on the host's first device."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_host[0])


@pytest.fixture
def native_kernels(monkeypatch):
    """Trace the Pallas kernels as the chip would: this process's default
    backend is the CPU, so the ops would otherwise pick interpret mode."""
    from ray_tpu.ops import (
        decode_attention, flash_attention, kda_step, kv_row_write,
        moe_experts, rmsnorm,
    )

    for kernel in (decode_attention, flash_attention, kda_step, kv_row_write,
                   moe_experts, rmsnorm):
        monkeypatch.setattr(kernel, "_use_interpret", lambda *name: False)
    # ... and compile the decode step with the options the chip gets, so
    # a compiler that no longer knows one refuses it here
    monkeypatch.setattr(platform, "is_tpu_backend", lambda: True)


def _on(chip, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree
    )


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _size(tree):
    return sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(tree))


def _assert_steps_in_place(decode, params, pool):
    """The compiled decode step writes its cache where it was handed it
    (S1): every cache leaf is an input aliased to the output that succeeds
    it, and no instruction copies a whole K or V of the pool."""
    text = decode.as_text()
    header = text.split("\n", 1)[0]
    aliases = {
        int(param): int(out) for out, param in re.findall(
            r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)", header
        )
    }
    # flattened arguments: the params' leaves, then the cache's; outputs:
    # the logits, then the cache's leaves in the same order
    first, leaves = len(jax.tree.leaves(params)), jax.tree.leaves(pool)
    assert aliases == {first + i: 1 + i for i in range(len(leaves))}
    # (the device pads each small index leaf to 512 bytes)
    aliased = decode.memory_analysis().alias_size_in_bytes
    assert _size(pool) <= aliased <= _size(pool) + 512 * len(leaves)
    kv = {",".join(map(str, s.shape)) for s in leaves if s.ndim == 4}
    assert len(kv) == 1  # one K/V shape: [slots, kv_heads, max_seq_len, 128]
    assert not re.search(rf"= \w+\[{kv.pop()}\]\S* copy\(", text)
    # so the step holds the pool once: weights + pool + its temporaries
    mem = decode.memory_analysis()
    assert mem.temp_size_in_bytes < 0.1e9
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes
            ) < _size(params) + _size(pool) + 0.1e9


def _assert_prefill_attends_to_the_prompt_alone(text, h, hk, max_seq_len, s=512):
    """The compiled prefill of an ``s``-token prompt into the cache it makes
    holds no score of the prompt against every position of that cache (the
    compiler drops the batch of one), and no float32 copy of the cache,
    whole or repeated over the query group (S4(b)). With a KV head a query
    head that copy's shape is also one the row write's own fusion names, a
    rotated key's halves joined in float32 and never stored: not asked."""
    assert not re.search(rf"f32\[(1,)?{h},{s},{max_seq_len}\]", text)
    if hk != h:
        assert not re.search(rf"f32\[1,({h}|{hk}),{max_seq_len},128\]", text)


def _assert_rows_written_by_the_kernel(text, layers, pool):
    """The decode step stores its new cache rows through ``kv_row_write``,
    one call a layer, and not as the loop a scatter compiles to: no
    ``while`` carries a leaf of the pool, and no ``dynamic-update-slice``
    has a leaf's shape."""
    assert len(re.findall(r"= \([^\n]*? custom-call\([^\n]*kv_row_write", text)
               ) == layers
    for shape in {s.shape for s in jax.tree.leaves(pool) if s.ndim == 4}:
        leaf = re.escape("[" + ",".join(map(str, shape)) + "]")
        assert not re.search(rf"{leaf}[^\n]* while\(", text)
        assert not re.search(rf"= \w+{leaf}\S* dynamic-update-slice\(", text)


def _assert_no_weight_relaid_in_hbm(text, params):
    """The compiled step re-lays no whole weight HBM to HBM (ROADMAP S11(a)'s
    recipe; PERF 6, 59.2, 59.3 and PR 60): no ``copy(`` whose result has the
    element count of a two-dimensional parameter of 16 MB or more and no
    ``S(1)`` in its layout. (A weight-shaped copy *with* ``S(1)`` lands in
    VMEM and is the weight's one read.)"""
    weights = {
        s.size for s in jax.tree.leaves(params)
        if s.ndim == 2 and s.size * s.dtype.itemsize >= 16e6
    }
    relaid = [
        f"[{shape}]{layout}"
        for shape, layout in re.findall(r"= \w+\[([\d,]+)\](\S*) copy\(", text)
        if math.prod(map(int, shape.split(","))) in weights
        and "S(1)" not in layout
    ]
    assert not relaid, relaid


def _wq_readers(text):
    """What reads each layer's ``W_q`` in the compiled step. A fusion that
    takes the parameter as stored, or through its prefetch into VMEM in the
    stored order (a ``copy-start`` / ``copy-done`` pair of the parameter's
    own shape), is given as (result, ``dim_labels``) of the convolution
    inside it; any other reader by its opcode alone (``("bitcast",)``: the
    transposed view in front of a re-laying ``copy``)."""
    entry = text[text.index("\nENTRY "):]
    found = []
    for name, stored in re.findall(
        r"%(params__layer_\d+____attn____wq____base____kernel__\S*) = "
        r"(bf16\[\d+,\d+\]\{1,0)\S* parameter\(", entry
    ):
        sources, stored = [name], re.escape(stored)
        for start in re.findall(
            rf"%(\S+) = \({stored}\S*S\(1\)\}}[^\n]* copy-start\(%{name}\)", entry
        ):
            sources += re.findall(
                rf"%(\S+) = {stored}\S*S\(1\)\}} copy-done\(%{re.escape(start)}\)",
                entry)
        for source in sources:
            for op, operands in re.findall(
                rf"= \S+ ([\w\-]+)\(([^\n]*%{re.escape(source)}[,)][^\n]*)", entry
            ):
                if op == "copy-start":
                    continue
                if op != "fusion":
                    found.append((op,))
                    continue
                called = re.search(r"calls=%([\w.\-]+)", operands).group(1)
                body = re.search(
                    rf"^%{re.escape(called)} \([^\n]*\{{\n(.*?)^\}}", text,
                    re.M | re.S).group(1)
                found += re.findall(
                    r"= (bf16\[[\d,]+\])\S* convolution\([^\n]*"
                    r"dim_labels=([\w>\-]+)", body)
    return found


# Llama-2-7B's heads at chip_smoke.py's length, and what one chip of the
# `mistral7b-lora-fsdp4` cell is handed a layer (2 x 4096 tokens, 32 heads
# after the GQA repeat): its default tiles are 1024 x 1024
@pytest.mark.parametrize(
    "shape", [(1, 32, 2048, 128), (2, 32, 4096, 128)], ids=["s2048", "lora4096"]
)
def test_flash_attention_forward_and_backward_compile(
    v5e_chip, native_kernels, shape
):
    from ray_tpu.ops.flash_attention import flash_attention

    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=v5e_chip)

    def forward(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def backward(q, k, v):
        return jax.grad(
            lambda *a: forward(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    text = _compile(forward, qkv, qkv, qkv)
    assert "tpu_custom_call" in text and "flash_fwd" in text
    # dq and dk/dv are separate kernels, after the recomputed forward; a
    # device trace lists each under its name
    text = _compile(backward, qkv, qkv, qkv)
    assert text.count("tpu_custom_call") >= 3
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in text, name


@pytest.mark.parametrize("window", [4096, None], ids=["band", "causal"])
def test_flash_attention_serving_forward_compiles_under_a_window(
    v5e_chip, native_kernels, window
):
    """`commandaplus-rag-backlog`'s longest prefill a layer: 128 query heads
    on 8 K/V heads as they are, 8192 tokens, under a 4096 band in a window
    layer (1024 x 1024 tiles: at 2048 x 2048 the described chip refused the
    banded body's 19.5 MiB of VMEM) and causal in a full one; no repeated
    K/V reaches the kernel."""
    from ray_tpu.ops.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((1, 128, 8192, 128), jnp.bfloat16, sharding=v5e_chip)
    kv = jax.ShapeDtypeStruct((1, 8, 8192, 128), jnp.bfloat16, sharding=v5e_chip)
    text = _compile(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window, forward_only=True),
        q, kv, kv)
    assert "tpu_custom_call" in text and "flash_fwd" in text
    assert "bf16[128,8192,128]" in text and "bf16[8,8192,128]" in text
    assert not re.search(r"= bf16\[1,128,8192,128\]\S* (broadcast|copy)\(", text)


@pytest.mark.parametrize("window", [4096, None], ids=["band", "causal"])
def test_flash_forward_compiles_at_a_group_of_seven(
    v5e_chip, native_kernels, window
):
    """`smallthinker-chat-mixed-backlog`'s longest prefill a layer: 28 query
    heads on 4 K/V heads as they are, 4096 tokens, under the 4096 band in a
    window layer and causal in a full one."""
    from ray_tpu.ops.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((1, 28, 4096, 128), jnp.bfloat16, sharding=v5e_chip)
    kv = jax.ShapeDtypeStruct((1, 4, 4096, 128), jnp.bfloat16, sharding=v5e_chip)
    text = _compile(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window, forward_only=True),
        q, kv, kv)
    assert "tpu_custom_call" in text and "flash_fwd" in text
    assert "bf16[28,4096,128]" in text and "bf16[4,4096,128]" in text
    assert not re.search(r"= bf16\[1,28,4096,128\]\S* (broadcast|copy)\(", text)


def test_moe_experts_compiles_under_relu_at_three_row_tiles(
    v5e_chip, native_kernels
):
    """`smallthinker-chat-mixed-backlog`'s expert kernel a layer: 64 rows x
    6 experts = 384 sorted assignments (three row tiles) through 64 ReGLU
    experts of 2560 x 768, whose matrix (3.9 MB) is one block."""
    from ray_tpu.ops import moe_experts as kernel

    def on(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    assert kernel.block_f(2560, 768, jnp.bfloat16) == 768
    assert kernel.tile_rows(384) == 128
    text = _compile(
        lambda *a: kernel.moe_experts(*a, activation="reglu"),
        on((384, 2560)), on((64, 2560, 768)), on((64, 2560, 768)),
        on((64, 768, 2560)), on((64,), jnp.int32))
    assert re.search(r"%moe_experts\S* = f32\[384,2560\]", text)
    assert "tpu_custom_call" in text


def test_rmsnorm_compiles(v5e_chip, native_kernels):
    from ray_tpu.ops.rmsnorm import rmsnorm

    x = jax.ShapeDtypeStruct((2, 2048, 4096), jnp.bfloat16, sharding=v5e_chip)
    w = jax.ShapeDtypeStruct((4096,), jnp.bfloat16, sharding=v5e_chip)
    assert "tpu_custom_call" in _compile(lambda a, b: rmsnorm(a, b, 1e-5), x, w)


# (b, h, hk, max_seq_len): the chat cells' pool (Mistral-7B: 32 query heads
# over 8 KV heads of 128, 16 slots x 4096) and chip_smoke.py's (Llama-2-7B)
MISTRAL_POOL = (16, 32, 8, 4096)
LLAMA2_POOL = (8, 32, 32, 2048)
# `commandaplus-rag-backlog`'s (Command A+: 128 query heads over 8 KV heads,
# 24 slots): a window layer's ring of 4096 and a full layer's row of 10240
C2MOE_RING = (24, 128, 8, 4096)
C2MOE_ROW = (24, 128, 8, 10240)
# `smallthinker-chat-mixed-backlog`'s (SmallThinker: 28 query heads over 4 KV
# heads, a group of 7, 64 slots): a ring of 4096 and a row of 5120
STMOE_RING = (64, 28, 4, 4096)
STMOE_ROW = (64, 28, 4, 5120)
# `falconh1-chat-backlog`'s (20 / 4 heads, 64 slots x 1024) and
# `nemotron3s-reasoning-backlog`'s (32 / 2: a group of 16, two row tiles)
FALCON_H1_POOL = (64, 20, 4, 1024)
NEMOTRON_H_POOL = (64, 32, 2, 4096)


@pytest.mark.parametrize(
    "pool, visit",
    [(MISTRAL_POOL, 128), (LLAMA2_POOL, 128), (C2MOE_RING, 128),
     (C2MOE_ROW, 128), (STMOE_RING, 512), (STMOE_ROW, 512),
     (FALCON_H1_POOL, 512), (NEMOTRON_H_POOL, 1024)],
    ids=["mistral", "llama2", "c2moe_ring", "c2moe_row", "stmoe_ring",
         "stmoe_row", "falcon_h1", "nemotron_h"])
def test_decode_attention_compiles(v5e_chip, native_kernels, pool, visit):
    """... at the key positions a visit the rule gives the cell's cache:
    three buffers a cache of 512 KB where 2 or 4 K/V heads share a visit,
    inside the compiler's default scoped VMEM (no ``vmem_limit_bytes``)."""
    from ray_tpu.ops.decode_attention import decode_attention, traced_chunk

    b, h, hk, max_seq_len = pool
    q = jax.ShapeDtypeStruct((b, h, 128), jnp.bfloat16, sharding=v5e_chip)
    kv = jax.ShapeDtypeStruct(
        (b, hk, max_seq_len, 128), jnp.bfloat16, sharding=v5e_chip
    )
    lengths = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=v5e_chip)
    text = _compile(decode_attention, q, kv, kv, lengths)
    assert "tpu_custom_call" in text and "vmem_limit_bytes" not in text
    assert traced_chunk(kv.shape) == visit


@pytest.mark.parametrize(
    "leaves",
    [[(16, 8, 4096, 128)] * 2, [(8, 16, 4096, 128)] * 2,
     [(24, 1, 8192, 512), (24, 1, 8192, 64)], [(64, 4, 4096, 128)] * 2],
    ids=["mistral", "olmoe", "moonlight", "stmoe_ring"],
)
def test_kv_row_write_compiles_in_place(v5e_chip, native_kernels, leaves):
    """A layer's leaves at each cell's shapes: one kernel, each leaf
    aliased to its output and none copied or transposed on its way in or
    out (the 64-wide leaf, stored sequence-minor, is written in that
    view)."""
    from ray_tpu.ops.kv_row_write import write_rows

    def on(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    step = jax.jit(write_rows, donate_argnums=(0,)).lower(
        [on(s) for s in leaves],
        [on(s[:2] + (1,) + s[3:]) for s in leaves], on(leaves[0][:1], jnp.int32),
    ).compile()
    text = step.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "kv_row_write" in text and " while(" not in text
    assert not re.search(
        r"= \w+\[\d+,\d+,(\d+,[48]\d\d\d|[48]\d\d\d,\d+)\]\S* (copy|transpose)\(", text)
    size = sum(2 * b * h * s * w for b, h, s, w in leaves)
    assert step.memory_analysis().alias_size_in_bytes == size
    assert step.memory_analysis().temp_size_in_bytes < 1e6


@pytest.mark.parametrize(
    "block, max_seq_len, pools",
    [(32, 4096, [(1536, 8, 32, 128)] * 24),
     (128, 8192, [(1536, 1, 128, 512), (1536, 1, 128, 64)] * 7)],
    ids=["mistral", "moonlight"],
)
def test_commit_program_writes_the_pools_in_place(
        v5e_chip, block, max_seq_len, pools):
    """The KV manager's one commit program at a cell's pools (S9): every
    pool an input aliased to its output, none copied, and nothing held
    beside them but a block's worth of scratch. A program that copied
    2.4 GB a commit would turn the host's idle into the device's busy."""
    from ray_tpu.kvcache.manager import commit_program

    def on(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    rows = [(1, p[1], max_seq_len, p[3]) for p in pools]
    step = commit_program(block).lower(
        [on(p) for p in pools], [on(r) for r in rows],
        on((max_seq_len // block, 2), jnp.int32), on((), jnp.int32),
    ).compile()
    text = step.as_text()
    aliases = re.findall(
        r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)", text.split("\n", 1)[0])
    assert sorted((int(o), int(i)) for o, i in aliases) == [
        (i, i) for i in range(len(pools))]
    for shape in set(pools):
        leaf = re.escape("[" + ",".join(map(str, shape)) + "]")
        assert not re.search(rf"= \w+{leaf}\S* copy\(", text)
    mem = step.memory_analysis()
    size = sum(2 * n * h * b * w for n, h, b, w in pools)
    assert mem.alias_size_in_bytes == size
    assert mem.temp_size_in_bytes < min(2 * h * s * w for _, h, s, w in rows)
    assert text.count(" while(") == 1  # the count is data: one loop, no unrolling


def test_decode_attention_compiles_per_shard_under_tp4(v5e_host, native_kernels):
    """The chat cells' pool with its KV heads over four chips, as
    PartitionPlan lays the decode cache out: two KV heads a chip."""
    from jax.sharding import NamedSharding

    from ray_tpu.ops.decode_attention import decode_attention
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.parallel.plan import KV_SPEC

    b, h, hk, max_seq_len = MISTRAL_POOL
    mesh = make_mesh(tp=4, fsdp=1, devices=v5e_host)

    def on(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*spec))
        )

    q = on((b, h, 128), jnp.bfloat16, None, "tp", None)
    kv = on((b, hk, max_seq_len, 128), jnp.bfloat16, *KV_SPEC)
    text = _compile(
        lambda *a: decode_attention(*a, mesh=mesh),
        q, kv, kv, on((b,), jnp.int32),
    )
    # each chip's kernel sees its own 2 of the 8 KV heads, and nothing is
    # gathered to run it
    assert f"bf16[{b},{hk // 4},8,128]" in text
    assert "all-gather" not in text and "all-reduce" not in text


DENSE_7B = pytest.mark.parametrize(
    "pool,widths",
    [(MISTRAL_POOL, dict(vocab_size=32768, intermediate=14336, rope_theta=1e6)),
     (LLAMA2_POOL, dict(vocab_size=32000, intermediate=11008))],
    ids=["mistral", "llama2"],
)


@pytest.fixture(scope="module")
def compiled():
    """What one test of a pair compiled, kept for its sibling."""
    return {}


def _decode_step(chip, cfg, slots):
    """The engine's own ``_decode`` for ``cfg`` (the jit object) with the
    operands a pooled step of ``slots`` rows hands it, as shapes on
    ``chip``: ``(model, params, pool, args, kwargs)`` with ``active`` rows
    and a routed model's running expert counts among the keywords."""
    from ray_tpu.llm.engine import _DecodeModelBase, _new_expert_counts
    from ray_tpu.models import init_params
    from ray_tpu.parallel.sharding import unbox_params

    params = jax.eval_shape(
        lambda k: unbox_params(init_params(cfg, k)), jax.random.PRNGKey(0)
    )
    model = _DecodeModelBase(cfg, None)
    prompt = jax.ShapeDtypeStruct((1, 512), jnp.int32)
    row = jax.eval_shape(model._prefill_impl, params, prompt)[1]
    pool = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((slots,) + s.shape[1:], s.dtype), row
    )
    last = jax.ShapeDtypeStruct((slots, 1), jnp.int32)
    active = jax.ShapeDtypeStruct((slots,), jnp.bool_)
    counts = jax.eval_shape(lambda: _new_expert_counts(cfg, slots))
    counted = {} if counts is None else {"expert_counts": _on(chip, counts)}
    args = (_on(chip, params), _on(chip, pool), _on(chip, last))
    return model, params, pool, args, dict(active=_on(chip, active), **counted)


def _compiled_decode(compiled, chip, cfg, slots):
    """``_decode_step``'s program on a described chip, compiled once:
    (model, params, pool, args, decode)."""
    key = "decode", repr((cfg, slots))
    if key not in compiled:
        model, params, pool, args, kwargs = _decode_step(chip, cfg, slots)
        decode = model._decode.lower(*args, **kwargs).compile()
        compiled[key] = model, params, pool, args, decode
    return compiled[key]


def _serving_programs(compiled, chip, cfg, slots):
    """The serving engine's two programs for ``cfg`` on a described chip,
    compiled once: the prefill function over a 512-token prompt, and the
    engine's own ``_decode`` as a pooled step calls it (``_decode_step``).
    Returns (prefill, decode, params, pool), the last two as shapes."""
    key = repr((cfg, slots))
    if key in compiled:
        return compiled[key]
    model, params, pool, args, decode = _compiled_decode(
        compiled, chip, cfg, slots)
    prompt = jax.ShapeDtypeStruct((1, 512), jnp.int32)
    prefill = jax.jit(model._prefill_impl).lower(
        args[0], _on(chip, prompt)
    ).compile()
    compiled[key] = prefill, decode, params, pool
    return compiled[key]


def _dense_7b_programs(compiled, chip, pool, widths):
    """At 7B widths, two layers deep."""
    from ray_tpu.models.llama import LlamaConfig

    slots, h, hk, max_seq_len = pool
    cfg = LlamaConfig(
        dim=4096, n_layers=2, n_heads=h, n_kv_heads=hk,
        max_seq_len=max_seq_len, param_dtype=jnp.bfloat16, **widths,
    )
    return _serving_programs(compiled, chip, cfg, slots)


@DENSE_7B
def test_decode_model_prefill_and_decode_compile(
    v5e_chip, native_kernels, compiled, pool, widths
):
    slots, h, hk, max_seq_len = pool
    prefill, decode, _, cache = _dense_7b_programs(
        compiled, v5e_chip, pool, widths
    )
    prefill_temp = prefill.memory_analysis().temp_size_in_bytes
    prefill, decode = prefill.as_text(), decode.as_text()
    # the prefill attends by einsum, over the prompt's own 512 keys:
    # rmsnorm is its only kernel (two a layer and the final one); a decode
    # step adds the attention kernel a layer and the cache write
    kernel = 'custom_call_target="tpu_custom_call"'
    assert prefill.count(kernel) == 5
    assert decode.count(kernel) == 9
    _assert_rows_written_by_the_kernel(decode, 2, cache)
    _assert_prefill_attends_to_the_prompt_alone(prefill, h, hk, max_seq_len)
    # what a 512-token prompt's two layers hold beside weights and row: 46
    # and 70 MB (Mistral's einsum over the cache's 4096 positions: 313)
    assert prefill_temp < 0.1e9
    # and holds no f32 copy of a cache, whole or expanded over the group
    assert not re.search(
        rf"f32\[{slots},{hk},(\d+,)?{max_seq_len},128\]", decode
    )


def _mistral_chat_step():
    """The chat cells' decode step: (12 layers at Mistral's widths, 16 slots)."""
    from ray_tpu.models.llama import LlamaConfig

    slots, h, hk, max_seq_len = MISTRAL_POOL
    return LlamaConfig(
        dim=4096, n_layers=12, n_heads=h, n_kv_heads=hk,
        max_seq_len=max_seq_len, param_dtype=jnp.bfloat16,
        vocab_size=32768, intermediate=14336, rope_theta=1e6,
    ), slots


def test_a_twelve_layer_decode_step_lowers_the_kernel_once(
    v5e_chip, native_kernels
):
    """What keeps ``setup_s``: the attention kernel sits under a jit of its
    own, so the chat cells' program (12 layers, one shape) traces and
    lowers it once and calls that twelve times; as an op of the layer it
    was lowered twelve times over (+19 s of set-up, ROADMAP S11(c))."""
    cfg, slots = _mistral_chat_step()
    model, _, _, args, kwargs = _decode_step(v5e_chip, cfg, slots)
    text = model._decode.lower(*args, **kwargs).as_text()
    assert len(re.findall(r"func\.func private @_attend\b", text)) == 1
    assert len(re.findall(r"call @_attend\b", text)) == 12
    # the one kernel body in the text is that function's
    assert len(re.findall(r'kernel_name = "decode_attention"', text)) == 1


@DENSE_7B
def test_decode_step_donates_its_cache(
    v5e_chip, native_kernels, compiled, pool, widths
):
    """S1: the step the engine calls aliases all of its cache, copies none
    of it, and so holds the pool once, not twice."""
    _, decode, params, cache = _dense_7b_programs(
        compiled, v5e_chip, pool, widths
    )
    _assert_steps_in_place(decode, params, cache)


def _olmoe_programs(compiled, chip):
    """The `olmoe-chat-backlog` cell's programs at its shapes: 8 slots x
    4096, 8 layers of 64 experts at the published widths."""
    from ray_tpu.models.moe import MoEConfig

    cfg = MoEConfig(
        vocab_size=50304, dim=2048, n_layers=8, n_heads=16, n_kv_heads=16,
        intermediate=1024, n_experts=64, experts_per_token=8,
        max_seq_len=4096, rope_theta=10000.0, param_dtype=jnp.bfloat16,
        dropless=True, norm_topk_prob=False, qk_norm=True, remat=False,
    )
    return (cfg,) + _serving_programs(compiled, chip, cfg, 8)


def test_olmoe_prefill_and_decode_compile(v5e_chip, native_kernels, compiled):
    """The cell's two programs hold the kernels they should and no
    capacity tensor anywhere, inside the configuration file's budget."""
    from ray_tpu.parallel.expert import expert_capacity

    cfg, prefill, decode, params, pool = _olmoe_programs(compiled, v5e_chip)
    slots, layers, experts, k = 8, 8, 64, 8
    kernel = 'custom_call_target="tpu_custom_call"'
    # a layer: four rmsnorms (two of them q_norm and k_norm) and the
    # grouped experts, in decode the cache write and the attention kernel
    # too; one final norm
    assert prefill.as_text().count(kernel) == 5 * layers + 1
    assert decode.as_text().count(kernel) == 7 * layers + 1
    _assert_prefill_attends_to_the_prompt_alone(
        prefill.as_text(), cfg.n_heads, cfg.n_kv_heads, cfg.max_seq_len)
    _assert_rows_written_by_the_kernel(decode.as_text(), layers, pool)
    for program, tokens in ((prefill, 512), (decode, slots)):
        capacity = expert_capacity(tokens, experts, cfg.capacity_factor, k)
        assert not re.search(
            rf"\[{tokens},{experts},{capacity}\]", program.as_text()
        )
    # the chip holds weights 7.12 GB, the slot cache of 2.15 GB once (S1:
    # the step donates it and writes in place; the sibling test holds the
    # step to weights + cache + 0.1 GB) and the pool's 1.61 GB; the step's
    # own temporaries and a 512-token prefill's must be small beside that
    assert 7.0e9 < _size(params) < 7.2e9
    assert 2.1e9 < _size(pool) < 2.2e9
    assert decode.memory_analysis().temp_size_in_bytes < 0.1e9
    # (26 MB; 0.5e9 was the bound while the prompt scored all 4096 positions)
    assert prefill.memory_analysis().temp_size_in_bytes < 0.05e9


def test_olmoe_decode_step_donates_its_cache(
    v5e_chip, native_kernels, compiled
):
    """S1 for the routed model: 8 layers' K, V and indices all aliased
    (the expert counts ride through undonated), no [8,16,4096,128] copy,
    and a footprint of params + pool, not params + 2 x pool."""
    _, _, decode, params, pool = _olmoe_programs(compiled, v5e_chip)
    _assert_steps_in_place(decode, params, pool)


def _moonlight_programs(compiled, chip):
    """The `moonlight-longctx-backlog` cell's programs at its widths, two
    layers deep (the dense one and a routed one): 24 slots x 8192."""
    from ray_tpu.models.deepseek import DeepseekConfig

    cfg = DeepseekConfig(n_layers=2, param_dtype=jnp.bfloat16)
    return (cfg,) + _serving_programs(compiled, chip, cfg, 24)


def test_moonlight_prefill_and_decode_compile(v5e_chip, native_kernels, compiled):
    """The real compiler takes the latent kernel at 16 heads on a 576-wide
    row and the grouped experts at an inner width of 1408; the step donates the latent rows and holds no copy and no
    up-projected key or value of them."""
    cfg, prefill, decode, params, pool = _moonlight_programs(compiled, v5e_chip)
    kernel = 'custom_call_target="tpu_custom_call"'
    # rmsnorms: two a layer, the latent's, the final one; the routed
    # layer's experts; in decode the cache write and the latent kernel a
    # layer
    assert prefill.as_text().count(kernel) == 3 * 2 + 1 + 1
    assert decode.as_text().count(kernel) == 5 * 2 + 1 + 1
    assert "latent_decode_attention" in decode.as_text()
    _assert_rows_written_by_the_kernel(decode.as_text(), 2, pool)
    kv = sorted(s.shape for s in jax.tree.leaves(pool) if s.ndim == 4)
    assert kv == [(24, 1, 8192, 64)] * 2 + [(24, 1, 8192, 512)] * 2
    text = decode.as_text()
    # no key or value up-projected over the cache, and no copy of either
    # leaf: the 512-wide one is row-major as stored, and the 64-wide one,
    # which the TPU stores sequence-minor, is read as (64, seq) (a single
    # 576-wide leaf was transposed whole every step)
    assert not re.search(r"\[24,(16,)?8192,(16,)?(128|192|256)\]", text)
    assert not re.search(r"= \w+\[24,1,(8192,\d+|\d+,8192)\]\S* (copy|transpose)\(", text)
    header = text.split("\n", 1)[0]
    aliases = re.findall(r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(aliases) == len(jax.tree.leaves(pool))  # every leaf in place
    mem = decode.memory_analysis()
    assert mem.temp_size_in_bytes < 0.1e9
    assert _size(pool) <= mem.alias_size_in_bytes <= _size(pool) + 512 * len(aliases)


def test_falcon_h1_decode_step_carries_its_state_in_place(
    v5e_chip, native_kernels, compiled
):
    """`falconh1-chat-backlog`'s programs at its widths, two blocks deep, 64
    slots x 1024: every cache leaf, the mixer's float32 state among them,
    is an input aliased to the output that succeeds it; the state update is
    one fusion a layer that reads the state once and writes it once (the
    free rows' zeroing fused into it), so no Pallas kernel was written for
    it; no instruction copies a state."""
    from ray_tpu.models.falcon_h1 import FalconH1Config

    cfg = FalconH1Config(n_layers=2, param_dtype=jnp.bfloat16, max_seq_len=1024)
    prefill, decode, params, pool = _serving_programs(compiled, v5e_chip, cfg, 64)
    text = decode.as_text()
    header = text.split("\n", 1)[0]
    aliases = re.findall(r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(aliases) == len(jax.tree.leaves(pool)) == 2 * 5
    state = r"f32\[64,32,128,256\]"
    assert not re.search(rf"= {state}\S* copy\(", text)
    updates = re.findall(
        rf"= \(f32\[64,32,128\]\S*, {state}\S*\) fusion\(", text)
    assert len(updates) == 2  # one a layer: (y, the new state)
    # nothing else produces a whole state (a second pass would)
    entry = text[text.index("\nENTRY "):]
    produced = re.findall(rf"^\s*%\S+ = {state}\S* (\w[\w\-]*)\(", entry, re.M)
    assert set(produced) <= {"get-tuple-element", "parameter", "bitcast"}, produced
    _assert_rows_written_by_the_kernel(text, 2, {
        "k": s for s in jax.tree.leaves(pool) if s.shape == (64, 4, 1024, 128)})
    mem = decode.memory_analysis()
    assert mem.temp_size_in_bytes < 0.1e9
    assert _size(pool) <= mem.alias_size_in_bytes <= _size(pool) + 512 * len(aliases)
    # 5.35 GB of embedding and head + 2 x 0.86 GB; 64 rows x 2 x 6.3 MB
    assert 7.0e9 < _size(params) < 7.1e9
    assert 0.80e9 < _size(pool) < 0.82e9
    assert prefill.memory_analysis().temp_size_in_bytes < 0.5e9


def _solar_open2_step():
    """`solar2-longgen-backlog`'s step a GQA and a KDA layer deep: (config,
    32 slots)."""
    from ray_tpu.models.solar_open2 import SolarOpen2Config

    return SolarOpen2Config(
        vocab_size=24576, n_layers=2, gqa_layers=(0,), experts_held=(0, 40),
        param_dtype=jnp.bfloat16, max_seq_len=2048), 32


def test_solar_open2_decode_step_moves_its_state_once(
    v5e_chip, native_kernels, compiled
):
    """`solar2-longgen-backlog`'s programs at its widths, a GQA and a KDA
    layer deep, 32 slots x 2048, 40 of 320 experts held: every cache leaf is
    an input aliased to the output that succeeds it; the delta rule is one
    `kda_step` call a KDA layer whose state operand is the cache's own
    parameter (the engine zeroes nothing in front of it: the family reads a
    fresh row's state as zero inside the kernel), and no other instruction
    produces or copies a state; the expert kernel is handed 40 experts'
    weights."""
    prefill, decode, params, pool = _serving_programs(
        compiled, v5e_chip, *_solar_open2_step())
    text = decode.as_text()
    header = text.split("\n", 1)[0]
    aliases = re.findall(r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)", header)
    # K, V and their index; the state, the tail and their index
    assert len(aliases) == len(jax.tree.leaves(pool)) == 6
    state = r"f32\[32,64,128,128\]"
    entry = text[text.index("\nENTRY "):]
    calls = re.findall(
        rf"%kda_step\S* = \({state}\S*, f32\[32,2,32,128\]\S*\) "
        r"custom-call\(([^)]*)\)", entry)
    assert len(calls) == 1
    assert "state_kda" in calls[0].split(", ")[1]  # the parameter itself
    assert not re.search(rf"= {state}\S* copy\(", text)
    _assert_no_weight_relaid_in_hbm(text, params)
    produced = re.findall(rf"^\s*%\S+ = {state}\S* (\w[\w\-]*)\(", entry, re.M)
    assert set(produced) <= {"get-tuple-element", "parameter", "bitcast"}, produced
    assert not re.search(rf"= \([^=]*{state}[^=]*\) fusion\(", entry)
    assert len(re.findall(r"%moe_experts\S* = f32\[256,4096\]", entry)) == 2  # 32 x 8 in two tiles
    assert "bf16[40,4096,1280]" in entry and "bf16[320,4096,1280]" not in text
    _assert_rows_written_by_the_kernel(text, 1, {
        "k": s for s in jax.tree.leaves(pool) if s.shape == (32, 8, 2048, 128)})
    mem = decode.memory_analysis()
    assert mem.temp_size_in_bytes < 0.1e9
    assert _size(pool) <= mem.alias_size_in_bytes <= _size(pool) + 512 * len(aliases)
    # 0.40 GB of embedding and head; a KDA layer 0.28 and a GQA layer 0.22 GB
    # of mixer; 2 x (40 experts 1.26 + shared and router 0.03)
    assert 3.4e9 < _size(params) < 3.6e9
    # 32 rows x (4.19 MB of state + 0.15 of tail + 8.39 of K and V)
    assert 0.40e9 < _size(pool) < 0.41e9
    assert prefill.memory_analysis().temp_size_in_bytes < 0.6e9


def test_nemotron_h_decode_step_at_a_group_of_sixteen_and_two_matrices(
    v5e_chip, native_kernels, compiled
):
    """`nemotron3s-reasoning-backlog`'s programs at its widths, one layer of
    each kind (`M*E`), 64 slots x 4096, 128 of 512 experts held: a layer
    keeps the leaves of its kind and the expert layer none, each aliased to
    its successor; the decode kernel takes 32 query heads over 2 KV heads
    (two row tiles a group: the described chip refused the kernel's 8-row
    mask here before any chip call); the expert kernel is handed two
    matrices of 128 experts in the 1024-wide latent and 1408 assignments;
    the state update is one fusion, as Falcon-H1's."""
    from ray_tpu.models.nemotron_h import NemotronHConfig

    cfg = NemotronHConfig(
        vocab_size=32768, pattern="M*E", experts_held=(0, 128),
        param_dtype=jnp.bfloat16, max_seq_len=4096)
    prefill, decode, params, pool = _serving_programs(compiled, v5e_chip, cfg, 64)
    assert set(pool) == {"layer_0", "layer_1"}  # the expert layer keeps nothing
    text = decode.as_text()
    header = text.split("\n", 1)[0]
    aliases = re.findall(r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(aliases) == len(jax.tree.leaves(pool)) == 2 + 3
    entry = text[text.index("\nENTRY "):]
    state = r"f32\[64,128,64,128\]"
    assert not re.search(rf"= {state}\S* copy\(", text)
    assert len(re.findall(
        rf"= \(f32\[64,128,64\]\S*, {state}\S*\) fusion\(", text)) == 1
    assert len(re.findall(r"%decode_attention\S* = bf16\[64,2,16,128\]", entry)) == 1
    calls = re.findall(
        r"%moe_experts\S* = f32\[1408,1024\]\S* custom-call\(([^)]*)\)", entry)
    # the grid's extent, the schedule's three arrays, the rows, two matrices
    operands = calls[0].split(", ")
    assert len(calls) == 1 and len(operands) == 1 + 3 + 1 + 2
    assert [o.split("moe____")[-1][:4] for o in operands[-2:]] == ["w_up", "w_do"]
    assert "bf16[128,1024,2688]" in entry and "bf16[128,2688,1024]" in entry
    assert "bf16[512,1024,2688]" not in text
    _assert_rows_written_by_the_kernel(text, 1, {
        "k": s for s in jax.tree.leaves(pool) if s.shape == (64, 2, 4096, 128)})
    mem = decode.memory_analysis()
    assert mem.temp_size_in_bytes < 0.1e9
    assert _size(pool) <= mem.alias_size_in_bytes <= _size(pool) + 512 * len(aliases)
    # 0.54 GB of embedding and head; a mixer layer 0.22, the attention layer
    # 0.07, the expert layer 128 x 11.0 MB + 0.11 GB
    assert 2.3e9 < _size(params) < 2.4e9
    # 64 rows x (4.26 MB of state and tail + 4.19 of K and V)
    assert 0.53e9 < _size(pool) < 0.55e9
    assert prefill.memory_analysis().temp_size_in_bytes < 0.5e9


def _cohere2_moe_step():
    """`commandaplus-rag-backlog`'s step one layer of each kind deep
    (``layer_switch`` 2): (config, 24 slots)."""
    from ray_tpu.models.cohere2_moe import Cohere2MoEConfig

    return Cohere2MoEConfig(
        vocab_size=32768, n_layers=2, layer_switch=2, experts_held=(0, 16),
        param_dtype=jnp.bfloat16, max_seq_len=10240), 24


def test_cohere2_moe_decode_step_reads_rings_and_model_wide_experts(
    v5e_chip, native_kernels, compiled
):
    """`commandaplus-rag-backlog`'s programs at its widths, one layer of
    each kind (``layer_switch`` 2), 24 slots x 10240, 16 of 128 experts
    held: a window layer keeps two rings of 4096 and a full layer two rows
    of 10240, each aliased to its successor and written by the row kernel;
    the decode kernel takes 128 query heads over 8 KV heads on both; the
    expert kernel is handed three 4096 x 4096 matrices of 16 experts (its
    blocking of a 4096-wide inner had never met the chip's compiler); a
    2048-token prefill goes through the flash kernel in both layers, K/V
    heads as they are."""
    model, params, pool, args, decode = _compiled_decode(
        compiled, v5e_chip, *_cohere2_moe_step())
    assert set(pool["layer_0"]["attn"]) == {
        "window_key", "window_value", "cache_index"}
    assert pool["layer_0"]["attn"]["window_key"].shape == (24, 8, 4096, 128)
    assert pool["layer_1"]["attn"]["cached_key"].shape == (24, 8, 10240, 128)
    text = decode.as_text()
    header = text.split("\n", 1)[0]
    aliases = re.findall(r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(aliases) == len(jax.tree.leaves(pool)) == 2 * 3
    entry = text[text.index("\nENTRY "):]
    assert len(re.findall(r"%decode_attention\S* = bf16\[24,8,16,128\]", entry)) == 2
    assert len(re.findall(r"%kv_row_write\S* = \(bf16\[24,8,4096,128\]", entry)) == 1
    assert len(re.findall(r"%kv_row_write\S* = \(bf16\[24,8,10240,128\]", entry)) == 1
    calls = re.findall(
        r"%moe_experts\S* = f32\[\d+,4096\]\S* custom-call\(([^)]*)\)", entry)
    operands = calls[0].split(", ")
    assert len(calls) == 2
    assert [o.split("moe____")[-1][:6] for o in operands[-3:]] == [
        "w_gate", "w_up__", "w_down"]
    assert entry.count("bf16[16,4096,4096]") >= 6
    assert "bf16[128,4096,4096]" not in text
    for ring in ("24,8,4096,128", "24,8,10240,128"):
        assert not re.search(rf"= bf16\[{ring}\]\S* copy\(", text)
    _assert_no_weight_relaid_in_hbm(text, params)
    mem = decode.memory_analysis()
    assert mem.temp_size_in_bytes < 0.3e9
    assert _size(pool) <= mem.alias_size_in_bytes <= _size(pool) + 512 * len(aliases)
    # 0.27 GB of tied embedding; a layer 0.689 GB + 16 x 100.7 MB
    assert 4.85e9 < _size(params) < 4.9e9
    # 24 rows x (2 x 8.4 MB of rings + 2 x 21.0 of K and V)
    assert 1.4e9 < _size(pool) < 1.42e9
    prompt = jax.ShapeDtypeStruct((1, 2048), jnp.int32)
    prefill = jax.jit(model._prefill_impl).lower(
        args[0], _on(v5e_chip, prompt)).compile()
    text = prefill.as_text()
    assert len(re.findall(r"%flash_fwd\S* = \(bf16\[128,2048,128\]", text)) == 2
    assert "f32[1,128,2048,2048]" not in text
    assert prefill.memory_analysis().temp_size_in_bytes < 0.8e9


def _smallthinker_step():
    """`smallthinker-chat-mixed-backlog`'s step one layer of each kind deep
    (``layer_period`` 2: the full layer first): (config, 64 slots)."""
    from ray_tpu.models.smallthinker import SmallThinkerConfig

    return SmallThinkerConfig(
        n_layers=2, layer_period=2, experts_held=(0, 64),
        param_dtype=jnp.bfloat16, max_seq_len=5120), 64


def test_smallthinker_decode_step_routes_ahead_and_reads_every_expert(
    v5e_chip, native_kernels, compiled
):
    """`smallthinker-chat-mixed-backlog`'s programs at its widths, one layer
    of each kind, 64 slots x 5120, all 64 experts held, the whole
    vocabulary: the full layer keeps two rows of 5120 and the window layer
    two rings of 4096, each aliased to its successor and written by the row
    kernel; the decode kernel takes 28 query heads as 4 groups of 7 (in 8 sublanes) on both;
    the expert kernel is handed 384 sorted rows (three tiles) and three
    matrices of 64 experts of 2560 x 768; the router's float32 product
    carries the family's scope around ``MoEFFN``'s own; no weight is re-laid
    in HBM; a 4096-token prefill goes through the flash kernel in both
    layers, K/V heads as they are."""
    model, params, pool, args, decode = _compiled_decode(
        compiled, v5e_chip, *_smallthinker_step())
    assert set(pool["layer_1"]["attn"]) == {
        "window_key", "window_value", "cache_index"}
    assert pool["layer_0"]["attn"]["cached_key"].shape == (64, 4, 5120, 128)
    assert pool["layer_1"]["attn"]["window_key"].shape == (64, 4, 4096, 128)
    text = decode.as_text()
    header = text.split("\n", 1)[0]
    aliases = re.findall(r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)", header)
    assert len(aliases) == len(jax.tree.leaves(pool)) == 2 * 3
    entry = text[text.index("\nENTRY "):]
    # (a group of 7 rides in 8 sublanes; a visit holds four 128-key pieces
    # of the ring and of the row: 3 MB of scratch a call)
    assert len(re.findall(r"%decode_attention\S* = bf16\[64,4,8,128\]", entry)) == 2
    from ray_tpu.ops.decode_attention import traced_chunk

    assert traced_chunk((64, 4, 4096, 128)) == traced_chunk((64, 4, 5120, 128)) == 512
    assert len(re.findall(r"%kv_row_write\S* = \(bf16\[64,4,4096,128\]", entry)) == 1
    assert len(re.findall(r"%kv_row_write\S* = \(bf16\[64,4,5120,128\]", entry)) == 1
    calls = re.findall(
        r"%moe_experts\S* = f32\[384,2560\]\S* custom-call\(([^)]*)\)", entry)
    assert len(calls) == 2
    assert [o.split("moe____")[-1][:6] for o in calls[0].split(", ")[-3:]] == [
        "w_gate", "w_up__", "w_down"]
    for scope in ("sthink.route/moe.route", "moe.experts/moe.sort",
                  "sthink.attn_full", "sthink.attn_window", "sthink.norm"):
        assert scope in text, scope
    for kept in ("64,4,4096,128", "64,4,5120,128"):
        assert not re.search(rf"= bf16\[{kept}\]\S* copy\(", text)
    _assert_no_weight_relaid_in_hbm(text, params)
    mem = decode.memory_analysis()
    assert mem.temp_size_in_bytes < 0.1e9
    assert _size(pool) <= mem.alias_size_in_bytes <= _size(pool) + 512 * len(aliases)
    # 2 x 0.778 GB of embedding and head; a layer 42.3 MB + 64 x 11.8 MB
    assert 3.14e9 < _size(params) < 3.16e9
    # 64 rows x (2 x 4.19 MB of rings + 2 x 5.24 of K and V)
    assert 1.2e9 < _size(pool) < 1.22e9
    prompt = jax.ShapeDtypeStruct((1, 4096), jnp.int32)
    prefill = jax.jit(model._prefill_impl).lower(
        args[0], _on(v5e_chip, prompt)).compile()
    text = prefill.as_text()
    assert len(re.findall(r"%flash_fwd\S* = \(bf16\[28,4096,128\]", text)) == 2
    assert "f32[1,28,4096,4096]" not in text
    # 1.24 GB of them every position's logits (ROADMAP S4: a head over the
    # last position only)
    assert 1.2e9 < prefill.memory_analysis().temp_size_in_bytes < 1.6e9


@pytest.mark.parametrize(
    "step,held",
    [(_cohere2_moe_step, True), (_solar_open2_step, True),
     (_mistral_chat_step, False)],
    ids=["commandaplus_128MiB", "solar2_64MiB", "mistral_32MiB"],
)
def test_a_query_projection_too_wide_to_stage_reads_its_weight_as_stored(
    v5e_chip, native_kernels, compiled, step, held
):
    """Each side of ``llama.holds_projection`` as a compiled plan. The
    decode kernel takes the query by K/V head and group, and the compiler
    makes that order by re-laying ``W_q``. Mistral's 32 MiB it stages in
    VMEM for the matmul anyway: those copies are the weights' one read
    (``S(1)``; layer 0's have no step before them to hide behind), nothing
    is held, and the plan is the one the chat cells have run since PR 31.
    Solar-Open2's 64 MiB and Command A+'s 128 it does not stage: the copy
    was an HBM round trip of the whole weight every step (0.31 and 1.63 ms,
    PERF 6, 59.3), so there the projection's output is held as the matmul
    made it: ``W_q`` is read once, as stored, by a plain convolution, and
    the heads' order is made on the activation."""
    cfg, slots = step()
    _, params, _, _, decode = _compiled_decode(compiled, v5e_chip, cfg, slots)
    text = decode.as_text()
    h, hk, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    readers = _wq_readers(text)
    copies = collections.Counter(
        (shape, "S(1)" in layout) for shape, layout in re.findall(
            r"= bf16\[(\d+,4096)\](\S*) copy\(", text)
    )
    if held:
        _assert_no_weight_relaid_in_hbm(text, params)
        assert readers and set(readers) == {
            (f"bf16[{slots},{h * d}]", "bf_io->bf")}
        assert not re.search(
            rf"= bf16\[{slots},{hk},{h // hk},{d}\]\S* convolution\(", text)
        # k and v are left alone: read into VMEM, once
        assert copies == {("1024,4096", True): 2 * len(readers)}
    else:
        assert copies == {
            ("4096,4096", True): 11, ("4096,4096", False): 1,
            ("1024,4096", True): 22, ("1024,4096", False): 2,
        }
        # the projection's result comes out by head: the weight is re-laid
        assert len(re.findall(
            rf"= bf16\[{slots},{h},{d}\]\S* convolution\(", text)) == cfg.n_layers
        assert readers == [("bitcast",)] * cfg.n_layers
        assert text.count(" slice-start(") == 88  # its prefetch plan (S11(e))


# ---------------------------------------------------------------------------
# 2. one process per chip, no fallback
# ---------------------------------------------------------------------------


def test_tpu_lease_owns_its_chips(ray_start_regular):
    """A num_tpus=1 task runs in a worker granted exactly one chip id, two
    concurrent ones get different chips, and a CPU task gets none."""

    @ray_tpu.remote(num_cpus=0, num_tpus=1)
    def chip_task(hold_s):
        import os
        import time

        time.sleep(hold_s)  # overlap with the other lease
        return ray_tpu.get_tpu_ids(), os.getpid(), os.environ["JAX_PLATFORMS"]

    @ray_tpu.remote(num_cpus=1)
    def cpu_task():
        import os

        return ray_tpu.get_tpu_ids(), os.environ["JAX_PLATFORMS"]

    (ids_a, pid_a, plat_a), (ids_b, pid_b, _) = ray_tpu.get(
        [chip_task.remote(1.0), chip_task.remote(1.0)], timeout=60
    )
    assert len(ids_a) == 1 and len(ids_b) == 1
    assert ids_a != ids_b and pid_a != pid_b
    # tests force the CPU platform, and a chip worker inherits that
    assert plat_a == "cpu"
    assert ray_tpu.get(cpu_task.remote(), timeout=60) == ([], "cpu")
    assert ray_tpu.get_tpu_ids() == []  # the driver owns no chip


def test_visible_chips_env():
    whole = accelerators.set_visible_chips([0, 1, 2, 3], 4)
    assert whole == {accelerators.GRANTED_CHIPS_ENV: "0,1,2,3"}
    one = accelerators.set_visible_chips([2], 4)
    assert one[accelerators.GRANTED_CHIPS_ENV] == "2"
    assert one["TPU_VISIBLE_CHIPS"] == "2"
    for name in ("TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_CHIPS_PER_HOST_BOUNDS",
                 "TPU_PROCESS_BOUNDS", "TPU_HOST_BOUNDS"):
        assert one[name] == "1,1,1"


def test_device_files_win_over_host_bounds(monkeypatch):
    """A one-chip machine cut from a 2x2 host still exports the host's
    bounds; what is attached is what counts."""
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    monkeypatch.setattr(accelerators, "count_chip_devices", lambda: 1)
    assert accelerators.TpuAcceleratorManager.detect_num_chips() == 1
    monkeypatch.setattr(accelerators, "count_chip_devices", lambda: 0)
    assert accelerators.TpuAcceleratorManager.detect_num_chips() == 4


def _run_python(code, **env):
    merged = {**os.environ, "PYTHONPATH": REPO, **env}
    merged = {k: v for k, v in merged.items() if v is not None}
    return subprocess.run(
        [sys.executable, "-c", code], env=merged, capture_output=True,
        text=True, timeout=120,
    )


def test_sampler_leaves_an_uninitialised_backend_alone():
    """Imported is not initialised: the metrics pusher's device sampler
    must not be what claims the chip in a driver, controller or proxy."""
    out = _run_python(
        "import jax\n"
        "from ray_tpu.util.metrics import sample_device_memory\n"
        "from ray_tpu._internal.platform import backend_initialized\n"
        "print(sample_device_memory(), backend_initialized())\n"
        "jax.devices()\n"
        "print(sorted(sample_device_memory()), backend_initialized())\n"
    )
    assert out.returncode == 0, out.stderr
    first, second = out.stdout.strip().splitlines()
    assert first == "{} False"
    assert second == "['cpu:0', 'cpu:1', 'cpu:2', 'cpu:3', 'cpu:4', 'cpu:5', " \
                     "'cpu:6', 'cpu:7'] True"


@pytest.mark.parametrize("cache_env", ["/some/dir", None])
def test_compile_cache_is_placed_from_outside(cache_env):
    """JAX_COMPILATION_CACHE_DIR set: nothing is set in code. Unset: one
    fixed directory inside the checkout."""
    out = _run_python(
        "import jax\n"
        "from ray_tpu._internal import compile_cache\n"
        "before = jax.config.jax_compilation_cache_dir\n"
        "print(before, compile_cache.configure(),"
        " jax.config.jax_compilation_cache_dir)\n",
        JAX_COMPILATION_CACHE_DIR=cache_env,
    )
    assert out.returncode == 0, out.stderr
    before, returned, after = out.stdout.split()
    if cache_env:
        assert (before, returned, after) == (cache_env,) * 3
    else:
        assert before == "None"
        assert returned == after == os.path.join(REPO, ".jax_cache")


def test_pin_cpu_platform_keeps_the_inherited_value_for_chip_workers():
    out = _run_python(
        "import os\n"
        "from ray_tpu._internal import platform\n"
        "import jax\n"
        "platform.pin_cpu_platform()\n"
        "print(os.environ['JAX_PLATFORMS'], jax.config.jax_platforms,"
        " platform.chip_worker_platforms())\n",
        JAX_PLATFORMS="tpu,cpu",
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["cpu", "cpu", "tpu,cpu"]


def test_kernels_interpret_on_cpu_only(monkeypatch):
    from ray_tpu.ops import (
        decode_attention, flash_attention, kv_row_write, rmsnorm,
    )

    assert platform.is_tpu_backend() is False
    assert platform.pallas_interpret("probe") is True
    assert platform.traced_kernel_modes()["probe"] == [True]
    for kernel in (decode_attention, flash_attention, kv_row_write, rmsnorm):
        assert kernel._use_interpret() is True
        name = kernel.__name__.rsplit(".", 1)[1]
        assert platform.traced_kernel_modes()[name] == [True]
    # a TPU compiler option only where the TPU's compiler reads it
    assert platform.decode_step_compiler_options() == {}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert list(platform.decode_step_compiler_options()) == [
        "xla_tpu_memory_bound_loop_optimizer_options"]
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="no lowering for platform 'gpu'"):
        platform.pallas_interpret("probe")


@pytest.mark.parametrize("script", ["chip_smoke.py"])
def test_no_chip_no_result(script):
    """Without an accelerator the chip script fails in seconds and prints
    neither ``"ok": true`` nor a metric line."""
    t0 = time.time()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, script)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert time.time() - t0 < 30
    assert '"ok"' not in out.stdout and '"metric"' not in out.stdout


def test_tpu_work_on_a_chipless_cluster_fails_typed(cluster):
    """A use_tpu trainer or a TPU replica on a cluster without chips raises
    instead of waiting on an infeasible lease."""
    from ray_tpu import serve, train
    from ray_tpu.exceptions import NoAcceleratorError

    trainer = train.JaxTrainer(
        lambda: None,
        scaling_config=train.ScalingConfig(num_workers=1, use_tpu=True),
    )
    with pytest.raises(NoAcceleratorError, match="a train worker needs 1"):
        trainer.fit()

    @serve.deployment(ray_actor_options={"num_tpus": 1})
    def on_chip(_):
        return "never"

    with pytest.raises(NoAcceleratorError, match="a replica of 'on_chip'"):
        serve.run(on_chip.bind())


def test_llm_replica_asks_for_the_chips_its_mesh_needs(monkeypatch):
    from ray_tpu.llm import LLMConfig

    assert LLMConfig().resources_per_replica == {"TPU": 0.0, "CPU": 1.0}
    monkeypatch.setattr(accelerators, "count_chip_devices", lambda: 4)
    assert LLMConfig().resources_per_replica["TPU"] == 1.0
    assert LLMConfig(mesh={"tp": 4}).resources_per_replica["TPU"] == 4.0
    explicit = LLMConfig(resources_per_replica={"CPU": 2.0})
    assert explicit.resources_per_replica == {"CPU": 2.0}
