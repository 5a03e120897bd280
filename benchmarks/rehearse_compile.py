#!/usr/bin/env python3
"""Compile-only rehearsal: the step programs of both configurations at their
published widths, for a *described* v5e 2x2 host (no chip attached).

Prints the TPU compiler's memory analysis for the decode step, each prefill
length a serving mix uses, and the fsdp=4 LoRA train step. Its output decides
the depth, slots and pool written into ``configs/*-serve-1chip.json`` and the
sequences a chip in ``traffic/lora-seq4096.json``. A compile that passes is
not a chip run: no time comes from here.

    JAX_PLATFORMS=cpu python benchmarks/rehearse_compile.py [--layers 12] [--slots 16]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.harness import manifest  # noqa: E402

GB = 1e9


def _analysis(compiled) -> dict:
    m = compiled.memory_analysis()
    return {
        "arguments_gb": round(m.argument_size_in_bytes / GB, 3),
        "outputs_gb": round(m.output_size_in_bytes / GB, 3),
        "aliased_gb": round(m.alias_size_in_bytes / GB, 3),
        "temporaries_gb": round(m.temp_size_in_bytes / GB, 3),
        "peak_gb": round(
            (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes) / GB, 3),
    }


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree
    )


def _native_kernels():
    """Trace the Pallas kernels as the chip would: this process's backend is
    the CPU, so the ops would otherwise pick interpret mode."""
    from ray_tpu.ops import flash_attention, rmsnorm

    flash_attention._use_interpret = lambda: False
    rmsnorm._use_interpret = lambda: False


def serve(topo, layers, slots, prompt_lens) -> None:
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.llm.engine import _DecodeModelBase
    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.parallel.sharding import unbox_params

    config = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", "mistral-7b-v0.3-serve-1chip.json"))
    serving = config["serving"]
    kwargs = manifest.llama_kwargs(config)
    kwargs["n_layers"] = layers or kwargs["n_layers"]
    slots = slots or serving["max_batch_size"]
    cfg = LlamaConfig(max_seq_len=serving["max_seq_len"],
                      param_dtype=jnp.bfloat16, **kwargs)
    chip = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(
        lambda k: unbox_params(init_params(cfg, k)), jax.random.PRNGKey(0))
    model = _DecodeModelBase(cfg, None)
    row = jax.eval_shape(
        model._prefill_impl, params, jax.ShapeDtypeStruct((1, 8), jnp.int32))[1]
    pool = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((slots,) + s.shape[1:], s.dtype), row)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    cache = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool))
    block = (2 * cfg.n_kv_heads * cfg.head_dim * 2 * serving["kv_block_size"]
             * cfg.n_layers)
    print(json.dumps({
        "serve": {"layers": cfg.n_layers, "slots": slots,
                  "max_seq_len": cfg.max_seq_len},
        "weights_gb": round(weights / GB, 3),
        "slot_cache_gb": round(cache / GB, 3),
        "block_pool_gb": round(block * serving["kv_cache_blocks"] / GB, 3),
        "block_bytes": block,
    }), flush=True)
    decode = jax.jit(model._decode_impl).lower(
        _on(chip, params), _on(chip, pool),
        _on(chip, jax.ShapeDtypeStruct((slots, 1), jnp.int32))).compile()
    print(json.dumps({"program": "decode", **_analysis(decode)}), flush=True)
    for n in prompt_lens:
        prefill = jax.jit(model._prefill_impl).lower(
            _on(chip, params),
            _on(chip, jax.ShapeDtypeStruct((1, n), jnp.int32))).compile()
        print(json.dumps({"program": f"prefill_{n}", **_analysis(prefill)}),
              flush=True)


def train(topo, batch_per_chip, seq) -> None:
    import optax

    from ray_tpu.models.llama import LlamaConfig, init_params, next_token_loss
    from ray_tpu.parallel.mesh import batch_sharding, make_mesh
    from ray_tpu.parallel.sharding import param_shardings, unbox_params
    from ray_tpu.train.lora import merge_lora, split_lora

    config = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", "mistral-7b-v0.3-lora-fsdp4.json"))
    cfg = LlamaConfig(
        max_seq_len=seq, param_dtype=jnp.bfloat16, remat=True, scan_layers=True,
        lora_rank=config["assumed"]["lora_rank"],
        lora_alpha=config["assumed"]["lora_alpha"],
        **manifest.llama_kwargs(config))
    mesh = make_mesh(devices=list(topo.devices), **config["mesh"])
    key = jax.random.PRNGKey(0)
    boxed = jax.eval_shape(lambda k: init_params(cfg, k), key)
    shardings = param_shardings(mesh, boxed)
    shapes = jax.eval_shape(lambda k: unbox_params(init_params(cfg, k)), key)
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings)
    base, lora = split_lora(params)
    optimizer = optax.adamw(1e-4)
    state = jax.eval_shape(optimizer.init, lora)
    from jax.sharding import NamedSharding, PartitionSpec

    replicated = NamedSharding(mesh, PartitionSpec())
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=replicated), state)

    def loss_fn(lora_p, base_p, tokens):
        return next_token_loss(cfg, mesh, merge_lora(base_p, lora_p), tokens)

    def train_step(base_p, lp, s, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(lp, base_p, tokens)
        updates, s2 = optimizer.update(grads, s, lp)
        return optax.apply_updates(lp, updates), s2, loss

    tokens = jax.ShapeDtypeStruct(
        (batch_per_chip * len(topo.devices), seq), jnp.int32,
        sharding=batch_sharding(mesh))
    compiled = jax.jit(train_step, donate_argnums=(1, 2)).lower(
        base, lora, state, tokens).compile()
    text = compiled.as_text()
    print(json.dumps({
        "program": f"train_step_fsdp4_b{batch_per_chip}_s{seq}",
        "per_device": _analysis(compiled),
        "all_gathers": text.count("all-gather-start") or text.count("all-gather("),
        "reduce_scatters": text.count("reduce-scatter"),
        "all_reduces": text.count("all-reduce-start") or text.count("all-reduce("),
        "kernels": text.count("tpu_custom_call"),
    }), flush=True)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--layers", type=int, default=0)
    parser.add_argument("--slots", type=int, default=0)
    parser.add_argument("--prompt-lens", default="128,256,512,2048,3072")
    parser.add_argument("--train-batch", default="1,2,4",
                        help="sequences a chip to try")
    parser.add_argument("--seq", type=int, default=4096)
    parser.add_argument("--only", choices=("serve", "train"), default=None)
    args = parser.parse_args()

    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    _native_kernels()
    if args.only != "train":
        serve(topo, args.layers, args.slots,
              [int(x) for x in args.prompt_lens.split(",") if x])
    if args.only != "serve":
        for b in [int(x) for x in args.train_batch.split(",") if x]:
            try:
                train(topo, b, args.seq)
            except Exception as e:  # the compiler refusing a size is the answer
                print(json.dumps({
                    "program": f"train_step_fsdp4_b{b}_s{args.seq}",
                    "refused": str(e).splitlines()[0][:300]}), flush=True)


if __name__ == "__main__":
    main()
