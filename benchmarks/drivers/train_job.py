"""A training job through ``JaxTrainer``, with the benchmark as the user.

The loop below is the benchmark's own: a copy of
``ray_tpu/train/examples/llama_lora.py``'s (which hard-codes Llama-2 widths
under ``model == "7b"``) built from the same public pieces (``models.llama``,
``parallel.mesh``, ``parallel.sharding``, ``train.lora``, ``train.report``),
with the widths taken from the configuration file, and with timing, the
reference check and the profiler run in-loop, because only the process that
owns the chips can do them. PERF.md lists it as what the ``tracing`` issue
replaces with public hooks on the trainer.

Mix keys: ``seq``, ``sequences_per_chip``, ``lr``, ``report_every``,
``warmup_steps``, ``parity_seq``, ``trace_steps``, ``tolerance``.
"""

from __future__ import annotations

import json
import math
import os
import time

from ..harness import xplane
from ..harness.cli import Run, emit, no_compilation


def train_loop(job: dict) -> None:
    import jax
    import jax.numpy as jnp
    import optax
    from flax import traverse_util

    from ray_tpu import train as rt_train
    from ray_tpu._internal import compile_cache
    from ray_tpu.models.llama import LlamaConfig, init_params, next_token_loss
    from ray_tpu.parallel.mesh import batch_sharding, make_mesh
    from ray_tpu.parallel.sharding import (
        param_shardings, process_local_batch, unbox_params)
    from ray_tpu.train.lora import merge_lora, split_lora

    from ..harness import manifest
    from ..harness.timing import timed
    from ..reference import llama_arch

    config, mix, seed = job["config"], job["mix"], job["seed"]
    seq = mix["seq"]
    devices = jax.local_devices()
    cfg = LlamaConfig(
        max_seq_len=seq, param_dtype=getattr(jnp, config["dtype"]),
        remat=config["training"]["remat"],
        scan_layers=config["training"]["scan_layers"],
        lora_rank=config["assumed"]["lora_rank"],
        lora_alpha=config["assumed"]["lora_alpha"],
        **manifest.llama_kwargs(config),
    )
    mesh = make_mesh(num_devices=len(devices), **config["mesh"])
    shape = dict(mesh.shape)
    data_extent = shape.get("dcn", 1) * shape.get("dp", 1) * shape.get("fsdp", 1)

    # born sharded, in one jitted call from the seed, in the served type
    key = jax.random.PRNGKey(seed)
    shardings = param_shardings(
        mesh, jax.eval_shape(lambda k: init_params(cfg, k), key))
    params = jax.jit(
        lambda k: unbox_params(init_params(cfg, k)), out_shardings=shardings)(key)
    base, lora = split_lora(params)
    del params
    optimizer = optax.adamw(mix["lr"])
    opt_state = jax.jit(optimizer.init)(lora)

    def loss_fn(lora_p, base_p, tokens):
        return next_token_loss(cfg, mesh, merge_lora(base_p, lora_p), tokens)

    @jax.jit
    def train_step(base_p, lp, s, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(lp, base_p, tokens)
        updates, s2 = optimizer.update(grads, s, lp)
        return optax.apply_updates(lp, updates), s2, loss

    # correct, part 1: the program's loss against the plain reference's, on
    # the same sharded weights, before any step (lora_b is zero, so the
    # adapters add nothing and the reference needs none)
    parity_tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(seed + 1),
                           (data_extent, mix["parity_seq"]), 0, cfg.vocab_size),
        batch_sharding(mesh))
    program_loss = float(jax.jit(loss_fn)(lora, base, parity_tokens))
    reference_loss = float(llama_arch.next_token_loss(
        traverse_util.unflatten_dict(base), parity_tokens,
        **llama_arch.sizes_of(config)))

    batch = mix["sequences_per_chip"] * len(devices)
    tokens_per_step = batch * seq

    def step(i: int):
        nonlocal lora, opt_state
        local = jax.random.randint(
            jax.random.PRNGKey(seed * 100_003 + i), (batch, seq), 0, cfg.vocab_size)
        tokens = process_local_batch(mesh, local)
        (lora, opt_state, loss), seconds = timed(  # a step ends on its loss
            train_step, base, lora, opt_state, tokens)
        return float(loss), seconds

    warm_losses = [step(i)[0] for i in range(mix["warmup_steps"])]
    compiles_before = compile_cache.stats()

    seconds, trace_dir = job["seconds"], job["trace_dir"]
    steps, stalls, traced = [], [], {}
    opened, opened_wall = time.perf_counter(), time.time()
    i, last_s = mix["warmup_steps"], 0.0
    trace_from = 2 if trace_dir else None
    while (time.perf_counter() - opened) + last_s <= seconds:
        n = len(steps)
        if trace_from is not None and n == trace_from:
            xplane.start_trace(trace_dir)
            traced["start"] = time.perf_counter() - opened
        loss, last_s = step(i)
        steps.append({"end": time.perf_counter() - opened, "seconds": last_s,
                      "loss": loss})
        i += 1
        if trace_from is not None and len(steps) == trace_from + mix["trace_steps"]:
            traced["stop"] = time.perf_counter() - opened
            traced["steps"] = mix["trace_steps"]
            jax.profiler.stop_trace()
            trace_from = None
        if len(steps) % mix["report_every"] == 0:
            t0 = time.perf_counter()
            rt_train.report({"step": i, "loss": loss})
            stalls.append(time.perf_counter() - t0)
            last_s += stalls[-1]
    if trace_from is not None and "start" in traced:  # window ended mid-trace
        traced["stop"] = time.perf_counter() - opened
        traced["steps"] = len(steps) - trace_from
        jax.profiler.stop_trace()
    rt_train.report({"bench": {
        "pid": os.getpid(),
        "device": {
            "platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices),
        },
        "opened_wall": opened_wall, "steps": steps, "report_stalls_s": stalls,
        "tokens_per_step": tokens_per_step, "warm_losses": warm_losses,
        "program_loss": program_loss, "reference_loss": reference_loss,
        "compile_before": compiles_before, "compile_after": compile_cache.stats(),
        "traced": traced, "mesh": {k: int(v) for k, v in shape.items()},
    }})


def scaling(chips: int):
    """One worker that owns all the cell's chips."""
    from ray_tpu import train

    return train.ScalingConfig(
        num_workers=1, use_tpu=True,
        resources_per_worker={"CPU": 1.0, "TPU": float(chips)})


def run(run: Run) -> dict:
    import ray_tpu
    from ray_tpu import train

    cell, args = run.cell, run.args
    config, mix = cell["config_file"], cell["traffic_file"]
    chips = cell["chips"]
    trace_dir = os.path.join(run.out_dir, "trace") if args.trace else None
    ray_tpu.init()
    pids: list = []
    try:
        fitted = train.JaxTrainer(
            train_loop,
            train_loop_config={
                "config": config, "mix": mix, "seed": args.seed,
                "seconds": float(args.seconds), "trace_dir": trace_dir},
            scaling_config=scaling(chips),
            run_config=train.RunConfig(
                name=cell["name"], storage_path=os.path.join(run.out_dir, "train_runs")),
        ).fit()
        if fitted.error is not None:
            # set-up, check and window are one call here: a worker that had
            # reported a step had opened the window
            run.phase = "window" if fitted.metrics_history else "setup"
            raise SystemExit(f"benchmark: {cell['name']}: {fitted.error}")
        facts = next(h["bench"] for h in fitted.metrics_history if "bench" in h)
        pids.append(facts["pid"])
        run.phase = "teardown"
    finally:
        ray_tpu.shutdown()
        run.reap(pids)
    run.check_device(facts["device"])
    run.setup_done(facts["opened_wall"])

    steps = facts["steps"]
    losses = facts["warm_losses"] + [s["loss"] for s in steps]
    tolerance = mix["tolerance"]
    parity = abs(facts["program_loss"] - facts["reference_loss"])
    first_off = abs(losses[0] - math.log(config["vocab_size"]))
    no_compiles = no_compilation(facts["compile_before"], facts["compile_after"])
    emit(check="train.loss_against_plain_reference", ok=parity <= tolerance["loss_parity"],
         program_loss=facts["program_loss"], reference_loss=facts["reference_loss"],
         tolerance=tolerance["loss_parity"])
    emit(check="train.first_loss_near_ln_vocab", ok=first_off <= tolerance["first_loss"],
         first_loss=losses[0], ln_vocab=math.log(config["vocab_size"]))
    emit(check="train.no_compilation_in_window", ok=no_compiles,
         before=facts["compile_before"], after=facts["compile_after"])
    failed = sum(1 for x in losses if not math.isfinite(x))
    reduced = None
    if args.trace:
        path = xplane.find_xplane(trace_dir)
        reduced = xplane.reduce(path) if path else None
    elapsed = steps[-1]["end"] if steps else None
    tok_per_s = len(steps) * facts["tokens_per_step"] / elapsed if steps else None
    result = {
        "correct": (parity <= tolerance["loss_parity"] and failed == 0 and no_compiles
                    and first_off <= tolerance["first_loss"] and len(steps) > 0),
        "attempted": len(steps), "failed": failed, "device": facts["device"],
        "steps": steps, "report_stalls_s": facts["report_stalls_s"],
        "tokens_per_step": facts["tokens_per_step"], "train_tok_per_s": tok_per_s,
        "traced": facts["traced"], "trace": reduced, "config": config, "mix": mix,
        "chips": chips,
        "end_to_end": {
            "train_tok_per_s_per_chip": tok_per_s / chips if tok_per_s else None},
    }
    with open(os.path.join(run.out_dir, "records.json"), "w") as f:
        json.dump({"facts": facts, "reduced_trace": reduced}, f)
    emit(end_to_end=result["end_to_end"], steps=len(steps),
         step_seconds=[round(s["seconds"], 4) for s in steps], losses=losses[:6])
    return result
