"""Llama LoRA fine-tune through JaxTrainer — the north-star Train config.

Reference config (BASELINE.json configs[2]): "Llama-2-7B LoRA fine-tune via
Ray Train JaxTrainer on v5e-64". This example is that pipeline end-to-end in
this framework: JaxTrainer gang-schedules one ranked worker per host (slice
reservation via TPUReservationCallback when ``use_tpu``/topology are set),
the Jax backend bootstraps jax.distributed so the slice is one SPMD program,
and each worker runs the same pjit/GSPMD-sharded LoRA step:

- base params bf16, frozen (no wgrads, no optimizer moments — train/lora.py
  split); LoRA adapters in adamw
- stacked layers under lax.scan + full per-layer remat (models/llama.py
  scan_layers — the form the ``mistral7b-lora-*`` cells of BENCHMARK.json
  measure)
- params sharded by the logical-axis rule table (embed→fsdp, mlp/heads→tp)
  over a mesh built from however many devices the slice exposes

``train_config`` keys: model ("tiny" | "7b"), n_layers, epochs,
steps_per_epoch, batch_per_worker, seq, lora_rank, seed, mesh axes
overrides. The tiny default runs on a CPU test cluster in seconds; "7b" is
the v5e-64 flagship, and ``n_layers`` cuts its depth to what fewer chips
hold (chip_smoke.py runs it on one).

Every report carries ``losses`` (one per optimizer step of the epoch) and
``base_bytes_per_device``: how the frozen base parameters' bytes are spread
over this process's devices.
"""

from __future__ import annotations


def _bytes_per_device(tree) -> list:
    """Bytes of ``tree``'s arrays resident on each local device, by id."""
    import jax

    held: dict = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            held[shard.device.id] = held.get(shard.device.id, 0) + shard.data.nbytes
    return [held[i] for i in sorted(held)]


def train_loop_per_worker(config: dict):
    import jax
    import jax.numpy as jnp
    import optax

    from ... import train as rt_train
    from ...models.llama import LlamaConfig, init_params, next_token_loss
    from ...parallel.mesh import make_mesh
    from ...parallel.sharding import param_shardings, unbox_params
    from ...train.lora import merge_lora, split_lora

    from ...parallel.sharding import process_local_batch

    ctx = rt_train.get_context()
    n_dev = len(jax.devices())

    if config.get("model") == "7b":
        cfg = LlamaConfig(
            vocab_size=32000, dim=4096,
            n_layers=config.get("n_layers", 32), n_heads=32,
            n_kv_heads=32, intermediate=11008,
            max_seq_len=config.get("seq", 2048),
            param_dtype=jnp.bfloat16, remat=True, scan_layers=True,
            lora_rank=config.get("lora_rank", 16),
        )
    else:
        cfg = LlamaConfig.tiny(
            max_seq_len=config.get("seq", 128),
            lora_rank=config.get("lora_rank", 4),
            scan_layers=True, remat=True,
            n_layers=config.get("n_layers", 2),
        )

    # mesh over every device jax.distributed exposes to this SPMD program;
    # fsdp by default (ZeRO-style param sharding), tp if requested
    axes = {"fsdp": config.get("fsdp", n_dev), "tp": config.get("tp", 1)}
    mesh = make_mesh(num_devices=n_dev, **axes)
    # activations shard batch over the data axes (dcn x dp x fsdp): the
    # per-worker batch must be a multiple of that product
    shape = dict(mesh.shape)
    data_shards = (
        shape.get("dcn", 1) * shape.get("dp", 1) * shape.get("fsdp", 1)
    )

    # born sharded: each device generates its own shard of every leaf, so
    # no device ever holds the whole model (the eager init-then-reshard
    # form kept a full copy on device 0 beside the sharded one)
    key = jax.random.PRNGKey(config.get("seed", 0))
    shardings = param_shardings(
        mesh, jax.eval_shape(lambda k: init_params(cfg, k), key)
    )
    params = jax.jit(
        lambda k: unbox_params(init_params(cfg, k)), out_shardings=shardings
    )(key)
    base, lora = split_lora(params)
    del params
    optimizer = optax.adamw(config.get("lr", 1e-4))
    opt_state = jax.jit(optimizer.init)(lora)

    def loss_fn(lora_p, base_p, tokens):
        return next_token_loss(cfg, mesh, merge_lora(base_p, lora_p), tokens)

    @jax.jit
    def train_step(base_p, lp, s, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(lp, base_p, tokens)
        updates, s2 = optimizer.update(grads, s, lp)
        return optax.apply_updates(lp, updates), s2, loss

    # per-PROCESS batch: the global batch (batch * process_count) must be a
    # multiple of the mesh's data extent, so each process's share rounds to
    # a multiple of its local slice of that extent
    local_shards = max(data_shards // jax.process_count(), 1)
    batch = config.get("batch_per_worker", 2)
    batch = max(batch, local_shards)
    batch -= batch % local_shards
    seq = cfg.max_seq_len
    steps = config.get("steps_per_epoch", 4)
    rank = ctx.get_world_rank()
    base_bytes = _bytes_per_device(base)
    for epoch in range(config.get("epochs", 2)):
        losses = []
        for step in range(steps):
            # each process contributes ITS shard of the global batch —
            # process_local_batch assembles the global sharded jax.Array
            # (feeding a rank-local array into a jit over a multi-host mesh
            # is an error). Seeded by WORLD RANK: under jax.distributed
            # rank == process_index, and in the non-distributed multi-worker
            # mode (independent single-process JAX per worker) every
            # process_index is 0 while ranks still differ.
            local = jax.random.randint(
                jax.random.PRNGKey(epoch * 10_000 + step * 100 + rank),
                (batch, seq), 0, cfg.vocab_size,
            )
            tokens = process_local_batch(mesh, local)
            lora, opt_state, loss = train_step(base, lora, opt_state, tokens)
            losses.append(loss)  # stays on the device until the report
        checkpoint = None
        if rank == 0:
            # LoRA-only checkpoint: adapters are the entire trainable state.
            # Staged under the run's storage path (one directory, epochs
            # overwrite); report() files it as checkpoint_<index>.
            import os
            import pickle

            from ...train.checkpoint import Checkpoint

            ckpt_dir = os.path.join(ctx.get_storage_path(), "lora_staging")
            os.makedirs(ckpt_dir, exist_ok=True)
            with open(os.path.join(ckpt_dir, "lora.pkl"), "wb") as f:
                pickle.dump(
                    {"lora": jax.device_get(lora), "epoch": epoch}, f
                )
            checkpoint = Checkpoint.from_directory(ckpt_dir)
        losses = [float(x) for x in losses]
        rt_train.report(
            {"epoch": epoch, "loss": losses[-1], "losses": losses,
             "rank": rank, "base_bytes_per_device": base_bytes},
            checkpoint=checkpoint,
        )


def make_trainer(
    num_workers: int = 1,
    use_tpu: bool = False,
    topology: str = "",
    train_config: dict | None = None,
):
    """Build the JaxTrainer for this example (reference shape:
    JaxTrainer(train_loop, scaling_config=ScalingConfig(use_tpu=True,
    topology="v5e-64")))."""
    from ... import train as rt_train

    return rt_train.JaxTrainer(
        train_loop_per_worker,
        train_loop_config=dict(train_config or {}),
        scaling_config=rt_train.ScalingConfig(
            num_workers=num_workers, use_tpu=use_tpu,
            topology=topology or None,
        ),
        run_config=rt_train.RunConfig(name="llama-lora"),
    )


if __name__ == "__main__":
    import argparse

    import ray_tpu

    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="tiny", choices=["tiny", "7b"])
    parser.add_argument("--num-workers", type=int, default=1)
    parser.add_argument("--topology", default="", help='e.g. "v5e-64"')
    parser.add_argument("--epochs", type=int, default=2)
    args = parser.parse_args()

    ray_tpu.init(ignore_reinit_error=True)
    result = make_trainer(
        num_workers=args.num_workers,
        use_tpu=bool(args.topology),
        topology=args.topology,
        train_config={"model": args.model, "epochs": args.epochs},
    ).fit()
    if result.error is not None:
        raise SystemExit(f"training failed: {result.error}")
    print({"final": result.metrics})
