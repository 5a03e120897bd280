#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that ray_tpu still starts on a v5e.

Drives the two main paths once, through the entry points a user calls, at
Llama-2-7B widths (dim 4096, 32 heads x 128, intermediate 11008, vocab
32000, bf16 params) with depth cut to what one 16 GB chip holds:

  serve   serve.run(build_llm_deployment(LLMConfig(...))), paged engine,
          requests through the handle and the HTTP proxy, prefix-cache hit,
          first-token parity against a plain full-sequence forward
  train   JaxTrainer + train/examples/llama_lora.py, LoRA rank 16 on a
          frozen bf16 base, scan_layers + remat, sequence 2048

Each phase is its own ``ray_tpu.init()`` ... ``shutdown()``; the second
starts only after the first's chip-owning worker process is gone. This
driver never initialises a JAX backend: the chip belongs to the worker the
raylet leases it to, and that worker reports the device. Weights and
prompts come from ``--seed``.

``--chips 4`` runs only the cross-chip paths and what each is compared
with: (a) the trainer on an fsdp=4 mesh against one device, (b) a tp=4
replica against a tp=1 replica, (c) four one-chip replicas side by side.

Every line of stdout is one JSON object. Any failed check, any phase that
raises, or no chip -> non-zero exit and no ``"ok": true``. On success the
last line is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import random
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

from ray_tpu._internal.accelerators import count_chip_devices  # noqa: E402

# Llama-2-7B widths (models/llama.py LlamaConfig.llama2_7b); depth is cut.
WIDTHS = dict(
    vocab_size=32000, dim=4096, n_heads=32, n_kv_heads=32, intermediate=11008,
)
# Depths, from TPU-compiler memory analysis of the step programs for a
# described v5e chip (16,909,336,064 bytes usable):
# - serve L=8: 3.5 GiB params + 2 x 2.0 GiB dense slot cache (decode does not
#   donate it, ROADMAP S1) + 0.5 GiB block pool; L=16 does not fit.
# - train L=16, batch 2: 6.5 GiB params + 1.6 GiB step temporaries; L=32
#   does not compile into 16 GB with the example's default layouts.
SERVE = dict(
    n_layers=8, max_seq_len=2048, kv_cache_blocks=128, kv_block_size=32,
    max_batch_size=8, prompt_lens=(128, 320, 512), prefix_len=256,
)
TRAIN = dict(
    model="7b", n_layers=16, seq=2048, batch_per_worker=2, lora_rank=16,
    steps_per_epoch=2, epochs=2,
)
# the cross-chip comparisons run shallower: four chips cost four times
# as much per second, and what they check does not depend on depth
TRAIN_4 = dict(TRAIN, n_layers=8, batch_per_worker=4, steps_per_epoch=1)

# Largest |logit| difference tolerated, over the whole vocabulary, between
# the engine's prefill (einsum attention over the cache, f32 scores) and the
# plain forward (flash kernel) on the same bf16 weights. First chip run (TPU
# v5 lite, L=8, 512 tokens) measured 0.03125: one bf16 step at the top
# logit (~5). With random weights every prompt's greedy token is the same
# one, so parity rests on this difference, not on the tokens.
LOGIT_TOLERANCE = 0.125
# |first loss - ln(vocab)| with random weights and zero lora_b. Random
# logits of standard deviation s cost ln(V) + s^2/2: first chip run measured
# 11.172 against ln(32000) = 10.374 (s ~ 1.26).
FIRST_LOSS_TOLERANCE = 1.0
# first-step loss, fsdp=4 against one device: same data, same weights,
# different reduction order in bf16
LOSS_PARITY_TOLERANCE = 0.05

ROUTE = "/llm"

_failed: list = []


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check(name: str, ok, **facts) -> None:
    emit(check=name, ok=bool(ok), **facts)
    if not ok:
        _failed.append(name)


def _pid_gone(pid: int) -> bool:
    """Exited, reaped or not: a zombie has released its devices."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] == "Z"
    except (FileNotFoundError, ProcessLookupError):
        return True


def wait_gone(pids, what: str, timeout_s: float = 60.0) -> None:
    deadline = time.time() + timeout_s
    while not all(_pid_gone(p) for p in pids):
        if time.time() > deadline:
            raise RuntimeError(f"{what}: chip worker pid(s) {pids} still alive")
        time.sleep(0.2)


def _store_kind() -> str:
    from ray_tpu import _worker_api

    kind = type(_worker_api.get_node().raylet.store).__name__
    return {"NativeObjectStore": "native", "ObjectStore": "python"}.get(kind, kind)


def _prompts(seed: int, sizes: dict) -> dict:
    rng = random.Random(seed)
    vocab = sizes["vocab_size"]

    def toks(n):
        return [rng.randrange(3, vocab - 1) for _ in range(n)]

    short, mid, long_ = sizes["prompt_lens"]
    prefix = toks(sizes["prefix_len"])
    return {
        "short": [toks(short) for _ in range(6)],
        "shared": [prefix + toks(mid - len(prefix)) for _ in range(2)],
        "long": toks(long_),
    }


def _llm_config(seed: int, sizes: dict, widths: dict, **overrides):
    import jax.numpy as jnp  # imported, never initialised, in this process

    from ray_tpu.llm import LLMConfig

    depth = sizes["n_layers"]
    model_kwargs = dict(widths, n_layers=depth, param_dtype=jnp.bfloat16)
    if "model_id" not in overrides:
        overrides["model_id"] = f"llama2-7b-widths-L{depth}"
    return LLMConfig(
        model_kwargs=model_kwargs,
        max_seq_len=sizes["max_seq_len"],
        max_batch_size=sizes["max_batch_size"],
        kv_cache_blocks=sizes["kv_cache_blocks"],
        kv_block_size=sizes["kv_block_size"],
        seed=seed,
        **overrides,
    )


@contextlib.contextmanager
def serving(cfg, proxy: bool = False):
    """One cluster serving ``cfg``: yields (handle, pids). The caller adds
    the chip-owning worker pids it learns; they are gone on exit."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_deployment

    ray_tpu.init()
    pids: list = []
    try:
        handle = serve.run(
            build_llm_deployment(cfg), name="smoke", route_prefix=ROUTE,
            _proxy=proxy,
        )
        yield handle.options(timeout_s=600), pids
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()
        wait_gone(pids, "serve")


def _ask(handle, tokens, n_new: int):
    return handle.remote(
        {"token_ids": tokens, "max_new_tokens": n_new, "temperature": 0.0}
    )


def _ask_http(tokens, n_new: int) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:8000{ROUTE}",
        data=json.dumps(
            {"token_ids": tokens, "max_new_tokens": n_new, "temperature": 0.0}
        ).encode(),
        headers={
            "Content-Type": "application/json",
            "X-Request-Timeout-S": "600",
        },
    )
    with urllib.request.urlopen(req, timeout=600) as resp:
        return json.loads(resp.read())["result"]


def _method(handle, name: str, *args):
    return handle.options(method_name=name, timeout_s=600).remote(*args).result()


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def serve_phase(seed: int, sizes: dict = SERVE, widths: dict = WIDTHS) -> dict:
    t0 = time.time()
    cfg = _llm_config(seed, sizes, widths)
    check(
        "serve.replica_leases_one_chip",
        cfg.resources_per_replica.get("TPU") == 1.0,
        resources_per_replica=cfg.resources_per_replica,
    )
    with serving(cfg, proxy=True) as (handle, pids):
        ready_s = time.time() - t0
        prompts = _prompts(seed, dict(sizes, **widths))
        short, shared, long_ = prompts["short"], prompts["shared"], prompts["long"]
        asked, got = [], []

        def done(n_new, out):
            asked.append(n_new)
            got.append(out)
            return out

        # the same prompt twice: once computed, once from its cached prefix
        first = done(16, _ask(handle, short[0], 16).result())
        again = done(16, _ask(handle, short[0], 16).result())
        # two prompts sharing a 256-token prefix
        done(8, _ask(handle, shared[0], 8).result())
        before = _method(handle, "kvcache_stats")
        done(8, _ask(handle, shared[1], 8).result())
        after = _method(handle, "kvcache_stats")
        # through the HTTP proxy
        via_http = done(16, _ask_http(long_, 16))
        done(8, _ask_http(short[1], 8))
        # a concurrent batch through the handle
        for resp in [_ask(handle, p, 12) for p in short[2:6]]:
            done(12, resp.result())

        check(
            "serve.every_request_returns_asked_tokens",
            [len(o["token_ids"]) for o in got] == asked,
            requests=len(got), asked=asked,
            returned=[len(o["token_ids"]) for o in got],
        )
        check(
            "serve.same_prompt_same_tokens",
            first["token_ids"] == again["token_ids"],
            tokens=first["token_ids"],
        )
        check(
            "serve.shared_prefix_hits_cache",
            after["prefix_hit_tokens"] - before["prefix_hit_tokens"] > 0,
            cached_tokens=after["prefix_hit_tokens"] - before["prefix_hit_tokens"],
            kvcache={k: after[k] for k in (
                "requests", "hits", "prefix_hit_tokens",
                "prefill_tokens_computed", "blocks_in_use", "capacity",
            )},
        )
        parity = _method(handle, "check_prefill_logits", long_)
        token = via_http["token_ids"][0]
        check(
            "serve.first_token_matches_plain_forward",
            parity["finite"]
            and parity["max_abs_logit_diff"] <= LOGIT_TOLERANCE
            and (
                token == parity["reference_argmax"]
                # a near-tie the two paths may break differently
                or parity["reference_top2_margin"] <= parity["max_abs_logit_diff"]
            ),
            first_token=token, tolerance=LOGIT_TOLERANCE, **parity,
        )
        info = _method(handle, "runtime_info")
        mesh = _method(handle, "mesh_info")
        pids.append(info["pid"])
        check(
            "serve.one_process_holds_the_chip",
            info["pid"] != os.getpid() and info["tpu_ids"] == [0]
            and mesh["num_devices"] == 1,
            driver_pid=os.getpid(), chip_worker_pid=info["pid"],
            tpu_ids=info["tpu_ids"], device_ids=mesh["device_ids"],
        )
        check(
            "serve.kernels_native",
            info["kernels"].get("rmsnorm") == [False]
            and info["kernels"].get("flash_attention") == [False]
            and info["kernels"].get("decode_attention") == [False],
            kernels=info["kernels"],
        )
        device = _device(mesh)
        emit(
            phase="serve", wall_s=round(time.time() - t0, 1),
            ready_s=round(ready_s, 1), **_compile_facts(info),
            object_store=_store_kind(), driver_pid=os.getpid(),
            chip_worker_pid=info["pid"], depth=sizes["n_layers"],
            widths=widths, requests=len(got),
            first_tokens=[o["token_ids"][0] for o in got],
            peak_hbm_bytes=info["peak_hbm_bytes"], device=device,
        )
    return device


def _device(facts: dict) -> dict:
    """The result line's device, as the chip-owning worker saw it."""
    return {
        "platform": facts["platform"], "kind": facts["device_kind"],
        "count": len(facts["device_ids"]),
    }


def _compile_facts(facts: dict) -> dict:
    c = facts["compile"]
    return dict(
        compile_s=round(c["compile_s"], 1),
        trace_lower_s=round(c["trace_lower_s"], 1),
        programs=c["programs"], cache_requests=c["cache_requests"],
        cache_hits=c["cache_hits"],
        compile_cache_dir=facts["compile_cache_dir"],
        # worker.startup: the chip worker's start by phase (util/tracing.py)
        startup=facts["startup"],
    )


def _train_loop(config: dict):
    """train/examples/llama_lora.py's loop, then one more report carrying
    what only the chip-owning process can say about itself."""
    import jax

    from ray_tpu import get_tpu_ids, train
    from ray_tpu._internal import compile_cache
    from ray_tpu._internal.platform import traced_kernel_modes
    from ray_tpu.train.examples.llama_lora import train_loop_per_worker
    from ray_tpu.util import tracing

    train_loop_per_worker(config)
    devices = jax.local_devices()
    train.report({"worker": {
        "pid": os.getpid(),
        "tpu_ids": get_tpu_ids(),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_ids": [d.id for d in devices],
        "peak_hbm_bytes": [
            (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices
        ],
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "compile": compile_cache.stats(),
        "startup": tracing.startup_record(),
        "kernels": traced_kernel_modes(),
    }})


def _fit(seed: int, train_config: dict, chips: int, run_name: str):
    """One JaxTrainer run in its own cluster. Returns (history, worker
    facts); the chip worker is gone when this returns."""
    import ray_tpu
    from ray_tpu import train

    ray_tpu.init()
    pid = None
    try:
        result = train.JaxTrainer(
            _train_loop,
            train_loop_config=dict(train_config, seed=seed),
            scaling_config=train.ScalingConfig(
                num_workers=1, use_tpu=True,
                resources_per_worker={"CPU": 1.0, "TPU": float(chips)},
            ),
            run_config=train.RunConfig(
                name=run_name,
                storage_path=os.path.join(REPO, "chiprun_out", "smoke_runs"),
            ),
        ).fit()
        if result.error is not None:
            raise RuntimeError(f"{run_name}: {result.error}")
        history = result.metrics_history
        worker = next(h["worker"] for h in history if "worker" in h)
        pid = worker["pid"]
        worker["object_store"] = _store_kind()
        worker["checkpoint"] = (
            result.checkpoint.path if result.checkpoint is not None else None
        )
        return [h for h in history if "losses" in h], worker
    finally:
        ray_tpu.shutdown()
        if pid is not None:
            wait_gone([pid], run_name)


def train_phase(seed: int, sizes: dict = TRAIN, widths: dict = WIDTHS) -> dict:
    t0 = time.time()
    reports, worker = _fit(seed, sizes, 1, "smoke-train")
    losses = [x for r in reports for x in r["losses"]]
    expect = math.log(widths["vocab_size"])
    check(
        "train.four_steps_all_finite",
        len(losses) >= 4 and all(math.isfinite(x) for x in losses),
        losses=losses,
    )
    check(
        "train.first_loss_near_ln_vocab",
        abs(losses[0] - expect) <= FIRST_LOSS_TOLERANCE,
        first_loss=losses[0], ln_vocab=round(expect, 4),
        tolerance=FIRST_LOSS_TOLERANCE,
    )
    check(
        "train.checkpoint_reported",
        worker["checkpoint"] is not None
        and os.path.exists(os.path.join(worker["checkpoint"], "lora.pkl")),
        checkpoint=worker["checkpoint"],
    )
    check(
        "train.kernels_native",
        worker["kernels"].get("flash_attention") == [False]
        and worker["kernels"].get("rmsnorm") == [False],
        kernels=worker["kernels"],
    )
    check(
        "train.one_process_holds_the_chip",
        worker["pid"] != os.getpid() and worker["tpu_ids"] == [0]
        and len(worker["device_ids"]) == 1,
        driver_pid=os.getpid(), chip_worker_pid=worker["pid"],
        tpu_ids=worker["tpu_ids"], device_ids=worker["device_ids"],
    )
    device = _device(worker)
    emit(
        phase="train", wall_s=round(time.time() - t0, 1),
        **_compile_facts(worker),
        object_store=worker["object_store"], driver_pid=os.getpid(),
        chip_worker_pid=worker["pid"], depth=sizes["n_layers"],
        widths=widths, seq=sizes["seq"], batch=sizes["batch_per_worker"],
        lora_rank=sizes["lora_rank"], losses=losses,
        base_bytes_per_device=reports[0]["base_bytes_per_device"],
        peak_hbm_bytes=worker["peak_hbm_bytes"], device=device,
    )
    return device


# ---------------------------------------------------------------------------
# --chips 4: the cross-chip paths, each against its one-chip form
# ---------------------------------------------------------------------------


def fsdp_against_one_device(seed: int, sizes: dict = TRAIN_4,
                            widths: dict = WIDTHS) -> dict:
    t0 = time.time()
    one_reports, one = _fit(seed, dict(sizes, fsdp=1), 1, "smoke-fsdp1")
    four_reports, four = _fit(seed, dict(sizes, fsdp=4), 4, "smoke-fsdp4")
    loss1, loss4 = one_reports[0]["losses"][0], four_reports[0]["losses"][0]
    check(
        "fsdp4.first_step_loss_matches_one_device",
        math.isfinite(loss4) and abs(loss1 - loss4) <= LOSS_PARITY_TOLERANCE,
        loss_one_device=loss1, loss_fsdp4=loss4,
        tolerance=LOSS_PARITY_TOLERANCE,
    )
    held = four_reports[0]["base_bytes_per_device"]
    whole = one_reports[0]["base_bytes_per_device"][0]
    check(
        "fsdp4.each_device_holds_a_quarter_of_the_base",
        len(held) == 4 and all(0.2 * whole <= b <= 0.35 * whole for b in held),
        base_bytes_one_device=whole, base_bytes_per_device=held,
    )
    check(
        "fsdp4.one_worker_owns_four_chips",
        four["tpu_ids"] == [0, 1, 2, 3] and len(four["device_ids"]) == 4
        and one["tpu_ids"] == [0] and len(one["device_ids"]) == 1
        and four["kernels"].get("rmsnorm") == [False],
        one_device_worker=one["pid"], fsdp4_worker=four["pid"],
        fsdp4_device_ids=four["device_ids"], kernels=four["kernels"],
    )
    emit(
        phase="fsdp4", wall_s=round(time.time() - t0, 1),
        depth=sizes["n_layers"], batch=sizes["batch_per_worker"],
        compile_s_one_device=round(one["compile"]["compile_s"], 1),
        compile_s_fsdp4=round(four["compile"]["compile_s"], 1),
        peak_hbm_bytes_one_device=one["peak_hbm_bytes"],
        peak_hbm_bytes_fsdp4=four["peak_hbm_bytes"],
    )
    return _device(four)


def _generate(handle, seed: int, sizes: dict, widths: dict):
    prompts = _prompts(seed, dict(sizes, **widths))
    asks = [prompts["short"][0], prompts["short"][1], *prompts["shared"]]
    return asks, [_ask(handle, p, 12).result()["token_ids"] for p in asks]


def tp4_against_tp1(seed: int, sizes: dict = SERVE, widths: dict = WIDTHS) -> dict:
    t0 = time.time()
    with serving(_llm_config(seed, sizes, widths)) as (handle, pids):
        asks, base_tokens = _generate(handle, seed, sizes, widths)
        info1 = _method(handle, "runtime_info")
        pids.append(info1["pid"])
    cfg = _llm_config(seed, sizes, widths, mesh={"tp": 4})
    check(
        "tp4.replica_leases_four_chips",
        cfg.resources_per_replica.get("TPU") == 4.0,
        resources_per_replica=cfg.resources_per_replica,
    )
    with serving(cfg) as (handle, pids):
        _, tokens = _generate(handle, seed, sizes, widths)
        info4 = _method(handle, "runtime_info")
        mesh = _method(handle, "mesh_info")
        pids.append(info4["pid"])
        # Where a stream leaves the tp=1 stream, ask the tp=4 replica how
        # close its own two best logits were at that position: all-reduce
        # over four partial sums rounds differently in bf16, and a near-tie
        # may break the other way without either replica being wrong.
        tie_flips = []
        for prompt, want, have in zip(asks, base_tokens, tokens):
            if want == have:
                continue
            at = next(i for i, (a, b) in enumerate(zip(want, have)) if a != b)
            parity = _method(
                handle, "check_prefill_logits", prompt + want[:at]
            )
            tie_flips.append({
                "position": at, "tp1_token": want[at], "tp4_token": have[at],
                "tp4_top2_margin": parity["reference_top2_margin"],
            })
        check(
            "tp4.tokens_match_tp1",
            all(len(t) == 12 for t in tokens)
            and all(f["tp4_top2_margin"] <= LOGIT_TOLERANCE for f in tie_flips),
            identical=not tie_flips, tie_flips=tie_flips,
            tp1_tokens=base_tokens, tp4_tokens=tokens,
        )
        hbm = mesh["per_device_hbm_bytes"]
        check(
            "tp4.hbm_spread_over_four_devices",
            len(hbm) == 4 and all(hbm) and max(hbm) <= 1.5 * min(hbm),
            per_device_hbm_bytes=hbm, device_ids=mesh["device_ids"],
            kv_pool_bytes_per_device=mesh.get("kv_pool_bytes_per_device"),
        )
        check(
            "tp4.one_worker_owns_four_chips",
            info4["tpu_ids"] == [0, 1, 2, 3] and mesh["num_devices"] == 4
            and info1["tpu_ids"] == [0]
            and info4["kernels"].get("rmsnorm") == [False]
            and info4["kernels"].get("decode_attention") == [False],
            tp1_worker=info1["pid"], tp4_worker=info4["pid"],
            kernels=info4["kernels"],
        )
    emit(
        phase="tp4", wall_s=round(time.time() - t0, 1), depth=sizes["n_layers"],
        compile_s_tp1=round(info1["compile"]["compile_s"], 1),
        compile_s_tp4=round(info4["compile"]["compile_s"], 1),
        peak_hbm_bytes_tp1=info1["peak_hbm_bytes"],
        peak_hbm_bytes_tp4=info4["peak_hbm_bytes"],
    )
    return _device(mesh)


def four_replicas(seed: int, sizes: dict = SERVE, widths: dict = WIDTHS) -> None:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_deployment

    t0 = time.time()
    prompt = _prompts(seed, dict(sizes, **widths))["short"][0]
    request = {"token_ids": prompt, "max_new_tokens": 8, "temperature": 0.0}
    # one replica first: it compiles everything and fills the cache
    with serving(_llm_config(seed, sizes, widths)) as (_, pids):
        controller = serve.api._require_controller()

        def replicas():
            table = ray_tpu.get(controller.get_routing_table.remote("smoke"))
            return [h for _, h, _ in next(iter(table.values()))["replicas"]]

        def call(replica, method, *args):
            return ray_tpu.get(
                replica.handle_request.remote(method, args, {}), timeout=600
            )

        first = replicas()[0]
        first_tokens = call(first, "__call__", request)["token_ids"]
        first_pid = call(first, "runtime_info")["pid"]
        # then four: the other three start side by side on their own chips
        handle = serve.run(
            build_llm_deployment(
                _llm_config(seed, sizes, widths, num_replicas=4)
            ),
            name="smoke", route_prefix=ROUTE, _proxy=False,
        ).options(timeout_s=600)
        deadline = time.time() + 600
        while len(replicas()) < 4:
            if time.time() > deadline:
                raise RuntimeError(f"only {len(replicas())} of 4 replicas RUNNING")
            time.sleep(1.0)
        rows = []
        for replica in replicas():
            out = call(replica, "__call__", request)
            info = call(replica, "runtime_info")
            pids.append(info["pid"])
            rows.append({
                "pid": info["pid"], "tpu_ids": info["tpu_ids"],
                "device_ids": call(replica, "mesh_info")["device_ids"],
                "tokens_match_first": out["token_ids"] == first_tokens,
                "cache_requests": info["compile"]["cache_requests"],
                "cache_hits": info["compile"]["cache_hits"],
                "compile_s": round(info["compile"]["compile_s"], 1),
            })
        routed = [_ask(handle, prompt, 8) for _ in range(8)]
        routed_ok = all(r.result()["token_ids"] == first_tokens for r in routed)
        rows.sort(key=lambda r: r["pid"] != first_pid)  # the first one first
        check(
            "replicas.four_processes_four_chips",
            len({r["pid"] for r in rows}) == 4
            and sorted(r["tpu_ids"] for r in rows) == [[0], [1], [2], [3]],
            replicas=rows,
        )
        check(
            "replicas.every_replica_answers_alike",
            all(r["tokens_match_first"] for r in rows) and routed_ok,
            routed_requests=len(routed),
        )
        # The first replica filled the cache, or found it filled by an
        # earlier phase. Only programs that take over a second to compile
        # are kept (JAX's threshold), so a hit is a model program; the
        # later replicas must hit, and compile nothing the first did not.
        def misses(r):
            return r["cache_requests"] - r["cache_hits"]

        check(
            "replicas.later_replicas_hit_the_compile_cache",
            rows[0]["pid"] == first_pid and all(
                r["cache_hits"] > 0 and misses(r) <= misses(rows[0])
                for r in rows[1:]
            ),
            first_replica=first_pid,
            cache_hits=[r["cache_hits"] for r in rows],
            cache_misses=[misses(r) for r in rows],
            compile_s=[r["compile_s"] for r in rows],
        )
    emit(phase="replicas", wall_s=round(time.time() - t0, 1),
         depth=sizes["n_layers"])


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args()

    # decided from the device files, before anything is started
    platforms = os.environ.get("JAX_PLATFORMS", "")
    found = count_chip_devices()
    if found < args.chips or (platforms and "tpu" not in platforms.split(",")):
        print(
            f"chip_smoke: needs {args.chips} TPU chip(s); device files show "
            f"{found}, JAX_PLATFORMS={platforms!r}. Nothing was started.",
            file=sys.stderr,
        )
        return 2

    emit(start="chip_smoke", seed=args.seed, chips=args.chips,
         driver_pid=os.getpid(), chip_device_files=found)
    if args.chips == 1:
        device = serve_phase(args.seed)
        trained_on = train_phase(args.seed)
        check("same_device_both_phases", device == trained_on,
              serve=device, train=trained_on)
    else:
        device = fsdp_against_one_device(args.seed)
        served_on = tp4_against_tp1(args.seed)
        four_replicas(args.seed)
        check("same_devices_all_phases", device == served_on,
              fsdp4=device, tp4=served_on)

    from ray_tpu._internal.platform import backend_initialized

    check("driver_never_initialised_a_jax_backend", not backend_initialized())
    if _failed:
        print(f"chip_smoke: failed checks: {_failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
