"""What the two serving kinds share: the cluster, the replica, one client.

Serving goes through ``serve.run`` -> handle (streaming) -> replica ->
``ContinuousBatchingEngine``, wired as ``llm.build_llm_deployment`` wires it.
The one addition is ``BenchReplica``: a subclass of the program's replica
that overrides nothing on the request path and adds what only the process
that owns the chip can do: start and stop a ``jax.profiler`` trace, run the
plain reference on the replica's own parameters, and say what device it
holds. PERF.md lists it as what the ``tracing`` issue replaces with public
hooks.
"""

from __future__ import annotations

import contextlib
import copy
import json
import os
import threading
import time
from typing import Callable, Dict, List, Optional

from ray_tpu.llm.serving import _LLMReplica

from ..harness import stats, traffic, xplane
from ..harness.cli import Run, emit, no_compilation

APP = "bench"
CHECK_TOKENS = 16


class BenchReplica(_LLMReplica):
    def bench_start_trace(self, log_dir: str) -> float:
        xplane.start_trace(log_dir)
        return time.time()

    def bench_stop_trace(self) -> float:
        import jax

        stopped = time.time()
        jax.profiler.stop_trace()
        return stopped

    def bench_device(self) -> dict:
        devices = self._devices()
        return {
            "platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "pid": os.getpid(),
            "memory_peak_bytes": max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
            ),
        }

    def bench_reference(self, sizes: dict, prompt: List[int], generated: List[int]) -> dict:
        """The plain reference's full forward over prompt + generated[:-1]
        on this replica's weights, against what the engine did: its prefill
        logits at the prompt's last position, and at every decoded position
        how far the reference's logit for the engine's token lies under the
        reference's largest (teacher-forced on the engine's tokens)."""
        import jax.numpy as jnp

        from ..reference import llama_arch

        params = self._engine._params
        n = len(generated)
        tokens = jnp.asarray([list(prompt) + list(generated[:-1])], jnp.int32)
        with self._engine._lock:
            ref = llama_arch.logits(params, tokens, last=n, **sizes)[0]
            engine_logits, _ = self._engine._prefill(
                params, jnp.asarray([list(prompt)], jnp.int32))
        eng = engine_logits[0].astype(jnp.float32)
        chosen = ref[jnp.arange(n), jnp.asarray(generated, jnp.int32)]
        return {
            "prefill_max_abs_logit_diff": float(jnp.max(jnp.abs(eng - ref[0]))),
            "token_gap_max": float(jnp.max(jnp.max(ref, axis=-1) - chosen)),
            "reference_top_logit": float(jnp.max(ref[0])),
            "tokens_equal_reference_argmax": int(
                jnp.sum(jnp.argmax(ref, axis=-1) == jnp.asarray(generated))),
            "finite": bool(jnp.all(jnp.isfinite(eng)) & jnp.all(jnp.isfinite(ref))),
        }


def llm_config(config: dict, seed: int):
    import jax.numpy as jnp  # imported, never initialised, in this process

    from ray_tpu.llm import LLMConfig

    from ..harness import manifest

    serving = config["serving"]
    return LLMConfig(
        model_id=config["name"],
        model_kwargs=dict(
            manifest.llama_kwargs(config), param_dtype=getattr(jnp, config["dtype"])),
        max_seq_len=serving["max_seq_len"],
        max_batch_size=serving["max_batch_size"],
        kv_cache_blocks=serving["kv_cache_blocks"],
        kv_block_size=serving["kv_block_size"],
        mesh=config["mesh"] or None,
        seed=seed,
    )


@contextlib.contextmanager
def serving(cfg, run: Run):
    """One cluster serving ``cfg`` through ``BenchReplica``: yields (handle,
    pids). The caller adds the chip-owning worker's pid, for the line; on exit
    every process the run started is gone (``Run.reap``)."""
    import ray_tpu
    from ray_tpu import serve

    resources = dict(cfg.resources_per_replica)
    actor = {"num_cpus": resources.pop("CPU", 1.0)}
    if resources.get("TPU"):
        actor["num_tpus"] = resources.pop("TPU")
    app = serve.deployment(
        BenchReplica, name=cfg.model_id, num_replicas=1, ray_actor_options=actor,
    ).bind(cfg, None, None, None, None, None)
    ray_tpu.init()
    pids: list = []
    try:
        handle = serve.run(app, name=APP, route_prefix=None, _proxy=False)
        yield handle.options(timeout_s=600), pids
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()
        run.reap(pids)


def call(handle, method: str, *args):
    return handle.options(method_name=method, timeout_s=600).remote(*args).result()


class Clients:
    """Streams requests through the handle and keeps one record a request
    (``harness/stats.py`` says what a record holds). ``clock`` is the
    harness's perf_counter less the window's opening."""

    def __init__(self, handle, clock: Callable[[], float]):
        self._handle = handle.options(stream=True, method_name="stream")
        self._clock = clock
        self.records: List[dict] = []
        self.cut = threading.Event()

    def one(self, request: dict, due: float, keep_tokens: bool = False) -> dict:
        record = {
            "due": due, "sent": self._clock(), "stamps": [],
            "prompt_len": len(request["token_ids"]),
            "asked": request["max_new_tokens"], "done": None, "error": None,
        }
        self.records.append(record)  # list.append is atomic under the GIL
        stream = None
        try:
            stream = self._handle.remote(dict(request, temperature=0.0))
            for item in stream:
                if "token_id" in item:
                    record["stamps"].append(self._clock())
                elif item.get("finished"):
                    if keep_tokens:
                        record["token_ids"] = list(item["token_ids"])
                    if len(item["token_ids"]) == record["asked"]:
                        record["done"] = self._clock()
                    else:
                        record["error"] = f"returned {len(item['token_ids'])} tokens"
                if self.cut.is_set():
                    break
        except Exception as exc:  # recorded as a failed request, not raised
            record["error"] = f"{type(exc).__name__}: {exc}"[:200]
        finally:
            if stream is not None:
                stream.close()
        return record


def check_and_warm(handle, cell: dict, seed: int, tolerance: dict) -> bool:
    """One seeded prompt of each length the mix uses, 16 or more greedy tokens each,
    through the request path, then the plain reference on the replica. This
    is also the warm-up: it compiles the prefill of every length, the decode
    step and the cache programs, and nothing else will run in the window."""
    from ..reference import llama_arch

    config, mix = cell["config_file"], cell["traffic_file"]
    sizes = llama_arch.sizes_of(config)
    clients = Clients(handle, time.perf_counter)
    rows, ok = [], True
    for i, n in enumerate(sorted(int(k) for k in mix["prompt_lens"])):
        # the first answer is long enough to cross a block boundary, so that
        # the retire-time commit of a decoded tail is compiled here too
        asked = CHECK_TOKENS if i else config["serving"]["kv_block_size"] + 2
        request = next(traffic.requests(
            dict(mix, prompt_lens={str(n): 1.0}, output_tokens=[asked] * 2),
            config["vocab_size"], seed, stream=1000 + i))
        t0 = time.perf_counter()
        record = clients.one(request, t0, keep_tokens=True)
        if record["done"] is not None:
            facts = call(handle, "bench_reference", sizes, request["token_ids"],
                         record["token_ids"])
        else:
            facts = {"error": record["error"] or "stream ended early"}
        good = (
            "error" not in facts and facts["finite"]
            and facts["prefill_max_abs_logit_diff"] <= tolerance["prefill_logit"]
            and facts["token_gap_max"] <= tolerance["token_gap"]
        )
        ok = ok and good
        rows.append(dict(facts, prompt_len=n, ok=good,
                         first_request_s=record["stamps"][0] - t0 if record["stamps"] else None))
    emit(check="serve.engine_against_plain_reference", ok=ok, tolerance=tolerance, rows=rows)
    return ok


def run_serving(run: Run, load: Callable) -> dict:
    """The whole of one serving run. ``load(clients, mix, vocab, seed, start,
    end, clock)`` offers the kind's traffic from ``start`` (negative: the
    ramp before the window opens at 0) until ``end`` and returns when it has
    sent its last request."""
    cell, args = run.cell, run.args
    config, mix = cell["config_file"], cell["traffic_file"]
    cfg = llm_config(config, args.seed)
    window = float(args.seconds)
    ramp = float(mix["ramp_s"])
    trace_dir = os.path.join(run.out_dir, "trace")
    with serving(cfg, run) as (handle, pids):
        device = call(handle, "bench_device")
        pids.append(device["pid"])
        run.check_device(device)
        run.phase = "check"
        checked = check_and_warm(handle, cell, args.seed, mix["tolerance"])
        run.phase = "setup"
        before = call(handle, "runtime_info")["compile"]

        opened = time.perf_counter() + ramp
        wall_opened = time.time() + ramp

        def clock() -> float:
            return time.perf_counter() - opened

        clients = Clients(handle, clock)
        pool_samples: List[dict] = []
        traced: Dict[str, float] = {}
        stop_side = threading.Event()

        def sample_pool():
            while not stop_side.wait(1.0):
                s = call(handle, "kvcache_stats")
                pool_samples.append({
                    "t": clock(), "blocks_in_use": s["blocks_in_use"],
                    "capacity": s["capacity"],
                    "admission_blocked": s.get("admission_blocked", 0)})

        def trace_middle():
            """Profile ``trace_s`` seconds in the middle of the window."""
            span = float(mix.get("trace_s", 5.0))
            if stop_side.wait(max(opened + (window - span) / 2 - time.perf_counter(), 0)):
                return
            started = call(handle, "bench_start_trace", trace_dir)
            stop_side.wait(span)
            stopped = call(handle, "bench_stop_trace")
            traced.update(start=started - wall_opened, stop=stopped - wall_opened)

        side = [threading.Thread(target=sample_pool, daemon=True)]
        if args.trace:
            side.append(threading.Thread(target=trace_middle, daemon=True))
        for t in side:
            t.start()
        run.setup_done(wall_opened)
        threads = load(clients, mix, config["vocab_size"], args.seed, -ramp, window, clock)
        # after the window: a grace for first tokens of requests due inside
        # it, then every stream is cut; nothing waits for long tails
        grace_end = window + float(mix.get("grace_s", 0.0))
        while clock() < grace_end and any(
            not r["stamps"] and r["error"] is None
            for r in stats.due_in(clients.records, 0.0, window)
        ):
            time.sleep(0.05)
        clients.cut.set()
        deadline = time.perf_counter() + 5.0
        for t in threads:
            t.join(timeout=max(deadline - time.perf_counter(), 0.0))
        # the run is judged as it stood here: what a stream that is still
        # waiting for an item raises when the cluster goes down is not the
        # system's failure
        records = copy.deepcopy(clients.records)
        stop_side.set()
        for t in side:
            t.join(timeout=120)
        after = call(handle, "runtime_info")
        kv = call(handle, "kvcache_stats")
        device = call(handle, "bench_device")
        run.phase = "teardown"
    judged = stats.due_in(records, 0.0, window)
    no_compiles = no_compilation(before, after["compile"])
    emit(check="serve.no_compilation_in_window", ok=no_compiles,
         before=before, after=after["compile"])
    reduced = None
    if args.trace:
        path = xplane.find_xplane(trace_dir)
        reduced = xplane.reduce(path) if path else None
    with open(os.path.join(run.out_dir, "records.json"), "w") as f:
        json.dump({"records": records, "pool": pool_samples, "traced": traced,
                   "kvcache": kv, "reduced_trace": reduced}, f)
    result = {
        "correct": checked and no_compiles and all(
            r["done"] is None or len(r["stamps"]) == r["asked"] for r in records),
        "attempted": len(judged),
        # no first token by the end of the grace counts as failed too
        "failed": sum(1 for r in judged if stats.failed(r) or not r["stamps"]),
        "device": device,
        "records": records, "window_s": window, "pool": pool_samples,
        "traced": traced, "trace": reduced, "config": config, "mix": mix,
    }
    result["end_to_end"] = end_to_end(result)
    emit(end_to_end=result["end_to_end"], requests=len(records),
         completed_in_window=len(stats.completed_in(records, 0.0, window)),
         kvcache={k: kv.get(k) for k in (
             "requests", "hits", "prefix_hit_tokens", "prefill_tokens_computed",
             "blocks_in_use", "capacity", "admission_blocked", "evictions")})
    return result


def _ms(seconds: Optional[float]) -> Optional[float]:
    return None if seconds is None else seconds * 1000.0


def end_to_end(result: dict) -> dict:
    """What a user of the served model sees, over the whole window."""
    records, window = result["records"], result["window_s"]
    done = stats.completed_in(records, 0.0, window)
    return {
        "out_tok_per_s": stats.tokens_in(records, 0.0, window) / window,
        "ttft_p50_ms": _ms(stats.median(stats.ttfts_s(records, 0.0, window))),
        "tpot_p50_ms": _ms(stats.median(
            t for t in map(stats.tpot_s, done) if t is not None)),
    }
