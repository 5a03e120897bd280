"""The serving run for any architecture that has a plain reference: what
``serve_common.py`` does for Llama, with the model chosen by the
configuration file's ``architecture`` key.

Adding an architecture to the benchmark (no new driver):

1. ``reference/<architecture>.py``: the plain float32 forward pass, with
   ``sizes_of(config)``, ``logits(params, tokens, last=, **sizes)`` and
   ``llm_arguments(config)`` (the published keys as ``LLMConfig``
   arguments: ``model_family`` and ``model_kwargs``). A reference without
   ``llm_arguments`` is taken to be the Llama family's
   (``harness/manifest.llama_kwargs``). What else this file reads of a
   running program it takes from the reference module too, each optional:
   ``TRACE_SCOPES`` / ``TRACE_KERNELS`` (named scopes and kernels of the
   decode program whose device time a traced run keeps, in
   ``result["scopes"]``), ``PROGRAM_COUNTERS`` (groups of
   ``runtime_info()`` kept at both ends of the window, in
   ``result["program_counters"]``), and for a model with a discontinuous
   choice (top-k experts) ``ROUTING_COLLECTION`` + ``program_routing`` +
   ``logits(follow=, slack=)``: the reference then uses the experts the
   program chose, reports how fair each choice was, and the logits of
   every position are held to the tolerance (``routed_facts``).
2. ``configs/<name>.json`` with ``"architecture": "<architecture>"`` and a
   ``serving`` group; ``traffic/<mix>.json`` with ``"kind":
   "serve_closed_loop_arch"`` (or a later ``*_arch`` kind: each is a dozen
   lines around ``run_serving`` here).
3. Entries in ``BENCHMARK.json``; the cell's name appended to the
   ``workloads`` of the metrics that read no shape.

From ``serve_common`` this takes what is not bound to a model: ``Clients``,
``call``, ``end_to_end``, and ``BenchReplica``'s trace and device methods.
Written anew: ``llm_config`` (refuses, before any cluster is started, a
program that cannot build the family), the replica's ``bench_reference``,
``check_and_warm``, and the run loop, which also keeps the counters the
architecture names at both ends of the run and the decode program's device
time by ``jax.named_scope`` and kernel name (``harness/xplane_scopes.py``:
the scopes are read from the program as the replica compiled it,
``ArchReplica.bench_op_scopes``).
"""

from __future__ import annotations

import contextlib
import copy
import importlib
import json
import os
import threading
import time
from typing import Callable, Dict, List

from ..harness import manifest, stats, traffic, xplane, xplane_scopes
from ..harness.cli import Run, emit, no_compilation
from .serve_common import (
    APP, CHECK_TOKENS, BenchReplica, Clients, call, end_to_end,
)

DECODE_MODULE = "_decode_impl"


def reference_of(architecture: str):
    return importlib.import_module(f"benchmarks.reference.{architecture}")


def reference_facts(ref, engine_last, picked) -> dict:
    """``ref``: the reference's logits (n, vocab) at the positions that
    chose ``picked``, the first of them the prompt's last; ``engine_last``:
    the engine's own prefill logits there."""
    import jax.numpy as jnp

    n = len(picked)
    eng = engine_last.astype(jnp.float32)
    gaps = jnp.max(ref, axis=-1) - ref[jnp.arange(n), jnp.asarray(picked, jnp.int32)]
    return {
        "prefill_max_abs_logit_diff": float(jnp.max(jnp.abs(eng - ref[0]))),
        "token_gap_max": float(jnp.max(gaps)),
        "reference_top_logit": float(jnp.max(ref[0])),
        "tokens_equal_reference_argmax": int(
            jnp.sum(jnp.argmax(ref, axis=-1) == jnp.asarray(picked, jnp.int32))),
        "finite": bool(jnp.all(jnp.isfinite(eng)) & jnp.all(jnp.isfinite(ref))),
    }


def forward_routed(model, collection: str):
    """The program's model over a whole sequence with what it sows into
    ``collection``: (logits (batch, seq, vocab), the sown tree). The module
    is the engine's own (``decode=True``, a fresh cache as in its prefill);
    the jitted function has a name of its own."""
    import jax

    def bench_forward_routed(params, tokens):
        logits, sown = model.apply(
            {"params": params}, tokens, mutable=["cache", collection])
        return logits, sown[collection]

    return jax.jit(bench_forward_routed)


def routed_facts(ref_all, slack, eng_all) -> dict:
    """A top-k choice is a discontinuity: where a bf16 hidden state swaps
    the k-th and (k+1)-th expert against the float32 reference the logits
    move by several bf16 steps (and so do those of every later position
    that attends to that one), which would hide a lower precision or a
    lost assignment. So the reference follows the program's choice of
    experts (``follow=``) and says how fair each choice was.

    ``ref_all`` / ``eng_all``: (seq, vocab) logits over prompt + answer,
    the reference's under the program's routing; ``slack``: a layer's
    (seq,) each, how far the least probable expert the program chose lies
    under the reference's k-th largest probability, as a share of it."""
    import jax.numpy as jnp

    slack = jnp.stack(slack)  # (layers, seq)
    diff = jnp.abs(eng_all.astype(jnp.float32) - ref_all)
    return {
        "positions": int(diff.shape[0]),
        "routing_agree_share": float(jnp.mean(jnp.all(slack == 0, axis=0))),
        "routing_slack_max": float(jnp.max(slack)),
        "max_abs_logit_diff": float(jnp.max(diff)),
        "rms_logit_diff": float(jnp.sqrt(jnp.mean(diff * diff))),
        "finite": bool(jnp.all(jnp.isfinite(diff))),
    }


def within(facts: dict, tolerance: dict) -> bool:
    """``bench_reference``'s facts against a mix file's ``tolerance``."""
    if "error" in facts or not facts["finite"]:
        return False
    if "routing_agree_share" not in facts:
        return (facts["prefill_max_abs_logit_diff"] <= tolerance["prefill_logit"]
                and facts["token_gap_max"] <= tolerance["token_gap"])
    return (
        facts["routing_agree_share"] >= tolerance["routing_agree_share"]
        and facts["routing_slack_max"] <= tolerance["routing_slack"]
        and facts["max_abs_logit_diff"] <= tolerance["prefill_logit"]
        and facts["rms_logit_diff"] <= tolerance["rms_logit"]
        and facts["token_gap_max"] <= tolerance["token_gap"]
        # the engine's own prefill program ran on the prompt alone and may
        # have chosen otherwise at its last position than the pass followed
        and facts["prefill_max_abs_logit_diff"] <= tolerance["unfollowed_logit"]
    )


class ArchReplica(BenchReplica):
    def bench_op_scopes(self, scopes) -> dict:
        """The decode program as this replica compiled it, reduced to
        instruction name -> ``jax.named_scope`` (``xplane_scopes.op_scopes``):
        what lets a traced run attribute device ops to scopes. Lowered from
        the engine's own jitted function on the arguments of a decode step,
        so it is the program that runs, and a compile-cache read."""
        import jax.numpy as jnp
        import numpy as np

        engine = self._engine
        slots = engine._num_slots
        counts = getattr(engine, "_expert_counts", None)
        counted = {} if counts is None else {"expert_counts": counts}
        with engine._lock:
            if engine._cache is None:
                return {}
            text = engine._decode.lower(
                engine._params, engine._cache, jnp.zeros((slots, 1), jnp.int32),
                *engine._adapter_args(np.full(slots, -1)),
                active=np.ones(slots, bool), **counted,
            ).compile().as_text()
        return xplane_scopes.op_scopes(text, scopes)

    def bench_reference(self, architecture: str, sizes: dict,
                        prompt: List[int], generated: List[int]) -> dict:
        """As ``BenchReplica.bench_reference``, against the reference the
        configuration names; for an architecture that sows its routing,
        the reference follows it and every position of prompt + answer is
        compared (``routed_facts``)."""
        import jax.numpy as jnp

        arch = reference_of(architecture)
        engine = self._engine
        params = engine._params
        n = len(generated)
        tokens = jnp.asarray([list(prompt) + list(generated[:-1])], jnp.int32)
        routed = hasattr(arch, "program_routing")
        with engine._lock:
            engine_logits, _ = engine._prefill(
                params, jnp.asarray([list(prompt)], jnp.int32))
            if not routed:
                ref = arch.logits(params, tokens, last=n, **sizes)[0]
                return reference_facts(ref, engine_logits[0], generated)
            eng_all, sown = forward_routed(
                engine._model, arch.ROUTING_COLLECTION)(params, tokens)
            slack: list = []
            ref_all = arch.logits(
                params, tokens, follow=arch.program_routing(sown, sizes["n_layers"]),
                slack=slack, **sizes)[0]
            facts = reference_facts(ref_all[-n:], engine_logits[0], generated)
            facts.update(routed_facts(ref_all, slack, eng_all[0]))
        return facts


def llm_config(config: dict, seed: int):
    import jax.numpy as jnp  # imported, never initialised, in this process

    from ray_tpu.llm import LLMConfig

    arch = reference_of(config["architecture"])
    if hasattr(arch, "llm_arguments"):
        arguments = arch.llm_arguments(config)
    else:
        arguments = {"model_family": "llama",
                     "model_kwargs": manifest.llama_kwargs(config)}
    arguments["model_kwargs"] = dict(
        arguments["model_kwargs"], param_dtype=getattr(jnp, config["dtype"]))
    serving = config["serving"]
    try:
        cfg = LLMConfig(
            model_id=config["name"],
            max_seq_len=serving["max_seq_len"],
            max_batch_size=serving["max_batch_size"],
            kv_cache_blocks=serving["kv_cache_blocks"],
            kv_block_size=serving["kv_block_size"],
            mesh=config["mesh"] or None,
            seed=seed,
            **arguments,
        )
        # what the replica would do first, done here where a refusal costs
        # no cluster and leaves no worker: a program without the family
        # (or without the arguments it now takes) says so in a second
        cfg.build_model_config()
        from ray_tpu.models import build  # noqa: F401  where the engine gets a model
    except (TypeError, ValueError, ImportError) as exc:
        raise SystemExit(
            f"benchmark: this program cannot build {config['name']} "
            f"({config['architecture']}): {type(exc).__name__}: {exc}")
    return cfg


@contextlib.contextmanager
def serving(cfg, run: Run):
    """One cluster serving ``cfg`` through ``ArchReplica``: yields (handle,
    pids), as ``serve_common.serving``."""
    import ray_tpu
    from ray_tpu import serve

    resources = dict(cfg.resources_per_replica)
    actor = {"num_cpus": resources.pop("CPU", 1.0)}
    if resources.get("TPU"):
        actor["num_tpus"] = resources.pop("TPU")
    app = serve.deployment(
        ArchReplica, name=cfg.model_id, num_replicas=1, ray_actor_options=actor,
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


def check_and_warm(handle, cell: dict, seed: int, tolerance: dict) -> bool:
    """As ``serve_common.check_and_warm``: one seeded prompt of each length
    the mix uses through the request path (the first answer crosses a block
    boundary), then the configuration's reference on the replica. Also the
    warm-up of every shape the window will use."""
    config, mix = cell["config_file"], cell["traffic_file"]
    sizes = reference_of(config["architecture"]).sizes_of(config)
    clients = Clients(handle, time.perf_counter)
    rows, ok = [], True
    for i, n in enumerate(sorted(int(k) for k in mix["prompt_lens"])):
        asked = CHECK_TOKENS if i else config["serving"]["kv_block_size"] + 2
        request = next(traffic.requests(
            dict(mix, prompt_lens={str(n): 1.0}, output_tokens=[asked] * 2),
            config["vocab_size"], seed, stream=1000 + i))
        t0 = time.perf_counter()
        record = clients.one(request, t0, keep_tokens=True)
        if record["done"] is not None:
            facts = call(handle, "bench_reference", config["architecture"],
                         sizes, request["token_ids"], record["token_ids"])
        else:
            facts = {"error": record["error"] or "stream ended early"}
        good = within(facts, tolerance)
        ok = ok and good
        rows.append(dict(facts, prompt_len=n, decoded=asked, ok=good,
                         first_request_s=record["stamps"][0] - t0 if record["stamps"] else None))
    emit(check="serve.engine_against_plain_reference",
         architecture=config["architecture"], ok=ok, tolerance=tolerance, rows=rows)
    return ok


def run_serving(run: Run, load: Callable) -> dict:
    """The whole of one serving run; ``load`` as in
    ``serve_common.run_serving``."""
    cell, args = run.cell, run.args
    config, mix = cell["config_file"], cell["traffic_file"]
    cfg = llm_config(config, args.seed)
    arch = reference_of(config["architecture"])
    scope_names = getattr(arch, "TRACE_SCOPES", ())
    kept = getattr(arch, "PROGRAM_COUNTERS", ())
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
        # before the compile counters are read: this reads the cache once
        scope_of = (call(handle, "bench_op_scopes", scope_names)
                    if args.trace and scope_names else {})
        before = call(handle, "runtime_info")

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
        # judged as it stood here (see serve_common.run_serving)
        records = copy.deepcopy(clients.records)
        stop_side.set()
        for t in side:
            t.join(timeout=120)
        after = call(handle, "runtime_info")
        kv = call(handle, "kvcache_stats")
        device = call(handle, "bench_device")
        run.phase = "teardown"
    judged = stats.due_in(records, 0.0, window)
    no_compiles = no_compilation(before["compile"], after["compile"])
    emit(check="serve.no_compilation_in_window", ok=no_compiles,
         before=before["compile"], after=after["compile"])
    reduced = scopes = None
    if args.trace:
        path = xplane.find_xplane(trace_dir)
        reduced = xplane.reduce(path) if path else None
        if reduced:
            scopes = xplane_scopes.by_name(
                path, DECODE_MODULE, scope_of, getattr(arch, "TRACE_KERNELS", ()))
    counters = {
        "before": {k: before.get(k) for k in kept},
        "after": {k: after.get(k) for k in kept}}
    with open(os.path.join(run.out_dir, "records.json"), "w") as f:
        json.dump({"records": records, "pool": pool_samples, "traced": traced,
                   "kvcache": kv, "reduced_trace": reduced, "scopes": scopes,
                   "program_counters": counters}, f)
    result = {
        "correct": checked and no_compiles and all(
            r["done"] is None or len(r["stamps"]) == r["asked"] for r in records),
        "attempted": len(judged),
        "failed": sum(1 for r in judged if stats.failed(r) or not r["stamps"]),
        "device": device,
        "records": records, "window_s": window, "pool": pool_samples,
        "traced": traced, "trace": reduced, "config": config, "mix": mix,
        "scopes": scopes, "program_counters": counters,
    }
    result["end_to_end"] = end_to_end(result)
    emit(end_to_end=result["end_to_end"], requests=len(records),
         completed_in_window=len(stats.completed_in(records, 0.0, window)),
         scopes=scopes, scoped_instructions=len(scope_of),
         program_counters_kept=[k for k in kept if counters["after"][k]],
         kvcache={k: kv.get(k) for k in (
             "requests", "hits", "prefix_hit_tokens", "prefill_tokens_computed",
             "blocks_in_use", "capacity", "admission_blocked", "evictions")})
    return result
