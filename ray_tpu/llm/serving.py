"""LLM serving: build a serve deployment around the TPU engine.

Role-equivalent of the reference's build_openai_app / LLM deployments
(llm/_internal/serve/builders/application_builders.py + vllm_models.py):
each replica holds one jitted engine (params resident in HBM), replicas
scale through serve's deployment config, and `tensor_parallel_size` maps
to the mesh ``tp`` axis of the replica's devices instead of vLLM's NCCL
workers.

Request/response shape (token-level; bring-your-own tokenizer, or pass
``tokenizer_name`` to use a HF tokenizer):
  {"token_ids": [...], "max_new_tokens": 32, "temperature": 0.0}
  {"prompt": "text", ...}   (with a tokenizer configured)
-> {"token_ids": [...], "num_prompt_tokens": N, "finished_reason": ...}

With ``LLMConfig.kv_cache_blocks`` set, the replica's engine runs over a
block pool (ray_tpu.kvcache): admission is gated on free KV blocks and
shared prompt prefixes prefill only their uncached suffix. Pair
it with prefix-affinity routing on the caller side —
``handle.options(prefix_affinity_tokens=cfg.prefix_affinity_tokens)`` —
so repeated prefixes (chat sessions, shared system prompts) land on the
replica whose pool already holds their blocks.

With ``LLMConfig.roles={"prefill": N, "decode": M}`` the application
disaggregates into prefill and decode replica pools behind a
``_DisaggIngress``: prefill replicas run admission prefill and ship the
committed KV blocks through ``_internal/transfer.py`` (registered in the
cluster KV tier when ``kv_tier=True``), decode replicas adopt the
shipment into their paged pool and stream tokens without re-running
prefill. See docs/ARCHITECTURE.md §18.
"""

from __future__ import annotations

import asyncio
import time
from typing import Any, Dict, Optional

from .. import serve
from .._internal.platform import backend_initialized
from ..util import tracing
from ..util.tracing import annotate_device_trace as _span
from .config import LLMConfig
from .engine import ContinuousBatchingEngine, GenerationRequest


class _LoopStreams:
    """The open streams of one event loop. The engine's stepping thread
    ``post``s a step's deliveries for all of them with one
    ``call_soon_threadsafe``; on the loop each goes to its stream's inbox,
    with the ``perf_counter_ns`` at which it was posted."""

    def __init__(self, loop: asyncio.AbstractEventLoop):
        self._loop = loop
        self.inboxes: Dict[int, asyncio.Queue] = {}

    def post(self, batch: list) -> None:  # any thread; never blocks
        self._loop.call_soon_threadsafe(
            self._fan_out, batch, time.perf_counter_ns())

    def _fan_out(self, batch: list, posted_ns: int) -> None:
        # post_lag_us: how far this loop runs behind the stepping thread
        with _span(
            "replica.fan_out", streams=len(batch),
            post_lag_us=(time.perf_counter_ns() - posted_ns) // 1000,
        ):
            for rid, tokens, end in batch:
                inbox = self.inboxes.get(rid)
                if inbox is not None:  # else: a stream closed meanwhile
                    inbox.put_nowait((tokens, end, posted_ns))


class _LLMReplica:
    """The replica callable (reference role: VLLMDeployment).

    ``role`` selects the disaggregated mode: "prefill" replicas serve
    ``prefill()`` (run admission prefill, ship the committed KV through
    the tier), "decode" replicas serve ``decode_shipped()`` (adopt the
    shipment and decode with zero prefill-computed tokens); None is the
    fused replica. ``tier_backend`` overrides the KV tier backend —
    cluster replicas default to the GCS-backed one, tests inject a shared
    ``kvtier.LocalTierBackend``."""

    def __init__(self, llm_config: LLMConfig, params_blob: Optional[bytes] = None,
                 tokenizer_name: Optional[str] = None,
                 weights_name: Optional[str] = None,
                 role: Optional[str] = None,
                 tier_backend=None):
        import jax

        from ..parallel.plan import PartitionPlan
        from ..parallel.sharding import unbox_params

        if not backend_initialized():
            # worker.startup: the chip attach gets a phase of its own, here
            # where the replica is about to touch its devices anyway
            with tracing.startup_phase("backend") as phase:
                phase.count(devices=len(jax.devices()))
        self._config = llm_config
        model_config = llm_config.build_model_config()
        tp, sp = llm_config.effective_parallelism()
        plan = None
        mesh = None
        if tp > 1 or sp > 1:
            # validates tp against the local device count and the model's
            # head counts (typed MeshValidationError, before any jit) and
            # builds the replica's mesh with tp on the fastest axis
            plan = PartitionPlan.for_model(model_config, tp, sp)
            mesh = plan.mesh
        self._plan = plan
        self._mesh = mesh
        self._weights_name = weights_name
        self._weights_sub = None
        self._weights_version = None
        self._weights_resolve_s = 0.0
        # weight-plane consumers resolve manifest chunks directly into the
        # sharded layout: the plan's name-matched rules become a callable
        # sharding (resolved against the assembled tree), so each device
        # pulls only its shard bytes and each chunk is fetched once
        self._weights_sharding = (
            plan.param_shardings if plan is not None else None
        )
        # worker.startup's ``weights`` phase, whichever branch has them;
        # ``_weights_resolve_s`` is the same stopwatch's reading
        with tracing.startup_phase("weights") as weights:
            if weights_name is not None:
                # hot-reloadable weights from the weight plane: the replica
                # subscribes to the named model and serves its head version;
                # reload_weights()/reconfigure swap in fresh versions in
                # place. Resolving here — inside __init__ — is what makes
                # cold scale-up correct: the serve controller's health probe
                # (and so the STARTING -> RUNNING transition) queues behind
                # __init__, so a replica never reports RUNNING with
                # unresolved weights.
                from ..weights import WeightSubscriber

                weights.count(weights_source="plane")
                self._weights_sub = WeightSubscriber(weights_name)
                self._weights_version, params = self._weights_sub.get(
                    timeout=60.0, sharding=self._weights_sharding
                )
            elif params_blob is not None:
                from .._internal import serialization

                weights.count(weights_source="blob")
                params = serialization.loads(params_blob)
            else:
                from .. import models

                weights.count(weights_source="init")
                params = unbox_params(
                    models.init_params(
                        model_config, jax.random.PRNGKey(llm_config.seed or 0)
                    )
                )
            # on the device, not merely dispatched: the time is this phase's
            params = jax.block_until_ready(params)
            weights.count(weights_bytes=sum(
                getattr(x, "nbytes", 0)
                for x in jax.tree_util.tree_leaves(params)))
        if weights_name is not None:
            self._weights_resolve_s = weights.us / 1e6
        if role not in (None, "prefill", "decode"):
            raise ValueError(f"unknown replica role {role!r}")
        self._role = role
        self._kv_tier = None
        self._kv_cache = None
        # worker.startup's ``engine`` phase: the block pool's manager and the
        # engine with what it is built from (tier, adapter store). The
        # slot rows and the pool are allocated by the first admission, later
        with tracing.startup_phase("engine"):
            if llm_config.kv_cache_blocks:
                # a shared KV block pool under the engine's slots: admission is
                # memory-gated and prompts sharing cached prefixes prefill only
                # the suffix. Without one, a slot is a dense row
                from ..kvcache import KVCacheManager

                self._kv_cache = KVCacheManager(
                    num_blocks=llm_config.kv_cache_blocks,
                    block_size=llm_config.kv_block_size,
                    plan=plan,
                )
                if llm_config.kv_tier or role is not None:
                    # cluster KV prefix tier: role replicas need it for the
                    # prefill->decode handoff; fused replicas opt in to share
                    # warm prefixes across the deployment
                    from ..kvtier import GcsTierBackend, KVTierClient

                    self._kv_tier = KVTierClient(
                        model=llm_config.model_id,
                        backend=(
                            tier_backend if tier_backend is not None
                            else GcsTierBackend()
                        ),
                        block_size=llm_config.kv_block_size,
                        codec=llm_config.kv_ship_codec,
                    )
            self._adapter_store = None
            if llm_config.adapters is not None:
                # multi-tenant LoRA plane: one paged AdapterStore per
                # replica; request threads resolve slot leases before
                # admission so cold weight-plane pulls never block the
                # engine loop
                from ..lora import AdapterStore

                ac = llm_config.adapters
                self._adapter_store = AdapterStore(
                    model_config,
                    max_live=ac.max_live,
                    rank=ac.slot_rank,
                    alpha=ac.alpha,
                    source=ac.source,
                    plan=plan,
                    param_dtype=model_config.param_dtype,
                )
            self._engine = ContinuousBatchingEngine(
                model_config, params, mesh,
                num_slots=llm_config.max_batch_size,
                kv_cache=self._kv_cache,
                seed=llm_config.seed,
                plan=plan,
                kv_tier=self._kv_tier,
                prefill_chunk_tokens=llm_config.prefill_chunk_tokens,
                adapter_store=self._adapter_store,
            )
        self._tokenizer = None
        if tokenizer_name:
            from transformers import AutoTokenizer

            self._tokenizer = AutoTokenizer.from_pretrained(tokenizer_name)
        # event loop -> its open streams, while it has any
        self._loop_streams: Dict[Any, _LoopStreams] = {}
        tracing.startup_ready()  # the process's first replica is built

    def shutdown(self) -> None:
        """Replica shutdown hook: stop the engine's stepping thread."""
        self._engine.close()

    def warmup(self) -> Dict[str, Any]:
        """Serve replica warmup hook (runs at the end of Replica.__init__,
        before the replica can report healthy): assert weight-plane
        resolution actually happened so a STARTING replica with a
        ``weights_name`` can never reach RUNNING serving unresolved
        weights."""
        if self._weights_name is not None and self._weights_version is None:
            raise RuntimeError(
                f"weights {self._weights_name!r} not resolved at warmup"
            )
        return {
            "weights_name": self._weights_name,
            "weights_version": self._weights_version,
            "weights_resolve_s": self._weights_resolve_s,
        }

    # -- hot weight reload (weight plane) ------------------------------------

    def reload_weights(self, version: Optional[int] = None) -> Dict[str, Any]:
        """Swap in a weight-plane version (head when None). Routed through
        the replica handle (or serve's reconfigure) — in-flight requests
        finish on the old pytree; the next prefill reads the new one."""
        if self._weights_sub is None:
            raise ValueError(
                "replica was not deployed with weights_name; hot reload "
                "needs the weight plane"
            )
        new_version, params = self._weights_sub.get(
            version, timeout=60.0, sharding=self._weights_sharding
        )
        if new_version != self._weights_version:
            self._engine.swap_params(params)
            self._weights_version = new_version
        return {
            "version": self._weights_version,
            "staleness": self._weights_sub.staleness(),
        }

    def reconfigure(self, user_config):
        """serve reconfigure hook: ``{"weights_version": v}`` (or
        ``{"weights_version": None}`` for head) hot-reloads without
        restarting the replica."""
        if isinstance(user_config, dict) and (
            "weights_version" in user_config
        ) and self._weights_sub is not None:
            self.reload_weights(user_config["weights_version"])

    def _devices(self) -> list:
        """The devices this replica spans: its mesh's, or the default one."""
        import jax

        if self._plan is None:
            return jax.devices()[:1]
        return list(self._plan.mesh.devices.flat)

    def mesh_info(self) -> Dict[str, Any]:
        """The replica's mesh ownership card — polled into the serve
        controller's replica inventory (``ray_tpu list replicas``,
        dashboard ``/api/serve``): mesh shape, the devices it spans
        (platform, kind, ids), per-device HBM in use where the backend
        reports it (CPU meshes report None), and the per-device KV
        block-pool footprint."""
        devices = self._devices()
        if self._plan is None:
            info: Dict[str, Any] = {
                "mesh": {}, "tag": "tp=1", "num_devices": 1,
            }
        else:
            info = {
                "mesh": self._plan.mesh_shape(),
                "tag": self._plan.describe(),
                "num_devices": self._plan.num_devices,
            }
        info["platform"] = devices[0].platform
        info["device_kind"] = devices[0].device_kind
        info["device_ids"] = [d.id for d in devices]
        hbm = []
        for d in devices:
            stats = d.memory_stats()  # None on backends that keep none
            hbm.append(
                int(stats["bytes_in_use"])
                if stats and "bytes_in_use" in stats else None
            )
        info["per_device_hbm_bytes"] = hbm
        if self._kv_cache is not None:
            info["kv_pool_bytes_per_device"] = self._kv_cache.pool_accounting()[
                "kv_pool_bytes_per_device"
            ]
        if self._weights_sub is not None:
            info["weight_chunk_pulls"] = self._weights_sub.chunk_pulls
            info["weight_wire_bytes_pulled"] = (
                self._weights_sub.wire_bytes_pulled
            )
        return info

    def runtime_info(self) -> Dict[str, Any]:
        """What this replica's process holds and compiled: its pid, the
        chips the raylet granted it, peak device bytes, where its compile
        cache lives and what it answered, and the mode each Pallas kernel
        was traced in. Read by chip_smoke.py; the process that owns the
        chip is the only one that can report these."""
        import os

        import jax

        from .. import get_tpu_ids
        from .._internal import compile_cache
        from .._internal.platform import traced_kernel_modes

        return {
            "pid": os.getpid(),
            "tpu_ids": get_tpu_ids(),
            "peak_hbm_bytes": [
                (d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in self._devices()
            ],
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
            "compile": compile_cache.stats(),
            # worker.startup as a profiler session gets it: where the time
            # from the process's start to this replica went, by phase, and
            # the compile totals of this moment (util/tracing.py)
            "startup": tracing.startup_record(),
            "kernels": traced_kernel_modes(),
            # a routed model's expert counters (engine.expert_stats());
            # None for a dense model
            "moe": self._engine.expert_stats(),
            # who steps: steps the engine's own thread ran and the times it
            # parked with nothing to do
            "engine": {"stepper": self._engine.stepper_stats()},
            # what a cached position costs (sequence leaves only), what a
            # row carries whatever its length (per-row state with no
            # sequence axis), how the decode step stores a position in
            # each sequence leaf (None before the first admission), and
            # whether a request may be served a cached prefix (False, with
            # the reason, for a family whose rows carry such state)
            "kv": {
                "cache_bytes_per_token": self._engine.cache_bytes_per_token(),
                "state_bytes_per_row": self._engine.state_bytes_per_row(),
                "window_bytes_per_row": self._engine.window_bytes_per_row(),
                "row_write": self._engine.row_write(),
                # visits the decode kernel made over the steps dispatched,
                # what a dense grid would have, and the key positions the
                # visits copied (attention_chunks_visited /
                # attention_chunks_dense / attention_positions_copied)
                **self._engine.attention_chunks(),
                **(
                    {} if self._kv_cache is None else {
                        "prefix_reuse": self._kv_cache.prefix_reuse,
                        "prefix_reuse_refused":
                            self._kv_cache.prefix_reuse_refused,
                    }
                ),
            },
        }

    def check_prefill_logits(self, token_ids) -> Dict[str, Any]:
        """Parity self-check on this replica's own weights: the last
        position's logits of ``token_ids`` from the engine's prefill
        program (einsum attention over the prompt's keys) against a plain
        full-sequence forward through the family's training-mode model
        (the flash kernel). Returns both argmaxes, the largest absolute logit
        difference, and the reference's margin between its best two
        tokens — a difference above the margin can flip a greedy token
        without either path being wrong."""
        import jax
        import jax.numpy as jnp

        from .. import models

        cfg = self._engine._cfg
        tokens = jnp.asarray([list(token_ids)], jnp.int32)
        engine_logits, _ = self._engine._prefill(self._engine._params, tokens)
        ref_logits = jax.jit(
            lambda p, t: models.build(cfg).apply({"params": p}, t)[:, -1, :]
        )(self._engine._params, tokens)
        eng = engine_logits[0].astype(jnp.float32)
        ref = ref_logits[0].astype(jnp.float32)
        top2 = jax.lax.top_k(ref, 2)[0]
        return {
            "reference_argmax": int(jnp.argmax(ref)),
            "engine_argmax": int(jnp.argmax(eng)),
            "max_abs_logit_diff": float(jnp.max(jnp.abs(eng - ref))),
            "reference_top2_margin": float(top2[0] - top2[1]),
            "finite": bool(jnp.all(jnp.isfinite(eng)) & jnp.all(jnp.isfinite(ref))),
        }

    def kvcache_stats(self) -> Optional[Dict[str, Any]]:
        """Replica-local KV-cache stats (None without a block pool); routed
        through handle.options(method_name="kvcache_stats")."""
        if self._kv_cache is None:
            return None
        return self._kv_cache.stats()

    def kvtier_stats(self) -> Optional[Dict[str, Any]]:
        """Replica-local KV tier stats — exports held, registry totals
        (None when the replica is not on the tier); routed through
        handle.options(method_name="kvtier_stats")."""
        if self._kv_tier is None:
            return None
        out = self._kv_tier.stats()
        out["role"] = self._role or "fused"
        return out

    # -- disaggregated roles -------------------------------------------------

    def prefill(self, request: Dict[str, Any]) -> Optional[bytes]:
        """Prefill role: run ONLY the admission prefill and ship the
        committed KV (plus the first sampled token). Returns the shipment
        blob for decode_shipped, or None when this replica can't serve it
        right now (pool backpressure) — the ingress falls back to fused
        decode, so the request still completes."""
        if self._kv_tier is None:
            return None
        if self._requested_adapter_id(request) is not None:
            # adapter-tinted KV never ships through the base-model tier;
            # the ingress falls back to fused decode for this request
            return None
        shipment = self._engine.prefill_only(self._parse_request(request))
        return shipment.to_blob() if shipment is not None else None

    def decode_shipped(self, request: Dict[str, Any],
                       shipment_blob: Optional[bytes]) -> Dict[str, Any]:
        """Decode role: adopt a shipped prefix and decode. A missing blob,
        a dead prefill holder, or any fetch failure degrades to a normal
        computed admission — a transfer-plane problem costs latency, never
        a request."""
        lease = self._resolve_adapter(request)
        gen_req = self._parse_request(request, lease)
        ship = None
        if shipment_blob is not None and self._kv_tier is not None:
            from ..kvtier import KVShipment

            shipment = KVShipment.from_blob(shipment_blob)
            payload = self._kv_tier.fetch_shipment(shipment)
            if payload is not None:
                ship = (shipment, payload)
        try:
            result = self._engine.generate_one(gen_req, shipment=ship)
        finally:
            if self._adapter_store is not None:
                self._adapter_store.release(lease)
        return self._summary(result, False)

    def weights_info(self) -> Dict[str, Any]:
        return {
            "weights_name": self._weights_name,
            "version": self._weights_version,
            "resolve_s": self._weights_resolve_s,
            "staleness": (
                self._weights_sub.staleness()
                if self._weights_sub is not None
                else None
            ),
            # chunk codec of the resolved version ("raw" / "int8") — how
            # operators confirm a quantized publisher actually reached
            # this replica compressed
            "codec": (
                self._weights_sub.current_codec
                if self._weights_sub is not None
                else None
            ),
        }

    def _parse_request(self, request: Dict[str, Any],
                       lease=None) -> GenerationRequest:
        token_ids = request.get("token_ids")
        if token_ids is None:
            prompt = request.get("prompt")
            if prompt is None:
                raise ValueError("request needs 'token_ids' or 'prompt'")
            if self._tokenizer is None:
                raise ValueError(
                    "'prompt' requires a tokenizer; deploy with tokenizer_name"
                )
            token_ids = self._tokenizer.encode(prompt)
        return GenerationRequest(
            token_ids=list(token_ids),
            max_new_tokens=int(
                request.get("max_new_tokens", self._config.max_new_tokens)
            ),
            temperature=float(
                request.get("temperature", self._config.temperature)
            ),
            eos_token_id=request.get("eos_token_id"),
            adapter_id=lease.adapter_id if lease is not None else None,
            adapter_slot=lease.slot if lease is not None else -1,
        )

    # -- multi-tenant adapters -----------------------------------------------

    def _requested_adapter_id(self, request: Dict[str, Any]) -> Optional[str]:
        """The tenant identity of a request: an explicit ``adapter_id``
        field wins, else the ``@serve.multiplexed`` model-id the router
        stamped on this call (serve/replica.py binds it to the request
        thread before user code runs)."""
        aid = request.get("adapter_id")
        if aid is None:
            aid = serve.get_multiplexed_model_id() or None
        return aid

    def _resolve_adapter(self, request: Dict[str, Any]):
        """Resolve adapter id -> slot lease BEFORE engine admission, on
        the replica's request thread — a cold adapter's weight-plane pull
        runs here, never under the engine lock, so in-flight decodes keep
        stepping (the no-stall property). When every slot is pinned the
        request backpressures like KV-pool exhaustion: BackPressureError
        is retryable, routers send the request elsewhere."""
        aid = self._requested_adapter_id(request)
        return None if aid is None else self._acquire_adapter(aid)

    def _acquire_adapter(self, aid: str):
        if self._adapter_store is None:
            raise ValueError(
                f"request names adapter {aid!r} but the deployment has no "
                "adapter plane; set LLMConfig(adapters=AdapterConfig(...))"
            )
        import time as _time

        from ..exceptions import BackPressureError

        deadline = (
            _time.monotonic() + self._config.adapters.acquire_timeout_s
        )
        while True:
            lease = self._adapter_store.acquire(aid)
            if lease is not None:
                return lease
            if _time.monotonic() >= deadline:
                raise BackPressureError(
                    f"adapter store exhausted: all "
                    f"{self._adapter_store.num_slots} slots pinned by "
                    "in-flight requests"
                )
            _time.sleep(0.02)

    def adapters_stats(self) -> Optional[Dict[str, Any]]:
        """Replica-local adapter-plane stats (None without an adapter
        store); routed through handle.options(method_name=...)."""
        if self._adapter_store is None:
            return None
        return self._adapter_store.stats()

    def __call__(self, request: Dict[str, Any]) -> Dict[str, Any]:
        # "stream": true through a plain (non-stream) handle is the
        # buffered result; the HTTP/handle streaming path calls .stream
        lease = self._resolve_adapter(request)
        try:
            result = self._engine.generate(
                [self._parse_request(request, lease)]
            )[0]
        finally:
            if self._adapter_store is not None:
                self._adapter_store.release(lease)
        return self._summary(result, request.get("stream"))

    def _summary(self, result, streamed) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "token_ids": result.token_ids,
            "num_prompt_tokens": result.num_prompt_tokens,
            "finished_reason": result.finished_reason,
        }
        if streamed:
            out["finished"] = True
        if self._tokenizer is not None:
            out["text"] = self._tokenizer.decode(result.token_ids)
        return out

    async def stream(self, request: Dict[str, Any]):
        """Token streaming (reference: ray.llm streaming responses through
        serve — DeploymentResponseGenerator): yields one dict per generated
        token as it is sampled, then a final summary dict. Time-to-first-
        token is prefill latency instead of full-generation latency.

        An ``async def`` generator: the replica runs it on its event loop
        and none of its pool's threads waits for a step. The request goes to
        the engine with a sink that forwards to this loop
        (``_LoopStreams``), under the context this coroutine runs in, so
        the engine's request spans join the stream's trace; the engine's
        stepping thread makes the tokens, and this coroutine awaits them. A
        caller that closes the stream drops the sink: the row runs to its
        end and its result goes nowhere."""
        loop = asyncio.get_running_loop()
        aid = self._requested_adapter_id(request)
        lease = None
        if aid is not None:
            # a cold adapter is pulled, and a full store waited for, here:
            # neither on the loop nor under the engine lock
            lease = await loop.run_in_executor(
                None, self._acquire_adapter, aid)
        streams = self._loop_streams.get(loop)
        if streams is None:
            streams = self._loop_streams[loop] = _LoopStreams(loop)
        rid = None
        try:
            # no await between the submission and the inbox's registration:
            # deliveries reach this loop as callbacks, which run after it
            rid = self._engine.stream_to(
                self._parse_request(request, lease), streams.post)
            inbox = streams.inboxes[rid] = asyncio.Queue()
            index = 0
            all_ids: list = []
            prev_text = ""
            while True:
                tokens, end, posted_ns = await inbox.get()
                if end is not None:
                    # once a request: how late a finished answer leaves,
                    # behind the acknowledgements of its earlier tokens. A
                    # count on a region that closes at once: the loop's
                    # coroutines interleave on one thread, so none stays
                    # open across an await
                    with _span(
                        "replica.stream_end", request_id=rid,
                        inbox_wait_us=(time.perf_counter_ns() - posted_ns) // 1000,
                        tokens=len(tokens),
                    ):
                        pass
                for item in tokens:
                    out: Dict[str, Any] = {"token_id": item, "index": index}
                    if self._tokenizer is not None:
                        # BPE/SentencePiece pieces don't decode standalone
                        # (leading-space markers, multi-token unicode):
                        # decode the running sequence and emit the delta so
                        # clients can concatenate the streamed text verbatim
                        all_ids.append(item)
                        full = self._tokenizer.decode(all_ids)
                        out["text"] = full[len(prev_text):]
                        prev_text = full
                    index += 1
                    yield out
                if isinstance(end, BaseException):
                    raise end
                if end is not None:
                    yield self._summary(end, True)
                    return
        finally:
            if rid is not None:
                self._engine.drop_sink(rid)
                streams.inboxes.pop(rid, None)
            if not streams.inboxes:
                self._loop_streams.pop(loop, None)
            if self._adapter_store is not None:
                self._adapter_store.release(lease)


class _DisaggIngress:
    """Disaggregated serving ingress: route a new request to a prefill
    replica (prefix-affinity biased, so shared prefixes keep hitting the
    replica whose radix already holds them), then hand the shipment blob
    to a decode replica. Every failure on the prefill side degrades to
    ``decode_shipped(request, None)`` — a fused computed admission on the
    decode replica — so disaggregation can only add latency, never
    errors."""

    def __init__(self, prefill_handle, decode_handle,
                 prefix_affinity_tokens: int = 0):
        self._prefill = prefill_handle.options(method_name="prefill")
        if prefix_affinity_tokens:
            self._prefill = self._prefill.options(
                prefix_affinity_tokens=prefix_affinity_tokens
            )
        self._decode = decode_handle.options(method_name="decode_shipped")

    def __call__(self, request: Dict[str, Any]) -> Dict[str, Any]:
        blob = None
        try:
            blob = self._prefill.remote(request).result()
        except Exception:
            blob = None  # prefill-side failure: decode computes it fused
        return self._decode.remote(request, blob).result()

    def stream(self, request: Dict[str, Any]):
        """Streaming through the disaggregated path: the prefill handoff
        happens up front, then tokens stream from the decode replica."""
        blob = None
        try:
            blob = self._prefill.remote(request).result()
        except Exception:
            blob = None
        yield self._decode.remote(request, blob).result()


def build_llm_deployment(
    llm_config: LLMConfig,
    *,
    params_blob: Optional[bytes] = None,
    tokenizer_name: Optional[str] = None,
    name: Optional[str] = None,
    weights_name: Optional[str] = None,
    tier_backend=None,
):
    """Return a bound serve Application for this LLM (reference:
    build_llm_deployment, llm/_internal/serve/builders).

    With ``llm_config.roles`` the application is three deployments:
    ``<name>-prefill`` / ``<name>-decode`` replica pools plus a
    ``_DisaggIngress`` root that routes the prefill→decode KV handoff.
    ``tier_backend`` (tests) injects a shared in-process tier backend."""
    base_name = name or llm_config.model_id

    def _common_options() -> Dict[str, Any]:
        # the replica actor leases exactly resources_per_replica; a TPU
        # share makes its worker the owner of those chips
        res = dict(llm_config.resources_per_replica)
        actor = {"num_cpus": res.pop("CPU", 1.0)}
        chips = res.pop("TPU", 0)
        if chips:
            actor["num_tpus"] = chips
        if res:
            actor["resources"] = res
        return dict(ray_actor_options=actor)

    if llm_config.roles is not None:
        prefill_dep = serve.deployment(
            _LLMReplica,
            name=f"{base_name}-prefill",
            num_replicas=llm_config.roles["prefill"],
            **_common_options(),
        ).bind(
            llm_config, params_blob, tokenizer_name, weights_name,
            "prefill", tier_backend,
        )
        decode_dep = serve.deployment(
            _LLMReplica,
            name=f"{base_name}-decode",
            num_replicas=llm_config.roles["decode"],
            **_common_options(),
        ).bind(
            llm_config, params_blob, tokenizer_name, weights_name,
            "decode", tier_backend,
        )
        ingress = serve.deployment(
            _DisaggIngress, name=base_name, num_replicas=1
        )
        return ingress.bind(
            prefill_dep, decode_dep,
            llm_config.prefix_affinity_tokens,
        )

    options = dict(name=base_name, **_common_options())
    autoscale_policy = getattr(llm_config, "autoscale_policy", None)
    if autoscale_policy:
        # closed-loop SLO autoscaling (serve/autoscale.py): TTFT p99 /
        # queue / shed pressure instead of the raw ongoing-requests signal
        options["autoscale_policy"] = (
            dict(autoscale_policy)
            if isinstance(autoscale_policy, dict)
            else autoscale_policy
        )
    elif llm_config.autoscaling_config:
        # TPU replica autoscaling: the serve controller adds/removes engine
        # replicas from queue depth (serve/_private autoscaling policy)
        options["autoscaling_config"] = dict(llm_config.autoscaling_config)
    else:
        options["num_replicas"] = llm_config.num_replicas
    dep = serve.deployment(_LLMReplica, **options)
    return dep.bind(
        llm_config, params_blob, tokenizer_name, weights_name,
        None, tier_backend,
    )


def publish_llm_weights(
    llm_config: LLMConfig,
    params,
    *,
    weights_name: Optional[str] = None,
    meta: Optional[dict] = None,
):
    """Publish one weight-plane version for a deployment's replicas,
    honoring ``llm_config.quantized`` (int8 chunk codec — the broadcast
    tree, the per-node store copies, and each replica's warm-up pull all
    carry the compressed form). Defaults the model name to
    ``llm/<model_id>``; pass the same ``weights_name`` the deployment was
    built with when it differs."""
    from .. import weights

    return weights.publish(
        weights_name or f"llm/{llm_config.model_id}",
        params,
        meta=meta,
        quantized=getattr(llm_config, "quantized", False),
    )
