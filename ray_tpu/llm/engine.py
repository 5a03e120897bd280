"""The TPU LLM engine: jitted prefill + decode with a KV cache.

Role-equivalent of the vLLM engine the reference wraps
(llm/_internal/batch/stages/vllm_engine_stage.py submits prompts to vLLM's
async engine); TPU-native design:

- **prefill** runs the model over one request's prompt in decode mode,
  writing every layer's K/V into the cache collection in one MXU-heavy
  pass, and the row is inserted into the slot pool
- **decode** is one token per step for the whole pool of slots: a single
  jit program re-run with the carried (donated) cache and a per-row cache
  index, so the HBM-resident cache never leaves the device
- **static shapes**: prefill compiles once a prompt length (or chunk) and
  hits the jit cache afterwards; the decode step has one shape. Free rows
  keep decoding at position 0, their outputs unread: wasted FLOPs on idle
  rows are the standard TPU trade for static shapes.

One engine, ``ContinuousBatchingEngine``, serves (``serving.py``) and runs
batch inference (``batch.py``), with a block pool (``kv_cache``) or with a
dense row a slot. Greedy and temperature sampling; per-request
max_new_tokens.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import os
import queue
import threading
import time
import weakref
import zlib
from typing import Any, Callable, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .._internal.host_sync import host_sync
from .._internal.platform import decode_step_compiler_options
from ..models import (
    INDEX, ROUTING as _ROUTING, SEQUENCE, STATE, WINDOW, cache_kinds,
)
from ..ops.decode_attention import traced_chunk, visits
from ..ops.kv_row_write import traced_form
from ..util import events as _events
from ..util import tracing as _tracing


def _resolve_seed(seed: Optional[int]) -> int:
    """Per-process default: replicas sampling at temperature > 0 must not
    emit identical streams, which a fixed PRNGKey(0) guarantees."""
    if seed is not None:
        return int(seed)
    return int.from_bytes(os.urandom(4), "little")


def _record_ttft(seconds: float, hit: bool, mesh: str = "tp=1",
                 tier: str = "local") -> None:
    """tier: where the prefix KV came from — "local" (this replica's radix
    index), "peer" (pulled/shipped through the KV tier), "miss" (computed
    from scratch)."""
    try:
        from ..util.metrics import record_kvcache_ttft

        record_kvcache_ttft(seconds, hit, mesh=mesh, tier=tier)
    except Exception:
        pass


def _record_itl(seconds: float, mesh: str = "tp=1") -> None:
    """Inter-token latency: one observation per emitted token."""
    try:
        from ..util.metrics import record_serve_itl

        record_serve_itl(seconds, mesh=mesh)
    except Exception:
        pass


def _sample_impl(logits, temps, key):
    """Fused device-side sampling: greedy where temps == 0, temperature
    categorical elsewhere — ONE program and one host transfer per step
    (the old form materialized argmax AND categorical separately)."""
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temps[:, None], 1e-6)
    sampled = jax.random.categorical(key, scaled, axis=-1)
    return jnp.where(temps == 0.0, greedy, sampled)


_fused_sample = jax.jit(_sample_impl)


@jax.jit
def _greedy_sample(logits):
    # a def, not a lambda: its XLA module is named jit__greedy_sample
    return jnp.argmax(logits, axis=-1)


@jax.jit
def _merge_last(sampled, fresh, first):
    """The tokens a pool step is fed, (slots, 1), made where the last
    step's samples already are: a row admitted since takes the token its
    prefill sampled (``first``, pool-shaped and on the device too:
    ``_put_first``), every other row what the step before sampled for it.
    It runs in every step, fresh rows or none, so a batch of concurrent
    requests reaches no program a lone request did not (jit__merge_last)."""
    return jnp.where(fresh, first, sampled)[:, None]


@jax.jit
def _put_first(firsts, token, si):
    """``firsts`` (slots,) with row ``si`` set to an admission's first token
    (``token``: (1,), the sampler's own output, or the host's where it has
    the token already). One program whatever the slot and however many
    admissions a step makes: ``si`` is data (jit__put_first)."""
    return jax.lax.dynamic_update_slice(
        firsts, token.astype(firsts.dtype), (si,))


_span = _tracing.annotate_device_trace

# Admissions dispatched whose first token the host has not read, at most.
# The parent of this bound held one (it read every token at once); two keep
# the device's queue full across an admission (while the host waits for
# admission j, j+1 is queued) and cost one solo row, one prompt's logits
# and one prefill's scratch of device memory more.
_UNREAD_ADMISSIONS = 2


def _prefill_path(cached: int, shipped: bool = False,
                  budgeted: bool = False) -> str:
    """An admission's ``path`` on its ``engine.prefill`` span, the program
    the prompt went through: "whole" (no cached prefix: one ``_prefill``,
    which makes the row's cache and attends to the prompt's own keys),
    "suffix" (chunks through ``_decode`` against every position of a cache
    that holds earlier keys: behind a prefix hit, and every chunk of a
    budgeted prefill, ``_advance_prefills``) or "shipped" (zero-prefill:
    none)."""
    if shipped:
        return "shipped"
    return "suffix" if cached or budgeted else "whole"


def _new_expert_counts(model_config, rows: int = 0) -> Optional[dict]:
    """Zeroed device-side counters for a model with routed experts, None
    for one without: ``steps`` decode steps, ``assignments`` (routed
    layers, experts held) choices made by live rows, ``touched`` (routed
    layers,) the sum over steps of distinct held experts live rows chose. A
    row a layer that has routed experts: every layer, unless the config
    names them (``routed_layers``; a dense layer has nothing to count).
    int32: at 8 choices a row and 50 steps a second an expert's count lasts
    two months.

    A config that holds a share of its experts (``experts_held``, a
    ``(first, stop)`` range of the ``n_experts`` routed over) counts over
    the experts held and has two entries more: ``absent`` (routed layers,),
    live rows' choices that fell on experts held elsewhere, and ``choice``
    (routed layers, ``rows``, k), the last step's choice a row over all
    ``n_experts`` (free rows' too): what a check that follows the
    program's routing reads where more than one row is live, since a sum
    over rows names no row. A family that holds all its experts has
    neither, and its step is the program it was."""
    n_experts = getattr(model_config, "n_experts", 0)
    if not n_experts:
        return None
    layers = len(getattr(
        model_config, "routed_layers", range(model_config.n_layers)
    ))
    held = getattr(model_config, "experts_held", None)
    counts = {
        "steps": jnp.zeros((), jnp.int32),
        "assignments": jnp.zeros(
            (layers, held[1] - held[0] if held else n_experts), jnp.int32),
        "touched": jnp.zeros((layers,), jnp.int32),
    }
    if held:
        counts["absent"] = jnp.zeros((layers,), jnp.int32)
        counts["choice"] = jnp.zeros(
            (layers, rows, model_config.experts_per_token), jnp.int32)
    return counts


def _count_experts(counts: dict, routing: dict, active,
                   first: int = 0) -> dict:
    """``counts`` plus one decode step's choices (``routing``: the sown
    collection, ``layer_<i>/moe/experts`` a tuple of one (rows, k) array
    for each layer that routes, taken in layer order), free rows left
    out. ``first``: the first expert held (``_new_expert_counts``)."""
    n_experts = counts["assignments"].shape[1]
    step = jnp.stack([
        routing[name]["moe"]["experts"][0]
        for name in sorted(routing, key=lambda n: int(n.rpartition("_")[2]))
    ])  # (routed layers, rows, k)
    # (an expert held elsewhere is no column of these: a row of zeros)
    hits = jax.nn.one_hot(
        step - first if first else step, n_experts, dtype=jnp.int32)
    if active is not None:
        hits = hits * jnp.asarray(active, jnp.int32)[None, :, None, None]
    per_expert = hits.sum(axis=(1, 2))  # (layers, experts)
    out = {
        "steps": counts["steps"] + 1,
        "assignments": counts["assignments"] + per_expert,
        "touched": counts["touched"] + (per_expert > 0).sum(axis=1),
    }
    if "absent" in counts:
        live = step.shape[2] * (
            step.shape[1] if active is None
            else jnp.sum(jnp.asarray(active, jnp.int32)))
        out["absent"] = counts["absent"] + live - per_expert.sum(axis=1)
        out["choice"] = step
    return out


class _EngineLock:
    """The engine lock: reentrant and a context manager, like the ``RLock``
    it wraps, and handed over. A thread that asks for it (``acquire``,
    ``with``) is counted while it waits; the stepping thread takes it with
    ``acquire_behind``, which lets everybody counted go first. A plain lock
    let go and taken again at once by one thread keeps every other thread
    out for as long as that thread has steps to run."""

    __slots__ = ("_lock", "_asked", "_asking")

    def __init__(self):
        self._lock = threading.RLock()
        self._asked = threading.Condition(threading.Lock())
        self._asking = 0

    def acquire(self) -> None:
        with self._asked:
            self._asking += 1
        try:
            self._lock.acquire()
        finally:
            with self._asked:
                self._asking -= 1
                if not self._asking:
                    self._asked.notify_all()

    def acquire_behind(self) -> None:
        with self._asked:
            while self._asking:
                self._asked.wait()
        self._lock.acquire()

    def release(self) -> None:
        self._lock.release()

    def __enter__(self):
        self.acquire()

    def __exit__(self, exc_type, exc, tb):
        self._lock.release()


class _StepLock:
    """The engine lock as a step takes it: the wait is an
    ``engine.lock_wait`` region in the profiler's trace. ``behind`` is the
    stepping thread's way in, after whoever else is asking."""

    __slots__ = ("_lock", "_acquire")

    def __init__(self, lock: _EngineLock, behind: bool = False):
        self._lock = lock
        self._acquire = lock.acquire_behind if behind else lock.acquire

    def __enter__(self):
        with _span("engine.lock_wait"):
            self._acquire()

    def __exit__(self, exc_type, exc, tb):
        self._lock.release()


class _Sink:
    """Where a request's output goes. After a step, whoever ran it calls
    ``post`` once with every delivery of that step whose sinks share the
    callable: a list of ``(request id, new tokens, end)``, ``end`` None
    while the request runs, then its ``GenerationResult``, or the exception
    of the step that failed it. ``stream`` False asks for the end alone."""

    __slots__ = ("post", "stream", "sent")

    def __init__(self, post: Callable[[list], None], stream: bool):
        self.post = post
        self.stream = stream
        self.sent = 0  # tokens handed over so far


class _Stepper:
    """What the stepping thread and its engine share while the thread holds
    no reference to the engine: parked, it keeps no engine alive."""

    __slots__ = ("cond", "kicked", "closed", "thread", "steps", "parked")

    def __init__(self):
        self.cond = threading.Condition()
        self.kicked = False
        self.closed = False
        self.thread: Optional[threading.Thread] = None
        self.steps = 0  # steps the thread ran
        self.parked = 0  # times it found nothing to do and waited

    def stop(self) -> None:
        with self.cond:
            self.closed = True
            self.cond.notify_all()

    def run(self, engine_ref) -> None:
        while True:
            with self.cond:
                while not (self.kicked or self.closed):
                    self.parked += 1
                    self.cond.wait()
                if self.closed:
                    return
                self.kicked = False
            engine = engine_ref()
            if engine is None:
                return
            engine._step_while_work(self)
            del engine


@dataclasses.dataclass
class GenerationRequest:
    token_ids: List[int]
    max_new_tokens: int = 64
    temperature: float = 0.0
    eos_token_id: Optional[int] = None
    # multi-tenant LoRA (ray_tpu.lora): the replica resolves adapter_id to
    # an AdapterStore slot lease at admission and stamps the slot index
    # here; -1 = base model. The engine only ever reads the index — lease
    # lifecycle (pin/release) belongs to the caller holding the lease.
    adapter_id: Optional[str] = None
    adapter_slot: int = -1


@dataclasses.dataclass
class GenerationResult:
    token_ids: List[int]  # generated tokens only
    num_prompt_tokens: int
    finished_reason: str  # "eos" | "length"


class _DecodeModelBase:
    """Shared jitted prefill/decode programs over the cached model of
    whatever family ``model_config`` belongs to (``ray_tpu.models`` says
    what a family has to offer)."""

    def __init__(self, model_config, params, mesh=None, plan=None,
                 adapter_store=None):
        from .. import models

        self._cfg = model_config
        self._zeroes_free_state = not models.restarts_own_state(model_config)
        self._mesh = mesh
        # multi-tenant LoRA slot bank (ray_tpu.lora.AdapterStore) or None.
        # With a store, every prefill/decode call threads (bank, slots)
        # through the SAME jitted programs — the bank is a traced argument
        # like params, so attaching/evicting adapters never re-compiles.
        self._adapter_store = adapter_store
        # tensor-parallel plan: explicit, or derived from a non-trivial
        # mesh so `mesh=` alone wires TP through either engine
        if plan is None and mesh is not None and mesh.shape.get("tp", 1) > 1:
            from ..parallel.plan import PartitionPlan

            plan = PartitionPlan(mesh)
        self._plan = plan
        self._mesh_tag = plan.describe() if plan is not None else "tp=1"
        self._model = models.build(model_config, mesh, decode=True)
        self._cache_shardings = None
        self._replicated = None
        if plan is not None:
            # compile-with-plan: params live sharded; both programs pin
            # their outputs (replicated logits for host sampling, the
            # decode cache sharded along the KV-heads axis) so GSPMD
            # inserts one psum per attention/MLP and the cache never
            # gathers. The cache *structure* is length-independent, so one
            # eval_shape fixes the out_shardings for every shape bucket.
            self._params = plan.shard_params(params)
            cache_shape = jax.eval_shape(
                self._prefill_impl, self._params,
                jax.ShapeDtypeStruct((1, 1), jnp.int32),
            )[1]
            cache_sh = plan.cache_shardings(cache_shape)
            rep = plan.replicated()
            self._cache_shardings = cache_sh
            self._replicated = rep
            self._prefill = jax.jit(
                self._prefill_impl, out_shardings=(rep, cache_sh)
            )
            # a program that takes a cache and returns its successor
            # donates it: the step writes one position a row in place
            # (the cache comes in under the sharding it leaves with, so
            # the alias holds per shard). Every caller rebinds what it
            # passed; a cache handed to _decode is gone afterwards
            self._decode = jax.jit(
                self._decode_impl, donate_argnums=(1,),
                out_shardings=(rep, cache_sh),
                compiler_options=decode_step_compiler_options(),
            )
        else:
            self._params = params
            self._prefill = jax.jit(self._prefill_impl)
            self._decode = jax.jit(
                self._decode_impl, donate_argnums=(1,),
                compiler_options=decode_step_compiler_options(),
            )

    def _prefill_impl(self, params, tokens, adapters=None, adapter_slots=None):
        logits, vars_out = self._model.apply(
            {"params": params}, tokens, adapters, adapter_slots,
            mutable=["cache"],
        )
        return logits[:, -1, :], vars_out["cache"]

    def _decode_impl(self, params, cache, last_tokens, adapters=None,
                     adapter_slots=None, active=None, expert_counts=None):
        if active is not None:
            # a pool steps every row, live or free. A free row's position
            # would otherwise run on, past the cache's end in time, and
            # attention reads as far as a row's position says: an inactive
            # row restarts at 0 each step, one key long, and whatever it
            # writes there the next admission's row insert replaces. State
            # that is carried and not indexed restarts with it: a free
            # row's is zero before every step, so it holds one step of
            # garbage at most and never what a request left (a family
            # that reads a row at position 0 as zero itself is left to)
            def restart(leaf, kind):
                if kind == INDEX:
                    return jnp.where(active, leaf, 0)
                if kind == STATE and self._zeroes_free_state:
                    live = jnp.asarray(active).reshape(
                        (-1,) + (1,) * (leaf.ndim - 1))
                    return jnp.where(live, leaf, jnp.zeros((), leaf.dtype))
                return leaf

            cache = jax.tree.map(restart, cache, cache_kinds(cache))
        # a routed model's step also says which experts its rows chose: the
        # running counts ride through the program, so counting costs the
        # host nothing and the step no sync
        counting = expert_counts is not None
        logits, vars_out = self._model.apply(
            {"params": params, "cache": cache}, last_tokens, adapters,
            adapter_slots,
            mutable=["cache", _ROUTING] if counting else ["cache"],
        )
        if counting:
            held = getattr(self._cfg, "experts_held", None)
            return logits[:, -1, :], vars_out["cache"], _count_experts(
                expert_counts, vars_out[_ROUTING], active,
                held[0] if held else 0,
            )
        return logits[:, -1, :], vars_out["cache"]

    def _adapter_args(self, slots) -> tuple:
        """Extra jit arguments for an adapter-aware call: the slot bank
        plus the per-row slot index vector (-1 = base model). Empty when
        the engine has no store, so every legacy 2/3-arg call keeps its
        compiled program."""
        if self._adapter_store is None:
            return ()
        return (
            self._adapter_store.bank(),
            jnp.asarray(np.asarray(slots, np.int32)),
        )

    def swap_params(self, params):
        """Hot weight reload: the jitted prefill/decode programs close over
        shapes only (params are traced arguments), so swapping the pytree
        retunes nothing — the next prefill simply reads the new weights.
        Under a partition plan the fresh pytree is re-placed into the
        sharded layout first (each device takes only its shard)."""
        if self._plan is not None:
            params = self._plan.shard_params(params)
        self._params = params

    @staticmethod
    def _sample_on_device(logits, temps: np.ndarray, key):
        """Greedy where temps==0, temperature-categorical elsewhere — the
        one sampling rule used everywhere. All-greedy batches
        skip the categorical entirely (and need no key); mixed batches run
        the fused sampler (one program). The ids stay on the device."""
        if temps.any():
            return _fused_sample(logits, jnp.asarray(temps), key)
        return _greedy_sample(logits)


@dataclasses.dataclass
class _Slot:
    request_id: int
    request: GenerationRequest
    generated: List[int]
    lease: Any = None  # KVCacheLease when the engine runs paged
    trace: Any = None  # {"ctx", "wall"} when the request is traced
    last_emit_ts: float = 0.0  # monotonic stamp of the last emitted token


@dataclasses.dataclass
class _Admission:
    """An admission whose first token the host has not read: the prefill
    and its sampler are dispatched, ``token`` (1,) is on the device (and,
    for a row that decodes on, in the pool's ``_firsts`` at ``si``).
    ``slot`` is the row's ``_Slot``, its ``generated`` still empty, or None
    for a request that wants one token at most and was never inserted."""
    si: int
    rid: int
    request: GenerationRequest
    token: Any
    slot: Optional[_Slot]
    lease: Any
    ttft: Optional[tuple]  # (enqueue stamp, cached tokens, tier source)


@dataclasses.dataclass
class _Step:
    """A pool decode step the device has been given and the host has not
    read: its sampled ids, still on the device, and the rows that were
    live when it was dispatched (slot index -> the ``_Slot`` itself: a
    slot index alone may name another request by the time it is read)."""
    tokens: Any
    rows: Dict[int, _Slot]


class ContinuousBatchingEngine(_DecodeModelBase):
    """Continuous (in-flight) batching: a fixed pool of decode slots; new
    requests prefill into free slots while other slots keep decoding, so
    short requests don't wait for long ones and the decode batch stays full.

    Role-equivalent of vLLM's continuous batching scheduler behind
    ``ray.llm`` (llm/_internal/serve — its async engine's admission), TPU-style:
    static shapes throughout. The decode program is ONE jitted step over the
    full (num_slots, 1) batch with a PER-ROW cache index (models/llama.py
    decode path); prefill runs per request at its prompt length and the
    resulting K/V rows are inserted into the pooled cache. XLA compiles one
    decode program + one prefill program per prompt-length bucket.

    The decode step runs one ahead of the host (``_dense_step``): a step's
    sampled ids feed the next step on the device, and the host reads step
    N while step N+1 runs. So a token reaches its caller one host read
    after it was computed, and a row leaves the batch when the host has
    *seen* its last token: it may ride one step more, whose token for it
    nobody reads. An admission is read the same way: its prefill and the
    sampler of its first token are dispatched, the token feeds the next
    step on the device (``_put_first``), and the host reads it behind that
    step's dispatch (``_read_firsts``); at most ``_UNREAD_ADMISSIONS`` are
    ever dispatched and unread (``_hold_to_bound``). Only what needs the
    token on the host at once reads it at once (``_reads_first_at_once``).

    One thread steps. ``generate``, ``generate_one``, ``generate_stream``
    and ``stream_to`` enqueue, wake the engine's stepping thread and wait
    for what it makes: after every step it hands each request that has a
    sink its new tokens, and at its end its result (``_deliver``). With no
    work the thread parks on a condition, without the lock. ``step()`` and
    ``run_until_complete()`` remain a caller's own drive under the same
    lock, for tests and batch callers that never start the thread; their
    steps deliver to sinks too, and the two serialise on ``_lock`` if
    mixed. ``_lock`` is handed over between steps (``_EngineLock``): what
    reads or replays the engine's state from outside (``expert_stats``, a
    benchmark driver's check) writes ``with engine._lock:`` and is in
    before the next step starts.
    """

    def __init__(
        self,
        model_config,
        params,
        mesh=None,
        num_slots: int = 8,
        kv_cache=None,
        seed: Optional[int] = None,
        plan=None,
        kv_tier=None,
        prefill_chunk_tokens: int = 0,
        adapter_store=None,
    ):
        from .. import models

        super().__init__(
            model_config, params, mesh, plan=plan, adapter_store=adapter_store
        )
        self._num_slots = num_slots
        self._slots: Dict[int, _Slot] = {}  # slot index -> active request
        # (request_id, GenerationRequest, shipment-or-None): the third
        # element carries a directed prefill->decode handoff
        # Appended by any thread under ``_submit_lock``, never under
        # ``_lock``: a submission does not wait for a running step. Taken
        # from the left by whoever steps
        self._pending: Deque[tuple] = collections.deque()
        self._submit_lock = threading.Lock()
        self._next_id = 0
        self._rng = jax.random.PRNGKey(_resolve_seed(seed))
        self._step_count = 0
        # the decode step in flight (dispatched, unread) and the newest
        # sampled ids, on the device: what the next step is fed
        self._inflight: Optional[_Step] = None
        self._sampled = jnp.zeros((num_slots,), jnp.int32)
        # the first token of every row's admission, where ``_merge_last``
        # reads a fresh row's, and the admissions whose token the host has
        # not read yet, oldest first (empty between steps)
        self._firsts = jnp.zeros((num_slots,), jnp.int32)
        if self._replicated is not None:
            self._firsts = jax.device_put(self._firsts, self._replicated)
        self._unread: List[_Admission] = []
        # running expert counts of a routed model (None for a dense one),
        # device-side; expert_stats() reads them
        self._expert_counts = _new_expert_counts(model_config, num_slots)
        # the decode kernel's visits over the steps dispatched: those it
        # made, those a grid of rows x the whole cache would have, and the
        # key positions the ones made copied (attention_chunks()), one
        # call's worth a step, counted from the rows' positions as the host
        # knows them
        self._attention_chunks = [0, 0, 0]
        self._attention_grid: Optional[tuple] = None  # (chunk, max_seq_len)
        self._cache = None  # pooled cache, allocated on first prefill
        # paged prefix cache (ray_tpu.kvcache.KVCacheManager) or None for
        # the dense per-slot pool; with a manager, _admit serves the
        # longest cached prefix, prefills only the suffix, and blocks
        # admission when the pool is out of blocks (backpressure, not OOM)
        self._kv = kv_cache
        # a family whose rows carry state with no sequence axis: the span
        # of a decode dispatch says how many rows' state the step carries
        # (the pool's, live or free), and the manager leases without
        # matching or committing, because K/V blocks alone would resume a
        # recurrent layer from a zero state
        self._state_span: Dict[str, int] = {}
        if models.carries_row_state(model_config):
            self._state_span = {"state_rows": num_slots}
            if kv_cache is not None:
                kv_cache.refuse_prefix_reuse(
                    f"a {type(model_config).__name__} row carries per-row "
                    "state with no sequence axis or a window layer's ring, "
                    "which no K/V block holds: a hit would resume those "
                    "layers from nothing"
                )
        if kv_cache is not None and self._plan is not None:
            # the manager's block pools must live in the same sharded
            # layout as the decode cache they exchange rows with
            kv_cache.adopt_plan(self._plan)
        # cluster KV prefix tier (ray_tpu.kvtier.KVTierClient) or None.
        # With a tier, admission resolves warm prefixes local-hit ->
        # peer-pull -> recompute, adopted blocks land in the paged pool,
        # and computed prefixes are exported for the rest of the cluster.
        # Requires a kv_cache (the tier ships paged blocks).
        self._tier = kv_tier
        # whoever steps, or reads or replays the engine's state from
        # outside a step, holds this (reentrant)
        self._lock = _EngineLock()
        self._step_lock = _StepLock(self._lock)
        # rid -> where that request's tokens and result go; a request
        # without one is a ``step()`` caller's, who reads the return value
        self._sinks: Dict[int, _Sink] = {}
        self._stepper = _Stepper()
        weakref.finalize(self, self._stepper.stop)
        # slot index -> monotonic stamp of its last retirement
        self._slot_freed: Dict[int, float] = {}
        self._enqueue_ts: Dict[int, float] = {}  # rid -> wall, for TTFT
        # rid -> {"ctx", "wall"}: populated only while the submitting
        # request is traced, so the untraced path never touches it
        self._req_trace: Dict[int, Any] = {}
        # rids already reported as blocked on KV admission (one flight
        # event per episode, not one per engine step while starved)
        self._blocked_rids: set = set()
        # slot-row readback for retire-time commits (si is traced: 1 program)
        self._extract_row = jax.jit(
            lambda pool, si: jax.tree.map(
                lambda p: jax.lax.dynamic_slice_in_dim(p, si, 1, axis=0), pool
            )
        )
        # donated in-place row insert, like _decode, the other program that
        # advances a cache: one compiled program for every slot (si is a
        # traced scalar), no full-pool copy per admission. The solo row is
        # read, not donated
        self._insert_row = jax.jit(
            lambda pool, solo, si: jax.tree.map(
                lambda p, s: jax.lax.dynamic_update_index_in_dim(
                    p, s[0], si, axis=0
                ),
                pool,
                solo,
            ),
            donate_argnums=(0,),
        )
        # -- chunked prefill ----------------------------------------------
        # per-STEP token budget across all in-progress prefills; 0 = run
        # each admission prefill to completion (the historical behavior).
        # In-progress prefills park in _prefilling keyed by their reserved
        # slot index, advancing <= budget tokens per step so in-flight
        # decodes keep stepping instead of stalling behind a long prompt.
        self._prefill_chunk = int(prefill_chunk_tokens or 0)
        self._prefilling: Dict[int, dict] = {}
        self._empty_row_shape = None
        # observability for the perf-smoke guard: prefill tokens actually
        # computed by the most recent step()
        self.last_step_prefill_tokens = 0

    # -- public API ----------------------------------------------------------

    def add_request(self, request: GenerationRequest,
                    shipment=None) -> int:
        """``shipment`` is an optional directed KV handoff: a
        ``(KVShipment, payload)`` pair from a prefill replica (fetched by
        the caller through the tier backend). Admission adopts the shipped
        blocks instead of re-running prefill. The request is the caller's
        to step (``step``, ``run_until_complete``); it never waits for a
        running step."""
        self._check(request)
        return self._submit(request, shipment, None)

    def _check(self, request: GenerationRequest) -> None:
        if len(request.token_ids) + request.max_new_tokens > self._cfg.max_seq_len:
            raise ValueError("prompt + max_new_tokens exceeds max_seq_len")

    def _submit(self, request: GenerationRequest, shipment,
                sink: Optional[_Sink]) -> int:
        # one stamp for the queue-wait span, TTFT and queue_wait_us
        now = time.time()
        tr = None
        if _tracing.is_tracing_enabled():
            tr = {"ctx": _tracing.current_context(), "wall": now}
        with self._submit_lock:
            if sink is not None and self._stepper.closed:
                raise RuntimeError("the engine is closed")
            rid = self._next_id
            self._next_id += 1
            self._enqueue_ts[rid] = now
            if tr is not None:
                self._req_trace[rid] = tr
            if sink is not None:
                self._sinks[rid] = sink
            # last: from here on a step may take it
            self._pending.append((rid, request, shipment))
        return rid

    @property
    def num_active(self) -> int:
        return len(self._slots) + len(self._pending) + len(self._prefilling)

    def step(self) -> List[tuple]:
        """One engine iteration: admit pending requests into free slots
        (prefill), decode one token for every occupied slot, retire finished
        requests. Returns [(request_id, GenerationResult)] finished now."""
        with self._step_lock:
            return self._step_locked()

    def _step_locked(self) -> List[tuple]:
        # wall_us lays the profiler's clock (which counts from its
        # session's start) beside the request spans' and the clients'
        with _span(
            "engine.step", step=self._step_count,
            pending=len(self._pending), prefilling=len(self._prefilling),
            wall_us=time.time_ns() // 1000,
        ):
            self.last_step_prefill_tokens = 0
            finished: List[tuple] = self._admit()
            if self._prefilling:
                self._advance_prefills(finished)
            self._dense_step(finished)
            self._deliver(finished)
            return finished

    def _deliver(self, finished: List[tuple]) -> None:
        """Hand every request that has a sink what this step made for it:
        the tokens it had not been given, and its result if it ended. One
        ``post`` a step for all the sinks that share it, so a loop with 60
        streams is woken once a step and not once a token."""
        sinks = self._sinks
        if not sinks:
            return
        with _span("engine.deliver"):
            batches: Dict[Callable, list] = {}
            for slot in self._slots.values():
                sink = sinks.get(slot.request_id)
                if (sink is not None and sink.stream
                        and len(slot.generated) > sink.sent):
                    batches.setdefault(sink.post, []).append(
                        (slot.request_id, slot.generated[sink.sent:], None))
                    sink.sent = len(slot.generated)
            for rid, result in finished:
                sink = sinks.pop(rid, None)
                if sink is not None:
                    tokens = result.token_ids[sink.sent:] if sink.stream else []
                    batches.setdefault(sink.post, []).append(
                        (rid, tokens, result))
            self._post(batches)

    def _post(self, batches: Dict[Callable, list]) -> None:
        for post, batch in batches.items():
            try:
                post(batch)
            except Exception:  # noqa: BLE001 - a loop that has closed
                for rid, _, _ in batch:
                    self._sinks.pop(rid, None)

    # -- the stepping thread ---------------------------------------------------

    def _has_work(self) -> bool:
        return bool(self.num_active or self._inflight is not None)

    def _wake_stepper(self) -> None:
        """Something was enqueued for the stepping thread (started here, by
        the first caller that waits for it)."""
        st = self._stepper
        with st.cond:
            if not st.closed and (st.thread is None or not st.thread.is_alive()):
                st.thread = threading.Thread(
                    target=st.run, args=(weakref.ref(self),), daemon=True,
                    name="engine-stepper",
                )
                st.thread.start()
            st.kicked = True
            st.cond.notify()

    def _step_while_work(self, st: _Stepper) -> None:
        """The stepping thread's drive: steps back to back, each under the
        lock and each behind whoever else asked for it meanwhile. A step
        that raises fails every waiting request and the thread goes on."""
        behind = _StepLock(self._lock, behind=True)
        while not st.closed and self._has_work():
            if st.steps & 31 == 0:
                # every 32nd turn, with no step open and no lock asked for:
                # a profiler session opened meanwhile gets worker.startup
                # and the programs' kept facts
                _tracing.replay_program_facts()
            try:
                with behind:
                    if self._has_work():  # a caller's own step() drained it
                        # counted first: whoever a step's delivery wakes
                        # finds the step in the count
                        st.steps += 1
                        self._step_locked()
            except Exception as exc:  # noqa: BLE001 - the waiters'
                self._fail_waiters(exc)

    def _fail_waiters(self, exc: BaseException) -> None:
        """A step raised: what it had in hand is in no known state. Every
        request the engine holds is given up, every waiting one gets the
        exception, and the engine is empty for the next."""
        with self._lock, self._submit_lock:
            failed = self._give_up_locked()
        batches: Dict[Callable, list] = {}
        for rid, sink in failed.items():
            batches.setdefault(sink.post, []).append((rid, [], exc))
        self._post(batches)

    def _give_up_locked(self) -> Dict[int, _Sink]:
        """Empty the engine; returns the sinks that were waiting."""
        held = [s.lease for s in self._slots.values()]
        held += [st["lease"] for st in self._prefilling.values()]
        held += [a.lease for a in self._unread if a.slot is None]
        if self._kv is not None:
            for lease in held:
                try:
                    if lease is not None:
                        self._kv.release(lease)
                except Exception:  # noqa: BLE001 - the waiters come first
                    pass
        now = time.monotonic()
        for si in list(self._slots) + list(self._prefilling):
            self._slot_freed[si] = now
        self._slots.clear()
        self._prefilling.clear()
        self._pending.clear()
        self._unread.clear()
        self._inflight = None
        self._enqueue_ts.clear()
        self._req_trace.clear()
        self._blocked_rids.clear()
        # a donated cache whose step failed may be gone with it
        if any(leaf.is_deleted() for leaf in jax.tree.leaves(self._cache)):
            self._cache = None
        failed, self._sinks = self._sinks, {}
        return failed

    def stepper_stats(self) -> Dict[str, int]:
        """``steps`` the stepping thread ran and the times it ``parked``
        with nothing to do (a caller's own ``step()`` counts in neither)."""
        return {"steps": self._stepper.steps, "parked": self._stepper.parked}

    def close(self) -> None:
        """Stop the stepping thread and wait for it. Whatever still waits
        is failed; the engine serves no waiting entry afterwards."""
        st = self._stepper
        st.stop()
        if st.thread is not None and st.thread is not threading.current_thread():
            st.thread.join()
        if self._sinks:
            self._fail_waiters(RuntimeError("the engine is closed"))

    def _dense_step(self, finished: List[tuple]) -> None:
        """Dispatch the next decode step, then read the one before it: the
        host's work of a step (dispatch, the wake-up after the sync, emit,
        retirements, the hand-over to the next caller) runs with a step
        queued on the device. With none in flight (the engine was idle) two
        are dispatched and the first is read, so a call still yields a
        token. Whatever ``_admit`` queued above (prefill, first token, row
        insert) runs behind the step in flight and before the one
        dispatched here, and is read here too, behind that dispatch."""
        step = self._inflight or self._dispatch_decode(None)
        if step is not None:
            self._inflight = self._dispatch_decode(step)
        self._read_firsts(finished)
        if step is None:
            return
        if not self._slots:
            # an idle engine's admissions all ended with their first token
            # (eos): nobody reads ``step`` or the one behind it, so neither
            # happened, and the next step takes the first one's number
            self._step_count -= 1 + (self._inflight is not None)
            self._inflight = None
            return
        with _span("engine.sample_sync"):  # the host waits for the device
            tokens = host_sync(step.tokens)
        with _span("engine.emit"):
            now = time.monotonic()
            for si, slot in step.rows.items():
                if self._slots.get(si) is not slot:
                    # it left when the step before was read and rode this
                    # one: its token here is nobody's
                    continue
                tok = int(tokens[si])
                slot.generated.append(tok)
                if slot.last_emit_ts:
                    _record_itl(now - slot.last_emit_ts, mesh=self._mesh_tag)
                slot.last_emit_ts = now
                req = slot.request
                done_eos = (
                    req.eos_token_id is not None and tok == req.eos_token_id
                )
                done_len = len(slot.generated) >= req.max_new_tokens
                if done_eos or done_len:
                    self._finish_slot(
                        si, slot, "eos" if done_eos else "length", finished
                    )
        if self._inflight is not None and not self._slots:
            # every row of the step in flight ended in the one just read:
            # nobody will read it, so it never happened, and the next step
            # takes its number (and with it its sampling key)
            self._inflight = None
            self._step_count -= 1

    def _dispatch_decode(self, unread: Optional[_Step]) -> Optional[_Step]:
        """Queue one decode step of the whole pool, and its sampler, behind
        whatever the device is doing; ``unread`` is the step in flight, if
        any. None, and nothing dispatched, when no live row can want the
        token: every one reaches its ``max_new_tokens`` in ``unread``.

        Free rows compute garbage at position 0 (static-shape trade) and
        are ignored. A row that ended in ``unread`` without the host's
        knowing (``eos``), or by count beside rows that go on, steps once
        more: it stays ``active``, so that its position is not reset and
        position 0 not rewritten before the retirement's commit has read
        the row; the step writes one position past the row's last full
        block at most, which no commit takes."""
        riding = unread.rows if unread is not None else {}
        fresh = np.zeros(self._num_slots, bool)
        active = np.zeros(self._num_slots, bool)
        temps = np.zeros(self._num_slots, np.float32)
        # keys a row holds for the step's attention: a free row restarts
        # at position 0 every step and is one key long
        keys = np.ones(self._num_slots, np.int64)
        batch = live_tokens = 0
        for si, slot in self._slots.items():
            active[si] = True
            temps[si] = max(slot.request.temperature, 0.0)
            in_flight = riding.get(si) is slot
            # admitted since: its first token is in ``_firsts``
            fresh[si] = not in_flight
            # (an admission the host has not read has made one token)
            have = max(len(slot.generated), 1) + in_flight
            keys[si] = len(slot.request.token_ids) + have
            if have < slot.request.max_new_tokens:
                batch += 1
                live_tokens += len(slot.request.token_ids) + have
        if not batch:
            return None
        # live_tokens: the key positions this step attends over the rows
        # that want it (prompt plus generated, the token fed included)
        with _span(
            "engine.decode_dispatch", batch=batch, live_tokens=live_tokens,
            ahead=int(unread is not None), **self._state_span,
        ):
            counted = (
                {} if self._expert_counts is None
                else {"expert_counts": self._expert_counts}
            )
            logits, self._cache, *counts = self._decode(
                self._params, self._cache,
                _merge_last(self._sampled, fresh, self._firsts),
                *self._adapter_args(self._row_adapter_slots()),
                active=active, **counted,
            )
            if counts:
                (self._expert_counts,) = counts
            self._count_attention_chunks(keys)
            self._step_count += 1
            self._sampled = self._sample_rows(logits, temps)
        return _Step(self._sampled, dict(self._slots))

    def expert_stats(self) -> Optional[dict]:
        """The routed model's running counts as plain numbers (this read
        waits for the device; the step never does): ``decode_steps``,
        ``assignments`` [layer][expert] by live rows, ``touched`` [layer]
        = sum over steps of distinct experts live rows chose; where the
        model holds a share of its experts, both over the experts held, and
        ``experts_routed``, ``experts_held`` and ``assignments_absent``
        [layer] (live rows' choices that fell on experts held elsewhere)
        beside them. None for a model without routed experts. A row is live
        to the device until the host has seen its last token: the step it
        rides meanwhile counts."""
        if self._expert_counts is None:
            return None
        with self._lock:
            counts = jax.tree.map(host_sync, self._expert_counts)
        out = {
            "decode_steps": int(counts["steps"]),
            "assignments": counts["assignments"].tolist(),
            "touched": counts["touched"].tolist(),
        }
        if "absent" in counts:
            first, stop = self._cfg.experts_held
            out.update(
                experts_routed=int(self._cfg.n_experts),
                experts_held=stop - first,
                assignments_absent=counts["absent"].tolist(),
            )
        return out

    def _cache_leaves(self, kind: str) -> Optional[List[tuple]]:
        """The live slot cache's leaves of ``kind`` as (name, leaf), a
        layer's after another's; None before the first admission made the
        cache."""
        with self._lock:
            if self._cache is None:
                return None
            kinds = jax.tree.leaves(cache_kinds(self._cache))
            return [
                (path[-1].key, leaf)
                for (path, leaf), k in zip(
                    jax.tree_util.tree_leaves_with_path(self._cache), kinds
                ) if k == kind
            ]

    def cache_bytes_per_token(self) -> Optional[int]:
        """Bytes one cached position costs over all layers, read off the
        live slot cache's sequence leaves (so another width or dtype
        shows); None before the first admission made the cache."""
        leaves = self._cache_leaves(SEQUENCE)
        if leaves is None:
            return None
        return sum(
            leaf.dtype.itemsize * leaf.shape[-1]
            * int(np.prod(leaf.shape[1:-2]))
            for _, leaf in leaves
        )

    def _row_bytes(self, kind: str) -> Optional[int]:
        """Bytes a slot row carries in its leaves of ``kind`` over all
        layers, whatever its length; None before the first admission."""
        leaves = self._cache_leaves(kind)
        if leaves is None:
            return None
        return sum(
            leaf.dtype.itemsize * int(np.prod(leaf.shape[1:]))
            for _, leaf in leaves
        )

    def state_bytes_per_row(self) -> Optional[int]:
        """Bytes of per-row state with no sequence axis a slot row carries
        over all layers, however long the row is (0 for a family that
        keeps none); None before the first admission made the cache."""
        return self._row_bytes(STATE)

    def window_bytes_per_row(self) -> Optional[int]:
        """Bytes of window rings a slot row carries over all layers,
        however long the row is (0 for a family that keeps none);
        ``cache_bytes_per_token`` counts the full-length layers only. None
        before the first admission made the cache."""
        return self._row_bytes(WINDOW)

    def row_write(self) -> Optional[Dict[str, Optional[str]]]:
        """How the compiled decode step stores a new position in each leaf
        of the live slot cache, by leaf name (``ops/kv_row_write.py``:
        ``"tile"``, or None for a leaf no traced step wrote through the
        kernel); None before the first admission made the cache."""
        leaves = self._cache_leaves(SEQUENCE)
        if leaves is None:
            return None
        return {name: traced_form(leaf.shape) for name, leaf in leaves}

    def _count_attention_chunks(self, keys: np.ndarray) -> None:
        """Add the decode step just dispatched over rows ``keys`` long to
        ``attention_chunks()``; nothing while no traced step took the
        kernel (``ops/decode_attention.traced_chunk``)."""
        if self._attention_grid is None:
            # (the full-length leaves': a window ring is walked whole)
            for _, leaf in self._cache_leaves(SEQUENCE):
                chunk = traced_chunk(leaf.shape)
                if chunk:
                    self._attention_grid = (chunk, leaf.shape[2])
                    break
            else:
                return
        chunk, max_seq_len = self._attention_grid
        visited = int(visits(np.minimum(keys, max_seq_len), chunk))
        self._attention_chunks[0] += visited
        self._attention_chunks[1] += self._num_slots * -(-max_seq_len // chunk)
        self._attention_chunks[2] += visited * chunk  # a visit copies whole

    def attention_chunks(self) -> Dict[str, int]:
        """``attention_chunks_visited``: visits the decode kernel made (a
        visit is the kernel's ``traced_chunk`` key positions of one row,
        copied whole) over the decode steps dispatched so far, one call a
        step; ``attention_chunks_dense``: what rows x the whole cache would
        have been. Their ratio is the share of the dense grid the traffic's
        lengths leave (1.0: every row full).
        ``attention_positions_copied``: key positions those visits copied;
        the steps' live positions over it is what a row's last visit, copied
        whole, leaves of every copy (1.0: every row ends on a visit's edge)."""
        visited, dense, copied = self._attention_chunks
        return {"attention_chunks_visited": visited,
                "attention_chunks_dense": dense,
                "attention_positions_copied": copied}

    def _row_adapter_slots(self) -> np.ndarray:
        """Per-row adapter slot indices for the pooled decode batch; free
        rows read -1 (base path — their garbage compute stays adapter-free
        and cheap)."""
        slots = np.full(self._num_slots, -1, np.int32)
        for si, slot in self._slots.items():
            slots[si] = slot.request.adapter_slot
        return slots

    @staticmethod
    def _kv_key_tokens(req: GenerationRequest, tokens=None) -> List[int]:
        """The radix/tier identity of a request's KV: adapter-tinted K/V
        (wq/wk/wv run through the adapter) must never collide with the
        base model's — or another adapter's — cached prefixes, so adapter
        requests salt every token id with the adapter id, namespacing the
        shared radix per tenant. Salted ids never reach the device; they
        exist only as trie keys."""
        toks = list(tokens if tokens is not None else req.token_ids)
        if req.adapter_id is None:
            return toks
        salt = (zlib.crc32(req.adapter_id.encode("utf-8")) + 1) << 32
        return [int(t) + salt for t in toks]

    def _finish_slot(self, si: int, slot: _Slot, reason: str,
                     finished: List[tuple]) -> None:
        req = slot.request
        result = GenerationResult(
            token_ids=slot.generated[: req.max_new_tokens],
            num_prompt_tokens=len(req.token_ids),
            finished_reason=reason,
        )
        finished.append((slot.request_id, result))
        if slot.trace is not None:
            _tracing.emit_span(
                "engine.decode", slot.trace["ctx"],
                slot.trace["wall"],
                time.time() - slot.trace["wall"],
                category="engine", request_id=slot.request_id,
                tokens=len(slot.generated),
                finished=result.finished_reason,
                mesh=self._mesh_tag,
            )
        self._retire_slot(si)

    def _commit_row_tail(self, si: int, slot: _Slot, key_tokens: List[int],
                         blocks: int, trace) -> None:
        """Read slot ``si``'s row back and commit its new full blocks: the
        decode-tail commit of the retire path."""
        with self._kv_commit_span(
            trace,
            {"request_id": slot.request_id, "tokens": len(key_tokens),
             "tail": True},
            tail=1, blocks=blocks,
        ):
            with _span("kv.extract_row", blocks=blocks):
                row = self._extract_row(self._cache, np.int32(si))
            self._kv.commit(slot.lease, key_tokens, row, pin=False)

    @contextlib.contextmanager
    def _kv_commit_span(self, trace, attrs: Optional[dict] = None, **counts):
        """The ``kv.commit`` step span around one ``KVCacheManager.commit``.
        As it closes it counts what the call cost: ``dispatches``, the
        device programs queued inside it (one for all of the call's
        missing blocks, none where nothing was missing, and a ``tail``'s
        row read), and ``evictions``, the blocks the call had to evict."""
        queued, evicted = self._kv.commit_counts()
        with _tracing.step_span(
            "kv.commit", trace, request_span="kvcache.commit",
            category="kvcache", attrs=attrs, **counts,
        ) as span:
            yield
            queued_now, evicted_now = self._kv.commit_counts()
            span.count(
                dispatches=queued_now - queued + counts.get("tail", 0),
                evictions=evicted_now - evicted,
            )

    def _retire_slot(self, si: int) -> None:
        """Free the slot; with a KV manager, first commit the sequence's
        full blocks (prompt + generated tail) so a follow-up request
        sharing the prefix hits, then release the lease's pins."""
        slot = self._slots.pop(si)
        self._slot_freed[si] = time.monotonic()
        if self._kv is None or slot.lease is None:
            return
        if slot.lease.cacheable is False:  # nothing of the row is kept
            self._kv.release(slot.lease)
            return
        req = slot.request
        # K/V exists for prompt + generated[:-1]: the final sampled token
        # was never fed back through the model
        tokens = list(req.token_ids) + slot.generated[:-1]
        already = len(req.token_ids) // self._kv.block_size
        full = len(tokens) // self._kv.block_size
        if full > already:
            self._commit_row_tail(
                si, slot, self._kv_key_tokens(req, tokens), full - already,
                trace=slot.trace,
            )
        self._kv.release(slot.lease)

    def run_until_complete(self) -> Dict[int, GenerationResult]:
        """Drain every queued request; returns request_id -> result.
        Long-running callers should consume step()'s return value instead —
        the engine keeps NO finished-result state (a serving loop would leak
        otherwise)."""
        out: Dict[int, GenerationResult] = {}
        with self._step_lock:
            while self.num_active:
                out.update(self._step_locked())
        return out

    def generate(
        self, requests: List[GenerationRequest]
    ) -> List[GenerationResult]:
        """Batch API: enqueue every request and
        wait until the stepping thread has finished all of them. Safe to
        call from several threads at once."""
        for r in requests:
            self._check(r)
        inbox: queue.SimpleQueue = queue.SimpleQueue()
        rids = [
            self._submit(r, None, _Sink(inbox.put, stream=False))
            for r in requests
        ]
        out = self._wait_results(inbox, rids)
        return [out[rid] for rid in rids]

    def generate_one(self, request: GenerationRequest,
                     shipment=None) -> GenerationResult:
        """generate() for ONE request, with an optional directed KV
        shipment (see add_request) — the decode-role entry point."""
        self._check(request)
        inbox: queue.SimpleQueue = queue.SimpleQueue()
        rid = self._submit(request, shipment, _Sink(inbox.put, stream=False))
        return self._wait_results(inbox, [rid])[rid]

    def _wait_results(self, inbox: queue.SimpleQueue,
                      rids: List[int]) -> Dict[int, GenerationResult]:
        out: Dict[int, GenerationResult] = {}
        try:
            self._wake_stepper()
            while len(out) < len(rids):
                for rid, _, end in inbox.get():
                    if isinstance(end, BaseException):
                        raise end
                    out[rid] = end
        finally:
            for rid in rids:
                self._sinks.pop(rid, None)
        return out

    def stream_to(self, request: GenerationRequest,
                  post: Callable[[list], None]) -> int:
        """Enqueue ``request`` with a sink that hands ``post`` its tokens
        as steps make them, then its result (``_Sink``); returns the id its
        deliveries carry. ``post`` is called on the stepping thread, once a
        step for all the requests that share it: it must not block."""
        self._check(request)
        rid = self._submit(request, None, _Sink(post, stream=True))
        self._wake_stepper()
        return rid

    def drop_sink(self, rid: int) -> None:
        """Nobody reads request ``rid`` any more (a stream its caller
        closed): its row runs to its end and its result is dropped."""
        self._sinks.pop(rid, None)

    def generate_stream(self, request: GenerationRequest):
        """Streaming API: yields each
        token of ONE request as the shared pool produces it, then the
        final GenerationResult. Other requests keep decoding in the same
        steps — this is what makes replica streaming continuous-batched."""
        inbox: queue.SimpleQueue = queue.SimpleQueue()
        rid = self.stream_to(request, inbox.put)
        try:
            while True:
                for _, tokens, end in inbox.get():
                    yield from tokens
                    if isinstance(end, BaseException):
                        raise end
                    if end is not None:
                        yield end
                        return
        finally:
            self.drop_sink(rid)

    # -- internals -----------------------------------------------------------

    def _admit(self) -> List[tuple]:
        """Prefill pending requests into free slots; returns the (rare)
        requests that finish AT admission (eos on the first token, or
        max_new_tokens == 1) so step() reports every finish.

        With a KV manager the admission is memory-aware: the request first
        acquires a lease (longest cached prefix + reserved blocks for the
        rest of the prompt). A None lease means the pool is exhausted — the
        request goes back to the HEAD of the pending queue and admission
        stops, preserving FIFO order, until a retiring request releases
        blocks. Cached prefixes are gathered into the slot row and only the
        uncached suffix is prefilled.

        With a KV tier on top, resolution is local-hit → peer-pull →
        recompute: a prompt the local radix can't cover consults the tier
        and adopts pulled blocks before acquiring. A directed shipment
        (disaggregated decode) or an exact tier hit that carries the whole
        prompt plus the first sampled token takes the zero-prefill fast
        path — the shipped payload becomes the slot row outright."""
        finished: List[tuple] = []
        free = [
            i for i in range(self._num_slots)
            if i not in self._slots and i not in self._prefilling
        ]
        while free and self._pending:
            si = free.pop(0)
            rid, req, ship = self._pending.popleft()
            now = time.time()
            # slot_free_us: how long the slot taken had been nobody's (0
            # for one never used). In a closed loop with requests waiting
            # it is what the scheduler left on the table
            freed = self._slot_freed.get(si)
            with _span(
                "engine.admit", request_id=rid,
                queue_wait_us=int(
                    (now - self._enqueue_ts.get(rid, now)) * 1e6
                ),
                prompt_tokens=len(req.token_ids),
                slot_free_us=(
                    0 if freed is None
                    else int((time.monotonic() - freed) * 1e6)
                ),
            ):
                admitted = self._admit_one(si, rid, req, ship, finished)
            if admitted is None:  # backpressure: wait for a release
                break
            if not admitted:
                free.insert(0, si)
        return finished

    def _admit_one(self, si, rid, req, ship, finished) -> Optional[bool]:
        """Admit one pending request into slot ``si``. True: the slot is
        taken (decoding, or parked for chunked prefill). False: the request
        finished at admission and the slot is free again. None: the pool
        has no blocks for it; it is back at the head of the queue."""
        tr = self._req_trace.get(rid)
        plen = len(req.token_ids)
        pulled = None
        # the cluster tier and directed shipments carry BASE-model KV;
        # adapter requests stay out of both (their prefixes live in the
        # adapter-salted local radix namespace instead)
        if (
            self._kv is not None and self._kv.prefix_reuse
            and req.adapter_id is None
        ):
            if ship is not None:
                pulled = self._as_pulled(ship, req)
            elif self._tier is not None:
                local = self._kv.cached_blocks(req.token_ids)
                if local < (plen - 1) // self._kv.block_size:
                    pulled = self._tier.pull(
                        req.token_ids, min_blocks=local
                    )
        fast = pulled is not None and pulled.exact
        tier_src = "peer" if pulled is not None else None
        lease = None
        if self._kv is not None:
            with _tracing.step_span(
                "kv.acquire", tr, request_span="kvcache.acquire",
                category="kvcache", attrs={"request_id": rid},
            ) as acquiring:
                if pulled is not None:
                    # shipped blocks land in the pool + radix BEFORE the
                    # acquire, so the lease pins them like any local hit
                    self._ensure_kv_ready()
                    self._kv.adopt_blocks(
                        req.token_ids, pulled.payload["blocks"],
                        pulled.shipment.nblocks if fast
                        else pulled.matched_blocks,
                    )
                lease = self._kv.acquire(self._kv_key_tokens(req))
                if lease is None:
                    acquiring.cancel()
                else:
                    acquiring.set(cached_tokens=lease.num_cached_tokens)
            if lease is None:
                self._pending.appendleft((rid, req, ship))
                if rid not in self._blocked_rids:
                    self._blocked_rids.add(rid)
                    _events.record_event(
                        _events.ENGINE_ADMISSION_BLOCKED,
                        request_id=rid,
                        prompt_tokens=len(req.token_ids),
                        pending=len(self._pending),
                    )
                return None
            self._blocked_rids.discard(rid)
        tr = self._req_trace.pop(rid, None)
        if tr:
            _tracing.emit_span(
                "engine.queue_wait", tr["ctx"], tr["wall"],
                time.time() - tr["wall"], category="engine", request_id=rid,
            )
        if self._prefill_chunk and not fast:
            # budgeted prefill: the request keeps its slot reservation
            # but computes nothing yet — _advance_prefills spreads the
            # prompt over engine steps alongside in-flight decodes
            self._prefilling[si] = {
                "rid": rid, "req": req, "lease": lease,
                "tier_src": tier_src, "tr": tr,
                "row": None, "pos": 0, "logits": None, "committed": 0,
                "pf_wall": time.time() if tr else 0.0,
            }
            return True
        cached = (
            plen if fast
            else lease.num_cached_tokens if lease is not None else 0
        )
        export = not fast and self._exports(req, lease)
        at_once = fast or self._reads_first_at_once(export)
        if not fast:
            self._hold_to_bound(finished)
        with _tracing.step_span(
            "engine.prefill", tr,
            attrs=self._prefill_attrs(rid, cached, tier_src),
            computed_tokens=plen - cached, cached_tokens=cached,
            path=_prefill_path(cached, fast),
            first="read" if at_once else "deferred",
        ):
            if fast:
                # zero-prefill: the payload covers every prompt token and
                # the first token was sampled by the shipping replica
                solo_cache = self._kv.build_row(pulled.payload, plen)
                first = int(pulled.shipment.first_token)
            else:
                logits, solo_cache = self._prefill_leased(
                    req, lease, trace=tr
                )
                self.last_step_prefill_tokens += plen - cached
                first = self._sample_first(logits, req, rid)
                if at_once:  # the host waits for the prefill here
                    first = self._read_first(first)
        return self._finish_admission(
            si, rid, req, lease, solo_cache, first, fast, tier_src,
            tr, finished, export,
        )

    def _prefill_attrs(self, rid, cached: int, tier_src) -> dict:
        return {
            "request_id": rid, "hit": cached > 0,
            "tier": tier_src or "local", "mesh": self._mesh_tag,
        }

    def _sample_first(self, logits, req: GenerationRequest, rid: int):
        """An admission's first token, (1,), sampled on the device and
        left there."""
        return self._sample_on_device(
            logits,
            np.array([max(req.temperature, 0.0)], np.float32),
            jax.random.fold_in(self._rng, rid),
        )

    @staticmethod
    def _read_first(token) -> int:
        """The transfer that reads ``_sample_first``: the host waits for
        the prefill and for whatever was queued in front of it."""
        return int(host_sync(token)[0])

    def _exports(self, req: GenerationRequest, lease) -> bool:
        """Whether this admission publishes its prompt's blocks to the
        tier: the first computation of the prefix here."""
        return (
            self._tier is not None
            and self._kv is not None
            and lease.cacheable
            and req.adapter_id is None
            and self._tier.should_export(
                req.token_ids, len(req.token_ids) // self._kv.block_size
            )
        )

    def _reads_first_at_once(self, export: bool) -> bool:
        """Who needs an admission's first token on the host before the
        next step's read: a tier export, whose shipment carries the token
        (and whose payload is host arrays: reading those waits for the
        prefill anyway)."""
        return export

    def _hold_to_bound(self, finished: List[tuple]) -> None:
        """Before another admission's prefill is dispatched: read the
        oldest unread first token while ``_UNREAD_ADMISSIONS`` are out. A
        program's outputs and temporaries are allocated when it is
        dispatched, so every unread admission holds a solo row, its logits
        and its prefill's scratch; the read returns when that admission
        has run, with the next one still queued on the device."""
        while len(self._unread) >= _UNREAD_ADMISSIONS:
            adm = self._unread.pop(0)
            with _span("engine.first_sync", rows=1, waited=1):
                first = self._read_first(adm.token)
            self._land_first(adm, first, finished)

    def _read_firsts(self, finished: List[tuple]) -> None:
        """Read every first token still on the device: one transfer of
        the pool's ``_firsts`` for the rows that decode on (and one of its
        own for a request that was never inserted). Called behind a decode
        dispatch, so the device has a step queued while the host waits."""
        if not self._unread:
            return
        unread, self._unread = self._unread, []
        with _span("engine.first_sync", rows=len(unread), waited=0):
            pool = (
                host_sync(self._firsts)
                if any(a.slot is not None for a in unread) else None
            )
            firsts = [
                int(pool[a.si]) if a.slot is not None
                else self._read_first(a.token)
                for a in unread
            ]
        for adm, first in zip(unread, firsts):
            self._land_first(adm, first, finished)

    def _land_first(self, adm: _Admission, first: int,
                    finished: List[tuple]) -> bool:
        """The host has an admission's first token: TTFT, then the row's
        first entry, or the request's end (eos on that token, or one token
        was all it wanted). False when it ended here."""
        req, slot = adm.request, adm.slot
        if adm.ttft is not None:
            ts, cached, tier_src = adm.ttft
            _record_ttft(
                max(time.time() - ts, 0.0), hit=cached > 0,
                mesh=self._mesh_tag,
                tier=tier_src or ("local" if cached > 0 else "miss"),
            )
        req_eos = req.eos_token_id is not None and first == req.eos_token_id
        if slot is None:
            result = GenerationResult(
                token_ids=[first][: req.max_new_tokens],
                num_prompt_tokens=len(req.token_ids),
                finished_reason="eos" if req_eos else "length",
            )
            finished.append((adm.rid, result))
            if self._kv is not None:
                self._kv.release(adm.lease)
            return False
        slot.generated.append(first)
        slot.last_emit_ts = time.monotonic()
        if req_eos:
            # found one read late: the row may ride a step already
            # dispatched, as a row that ended in a step does
            self._finish_slot(adm.si, slot, "eos", finished)
            return False
        return True

    def _finish_admission(self, si, rid, req, lease, solo_cache, first,
                          fast, tier_src, tr, finished,
                          export: bool = False) -> bool:
        """The admission tail every prefill path funnels through (inline,
        chunked, zero-prefill): prefill metrics, prompt-block commit + tier
        export, pool row insert, slot creation. ``first`` is the host's int
        where it has the token (a shipment's; ``_reads_first_at_once``),
        else the sampler's device scalar: nothing here needs it, so
        everything is dispatched behind the prefill and the token is landed
        (``_land_first``: TTFT, the row's first entry, an eos) when
        ``_read_firsts`` has it. Returns False when the slot is free again:
        the request wants one token at most and is not inserted, or ended
        with a token the host already has."""
        plen = len(req.token_ids)
        ts = self._enqueue_ts.pop(rid, None)
        known = isinstance(first, int)
        ttft = None
        if self._kv is not None:
            cached = plen if fast else lease.num_cached_tokens
            self._kv.record_prefill(cached, plen - cached)
            if ts is not None:
                ttft = (ts, cached, tier_src)
            if not fast:
                # commit the prompt's full blocks while the prefilled
                # row is at hand; reserved blocks are consumed here
                # (the fast path adopted them instead)
                bs = self._kv.block_size
                with self._kv_commit_span(
                    tr, {"request_id": rid, "tokens": plen},
                    blocks=plen // bs - cached // bs,
                ):
                    self._kv.commit(
                        lease, self._kv_key_tokens(req), solo_cache
                    )
                if export:
                    # first computation of this prefix here: publish
                    # it so every other replica (and fresh scale-ups)
                    # can peer-pull instead of recomputing
                    payload = self._kv.extract_row_payload(
                        solo_cache, plen
                    )
                    self._tier.export_and_register(
                        req.token_ids, payload,
                        plen // self._kv.block_size,
                        first_token=first,
                    )
        slot = None
        if req.max_new_tokens > 1 and not (
            known and first == req.eos_token_id
        ):
            with _span("kv.insert_row"):
                if self._cache is None:
                    self._cache = self._empty_cache(solo_cache)
                # insert the prefilled K/V row + its write position into
                # slot si, and its first token where the next step reads it
                self._cache = self._insert_row(
                    self._cache, solo_cache, np.int32(si)
                )
                self._firsts = _put_first(
                    self._firsts,
                    np.array([first], np.int32) if known else first,
                    np.int32(si),
                )
            slot = self._slots[si] = _Slot(
                request_id=rid, request=req, generated=[], lease=lease,
                trace=(
                    {"ctx": tr["ctx"], "wall": time.time()} if tr else None
                ),
            )
        adm = _Admission(si, rid, req, first, slot, lease, ttft)
        if known:
            return self._land_first(adm, first, finished)
        self._unread.append(adm)
        return slot is not None

    def _advance_prefills(self, finished: List[tuple]) -> None:
        """Advance in-progress chunked prefills, spending at most
        ``prefill_chunk_tokens`` across ALL of them this step. Chunks stay
        <= block_size (paged) so XLA keeps the same bounded program set as
        suffix prefill; a completed prompt takes the normal admission tail
        (first-token sample, TTFT, commit, slot insert) and decodes in
        the very same step."""
        budget = self._prefill_chunk - self.last_step_prefill_tokens
        chunk_max = self._kv.block_size if self._kv is not None else 32
        for si in list(self._prefilling):
            if budget <= 0:
                break
            st = self._prefilling[si]
            req, lease, tr = st["req"], st["lease"], st["tr"]
            tokens = req.token_ids
            cached = lease.num_cached_tokens if lease is not None else 0
            self._hold_to_bound(finished)
            region = _span(
                "engine.prefill",
                computed_tokens=min(
                    len(tokens) - (st["pos"] or cached), budget
                ),
                cached_tokens=cached,
                path=_prefill_path(cached, budgeted=True),
            )
            with region:
                if st["row"] is None:
                    if cached:
                        with _tracing.step_span(
                            "kv.assemble", tr,
                            request_span="kvcache.assemble",
                            category="kvcache", cached_tokens=cached,
                        ):
                            st["row"] = self._kv.assemble(lease)
                        st["pos"] = cached
                        st["committed"] = cached // self._kv.block_size
                    else:
                        st["row"] = self._empty_row()
                pos = st["pos"]
                while pos < len(tokens) and budget > 0:
                    take = min(chunk_max, len(tokens) - pos, budget)
                    chunk = jnp.asarray([tokens[pos:pos + take]], jnp.int32)
                    st["logits"], st["row"] = self._decode(
                        self._params, st["row"], chunk,
                        *self._adapter_args([req.adapter_slot]),
                    )
                    pos += take
                    budget -= take
                    self.last_step_prefill_tokens += take
                st["pos"] = pos
                if pos < len(tokens):
                    bs = self._kv.block_size if self._kv is not None else 0
                    if (
                        self._kv is not None and lease is not None
                        and pos // bs > st["committed"]
                    ):
                        # partial commit: completed full blocks become
                        # hittable for concurrent shared-prefix admissions
                        # NOW, not when the whole prompt lands
                        with self._kv_commit_span(
                            None, blocks=pos // bs - st["committed"]
                        ):
                            self._kv.commit(
                                lease,
                                self._kv_key_tokens(req, tokens[:pos]),
                                st["row"],
                            )
                        st["committed"] = pos // bs
                    continue
                del self._prefilling[si]
                export = self._exports(req, lease)
                at_once = self._reads_first_at_once(export)
                region.set_metadata(first="read" if at_once else "deferred")
                first = self._sample_first(st["logits"], req, st["rid"])
                if at_once:
                    first = self._read_first(first)
            if tr:
                # the request's prefill span runs from its parking to here,
                # across steps: no one block brackets it
                _tracing.emit_span(
                    "engine.prefill", tr["ctx"], st["pf_wall"],
                    time.time() - st["pf_wall"], category="engine",
                    cached_tokens=cached,
                    computed_tokens=len(tokens) - cached,
                    path=_prefill_path(cached, budgeted=True),
                    **self._prefill_attrs(st["rid"], cached, st["tier_src"]),
                )
            self._finish_admission(
                si, st["rid"], req, lease, st["row"], first, False,
                st["tier_src"], tr, finished, export,
            )

    def _empty_row(self):
        """A fresh all-zero solo cache row with write position 0 — the
        chunked prefill seed when no cached prefix exists. The shapes are
        memoized (the eval_shape trace walks the whole model, ~hundreds of
        ms, and never changes); the zeros are built anew each call, because
        ``_decode`` donates its cache argument: a row handed to it is
        consumed, so no two prefills may share one."""
        if self._empty_row_shape is None:
            self._empty_row_shape = jax.eval_shape(
                self._prefill_impl, self._params,
                jax.ShapeDtypeStruct((1, 1), jnp.int32),
            )[1]
        row = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), self._empty_row_shape
        )
        if self._plan is not None:
            row = jax.tree.map(
                jax.device_put, row, self._plan.cache_shardings(row)
            )
        return row

    def _ensure_kv_ready(self) -> None:
        """Shape the manager's block pools before the first adopt/build.
        A scale-up replica's first request can arrive via the tier before
        it has computed ANY prefill, so the pools are shaped from
        eval_shape of the prefill program — no compute, just structure."""
        if self._kv.ready:
            return
        cache_shape = jax.eval_shape(
            self._prefill_impl, self._params,
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        )[1]
        self._kv.initialize(
            jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), cache_shape
            )
        )

    @staticmethod
    def _as_pulled(ship, req: GenerationRequest):
        """Normalize a directed (KVShipment, payload) handoff into the
        same shape a tier pull returns, trimmed to OUR prompt: matched
        blocks is the common full-block prefix, exact means the payload
        covers the whole prompt token-for-token with a first token."""
        from ..kvtier import PulledPrefix

        shipment, payload = ship
        prompt = [int(t) for t in req.token_ids]
        bs = shipment.block_size
        nb = 0
        for i in range(min(shipment.nblocks, len(prompt) // bs)):
            if (
                prompt[i * bs : (i + 1) * bs]
                == [int(t) for t in shipment.token_ids[i * bs : (i + 1) * bs]]
            ):
                nb += 1
            else:
                break
        exact = (
            shipment.first_token is not None
            and shipment.ntokens == len(prompt)
            and [int(t) for t in shipment.token_ids] == prompt
        )
        if nb == 0 and not exact:
            return None
        return PulledPrefix(
            shipment=shipment, payload=payload,
            matched_blocks=nb, exact=exact,
        )

    def prefill_only(self, request: GenerationRequest):
        """Disaggregated prefill role: run the admission prefill for ONE
        request and ship the resulting KV (every prompt token plus the
        first sampled token) through the tier. Returns the KVShipment the
        decode role adopts, or None when the pool or tier cannot serve it
        — the caller falls back to fused serving, so a prefill-side
        problem costs latency, never a request."""
        if self._kv is None or self._tier is None:
            return None
        if not self._kv.prefix_reuse:
            # nothing to ship: a row's state is in no block
            return None
        if request.adapter_id is not None:
            # adapter-tinted KV must not ship through the base-model tier;
            # the caller falls back to fused serving for this request
            return None
        if len(request.token_ids) + request.max_new_tokens > self._cfg.max_seq_len:
            raise ValueError("prompt + max_new_tokens exceeds max_seq_len")
        with self._lock:
            plen = len(request.token_ids)
            lease = self._kv.acquire(request.token_ids)
            if lease is None:
                return None
            with self._submit_lock:
                rid = self._next_id
                self._next_id += 1
            try:
                logits, solo_cache = self._prefill_leased(request, lease)
                # shipped with the payload: read at once
                first = self._read_first(
                    self._sample_first(logits, request, rid))
                cached = lease.num_cached_tokens
                self._kv.record_prefill(cached, plen - cached)
                self._kv.commit(lease, request.token_ids, solo_cache)
                payload = self._kv.extract_row_payload(solo_cache, plen)
                return self._tier.ship_direct(
                    request.token_ids, payload,
                    plen // self._kv.block_size, first_token=first,
                )
            finally:
                # committed blocks stay in the radix index (refcounted by
                # the index itself) — the prefill replica's cache warms
                # even though it never decodes
                self._kv.release(lease)

    def _prefill_leased(self, req: GenerationRequest, lease, trace=None):
        """Prefill a request, reusing the lease's cached prefix: a full
        prefill on a miss; on a hit, gather the cached blocks into a slot
        row and run only the uncached suffix through the decode program in
        block-size chunks (so XLA compiles at most one program per chunk
        length <= block_size, not one per suffix length)."""
        tokens = req.token_ids
        if lease is None or lease.num_cached_tokens == 0:
            return self._prefill(
                self._params, jnp.asarray([tokens], jnp.int32),
                *self._adapter_args([req.adapter_slot]),
            )
        with _tracing.step_span(
            "kv.assemble", trace, request_span="kvcache.assemble",
            category="kvcache", cached_tokens=lease.num_cached_tokens,
        ):
            row = self._kv.assemble(lease)
        logits = None
        pos = lease.num_cached_tokens
        while pos < len(tokens):
            take = min(self._kv.block_size, len(tokens) - pos)
            chunk = jnp.asarray([tokens[pos : pos + take]], jnp.int32)
            logits, row = self._decode(
                self._params, row, chunk,
                *self._adapter_args([req.adapter_slot]),
            )
            pos += take
        return logits, row

    def _empty_cache(self, solo_cache):
        """Pooled cache with num_slots rows, shaped from a solo prefill.
        Under a plan the pool is *born* sharded (KV heads over tp — the
        slot axis simply replaces the batch axis, so the same spec holds);
        a replicated pool would silently gather every insert."""
        def widen(x):
            return jnp.zeros(
                (self._num_slots,) + tuple(x.shape[1:]), x.dtype
            )

        pooled = jax.tree.map(widen, solo_cache)
        if self._plan is not None:
            pooled = jax.tree.map(
                jax.device_put, pooled, self._plan.cache_shardings(pooled)
            )
        return pooled

    def _sample_rows(self, logits, temps: np.ndarray):
        """The pool step's ids, sampled on the device and left there."""
        key = (
            jax.random.fold_in(self._rng, 10_000 + self._step_count)
            if temps.any() else None
        )
        return self._sample_on_device(logits, temps, key)
