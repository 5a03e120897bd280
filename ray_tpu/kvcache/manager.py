"""KVCacheManager: paged HBM KV pool + prefix reuse behind a lease API.

The manager owns the pooled device arrays (one ``(num_blocks, ...,
block_size, head_dim)`` array per KV leaf of the model's cache pytree) and
wires the logical halves together: the refcounted
:class:`~ray_tpu.kvcache.block_allocator.BlockAllocator` and the
:class:`~ray_tpu.kvcache.prefix_index.PrefixIndex` radix tree. The engine
talks to it through four calls:

- ``acquire(token_ids)`` — longest-prefix match + admission gate. Matched
  blocks are pinned and the blocks the prompt will need are *reserved*
  up front (evicting LRU leaves as needed); if the pool cannot cover the
  prompt, every ref is rolled back and ``None`` is returned so the engine
  keeps the request pending — backpressure instead of OOM.
- ``assemble(lease)`` — gather the matched block chain into a dense slot
  row (jitted gather; one compiled program per block-count bucket, so XLA
  sees a bounded program set) with the cache write position set to the
  cached length; the engine then prefills only the uncached suffix.
- ``commit(lease, token_ids, cache_row)`` — insert the row's full blocks
  into the radix tree and slice the missing ones out of the
  prefilled/decoded row into reserved pool blocks: ONE jitted program a
  call and one compiled program in all (block ids, token offsets and
  their count are data: a device loop of ``dynamic_update_slice`` over
  the donated pools).
- ``release(lease)`` — drop the request's pins; blocks whose only
  remaining reference is the index become LRU-evictable.

Blocks in the index are immutable — only *full* blocks are ever committed,
so shared prefixes never see partial writes. ``update_block`` exposes the
copy-on-write path (shared block -> fresh copy) for callers that do mutate
per-request state in place.

Everything here assumes the cache layout ``ray_tpu.models`` sets out, and
asks a leaf's kind there (``models.cache_kinds``), never by its rank:
``SEQUENCE`` leaves are ``(1, ..., max_seq_len, head_dim)`` with the
sequence axis at -2 and get a pool each; an ``INDEX`` leaf is a
write-position filled with the cached token count at assembly. A family
with ``STATE`` leaves (per-row state with no sequence axis: nothing to cut
into blocks, and K/V blocks alone would resume its recurrent layers from a
zero state) or ``WINDOW`` leaves (a ring of a window layer's last positions:
what a hit would restore into it is ROADMAP R4) gets **no prefix reuse** (``refuse_prefix_reuse``): every lease
is uncacheable, nothing is matched, committed, adopted or assembled, and no
device pool is ever allocated.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .._internal.host_sync import host_sync
from ..models import SEQUENCE, STATE, WINDOW, cache_kinds
from .block_allocator import BlockAllocator
from .prefix_index import PrefixIndex


@dataclasses.dataclass
class KVCacheLease:
    """One request's claim on the pool: matched chain + reserved blocks."""

    num_cached_tokens: int
    block_ids: List[int]  # matched prefix chain, root-to-leaf order
    reserved: List[int]  # pre-allocated for the prompt's uncached blocks
    pinned: List[int]  # every block this lease holds a reference on
    cacheable: bool = True  # False: prompt exceeds pool, serve hits only
    closed: bool = False


def commit_program(block_size: int, out_shardings=None):
    """The one program behind every ``commit`` and ``update_block``:
    ``(pools, kv_row, writes, count) -> pools`` with the pools donated and
    written in place. ``writes`` is ``(max_seq_len // block_size, 2)`` int32
    rows of (block id, token offset), the first ``count`` of them live and
    written in order (a block id met twice keeps the later write), so the
    number of blocks is data and one compilation serves every call. A
    trace names it ``jit_commit_impl``."""

    def commit_impl(pools, kv_row, writes, count):
        def write_one(i, pools):
            bid, off = writes[i, 0], writes[i, 1]
            return [
                jax.lax.dynamic_update_index_in_dim(
                    p,
                    jax.lax.dynamic_slice_in_dim(
                        r[0], off, block_size, axis=-2
                    ),
                    bid,
                    axis=0,
                )
                for p, r in zip(pools, kv_row)
            ]

        return jax.lax.fori_loop(0, count, write_one, list(pools))

    return jax.jit(
        commit_impl, donate_argnums=(0,), out_shardings=out_shardings
    )


class KVCacheManager:
    def __init__(self, num_blocks: int, block_size: int = 32, plan=None):
        if block_size <= 0:
            raise ValueError(f"block_size must be positive, got {block_size}")
        self._block_size = int(block_size)
        self._alloc = BlockAllocator(num_blocks)
        self._index = PrefixIndex(self._block_size, self._alloc)
        # tensor-parallel partition plan: pools are born sharded along the
        # KV-heads axis (axis 1 of every pool), each device owning its
        # heads-slice of EVERY block — per-device block pools behind one
        # logical allocator, so prefix matching/refcounting stay global
        # while commit/assemble run as single jitted programs over the
        # sharded buffers
        self._plan = plan
        self._mesh_tag = plan.describe() if plan is not None else "tp=1"
        # device state, lazily shaped from the first committed cache row
        self._pools: Optional[List[jax.Array]] = None
        self._treedef = None
        self._leaf_meta: List[tuple] = []  # (kind, shape, dtype) per leaf
        # why this manager matches and commits nothing, or None
        self._reuse_refused: Optional[str] = None
        self._max_seq_len = 0
        self._assemble_fns: Dict[int, Any] = {}  # block count -> jitted gather
        self._jit_commit = None
        self._jit_copy = None
        self._jit_adopt = None
        # (nblocks, tail_len) -> jitted extract / build programs for the
        # KV-tier shipment paths; bounded like the assemble bucket set
        self._extract_fns: Dict[tuple, Any] = {}
        self._build_fns: Dict[tuple, Any] = {}
        self._stats: Dict[str, int] = {
            "requests": 0,
            "hits": 0,
            "misses": 0,
            "prefix_hit_tokens": 0,
            "prefill_tokens_computed": 0,
            "admission_blocked": 0,
            "adopted_blocks": 0,
            # device programs queued by commit / update_block's writes
            "commit_dispatches": 0,
            # leases given without a match, and the full prompt blocks they
            # would have committed, because prefix reuse is refused
            "reuse_refused_leases": 0,
            "reuse_refused_blocks": 0,
        }

    def adopt_plan(self, plan) -> None:
        """Late plan wiring (the engine passes its plan at construction).
        Must land before the first commit shapes the pools; afterwards the
        layouts would disagree, so a late adopt is an error."""
        if self._plan is plan or plan is None:
            return
        if self._pools is not None:
            raise RuntimeError(
                "adopt_plan() after the block pools were initialized; "
                "construct the KVCacheManager with plan= instead"
            )
        self._plan = plan
        self._mesh_tag = plan.describe()

    def refuse_prefix_reuse(self, reason: str) -> None:
        """Serve a family whose rows carry state no block holds: from here
        on every lease is uncacheable and matches nothing, and no pool is
        shaped. Before the first commit, as ``adopt_plan``."""
        if self._pools is not None:
            raise RuntimeError(
                "refuse_prefix_reuse() after the block pools were "
                "initialized: blocks are already shared"
            )
        self._reuse_refused = reason

    @property
    def prefix_reuse(self) -> bool:
        return self._reuse_refused is None

    @property
    def prefix_reuse_refused(self) -> Optional[str]:
        """Why no request is served a cached prefix, or None."""
        return self._reuse_refused

    # -- accounting ----------------------------------------------------------

    @property
    def block_size(self) -> int:
        return self._block_size

    @property
    def ready(self) -> bool:
        """True once the block pools have been shaped (first commit /
        initialize); adopt_blocks and build_row require this."""
        return self._pools is not None

    def cached_blocks(self, token_ids: Sequence[int]) -> int:
        """Leading full blocks the LOCAL index already holds for this
        prompt (capped like acquire: the last prompt token is never
        matched). Takes no references — the tier consult uses this to skip
        peer pulls that could not beat the local radix."""
        if not self.prefix_reuse:
            return 0
        plen = len(token_ids)
        max_blocks = (plen - 1) // self._block_size if plen else 0
        return len(self._index.match(token_ids, max_blocks))

    @property
    def capacity(self) -> int:
        return self._alloc.capacity

    @property
    def blocks_in_use(self) -> int:
        return self._alloc.num_allocated

    def commit_counts(self) -> Tuple[int, int]:
        """(programs ``commit`` / ``update_block`` queued to write blocks,
        blocks evicted) so far: a caller's ``kv.commit`` span counts one
        call by the difference across it."""
        return self._stats["commit_dispatches"], self._index.num_evictions

    def stats(self) -> Dict[str, Any]:
        out: Dict[str, Any] = dict(self._stats)
        out.update(
            capacity=self._alloc.capacity,
            block_size=self._block_size,
            blocks_in_use=self._alloc.num_allocated,
            blocks_free=self._alloc.num_free,
            evictions=self._index.num_evictions,
            index_nodes=self._index.num_nodes,
            mesh=self._mesh_tag,
            num_devices=(
                self._plan.num_devices if self._plan is not None else 1
            ),
            prefix_reuse=self.prefix_reuse,
            prefix_reuse_refused=self._reuse_refused,
        )
        out.update(self.pool_accounting())
        return out

    def pool_accounting(self) -> Dict[str, Any]:
        """Per-device block-pool accounting. Each device owns its
        heads-slice of every block, so a device's pool is
        ``total_bytes / num_devices`` and holds ``heads / tp`` heads —
        the numbers an operator needs to size ``num_blocks`` against
        per-chip HBM. Zeros before the first commit shapes the pools."""
        if self._pools is None:
            return {
                "kv_pool_bytes_total": 0,
                "kv_pool_bytes_per_device": 0,
                "heads_per_device": 0,
            }
        total = sum(int(p.nbytes) for p in self._pools)
        ndev = self._plan.num_devices if self._plan is not None else 1
        heads = self._pools[0].shape[1]
        tp = self._plan.tp if self._plan is not None else 1
        return {
            "kv_pool_bytes_total": total,
            "kv_pool_bytes_per_device": total // ndev,
            "heads_per_device": heads // tp,
        }

    # -- lease lifecycle -----------------------------------------------------

    def acquire(self, token_ids: Sequence[int]) -> Optional[KVCacheLease]:
        """Match + admission gate. None == not enough blocks: the caller
        must keep the request queued and retry after a release."""
        plen = len(token_ids)
        if not self.prefix_reuse:
            self._stats["reuse_refused_leases"] += 1
            self._stats["reuse_refused_blocks"] += plen // self._block_size
            return KVCacheLease(0, [], [], [], cacheable=False)
        # never match the whole prompt: at least one token must be
        # prefilled to produce the first-token logits
        max_blocks = (plen - 1) // self._block_size if plen else 0
        matched = self._index.match(token_ids, max_blocks)
        lease = KVCacheLease(
            num_cached_tokens=len(matched) * self._block_size,
            block_ids=[n.block_id for n in matched],
            reserved=[],
            pinned=[],
        )
        for node in matched:
            self._alloc.ref(node.block_id)
            lease.pinned.append(node.block_id)
        needed = plen // self._block_size - len(matched)
        if needed > self._alloc.capacity - len(matched):
            # the prompt can never fit alongside its own matched chain:
            # degrade to an uncacheable lease (hits still served) rather
            # than deadlocking admission forever
            lease.cacheable = False
            return lease
        for _ in range(needed):
            bid = self._allocate_or_evict()
            if bid is None:
                self.release(lease)
                self._stats["admission_blocked"] += 1
                self._record_blocked()
                return None
            lease.reserved.append(bid)
        return lease

    def release(self, lease: KVCacheLease) -> None:
        """Drop every reference the lease holds (idempotent)."""
        if lease.closed:
            return
        lease.closed = True
        for bid in lease.pinned:
            self._alloc.release(bid)
        for bid in lease.reserved:
            self._alloc.release(bid)
        lease.pinned = []
        lease.reserved = []
        self._update_gauges()

    # -- device state --------------------------------------------------------

    def initialize(self, cache_row) -> None:
        """Shape the block pools from a solo cache row (no-op after the
        first call). Sequence leaves (sequence axis -2) get a pooled array
        each; an index leaf is a write position. A row with a state leaf
        shapes nothing: prefix reuse is refused for it, here if the engine
        has not said so already."""
        if self._pools is not None:
            return
        leaves, treedef = jax.tree_util.tree_flatten(cache_row)
        kinds = jax.tree_util.tree_leaves(cache_kinds(cache_row))
        if (STATE in kinds or WINDOW in kinds) and self.prefix_reuse:
            self.refuse_prefix_reuse(
                "the cache row holds per-row state with no sequence axis, "
                "or a window layer's ring"
            )
        if not self.prefix_reuse:
            return
        self._treedef = treedef
        self._leaf_meta = [
            (kind, tuple(l.shape), l.dtype) for kind, l in zip(kinds, leaves)
        ]
        seq_lens = {s[-2] for kind, s, _ in self._leaf_meta if kind == SEQUENCE}
        if len(seq_lens) != 1:
            raise ValueError(f"inconsistent cache sequence axes: {seq_lens}")
        self._max_seq_len = seq_lens.pop()
        if self._max_seq_len < self._block_size:
            raise ValueError(
                f"block_size {self._block_size} exceeds max_seq_len "
                f"{self._max_seq_len}"
            )
        kv_sh = self._plan.kv_sharding() if self._plan is not None else None
        self._pools = [
            jnp.zeros(
                (self._alloc.capacity,)
                + shape[1:-2]
                + (self._block_size, shape[-1]),
                dtype,
            )
            for kind, shape, dtype in self._leaf_meta
            if kind == SEQUENCE
        ]
        if kv_sh is not None:
            # pool layout (capacity, heads, block, d): heads is axis 1,
            # the same axis the decode cache shards — place, don't copy
            self._pools = [jax.device_put(p, kv_sh) for p in self._pools]

        def copy_impl(pools, src, dst):
            return [
                jax.lax.dynamic_update_index_in_dim(
                    p,
                    jax.lax.dynamic_index_in_dim(
                        p, src, axis=0, keepdims=False
                    ),
                    dst,
                    axis=0,
                )
                for p in pools
            ]

        def adopt_impl(pools, blk_leaves, bid):
            # blk_leaves: one (..., block_size, d) host block per pool —
            # a shipped block landing directly in its pool slot
            return [
                jax.lax.dynamic_update_index_in_dim(p, blk, bid, axis=0)
                for p, blk in zip(pools, blk_leaves)
            ]

        # block ids, token offsets and their count are data: ONE compiled
        # program each, reused for every commit (whatever its number of
        # missing blocks), COW copy and adopted shipment block. The host
        # hands them over as numpy values: a ``jnp.asarray`` of a Python
        # int is a device program of its own. Under a plan the outputs are
        # pinned to the pool sharding so the buffers stay sharded through
        # every donation cycle (inference would keep them sharded too, but
        # pinning makes drift impossible).
        out_sh = [kv_sh] * len(self._pools) if kv_sh is not None else None
        self._jit_commit = commit_program(self._block_size, out_sh)
        self._jit_copy = jax.jit(
            copy_impl, donate_argnums=(0,), out_shardings=out_sh
        )
        self._jit_adopt = jax.jit(
            adopt_impl, donate_argnums=(0,), out_shardings=out_sh
        )

    def assemble(self, lease: KVCacheLease):
        """Gather the lease's matched chain into a dense (1, ..., S, d)
        cache row whose write position is the cached token count — ready
        for the engine to decode the uncached suffix into."""
        if self._pools is None:
            raise RuntimeError("assemble() before any commit")
        n = len(lease.block_ids)
        if n == 0:
            raise ValueError("assemble() on a lease with no cached blocks")
        fn = self._assemble_fns.get(n)
        if fn is None:
            fn = self._make_assemble(n)
            self._assemble_fns[n] = fn
        kv_out = list(fn(self._pools, jnp.asarray(lease.block_ids, jnp.int32)))
        return self._row_of(kv_out, lease.num_cached_tokens)

    def _sequence_leaves(self, cache_row) -> list:
        """``cache_row``'s sequence leaves, in the pools' order."""
        return [
            leaf
            for leaf, (kind, _, _) in zip(
                jax.tree_util.tree_leaves(cache_row), self._leaf_meta
            )
            if kind == SEQUENCE
        ]

    def _row_of(self, sequence_leaves: list, position: int):
        """A dense cache row from its sequence leaves (the pools' order),
        its write position at ``position``."""
        sequence_leaves = list(sequence_leaves)
        return jax.tree_util.tree_unflatten(self._treedef, [
            sequence_leaves.pop(0) if kind == SEQUENCE
            else jnp.full(shape, position, dtype)
            for kind, shape, dtype in self._leaf_meta
        ])

    def _make_assemble(self, n: int):
        bs = self._block_size
        seq_len = self._max_seq_len

        def impl(pools, bids):
            out = []
            for p in pools:
                g = jnp.take(p, bids, axis=0)  # (n, ..., bs, d)
                g = jnp.moveaxis(g, 0, -3)  # (..., n, bs, d)
                g = g.reshape(g.shape[:-3] + (n * bs, g.shape[-1]))
                pad = [(0, 0)] * (g.ndim - 2) + [(0, seq_len - n * bs), (0, 0)]
                out.append(jnp.pad(g, pad)[None])  # (1, ..., S, d)
            return out

        if self._plan is not None:
            # assembled rows feed straight back into the sharded decode
            # program: keep them in the KV layout (heads over tp)
            return jax.jit(
                impl,
                out_shardings=[self._plan.kv_sharding()] * len(self._pools),
            )
        return jax.jit(impl)

    # -- commit --------------------------------------------------------------

    def commit(
        self,
        lease: KVCacheLease,
        token_ids: Sequence[int],
        cache_row,
        pin: bool = True,
    ) -> int:
        """Walk/extend the radix tree with every full block of
        ``token_ids``, copying missing blocks out of ``cache_row`` (whose
        K/V must cover the sequence). Reserved blocks are consumed first;
        past the reservation (decode tail at retire) allocation is
        best-effort — on exhaustion the tail simply is not cached. With
        ``pin``, blocks touched are pinned into the lease so they survive
        until release. Returns the number of newly committed blocks.

        The walk only collects the missing blocks; one program writes them
        all once it is over (``_write_blocks``). The index runs ahead of
        the device by that much, which no reader can see: whatever reads
        the pools next takes them from that program's outputs."""
        if lease.cacheable is False:
            return 0
        self.initialize(cache_row)
        writes: List[Tuple[int, int]] = []  # (block id, token offset)
        node = self._index.root
        for i in range(len(token_ids) // self._block_size):
            key = tuple(
                int(t)
                for t in token_ids[
                    i * self._block_size : (i + 1) * self._block_size
                ]
            )
            child = self._index.child(node, key)
            if child is None:
                if lease.reserved:
                    bid = lease.reserved.pop(0)
                else:
                    bid = self._allocate_or_evict()
                    if bid is None:
                        break
                writes.append((bid, i * self._block_size))
                child = self._index.insert_child(node, key, bid)
                if pin:
                    lease.pinned.append(bid)  # reservation ref becomes pin
                else:
                    self._alloc.release(bid)
            else:
                self._index.touch(child)
                if pin and child.block_id not in lease.pinned:
                    self._alloc.ref(child.block_id)
                    lease.pinned.append(child.block_id)
            node = child
        self._write_blocks(writes, cache_row)
        self._update_gauges()
        return len(writes)

    def update_block(self, block_id: int, cache_row, tok_offset: int):
        """Overwrite one block from ``cache_row`` at ``tok_offset``,
        copy-on-write when the block is shared. The caller must own a
        reference on ``block_id``; that reference moves to the returned
        block id. None == pool exhausted mid-COW."""
        new_id = self._alloc.copy_on_write(block_id, copy_fn=self._copy_block)
        if new_id is None:
            return None
        self._write_blocks([(new_id, tok_offset)], cache_row)
        return new_id

    def _write_blocks(self, writes: List[Tuple[int, int]], cache_row) -> None:
        """Queue the one program that copies ``cache_row``'s block at each
        ``(block id, token offset)`` of ``writes`` into the pools, in
        order; nothing for an empty list."""
        if not writes:
            return
        table = np.zeros((self._max_seq_len // self._block_size, 2), np.int32)
        table[: len(writes)] = writes
        self._pools = list(
            self._jit_commit(
                self._pools,
                self._sequence_leaves(cache_row),
                table,
                np.int32(len(writes)),
            )
        )
        self._stats["commit_dispatches"] += 1

    def _copy_block(self, src: int, dst: int) -> None:
        self._pools = list(
            self._jit_copy(self._pools, np.int32(src), np.int32(dst))
        )

    def _allocate_or_evict(self) -> Optional[int]:
        bid = self._alloc.allocate()
        while bid is None:
            if not self._index.evict_lru(1):
                return None
            self._record_eviction(1)
            bid = self._alloc.allocate()
        return bid

    # -- tier shipment interop ----------------------------------------------
    #
    # The KV tier ships committed prefixes between replicas as a payload
    # pytree: {"blocks": [per-KV-leaf (nblocks, ..., block_size, d)],
    # "tail": [per-KV-leaf (..., tail_len, d)] or None}. extract_ builds
    # that payload from a request's dense cache row, adopt_ lands shipped
    # blocks in the pool + radix index (so later LOCAL requests hit them),
    # and build_row turns a full payload back into a dense slot row so the
    # decode engine starts without re-running prefill.

    def extract_row_payload(self, cache_row, ntokens: int):
        """Slice the first ``ntokens`` tokens of KV out of a dense
        ``(1, ..., S, d)`` cache row as a shipment payload of host arrays."""
        if self._pools is None:
            self.initialize(cache_row)
        nblocks = ntokens // self._block_size
        tail_len = ntokens - nblocks * self._block_size
        fn = self._extract_fns.get((nblocks, tail_len))
        if fn is None:
            fn = self._make_extract(nblocks, tail_len)
            self._extract_fns[(nblocks, tail_len)] = fn
        kv_row = self._sequence_leaves(cache_row)
        blocks, tail = fn(kv_row)
        return {
            "blocks": [host_sync(b) for b in blocks],
            "tail": [host_sync(t) for t in tail] if tail else None,
        }

    def _make_extract(self, nblocks: int, tail_len: int):
        bs = self._block_size

        def impl(kv_row):
            blocks, tail = [], []
            for r in kv_row:
                x = r[0]  # (..., S, d)
                if nblocks:
                    g = jax.lax.slice_in_dim(x, 0, nblocks * bs, axis=-2)
                    g = g.reshape(
                        g.shape[:-2] + (nblocks, bs, g.shape[-1])
                    )
                    blocks.append(jnp.moveaxis(g, -3, 0))
                else:
                    blocks.append(
                        jnp.zeros((0,) + x.shape[:-2] + (bs, x.shape[-1]),
                                  x.dtype)
                    )
                if tail_len:
                    tail.append(
                        jax.lax.slice_in_dim(
                            x, nblocks * bs, nblocks * bs + tail_len,
                            axis=-2,
                        )
                    )
            return blocks, tail

        return jax.jit(impl)

    def adopt_blocks(self, token_ids: Sequence[int], block_leaves,
                     nblocks: int) -> int:
        """Admit shipped blocks into the pool + radix index. Walks the
        first ``nblocks`` full-block keys of ``token_ids``: blocks the
        index already holds are just touched (COW-safe — a shipped copy
        never overwrites a live shared block), missing ones get a fresh
        pool slot. Allocation failure stops the walk — partial adoption in
        chain order keeps the prefix property, and the un-adopted suffix
        is simply recomputed (admission backpressure, not an error).
        Returns how many leading blocks the index holds afterwards."""
        if self._pools is None:
            raise RuntimeError(
                "adopt_blocks() before the pools are initialized"
            )
        present = 0
        adopted = 0
        node = self._index.root
        for i in range(nblocks):
            key = tuple(
                int(t)
                for t in token_ids[
                    i * self._block_size : (i + 1) * self._block_size
                ]
            )
            child = self._index.child(node, key)
            if child is None:
                bid = self._allocate_or_evict()
                if bid is None:
                    break
                self._pools = list(
                    self._jit_adopt(
                        self._pools,
                        [leaf[i] for leaf in block_leaves],
                        jnp.asarray(bid, jnp.int32),
                    )
                )
                child = self._index.insert_child(node, key, bid)
                adopted += 1
            else:
                self._index.touch(child)
            present += 1
            node = child
        if adopted:
            self._stats["adopted_blocks"] += adopted
        self._update_gauges()
        return present

    def build_row(self, payload, ntokens: int):
        """Turn a FULL shipment payload (blocks + tail covering exactly
        ``ntokens``) back into a dense cache row with the write position
        set past the whole prompt — the zero-prefill decode entry point."""
        if self._pools is None:
            raise RuntimeError("build_row() before the pools are initialized")
        nblocks = ntokens // self._block_size
        tail_len = ntokens - nblocks * self._block_size
        fn = self._build_fns.get((nblocks, tail_len))
        if fn is None:
            fn = self._make_build(nblocks, tail_len)
            self._build_fns[(nblocks, tail_len)] = fn
        kv_out = list(fn(payload["blocks"], payload["tail"]))
        return self._row_of(kv_out, ntokens)

    def _make_build(self, nblocks: int, tail_len: int):
        bs = self._block_size
        seq_len = self._max_seq_len

        def impl(blocks, tail):
            out = []
            for i, b in enumerate(blocks):
                g = jnp.moveaxis(b, 0, -3)  # (..., nblocks, bs, d)
                g = g.reshape(g.shape[:-3] + (nblocks * bs, g.shape[-1]))
                if tail_len:
                    g = jnp.concatenate([g, tail[i]], axis=-2)
                pad = [(0, 0)] * (g.ndim - 2) + [
                    (0, seq_len - nblocks * bs - tail_len),
                    (0, 0),
                ]
                out.append(jnp.pad(g, pad)[None])  # (1, ..., S, d)
            return out

        if self._plan is not None:
            # built rows feed the sharded decode program directly: land
            # them in the KV layout (heads over tp), not replicated
            return jax.jit(
                impl,
                out_shardings=[self._plan.kv_sharding()] * len(self._pools),
            )
        return jax.jit(impl)

    # -- metrics -------------------------------------------------------------

    def record_prefill(self, hit_tokens: int, computed_tokens: int) -> None:
        """Called by the engine after each admission prefill."""
        self._stats["requests"] += 1
        self._stats["hits" if hit_tokens else "misses"] += 1
        self._stats["prefix_hit_tokens"] += hit_tokens
        self._stats["prefill_tokens_computed"] += computed_tokens
        try:
            from ..util.metrics import record_kvcache_prefill

            record_kvcache_prefill(
                hit_tokens, computed_tokens, mesh=self._mesh_tag
            )
        except Exception:
            pass
        self._update_gauges()

    def _record_blocked(self) -> None:
        try:
            from ..util.metrics import record_kvcache_blocked

            record_kvcache_blocked(mesh=self._mesh_tag)
        except Exception:
            pass
        try:
            from ..util import events

            events.record_event(
                events.ADMISSION_BLOCKED,
                blocks_free=self._alloc.num_free,
                blocked_total=self._stats["admission_blocked"],
            )
        except Exception:
            pass

    def _record_eviction(self, n: int) -> None:
        try:
            from ..util.metrics import record_kvcache_eviction

            record_kvcache_eviction(n, mesh=self._mesh_tag)
        except Exception:
            pass

    def _update_gauges(self) -> None:
        try:
            from ..util.metrics import set_kvcache_blocks

            set_kvcache_blocks(
                self._alloc.num_allocated, self._alloc.capacity,
                mesh=self._mesh_tag,
            )
        except Exception:
            pass
