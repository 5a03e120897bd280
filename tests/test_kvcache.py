"""ray_tpu.kvcache tests: paged, prefix-reusing KV-cache plane.

Three layers, bottom-up: the refcounted BlockAllocator (pure Python), the
PrefixIndex radix tree (match / insert / LRU evict), the KVCacheManager
lease lifecycle over a synthetic cache pytree (commit, assemble, COW,
backpressure), then end-to-end: the paged ContinuousBatchingEngine must be
token-for-token identical to the dense engine under greedy decoding —
including a second request that shares a prefix with the first and
prefills only its uncached suffix.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.kvcache import BlockAllocator, KVCacheManager, PrefixIndex


# ---------------------------------------------------------------------------
# BlockAllocator


class TestBlockAllocator:
    def test_allocate_release_accounting(self):
        a = BlockAllocator(4)
        assert a.capacity == 4 and a.num_free == 4
        bids = [a.allocate() for _ in range(4)]
        assert sorted(bids) == [0, 1, 2, 3]
        assert a.num_free == 0 and a.num_allocated == 4
        assert a.allocate() is None  # exhausted, no raise
        a.release(bids[0])
        assert a.num_free == 1
        assert a.allocate() == bids[0]  # returned to the free list

    def test_refcount_lifecycle(self):
        a = BlockAllocator(2)
        b = a.allocate()
        assert a.refcount(b) == 1
        a.ref(b)
        assert a.refcount(b) == 2
        a.release(b)
        assert a.refcount(b) == 1 and a.num_allocated == 1
        a.release(b)
        assert a.num_allocated == 0

    def test_release_free_block_raises(self):
        a = BlockAllocator(1)
        b = a.allocate()
        a.release(b)
        with pytest.raises(ValueError):
            a.release(b)

    def test_ref_free_block_raises(self):
        a = BlockAllocator(1)
        with pytest.raises(ValueError):
            a.ref(0)

    def test_cow_exclusive_reuses_block(self):
        copies = []
        a = BlockAllocator(2)
        b = a.allocate()
        out = a.copy_on_write(b, copy_fn=lambda s, d: copies.append((s, d)))
        assert out == b  # rc==1: writable in place, no copy
        assert copies == []

    def test_cow_shared_copies_and_moves_ref(self):
        copies = []
        a = BlockAllocator(2)
        b = a.allocate()
        a.ref(b)  # shared: rc == 2
        out = a.copy_on_write(b, copy_fn=lambda s, d: copies.append((s, d)))
        assert out is not None and out != b
        assert copies == [(b, out)]
        # the caller's ref moved: source back to rc 1, copy owned by caller
        assert a.refcount(b) == 1
        assert a.refcount(out) == 1

    def test_cow_exhausted_returns_none(self):
        a = BlockAllocator(1)
        b = a.allocate()
        a.ref(b)
        assert a.copy_on_write(b, copy_fn=lambda s, d: None) is None
        assert a.refcount(b) == 2  # rolled back, no ref leaked


# ---------------------------------------------------------------------------
# PrefixIndex


def _index(num_blocks=8, block_size=4):
    a = BlockAllocator(num_blocks)
    return PrefixIndex(block_size, a), a


class TestPrefixIndex:
    def test_match_walks_full_blocks_only(self):
        idx, a = _index(block_size=4)
        toks = list(range(10))  # 2 full blocks + 2-token tail
        n1 = idx.insert_child(idx.root, tuple(toks[0:4]), a.allocate())
        idx.insert_child(n1, tuple(toks[4:8]), a.allocate())
        matched = idx.match(toks, max_blocks=8)
        assert len(matched) == 2
        assert matched[0] is n1
        # divergent second block stops the walk after one match
        assert len(idx.match(toks[:4] + [99] * 4, max_blocks=8)) == 1
        assert idx.match([7] * 8, max_blocks=8) == []

    def test_match_respects_cap(self):
        idx, a = _index(block_size=2)
        node = idx.root
        for i in range(3):
            node = idx.insert_child(
                node, (2 * i, 2 * i + 1), a.allocate()
            )
        assert len(idx.match(list(range(6)), max_blocks=1)) == 1

    def test_insert_takes_its_own_ref(self):
        idx, a = _index()
        bid = a.allocate()
        idx.insert_child(idx.root, (1, 2, 3, 4), bid)
        # caller's allocate ref + the index's ref
        assert a.refcount(bid) == 2

    def test_evict_lru_releases_and_prefers_oldest(self):
        idx, a = _index(num_blocks=4, block_size=2)
        old = idx.insert_child(idx.root, (1, 2), a.allocate())
        new = idx.insert_child(idx.root, (3, 4), a.allocate())
        for n in (old, new):  # drop caller refs; index refs remain
            a.release(n.block_id)
        idx.touch(new)
        assert idx.evict_lru(1) == 1
        assert idx.child(idx.root, (1, 2)) is None  # oldest gone
        assert idx.child(idx.root, (3, 4)) is new
        assert a.num_allocated == 1

    def test_evict_skips_referenced_and_interior(self):
        idx, a = _index(num_blocks=4, block_size=2)
        parent = idx.insert_child(idx.root, (1, 2), a.allocate())
        leaf = idx.insert_child(parent, (3, 4), a.allocate())
        a.release(parent.block_id)  # interior: childless is false anyway
        # leaf keeps the caller ref => rc 2 => not evictable
        assert idx.evict_lru(1) == 0
        a.release(leaf.block_id)
        # now the leaf goes first, which unblocks the parent
        assert idx.evict_lru(2) == 2
        assert a.num_allocated == 0
        assert idx.num_evictions == 2


# ---------------------------------------------------------------------------
# KVCacheManager over a synthetic cache pytree (no model needed)


S, D = 32, 4  # max_seq_len, head_dim
BS = 8  # block_size


def _row(fill_fn):
    """A two-leaf fake decode cache: one KV leaf (1, 2, S, D) whose value
    at [0, h, t, d] is fill_fn(h, t, d), plus a write-position index."""
    h = jnp.arange(2).reshape(2, 1, 1)
    t = jnp.arange(S).reshape(1, S, 1)
    d = jnp.arange(D).reshape(1, 1, D)
    k = jnp.broadcast_to(
        jnp.asarray(fill_fn(h, t, d), jnp.float32), (2, S, D)
    )
    return {
        "k": k[None],
        "cache_index": jnp.zeros((1,), jnp.int32),
    }


def _mk_manager(num_blocks=4):
    m = KVCacheManager(num_blocks=num_blocks, block_size=BS)
    m.initialize(_row(lambda h, t, d: h * 0.0 + t * 0.0 + d * 0.0))
    return m


class TestKVCacheManager:
    def test_commit_assemble_roundtrip(self):
        m = _mk_manager()
        toks = list(range(20))  # 2 full blocks + tail
        lease = m.acquire(toks)
        assert lease is not None and lease.num_cached_tokens == 0
        assert len(lease.reserved) == 2
        m.commit(lease, toks, _row(lambda h, t, d: 100 * h + t + 0.01 * d))
        m.release(lease)

        lease2 = m.acquire(toks)
        assert lease2.num_cached_tokens == 16
        row = m.assemble(lease2)
        assert int(row["cache_index"][0]) == 16
        k = np.asarray(row["k"])[0]
        h, t, d = np.ogrid[0:2, 0:16, 0:D]
        np.testing.assert_allclose(k[:, :16], 100 * h + t + 0.01 * d)
        # past the cached region the row is zero padding
        assert not k[:, 16:].any()
        m.release(lease2)

    def test_acquire_never_matches_whole_prompt(self):
        m = _mk_manager()
        toks = list(range(16))  # exactly 2 blocks
        lease = m.acquire(toks)
        m.commit(lease, toks, _row(lambda h, t, d: t))
        m.release(lease)
        again = m.acquire(toks)
        # at least one token must be prefilled for first-token logits
        assert again.num_cached_tokens == 8
        m.release(again)

    def test_backpressure_blocks_then_resumes(self):
        m = _mk_manager(num_blocks=2)
        toks = list(range(16))
        holder = m.acquire(toks)
        m.commit(holder, toks, _row(lambda h, t, d: t))  # pool now full, pinned
        blocked = m.acquire([50 + i for i in range(16)])
        assert blocked is None  # no crash, no OOM: admission gate
        assert m.stats()["admission_blocked"] == 1
        m.release(holder)  # blocks become evictable
        resumed = m.acquire([50 + i for i in range(16)])
        assert resumed is not None and len(resumed.reserved) == 2
        assert m.stats()["evictions"] == 2
        m.release(resumed)

    def test_oversized_prompt_degrades_to_uncacheable(self):
        m = _mk_manager(num_blocks=2)
        toks = list(range(32))  # 4 blocks > capacity
        lease = m.acquire(toks)
        assert lease is not None and lease.cacheable is False
        assert m.commit(lease, toks, _row(lambda h, t, d: t)) == 0
        m.release(lease)
        assert m.blocks_in_use == 0

    def test_update_block_cow_preserves_shared_prefix(self):
        m = _mk_manager()
        toks = list(range(16))
        lease = m.acquire(toks)
        m.commit(lease, toks, _row(lambda h, t, d: 1.0 * t))
        shared = lease.pinned[0]
        # index holds a ref too => shared => COW must copy
        new_id = m.update_block(
            shared, _row(lambda h, t, d: -1.0 * t), tok_offset=0
        )
        assert new_id is not None and new_id != shared
        lease.pinned[lease.pinned.index(shared)] = new_id
        m.release(lease)

        # the index's original block is untouched
        lease2 = m.acquire(toks)
        k = np.asarray(m.assemble(lease2)["k"])[0]
        np.testing.assert_allclose(
            k[0, :8], np.broadcast_to(np.arange(8.0).reshape(8, 1), (8, D))
        )
        m.release(lease2)

    def test_decode_tail_commit_is_best_effort(self):
        m = _mk_manager(num_blocks=2)
        toks = list(range(16))
        lease = m.acquire(toks)
        m.commit(lease, toks, _row(lambda h, t, d: t))
        # pool exhausted: committing more full blocks silently stops
        longer = toks + list(range(100, 108))
        n = m.commit(lease, longer, _row(lambda h, t, d: t), pin=False)
        assert n == 0
        m.release(lease)

    def test_stats_shape(self):
        m = _mk_manager()
        s = m.stats()
        for key in (
            "requests", "hits", "misses", "prefix_hit_tokens",
            "prefill_tokens_computed", "admission_blocked", "capacity",
            "block_size", "blocks_in_use", "blocks_free", "evictions",
            "index_nodes",
        ):
            assert key in s


# ---------------------------------------------------------------------------
# commit: one program a call, the block-by-block commit's bookkeeping


CS, CBS = 64, 8  # the commit cases' max_seq_len and block size: 8 blocks a row


def _np_row(seed, family="llama"):
    """A cache row of seeded values, as numpy: ``llama``'s one K leaf of two
    heads, or ``deepseek``'s two leaves of one head and unequal widths."""
    rng = np.random.default_rng(seed)
    widths = {"cached_latent": 16, "cached_rope": 4} if family == "deepseek" else {"k": D}
    heads = 1 if family == "deepseek" else 2
    row = {name: rng.standard_normal((1, heads, CS, w)).astype(np.float32)
           for name, w in widths.items()}
    row["cache_index"] = np.zeros((1,), np.int32)
    return row


def _toks(base, nblocks):
    return [base + i for i in range(min(nblocks * CBS + 3, CS))]


def _sequence_leaves(row):
    return [row[name] for name in sorted(row) if name != "cache_index"]


class _BlockByBlock:
    """The commit this file's cases are held to: the walk as it was before
    one program wrote a call's blocks, over the manager's own allocator and
    index and a plain numpy copy a block. None of the manager's device
    programs run."""

    def __init__(self, m):
        self.m = m
        self.pools = [np.asarray(p).copy() for p in m._pools]
        m._pools, m._jit_commit, m._jit_copy = "not the device's", None, None
        self.written = 0  # calls that wrote at least one block

    def _write(self, bid, row, off):
        for pool, leaf in zip(self.pools, _sequence_leaves(row)):
            pool[bid] = leaf[0, ..., off:off + CBS, :]

    def commit(self, lease, toks, row, pin=True):
        m = self.m
        if lease.cacheable is False:
            return 0
        committed, node = 0, m._index.root
        for i in range(len(toks) // CBS):
            key = tuple(toks[i * CBS:(i + 1) * CBS])
            child = m._index.child(node, key)
            if child is None:
                if lease.reserved:
                    bid = lease.reserved.pop(0)
                else:
                    bid = m._allocate_or_evict()
                    if bid is None:
                        break
                self._write(bid, row, i * CBS)
                child = m._index.insert_child(node, key, bid)
                committed += 1
                if pin:
                    lease.pinned.append(bid)
                else:
                    m._alloc.release(bid)
            else:
                m._index.touch(child)
                if pin and child.block_id not in lease.pinned:
                    m._alloc.ref(child.block_id)
                    lease.pinned.append(child.block_id)
            node = child
        self.written += committed > 0
        return committed

    def update_block(self, bid, row, tok_offset):
        def copy(src, dst):
            for pool in self.pools:
                pool[dst] = pool[src]

        new_id = self.m._alloc.copy_on_write(bid, copy_fn=copy)
        if new_id is not None:
            self._write(new_id, row, tok_offset)
            self.written += 1
        return new_id


class _OneProgram:
    """The manager's own commit, its jitted program's calls counted."""

    def __init__(self, m):
        self.m = m
        self.calls = 0
        program = m._jit_commit

        def counted(*args):
            self.calls += 1
            return program(*args)

        m._jit_commit = counted
        self.program = program
        self.commit, self.update_block = m.commit, m.update_block


def _tree(node):
    return {key: (child.block_id, child.last_used, _tree(child))
            for key, child in node.children.items()}


def _bookkeeping(m, leases, freed):
    return {
        "tree": _tree(m._index.root),
        "refcounts": list(m._alloc._refcounts),
        "free": list(m._alloc._free),
        "freed_in_order": freed,
        "evictions": m._index.num_evictions,
        "leases": [(l.block_ids, l.reserved, l.pinned, l.num_cached_tokens)
                   for l in leases],
    }


def _missing(n, pin):
    def case(kv, row):
        lease = kv.m.acquire(_toks(0, n))
        return [kv.commit(lease, _toks(0, n), row(0), pin=pin)], [lease]

    return case


def _partly_shared(pin):
    def case(kv, row):
        a, b = _toks(0, 4), _toks(0, 4)[:2 * CBS] + _toks(500, 3)
        la = kv.m.acquire(a)
        got = [kv.commit(la, a, row(0), pin=pin)]
        lb = kv.m.acquire(b)  # two blocks matched, three reserved
        got.append(kv.commit(lb, b, row(1), pin=pin))
        got.append(kv.commit(la, a, row(2), pin=pin))  # nothing missing
        return got, [la, lb]

    return case


def _reservation_runs_out(kv, row):
    """A decoded tail past the reservation: its blocks are allocated as
    the walk meets them."""
    lease = kv.m.acquire(_toks(0, 2))
    got = [kv.commit(lease, _toks(0, 2), row(0))]
    got.append(kv.commit(lease, _toks(0, 6), row(1), pin=False))
    return got, [lease]


def _pool_runs_out(kv, row):
    """Four blocks, every one pinned by the time the walk wants a fifth:
    it stops there."""
    lease = kv.m.acquire(_toks(0, 2))
    got = [kv.commit(lease, _toks(0, 2), row(0))]
    got.append(kv.commit(lease, _toks(0, 7), row(1)))
    return got, [lease]


def _evicts_least_recent_leaves(kv, row):
    """A full pool of other requests' blocks, some used since: the tail's
    walk evicts as it goes, the least recently used leaf first."""
    got, leases = [], []
    for base in (100, 200, 300):
        lease = kv.m.acquire(_toks(base, 2))
        got.append(kv.commit(lease, _toks(base, 2), row(base)))
        kv.m.release(lease)
        leases.append(lease)
    kv.m.release(kv.m.acquire(_toks(100, 2)))  # 100's first block: used last
    lease = kv.m.acquire(_toks(0, 1))
    got.append(kv.commit(lease, _toks(0, 1), row(1)))
    got.append(kv.commit(lease, _toks(0, 6), row(2), pin=False))
    return got, leases + [lease]


def _evicts_its_own_leaf(kv, row):
    """Five blocks and a tail of seven with nothing pinned past the second:
    the only leaf the sixth block can evict is the fifth, written in the
    same call, and the seventh takes the sixth's: one block id, written
    more than once, keeps what was written last."""
    lease = kv.m.acquire(_toks(0, 2))
    got = [kv.commit(lease, _toks(0, 2), row(0))]
    got.append(kv.commit(lease, _toks(0, 7), row(1), pin=False))
    return got, [lease]


def _copy_on_write(kv, row):
    lease = kv.m.acquire(_toks(0, 2))
    got = [kv.commit(lease, _toks(0, 2), row(0))]
    shared = lease.pinned[0]  # the index holds it too
    new_id = kv.update_block(shared, row(1), CBS)
    lease.pinned[0] = new_id
    got.append(new_id)
    # ... and the copy, the caller's alone, is written where it is
    got.append(kv.update_block(new_id, row(2), 0))
    return got, [lease]


COMMIT_CASES = [
    pytest.param(_missing(n, pin), {}, id=f"{name}-missing-pin-{pin}")
    for n, name in ((1, "1"), (2, "2"), (7, "7"), (CS // CBS, "all"))
    for pin in (True, False)
] + [
    pytest.param(_partly_shared(True), {}, id="partly-shared-pin-True"),
    pytest.param(_partly_shared(False), {}, id="partly-shared-pin-False"),
    pytest.param(_reservation_runs_out, {}, id="reservation-runs-out"),
    pytest.param(_pool_runs_out, {"blocks": 4}, id="pool-runs-out"),
    pytest.param(_evicts_least_recent_leaves, {"blocks": 8},
                 id="evicts-least-recent-leaves"),
    pytest.param(_evicts_its_own_leaf, {"blocks": 5}, id="evicts-its-own-leaf"),
    pytest.param(_copy_on_write, {}, id="update-block-copy-on-write"),
    pytest.param(_missing(3, True), {"family": "deepseek"},
                 id="deepseek-two-leaves"),
    pytest.param(_missing(3, True), {"tp": 2}, id="tp2-plan"),
]


def _commit_manager(blocks, family, tp):
    plan = None
    if tp > 1:
        if len(jax.devices()) < tp:
            pytest.skip(f"needs {tp} (host) devices")
        from ray_tpu.models.llama import LlamaConfig
        from ray_tpu.parallel.plan import PartitionPlan

        plan = PartitionPlan.for_model(LlamaConfig.tiny(max_seq_len=CS), tp)
    m = KVCacheManager(num_blocks=blocks, block_size=CBS, plan=plan)
    # the block gauges are the process's, a series a mesh, and a later test
    # of this file reads their sum
    m._update_gauges = lambda: None

    def on_device(row):
        row = {name: jnp.asarray(leaf) for name, leaf in row.items()}
        if plan is not None:
            row = {name: leaf if name == "cache_index"
                   else jax.device_put(leaf, plan.kv_sharding())
                   for name, leaf in row.items()}
        return row

    m.initialize(on_device(_np_row(0, family)))
    return m, on_device


def _log_freed(m):
    """Block ids in the order their last reference went (evictions and
    releases alike)."""
    freed, release = [], m._alloc.release

    def logged(bid):
        left = release(bid)
        if left == 0:
            freed.append(bid)
        return left

    m._alloc.release = logged
    return freed


@pytest.mark.parametrize("case, options", COMMIT_CASES)
def test_commit_is_one_program_and_the_block_by_block_bookkeeping(
        case, options, monkeypatch):
    blocks = options.get("blocks", 16)
    family, tp = options.get("family", "llama"), options.get("tp", 1)
    m, on_device = _commit_manager(blocks, family, tp)
    change = _OneProgram(m)
    rows = {seed: on_device(_np_row(seed, family))
            for seed in (0, 1, 2, 100, 200, 300)}
    converts = []
    impl = jax.lax.convert_element_type_p.impl
    monkeypatch.setattr(
        jax.lax.convert_element_type_p, "impl",
        lambda *a, **k: converts.append(1) or impl(*a, **k))
    freed = _log_freed(m)
    got, leases = case(change, rows.__getitem__)
    monkeypatch.undo()
    after = _bookkeeping(m, leases, freed)

    parent = _BlockByBlock(_commit_manager(blocks, family, 1)[0])
    freed = _log_freed(parent.m)
    want, leases = case(parent, lambda seed: _np_row(seed, family))
    assert got == want
    assert after == _bookkeeping(parent.m, leases, freed)
    assert len(m._pools) == len(parent.pools)
    for pool, plain in zip(m._pools, parent.pools):
        assert np.asarray(pool).tobytes() == plain.tobytes()
    if tp > 1:
        assert all(p.sharding == m._plan.kv_sharding() for p in m._pools)
    # one call of the one program for each call that had a block to write,
    # and no scalar made on the device on the way
    assert change.calls == parent.written == m.commit_counts()[0]
    assert change.program._cache_size() == 1
    assert converts == []


def test_one_compiled_commit_program_for_every_count():
    m, on_device = _commit_manager(64, "llama", 1)
    row = on_device(_np_row(1))
    for n in range(1, CS // CBS + 1):
        lease = m.acquire(_toks(1000 * n, n))
        assert m.commit(lease, _toks(1000 * n, n), row) == n
        m.release(lease)
    assert m.commit_counts() == (CS // CBS, 0)
    assert m._jit_commit._cache_size() == 1


# ---------------------------------------------------------------------------
# End-to-end: the engine over a block pool == the engine over dense rows,
# token for token


@pytest.fixture(scope="module")
def paged_setup():
    from ray_tpu.llm.engine import ContinuousBatchingEngine
    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.parallel.sharding import unbox_params

    cfg = LlamaConfig.tiny(max_seq_len=128)
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
    dense = ContinuousBatchingEngine(cfg, params, num_slots=4, seed=7)
    kv = KVCacheManager(num_blocks=32, block_size=16)
    paged = ContinuousBatchingEngine(
        cfg, params, num_slots=4, kv_cache=kv, seed=7
    )
    return dense, paged, kv


class TestPagedEngineEquality:
    def test_mixed_lengths_match_dense(self, paged_setup):
        from ray_tpu.llm.engine import GenerationRequest

        dense, paged, _ = paged_setup
        prompts = [
            list(range(5, 40)),  # 2 full blocks + tail
            list(range(100, 117)),  # 1 block + 1 token
            list(range(3, 10)),  # shorter than a block
        ]
        reqs = [
            GenerationRequest(token_ids=p, max_new_tokens=8, temperature=0.0)
            for p in prompts
        ]
        d = dense.generate(reqs)
        p = paged.generate(reqs)
        for i, (a, b) in enumerate(zip(d, p)):
            assert a.token_ids == b.token_ids, f"prompt {i} diverged"
            assert b.finished_reason == a.finished_reason

    def test_shared_prefix_second_request(self, paged_setup):
        """The warm path: a second request sharing the first's prefix must
        (a) hit the radix tree and prefill only the suffix, (b) still be
        token-identical to the dense engine."""
        from ray_tpu.llm.engine import GenerationRequest

        dense, paged, kv = paged_setup
        prefix = list(range(5, 40))  # cached by test_mixed_lengths (35 toks)
        prompt = prefix + [77, 78, 79]
        before = kv.stats()
        d = dense.generate(
            [GenerationRequest(token_ids=prompt, max_new_tokens=8,
                               temperature=0.0)]
        )[0]
        p = paged.generate(
            [GenerationRequest(token_ids=prompt, max_new_tokens=8,
                               temperature=0.0)]
        )[0]
        after = kv.stats()
        assert p.token_ids == d.token_ids
        hit = after["prefix_hit_tokens"] - before["prefix_hit_tokens"]
        computed = (
            after["prefill_tokens_computed"]
            - before["prefill_tokens_computed"]
        )
        assert hit == 32  # two 16-token blocks served from cache
        assert computed == len(prompt) - 32

    def test_eos_and_slot_reuse_with_cache(self, paged_setup):
        from ray_tpu.llm.engine import GenerationRequest

        dense, paged, _ = paged_setup
        prompt = list(range(40, 60))
        ref = dense.generate(
            [GenerationRequest(token_ids=prompt, max_new_tokens=6,
                               temperature=0.0)]
        )[0]
        eos = ref.token_ids[1]
        out = paged.generate(
            [GenerationRequest(token_ids=prompt, max_new_tokens=6,
                               temperature=0.0, eos_token_id=eos)]
        )[0]
        assert out.finished_reason == "eos"
        assert out.token_ids == ref.token_ids[:2]
        # no leaked slots or leases
        assert paged.num_active == 0
        assert not paged._slots


def test_memory_gated_admission_end_to_end():
    """A pool too small for two prompts at once: the second request stays
    pending (admission blocked, no OOM) until the first finishes, then
    admits and completes — and the totals balance at the end."""
    from ray_tpu.llm.engine import (
        ContinuousBatchingEngine,
        GenerationRequest,
    )
    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.parallel.sharding import unbox_params

    cfg = LlamaConfig.tiny(max_seq_len=128)
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
    kv = KVCacheManager(num_blocks=2, block_size=16)
    eng = ContinuousBatchingEngine(
        cfg, params, num_slots=4, kv_cache=kv, seed=3
    )
    r1 = eng.add_request(
        GenerationRequest(token_ids=list(range(5, 38)), max_new_tokens=4,
                          temperature=0.0)
    )
    r2 = eng.add_request(
        GenerationRequest(token_ids=list(range(60, 93)), max_new_tokens=4,
                          temperature=0.0)
    )
    eng.step()
    # r1 holds both blocks; r2 must be waiting, not crashed
    assert kv.stats()["admission_blocked"] >= 1
    assert eng.num_active == 2
    results = eng.run_until_complete()
    assert set(results) == {r1, r2}
    assert all(len(r.token_ids) == 4 for r in results.values())
    assert eng.num_active == 0


def test_kvcache_metrics_visible_in_state(cluster):
    """kvcache_* counters flow through the metrics pusher into
    state.metrics_summary() (and therefore the CLI/dashboard)."""
    import time

    from ray_tpu.util import state
    from ray_tpu.util.metrics import (
        record_kvcache_blocked,
        record_kvcache_prefill,
        record_kvcache_ttft,
        set_kvcache_blocks,
    )

    record_kvcache_prefill(48, 16)
    record_kvcache_blocked()
    set_kvcache_blocks(3, 64)
    record_kvcache_ttft(0.025, hit=True)
    record_kvcache_ttft(0.110, hit=False)

    deadline = time.time() + 20
    summary = {}
    while time.time() < deadline:
        summary = state.metrics_summary().get("kvcache", {})
        if summary.get("prefix_hit_tokens", 0) >= 48:
            break
        time.sleep(1)
    assert summary.get("prefix_hit_tokens", 0) >= 48
    assert summary.get("prefill_tokens_computed", 0) >= 16
    assert summary.get("admission_blocked", 0) >= 1
    assert summary.get("blocks_capacity") == 64
    ttft = summary.get("ttft_ms", {})
    assert ttft.get("hit", {}).get("count", 0) >= 1
    assert ttft.get("miss", {}).get("count", 0) >= 1
