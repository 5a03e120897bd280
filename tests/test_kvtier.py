"""Cluster-wide KV prefix tier + disaggregated prefill/decode serving.

The four load-bearing scenarios from the serving plane's contract:

1. Disaggregated (prefill replica ships KV -> decode replica adopts)
   equals fused, token for token, at temperature 0.
2. A fresh scale-up replica serves its first warm-prefix request by
   peer-pulling the blocks — ZERO prefill-computed tokens, asserted on
   the kvcache counters, with the tier counters showing the pull.
3. int8-shipped KV decodes to the same tokens, at ~0.25x wire bytes on
   an f32 KV cache.
4. A SIGKILLed holder degrades to recompute: the request still succeeds
   with identical tokens, and the fallback is visible as a recompute.

Everything runs clusterless: ``LocalTierBackend`` wraps the REAL
``GcsKVTierRegistry`` (same register/resolve/lease/evict/notice protocol
the GCS serves) over an inline chunk store, so two engines in one
process are two replicas in every way except the byte transport.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.kvcache import KVCacheManager
from ray_tpu.kvtier import (
    KVShipment,
    KVTierClient,
    LocalTierBackend,
    block_fingerprints,
)
from ray_tpu.llm.engine import ContinuousBatchingEngine, GenerationRequest
from ray_tpu.models.llama import LlamaConfig, init_params
from ray_tpu.parallel.sharding import unbox_params
from ray_tpu.util.metrics import kvcache_counters, kvtier_counters

BLOCK = 8


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig.tiny(max_seq_len=128)
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, params


@pytest.fixture(scope="module")
def tiny_f32():
    # f32 KV shows the int8 codec's real compression (1B codes + per-256
    # scales over 4B elements ~= 0.25x); bf16 KV only reaches ~0.52x
    cfg = dataclasses.replace(
        LlamaConfig.tiny(max_seq_len=128), dtype=jnp.float32
    )
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, params


def _engine(cfg, params, backend=None, holder=None, codec="raw",
            num_blocks=64):
    tier = None
    if backend is not None:
        tier = KVTierClient(
            model=cfg.__class__.__name__, backend=backend,
            block_size=BLOCK, codec=codec, holder_id=holder,
        )
    kv = KVCacheManager(num_blocks=num_blocks, block_size=BLOCK)
    eng = ContinuousBatchingEngine(
        cfg, params, num_slots=4, kv_cache=kv, seed=7, kv_tier=tier
    )
    return eng, tier


def _req(prompt, n=8):
    return GenerationRequest(
        token_ids=list(prompt), max_new_tokens=n, temperature=0.0
    )


# ---------------------------------------------------------- fingerprints


class TestFingerprints:
    def test_full_blocks_only_and_deterministic(self):
        toks = list(range(1, 21))  # 20 tokens, block 8 -> 2 full blocks
        fps = block_fingerprints(toks, 8)
        assert len(fps) == 2
        assert fps == block_fingerprints(toks, 8)
        assert all(len(fp) == 32 for fp in fps)

    def test_chained_prefix_property(self):
        a = list(range(1, 25))
        b = list(a)
        b[10] = 99  # mutate block 1
        fa, fb = block_fingerprints(a, 8), block_fingerprints(b, 8)
        assert fa[0] == fb[0]  # block 0 untouched
        assert fa[1] != fb[1]
        assert fa[2] != fb[2]  # chained: the change propagates forward


# ------------------------------------------------------- registry protocol


def _registry(max_entries=4096, lease_s=60.0):
    return LocalTierBackend(max_entries=max_entries, lease_s=lease_s).registry


def _register(reg, fps, holder="h1", model="m", entry_bytes=None):
    return reg.register(
        model, fps, holder, ("node", 1), entry_bytes or b"blob",
        meta={"nblocks": len(fps), "wire_bytes": 10, "logical_bytes": 20},
    )


class TestKVTierRegistry:
    def test_resolve_longest_first(self):
        reg = _registry()
        _register(reg, ["aa", "bb", "cc"])
        got = reg.resolve("m", ["cc", "bb", "aa"])  # caller sends longest-first
        assert got is not None and got["fp"] == "cc" and got["fp_rank"] == 0
        got = reg.resolve("m", ["zz", "bb"])
        assert got["fp"] == "bb"
        assert reg.resolve("m", ["zz"]) is None
        assert reg.resolve("other-model", ["cc"]) is None

    def test_fingerprint_takeover_fresher_holder_wins(self):
        reg = _registry()
        e1 = _register(reg, ["aa", "bb"], holder="h1")["entry_id"]
        e2 = _register(reg, ["aa", "bb", "cc"], holder="h2")["entry_id"]
        # h2 took over both shared fps; h1's entry covers nothing and was
        # evicted with a notice queued for h1
        assert reg.resolve("m", ["bb"])["entry_id"] == e2
        assert reg.collect("h1")["released"] == [e1]

    def test_capacity_lru_skips_leased(self):
        reg = _registry(max_entries=2)
        e1 = _register(reg, ["aa"], holder="h1")["entry_id"]
        assert reg.lease(e1, "pull-1")
        e2 = _register(reg, ["bb"], holder="h1")["entry_id"]
        _register(reg, ["cc"], holder="h2")
        # over cap: e1 is oldest but leased (a puller mid-transfer), so
        # e2 is the one LRU evicts
        assert reg.resolve("m", ["aa"]) is not None
        assert reg.resolve("m", ["bb"]) is None
        assert e2 in reg.collect("h1")["released"]
        # release + another register: back at cap, and the true LRU
        # ("cc", untouched since insert) goes — "aa" survives because the
        # resolve above refreshed its last_used
        reg.release(e1, "pull-1")
        _register(reg, ["dd"], holder="h2")
        assert reg.stats()["entries"] == 2
        assert reg.resolve("m", ["cc"]) is None
        assert reg.resolve("m", ["aa"]) is not None

    def test_notices_drained_once_by_register(self):
        reg = _registry(max_entries=1)
        e1 = _register(reg, ["aa"], holder="h1")["entry_id"]
        reply = _register(reg, ["bb"], holder="h1")
        # h1's next register drains the eviction notice for e1
        assert reply["released"] == [e1]
        assert reg.collect("h1")["released"] == []

    def test_holder_evict_requires_ownership(self):
        reg = _registry()
        e1 = _register(reg, ["aa"], holder="h1")["entry_id"]
        assert reg.evict([e1], holder_id="h2") == 0  # not the holder
        assert reg.resolve("m", ["aa"]) is not None
        assert reg.evict([e1], holder_id="h1") == 1
        assert reg.resolve("m", ["aa"]) is None
        # holder-initiated: no notice queued back at the initiator
        assert reg.collect("h1")["released"] == []

    def test_node_death_sweeps_holder_entries(self):
        reg = _registry()
        _register(reg, ["aa"], holder="h1")
        reg.register("m", ["bb"], "h2", ("other", 2), b"x", meta={})
        reg.on_node_death(("node", 1))
        assert reg.resolve("m", ["aa"]) is None  # swept with the node
        assert reg.resolve("m", ["bb"]) is not None
        assert reg.stats()["dead_holder_sweeps"] == 1

    def test_lease_on_gone_entry_fails(self):
        reg = _registry()
        e1 = _register(reg, ["aa"], holder="h1")["entry_id"]
        assert reg.evict([e1], holder_id="h1") == 1
        assert not reg.lease(e1, "pull-1")
        assert reg.stats()["lease_conflicts"] == 1


# ----------------------------------------- scenario 2: scale-up peer pull


def test_scale_up_first_request_zero_prefill(tiny):
    """A fresh replica's FIRST warm-prefix request peer-pulls the whole
    prefix (plus the first token) and computes zero prefill tokens."""
    cfg, params = tiny
    backend = LocalTierBackend()
    warm, _ = _engine(cfg, params, backend, "warm-replica")
    prompt = list(range(1, 25))  # 3 full blocks
    base = warm.generate_one(_req(prompt))

    fresh, _ = _engine(cfg, params, backend, "scale-up")
    t0, k0 = kvtier_counters(), kvcache_counters()
    out = fresh.generate_one(_req(prompt))
    t1, k1 = kvtier_counters(), kvcache_counters()

    assert out.token_ids == base.token_ids
    assert k1["prefill_tokens_computed"] - k0["prefill_tokens_computed"] == 0
    assert t1["hit"] - t0["hit"] == 1
    assert t1["peer_pull"] - t0["peer_pull"] == 1
    assert t1["recompute"] - t0["recompute"] == 0
    assert t1["transfer_wire_bytes"] > t0["transfer_wire_bytes"]


def test_partial_prefix_pull_then_suffix_prefill(tiny):
    """A longer prompt sharing only the first blocks adopts the pulled
    prefix and prefills just the suffix."""
    cfg, params = tiny
    backend = LocalTierBackend()
    warm, _ = _engine(cfg, params, backend, "warm")
    shared = list(range(1, 17))  # 2 full blocks
    warm.generate_one(_req(shared))

    fresh, _ = _engine(cfg, params, backend, "fresh")
    longer = shared + [40, 41, 42, 43, 44, 45, 46, 47, 48, 49]
    k0 = kvcache_counters()
    t0 = kvtier_counters()
    out = fresh.generate_one(_req(longer))
    k1 = kvcache_counters()
    t1 = kvtier_counters()
    computed = k1["prefill_tokens_computed"] - k0["prefill_tokens_computed"]
    assert t1["peer_pull"] - t0["peer_pull"] == 1
    # adopted 2 blocks (16 tokens) of a 26-token prompt: only the suffix
    # (and at most one block-boundary remainder) is computed
    assert 0 < computed <= len(longer) - 16
    # parity: the warm engine computes the same prompt through its own
    # radix-cached prefix — an independent KV lineage for the same tokens
    assert out.token_ids == warm.generate_one(_req(longer)).token_ids


# -------------------------------------- scenario 1: disagg == fused parity


def test_disagg_handoff_matches_fused(tiny):
    """prefill_only on one engine -> directed shipment -> generate_one on
    another equals the fused engine, token for token (temperature 0)."""
    cfg, params = tiny
    backend = LocalTierBackend()
    pre, pre_tier = _engine(cfg, params, backend, "prefill-replica")
    dec, dec_tier = _engine(cfg, params, backend, "decode-replica")

    for prompt in (list(range(50, 77)),
                   [1, 2, 3]):  # sub-block prompt: ships tail only
        shipment = pre.prefill_only(_req(prompt))
        assert shipment is not None
        # blob round-trip, as it crosses the ingress wire
        shipment = KVShipment.from_blob(shipment.to_blob())
        payload = dec_tier.fetch_shipment(shipment)
        assert payload is not None
        k0 = kvcache_counters()
        out = dec.generate_one(_req(prompt), shipment=(shipment, payload))
        k1 = kvcache_counters()
        assert (k1["prefill_tokens_computed"]
                - k0["prefill_tokens_computed"]) == 0
        # parity reference: the prefill engine decodes from its OWN
        # locally-computed blocks — an independent exact-KV lineage
        assert out.token_ids == pre.generate_one(_req(prompt)).token_ids


# --------------------------------------------- scenario 3: int8 shipments


def test_int8_shipment_parity_and_wire_ratio(tiny_f32):
    """What an 8-bit codec promises, and no more: half the wire, every
    adopted value within half a quantisation step of the exact one, and
    the next token decoded over the adopted K/V unchanged. Token-for-token
    equality further on is not promised: a step whose two best logits are
    closer than the rounding moves them (the sixth here, 0.004 apart) may
    go either way."""
    from ray_tpu._internal.quantization import DEFAULT_BLOCK

    cfg, params = tiny_f32
    backend = LocalTierBackend()
    pre, _ = _engine(cfg, params, backend, "pre8", codec="int8")
    dec, dec_tier = _engine(cfg, params, backend, "dec8", codec="int8")

    prompt = list(range(3, 35))  # 4 full blocks, f32 KV
    shipment = pre.prefill_only(_req(prompt))
    assert shipment is not None and shipment.codec == "int8"
    assert shipment.wire_bytes <= 0.51 * shipment.logical_bytes
    t0 = kvtier_counters()
    payload = dec_tier.fetch_shipment(shipment)
    t1 = kvtier_counters()
    wire = t1["transfer_wire_bytes"] - t0["transfer_wire_bytes"]
    logical = t1["transfer_logical_bytes"] - t0["transfer_logical_bytes"]
    assert 0 < wire <= 0.51 * logical

    # the same prefill shipped raw: the exact blocks
    exact_eng, exact_tier = _engine(
        cfg, params, LocalTierBackend(), "exact", codec="raw")
    exact = exact_tier.fetch_shipment(exact_eng.prefill_only(_req(prompt)))
    adopted = jax.tree.leaves(payload)
    assert len(adopted) == len(jax.tree.leaves(exact)) > 0
    for got, want in zip(adopted, jax.tree.leaves(exact)):
        want = np.asarray(want, np.float32).reshape(-1, DEFAULT_BLOCK)
        got = np.asarray(got, np.float32).reshape(want.shape)
        # a scale is its block's largest magnitude / 127; the float32
        # divide and multiply around the rounding add a few ulps of it
        amax = np.abs(want).max(axis=1, keepdims=True)
        half_step = amax / 254.0 + 4 * np.finfo(np.float32).eps * amax
        assert (np.abs(got - want) <= half_step).all()
        assert (got != want).any()  # it really went through the codec

    out = dec.generate_one(_req(prompt), shipment=(shipment, payload))
    lineage = pre.generate_one(_req(prompt)).token_ids
    assert len(out.token_ids) == len(lineage)
    # the shipped first token, and the first one decoded over int8-adopted
    # K/V, against the prefill engine's exact f32 KV lineage
    assert out.token_ids[:2] == lineage[:2]


# -------------------------------------- scenario 4: dead-holder fallback


def test_dead_holder_falls_back_to_recompute(tiny):
    """Both dead-holder degradations on one SIGKILLed peer: a tier
    resolve against the stale registry entry recomputes (no peer_pull),
    and a directed handoff whose chunks died fetches None and decodes
    fused-style — identical tokens on both paths."""
    cfg, params = tiny
    backend = LocalTierBackend()
    warm, _ = _engine(cfg, params, backend, "doomed")
    prompt = list(range(1, 25))
    base = warm.generate_one(_req(prompt))
    shipment = warm.prefill_only(_req(prompt))
    assert shipment is not None

    backend.kill_holder("doomed")  # chunks gone, registry entry stale

    fresh, fresh_tier = _engine(cfg, params, backend, "survivor")
    # directed handoff: the shipment's chunks are gone — visible failure,
    # the decode side falls back to computing the prefill itself
    assert fresh_tier.fetch_shipment(shipment) is None
    t0 = kvtier_counters()
    out = fresh.generate_one(_req(prompt), shipment=None)  # must not raise
    t1 = kvtier_counters()
    assert out.token_ids == base.token_ids
    assert t1["recompute"] - t0["recompute"] >= 1
    assert t1["peer_pull"] - t0["peer_pull"] == 0


# ------------------------------------------------- serve-level local mode


def test_serve_local_disagg_roles(tiny):
    """roles={'prefill','decode'} through the serve layer (local mode):
    ingress routes the handoff, decode computes zero prefill tokens,
    output matches a fused deployment."""
    from ray_tpu.llm.config import LLMConfig
    from ray_tpu.llm.serving import build_llm_deployment
    from ray_tpu.serve.local_mode import run_local

    backend = LocalTierBackend()
    disagg_cfg = LLMConfig(
        model_id="llama-tiny", max_seq_len=64, max_new_tokens=6,
        kv_cache_blocks=64, kv_block_size=8,
        roles={"prefill": 1, "decode": 1},
    )
    fused_cfg = dataclasses.replace(disagg_cfg, roles=None)
    disagg = run_local(
        build_llm_deployment(disagg_cfg, tier_backend=backend),
        name="disagg",
    )
    fused = run_local(build_llm_deployment(fused_cfg), name="fused")

    request = {"token_ids": list(range(1, 21)), "max_new_tokens": 6}
    k0 = kvcache_counters()
    got = disagg.remote(dict(request)).result()
    want = fused.remote(dict(request)).result()
    assert got["token_ids"] == want["token_ids"]

    # the decode replica adopted every block the prefill replica shipped
    decode = disagg._instances["llama-tiny-decode"]
    stats = decode.kvcache_stats()
    assert stats["adopted_blocks"] >= 2
    tier_stats = decode.kvtier_stats()
    assert tier_stats["role"] == "decode"
    prefill = disagg._instances["llama-tiny-prefill"]
    assert prefill.kvtier_stats()["role"] == "prefill"


def test_llm_config_validation():
    from ray_tpu.llm.config import LLMConfig

    with pytest.raises(ValueError, match="kv_cache_blocks"):
        LLMConfig(roles={"prefill": 1, "decode": 1})
    with pytest.raises(ValueError, match="positive int"):
        LLMConfig(roles={"prefill": 1}, kv_cache_blocks=64)
    with pytest.raises(ValueError, match="roles keys"):
        LLMConfig(roles={"prefill": 1, "verify": 1}, kv_cache_blocks=64)
    with pytest.raises(ValueError, match="kv_ship_codec"):
        LLMConfig(kv_ship_codec="fp4", kv_cache_blocks=64)
    with pytest.raises(ValueError, match="kv_cache_blocks"):
        LLMConfig(kv_tier=True)


# ------------------------------------------------------ metrics rollup


def test_kvtier_summary_rollup():
    from ray_tpu.util.metrics import kvtier_summary

    payloads = [{
        "metrics": [
            {"name": "kvtier_hit_total", "tag_keys": ["model"],
             "values": {'["m"]': 3.0}},
            {"name": "kvtier_peer_pull_total", "tag_keys": ["model"],
             "values": {'["m"]': 2.0}},
            {"name": "kvtier_recompute_total", "tag_keys": ["model"],
             "values": {'["m"]': 1.0}},
            {"name": "kvtier_transfer_bytes_total",
             "tag_keys": ["model", "kind"],
             "values": {'["m", "logical"]': 1000.0, '["m", "wire"]': 260.0}},
            {"name": "kvcache_ttft_ms",
             "tag_keys": ["cache", "mesh", "tier"],
             "boundaries": [1, 10, 100],
             "counts": {'["hit", "tp=1", "peer"]': [0, 2, 0, 0],
                        '["miss", "tp=1", "miss"]': [0, 0, 1, 0]},
             "values": {'["hit", "tp=1", "peer"]': 12.0,
                        '["miss", "tp=1", "miss"]': 80.0}},
        ],
    }]
    out = kvtier_summary(payloads)
    assert out["hit"] == 3.0
    assert out["peer_pull"] == 2.0
    assert out["recompute"] == 1.0
    assert out["transfer_bytes"] == {"logical": 1000.0, "wire": 260.0}
    peer = out["ttft_ms_by_tier"]["peer"]
    assert peer["count"] == 2.0 and peer["mean_ms"] == 6.0
    assert out["ttft_ms_by_tier"]["miss"]["count"] == 1.0


# ------------------------------------- the prefill span names its program


@pytest.fixture(scope="module")
def prefill_spans(tiny):
    """The ``engine.prefill`` request span of one admission of each kind,
    by the kind's name."""
    from ray_tpu.util import tracing

    cfg, params = tiny
    backend = LocalTierBackend()
    warm, _ = _engine(cfg, params, backend, "span-warm")
    cold, _ = _engine(cfg, params, backend, "span-cold")
    kv = KVCacheManager(num_blocks=16, block_size=BLOCK)
    chunked = ContinuousBatchingEngine(
        cfg, params, num_slots=2, kv_cache=kv, prefill_chunk_tokens=BLOCK)
    prompt = list(range(101, 125))  # three full blocks
    admissions = {
        "miss": (warm, prompt),
        # two of its blocks are in warm's own radix tree by now
        "hit": (warm, prompt[:16] + [7, 8, 9, 10, 11]),
        # all of it, and its first token, come from warm through the tier
        "shipped": (cold, prompt),
        "budgeted_miss": (chunked, prompt),
    }
    found = {}
    with pytest.MonkeyPatch.context() as patch:
        # a span pusher an earlier cluster test started would trim the ring
        patch.setattr(tracing, "flush_spans", lambda: None)
        was = tracing._enabled
        tracing.enable_tracing()
        try:
            for kind, (eng, tokens) in admissions.items():
                ctx = tracing.new_trace_context()
                with tracing.request_span("test.request", ctx):
                    eng.generate_one(_req(tokens, n=2))
                (found[kind],) = [
                    s["args"] for s in tracing.get_spans()
                    if s["trace_id"] == ctx["trace_id"]
                    and s["name"] == "engine.prefill"
                ]
        finally:
            tracing._enabled = was
            tracing.clear_spans()
            for eng in (warm, cold, chunked):
                eng.close()
    return found


@pytest.mark.parametrize(
    "kind,path,computed",
    [("miss", "whole", 24), ("hit", "suffix", 5), ("shipped", "shipped", 0),
     ("budgeted_miss", "suffix", 24)],
)
def test_prefill_span_names_the_program_the_prompt_took(
    prefill_spans, kind, path, computed
):
    """``path`` on ``engine.prefill``: "whole" for a prompt with no cached
    prefix (one ``_prefill``, over the prompt's own keys), "suffix" for
    chunks through ``_decode`` against a cache (behind a hit; every chunk of
    a budgeted prefill), "shipped" for a zero-prefill admission."""
    span = prefill_spans[kind]
    assert span["path"] == path
    assert span["computed_tokens"] == computed
    assert span["hit"] == (kind in ("hit", "shipped"))
