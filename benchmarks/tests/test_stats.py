"""Percentile and due-time arithmetic on a hand-made record set, the
traffic generator's promises, and the manifest's agreement with the files."""

import importlib
import json
import os

import pytest

from benchmarks.harness import flops, manifest, stats, traffic


def _record(due, sent, stamps, asked, done=None, error=None, prompt_len=8):
    return {"due": due, "sent": sent, "stamps": stamps, "asked": asked,
            "done": done, "error": error, "prompt_len": prompt_len}


RECORDS = [
    # sent 0.5 s late: the wait counts, because latency runs from ``due``
    _record(1.0, 1.5, [2.0, 2.1, 2.3], 3, done=2.3),
    _record(2.0, 2.0, [2.4, 2.6], 2, done=2.6),
    _record(9.5, 9.5, [10.5], 4),                # cut off by the end of the run
    _record(3.0, 3.0, [], 2, error="Boom"),       # failed
    _record(-1.0, -1.0, [0.5, 1.5], 2, done=1.5),  # due in the ramp
]


def test_percentiles_interpolate():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile([1, 2, 3, 4], 0) == 1 and stats.percentile([1, 2, 3, 4], 100) == 4
    assert stats.percentile([5], 99) == 5 and stats.percentile([], 50) is None
    assert stats.percentile(range(101), 90) == 90
    assert stats.spread([9, 10, 11, 10]) == pytest.approx(0.5 / 10)


def test_latency_runs_from_due_not_from_sent():
    assert stats.ttft_s(RECORDS[0]) == pytest.approx(1.0)
    assert stats.tpot_s(RECORDS[0]) == pytest.approx(0.15)
    assert stats.tpot_s(RECORDS[2]) is None and stats.ttft_s(RECORDS[3]) is None
    assert stats.lateness(RECORDS, 0, 10) == pytest.approx([0.5, 0.0, 0.0, 0.0])


def test_window_membership():
    assert [r["due"] for r in stats.due_in(RECORDS, 0, 10)] == [1.0, 2.0, 9.5, 3.0]
    assert [r["due"] for r in stats.completed_in(RECORDS, 0, 10)] == [1.0, 2.0, -1.0]
    # the ramp request's tokens inside the window count; the one at 10.5 does not
    assert stats.tokens_in(RECORDS, 0, 10) == 7
    assert sorted(stats.inter_token_gaps(RECORDS, 0, 10)) == pytest.approx(
        [0.1, 0.2, 0.2, 1.0])
    assert [stats.failed(r) for r in RECORDS] == [False, False, False, True, False]
    assert stats.failed(_record(0, 0, [1, 2, 3], 2, done=3))


def test_traffic_is_seeded_and_stratified():
    mix = {"prompt_lens": {"128": 0.5, "256": 0.3, "512": 0.2}, "output_tokens": [64, 320]}
    a = [next(g) for g in [traffic.requests(mix, 1000, 5)] for _ in range(40)]
    b = [next(g) for g in [traffic.requests(mix, 1000, 5)] for _ in range(40)]
    c = [next(g) for g in [traffic.requests(mix, 1000, 6)] for _ in range(40)]
    assert a == b and a != c
    for group in (a[:20], a[20:]):  # every group of 20 carries the same work
        lens = sorted(len(r["token_ids"]) for r in group)
        assert lens == [128] * 10 + [256] * 6 + [512] * 4
        asked = sorted(r["max_new_tokens"] for r in group)
        assert asked[0] == 64 and asked[-1] == 320 and sum(asked) == 3840
    own = next(traffic.requests(mix, 1000, 5, stream=1))
    assert own["token_ids"] != a[0]["token_ids"]


def test_arrivals_keep_their_rate():
    for process in ({"process": "poisson"}, {"process": "gamma", "shape": 4}):
        times = traffic.arrival_times(dict(process, rate_per_s=5.0), -10.0, 990.0, 3)
        assert times == traffic.arrival_times(dict(process, rate_per_s=5.0), -10.0, 990.0, 3)
        assert times[0] >= -10.0 and times[-1] < 990.0 and times == sorted(times)
        assert len(times) == pytest.approx(5000, rel=0.05)


def test_a_fixed_count_is_fixed_in_the_ramp_and_in_the_window():
    """Poisson conditioned on its counts: every seed offers the window the
    same number of requests, at other moments, as bursty as Poisson."""
    arrivals = {"process": "poisson", "rate_per_s": 1.3, "count": "fixed"}
    gaps = []
    for seed in range(40):
        times = traffic.arrival_times(arrivals, -8.0, 50.0, seed)
        assert times == sorted(times) == traffic.arrival_times(arrivals, -8.0, 50.0, seed)
        assert sum(t < 0.0 for t in times) == 10 and sum(t >= 0.0 for t in times) == 65
        assert -8.0 <= times[0] and times[-1] < 50.0
        gaps += [b - a for a, b in zip(times, times[1:])]
    mean = sum(gaps) / len(gaps)
    cv = (sum((g - mean) ** 2 for g in gaps) / len(gaps)) ** 0.5 / mean
    assert cv == pytest.approx(1.0, abs=0.1)  # exponential gaps, not paced ones


def test_flops_from_published_shapes():
    config = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", "mistral-7b-v0.3-lora-fsdp4.json"))
    assert flops.total_params(config) == 7_248_023_552  # Mistral-7B-v0.3's card
    per_token = flops.lora_train_model_flops_per_token(config, 4096)
    assert per_token == 4 * flops.matmul_params(config) + 12 * 32 * 4096 * 4096 * 0.5
    # a decode step with nothing in context reads the matmul weights once
    assert flops.decode_step_min_bytes(config, 0) == 2 * flops.matmul_params(config)
    assert (flops.decode_step_min_bytes(config, 1000)
            - flops.decode_step_min_bytes(config, 0)) == 1000 * 2 * 8 * 128 * 32 * 2


def test_manifest_and_files_agree():
    bench = manifest.benchmark()
    for cell in bench["workloads"]:
        loaded = manifest.cell(cell["name"])
        kind = loaded["traffic_file"]["kind"]
        importlib.import_module(f"benchmarks.drivers.{kind}")
        assert loaded["config_file"]["chips"] == cell["chips"]
    for config in bench["configs"]:
        file = manifest.load_json(os.path.join(manifest.ROOT, config["file"]))
        assert file["reduced"] == config["reduced"] and file["source"] == config["source"]
        for key, published in {"hidden_size": 4096, "intermediate_size": 14336,
                               "num_attention_heads": 32, "num_key_value_heads": 8,
                               "head_dim": 128, "vocab_size": 32768}.items():
            assert file[key] == published
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for entry in bench["per_layer"]:
        meta = importlib.import_module(f"benchmarks.layer_metrics.{entry['name']}").META
        assert {k: entry[k] for k in meta} == meta, entry["name"]
        assert entry["moves"] in end_to_end
    assert len(json.dumps(bench)) < 64 * 1024
