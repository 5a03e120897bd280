"""Rehearsals of the benchmark without a chip. Run by hand, not by tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

TINY_CELLS = [
    {"name": "tiny-backlog", "config": "tiny-serve", "traffic": "tiny-backlog", "chips": 1, "why": "test"},
    {"name": "tiny-steady", "config": "tiny-serve", "traffic": "tiny-steady", "chips": 1, "why": "test"},
    {"name": "tiny-lora", "config": "tiny-lora", "traffic": "tiny-lora", "chips": 4, "why": "test"},
]


@pytest.fixture
def tiny_benchmark(monkeypatch, tmp_path):
    """BENCHMARK.json's metrics over toy cells, with the chip checks (and
    only those) switched off here in the test, not by an option of the
    harness."""
    from benchmarks.drivers import train_job
    from benchmarks.harness import cli, manifest
    from ray_tpu import train

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    bench = copy.deepcopy(real)
    bench["configs"] = [
        {"name": n, "source": "test", "reduced": [],
         "file": f"benchmarks/tests/data/configs/{n}.json"}
        for n in ("tiny-serve", "tiny-lora")
    ]
    bench["workloads"] = TINY_CELLS
    swap = {"mistral7b-chat-backlog": "tiny-backlog", "mistral7b-chat-steady": "tiny-steady",
            "mistral7b-lora-fsdp4": "tiny-lora"}
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = sorted({swap[w] for w in metric["workloads"]})
    monkeypatch.setattr(manifest, "benchmark", lambda: bench)
    monkeypatch.setattr(manifest, "TRAFFIC_DIR", os.path.join(HERE, "data", "traffic"))
    monkeypatch.setattr(cli, "require_chips", lambda chips: None)
    monkeypatch.setattr(cli, "require_device", lambda device, chips: None)
    monkeypatch.setattr(train_job, "scaling", lambda chips: train.ScalingConfig(
        num_workers=1, use_tpu=False, resources_per_worker={"CPU": 1.0}))
    table = dict(cli.peaks())
    table["cpu"] = table["TPU v5 lite"]
    monkeypatch.setattr(cli, "peaks", lambda: table)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    return bench
