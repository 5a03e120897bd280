"""The six ``startup_*`` readers over a trace recorded here on the CPU, with
the program's ``worker.startup`` record in it and without (the parent of the
PR that added it writes none: every reader gives None), and the six entries
of ``BENCHMARK.json`` against the cells each can read in.

The trace is a profiler session of this process around
``tracing.replay_program_facts()``: the record is made through the program's
own ``startup_reached`` / ``startup_phase`` / ``startup_ready`` with the
stopwatches' readings replaced by round numbers, and written twice, the
compile totals moving between (the reader takes the last).
"""

import importlib
import json
import os

import pytest

from benchmarks.harness import hostplane, manifest, startup
from ray_tpu._internal import compile_cache
from ray_tpu.util import tracing

READERS = ("startup_ready_s", "startup_backend_s", "startup_compile_s",
           "startup_cache_hit_share", "startup_weights_s", "startup_engine_s")
RECORD = {
    "process_start_wall_us": 1_791_000_000_000_000,
    "main_us": 2_500_000, "register_us": 6_000_000, "wait_us": 250_000,
    "backend_us": 11_000_000, "devices": 1,
    "weights_us": 9_000_000, "weights_source": "init", "weights_bytes": 10**10,
    "engine_us": 750_000, "other_us": 1_500_000, "ready_us": 31_000_000,
}


def _session(tmp_path, name, write):
    from benchmarks.harness import xplane

    logdir = str(tmp_path / name)
    with tracing.device_profile(logdir):
        with tracing.annotate_device_trace("engine.step", step=0):
            pass
        write()
    return xplane.find_xplane(logdir)


@pytest.fixture
def traces(tmp_path, monkeypatch):
    monkeypatch.setattr(tracing, "_startup", dict(RECORD))
    monkeypatch.setattr(compile_cache, "_counting", True)
    monkeypatch.setattr(compile_cache, "_compile_s_by_name", {})
    monkeypatch.setattr(compile_cache, "_stats", {
        "compile_s": 20.0, "trace_lower_s": 5.0, "programs": 100,
        "cache_requests": 100, "cache_hits": 100})

    def twice():
        tracing.replay_program_facts()
        compile_cache._stats.update(
            compile_s=41.5, trace_lower_s=13.25, programs=200, cache_requests=200,
            cache_hits=150)
        tracing.replay_program_facts()

    paths = {"change": _session(tmp_path, "change", twice),
             "parent": _session(tmp_path, "parent", lambda: None)}
    monkeypatch.setattr(hostplane, "path_of", lambda result: paths.get(result.get("trace")))
    return paths


def _read(name, result):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read(result)


def test_the_loader_takes_the_last_record_of_the_trace(traces):
    record = startup.load(traces["change"])
    assert {k: record[k] for k in RECORD} == RECORD
    assert (record["compile_us"], record["trace_lower_us"]) == (41_500_000, 13_250_000)
    assert sum(record[k] for k in startup.PHASES) == record["ready_us"]
    assert startup.load(traces["parent"]) is None


@pytest.mark.parametrize("name,value", [
    ("startup_ready_s", 31.0), ("startup_backend_s", 11.0),
    ("startup_compile_s", 54.75), ("startup_cache_hit_share", 75.0),
    ("startup_weights_s", 9.0), ("startup_engine_s", 0.75)])
def test_reader_with_the_record_and_without(traces, name, value):
    assert _read(name, {"trace": "change"}) == value
    # a program that writes no such record, no trace, no traced run: nothing
    assert _read(name, {"trace": "parent"}) is None
    assert _read(name, {"trace": None}) is None
    assert _read(name, {}) is None


def test_a_phase_that_did_not_happen_reads_none(tmp_path, monkeypatch):
    """A training worker's record has no weights and no engine; a worker
    whose cache was asked nothing has no hit share."""
    record = {k: v for k, v in RECORD.items() if not k.startswith(("weights", "engine"))}
    monkeypatch.setattr(tracing, "_startup", record)
    monkeypatch.setattr(compile_cache, "_counting", True)
    monkeypatch.setattr(compile_cache, "_stats", dict.fromkeys(compile_cache._stats, 0))
    path = _session(tmp_path, "train", tracing.replay_program_facts)
    monkeypatch.setattr(hostplane, "path_of", lambda result: path)
    got = {name: _read(name, {"trace": "train"}) for name in READERS}
    assert got == {"startup_ready_s": 31.0, "startup_backend_s": 11.0,
                   "startup_compile_s": 0.0, "startup_cache_hit_share": None,
                   "startup_weights_s": None, "startup_engine_s": None}


def test_the_entries_list_the_cells_each_reads_in():
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    training = [c for c in cells if "lora" in c]
    serving = [c for c in cells if c not in training]
    assert len(serving) == 8 and len(training) == 2
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-6:]] == list(READERS)
    for name in READERS:
        entry = entries[name]
        meta = importlib.import_module(f"benchmarks.layer_metrics.{name}").META
        assert {k: entry[k] for k in meta} == meta
        assert entry["layer"] == "worker start-up" and entry["moves"] == "setup_s"
        assert entry["source"] == "program_counter"
        assert entry["better"] == ("higher" if name.endswith("share") else "lower")
        wanted = serving if name in ("startup_weights_s", "startup_engine_s") else cells
        assert entry["workloads"] == wanted
