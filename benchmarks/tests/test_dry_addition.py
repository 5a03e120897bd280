"""A later PR adds files and entries and edits nothing that is there: a copy
of one configuration, one mix and one per-layer metric under new names is
picked up by the harness as it stands (``benchmarks/README.md``)."""

import json
import os
import shutil

from benchmarks.harness import cli, manifest


def test_copies_under_new_names_are_picked_up(tiny_benchmark, tmp_path, monkeypatch):
    here = os.path.dirname(os.path.abspath(__file__))
    metric_dir = os.path.join(manifest.BENCH_DIR, "layer_metrics")
    traffic_dir = tmp_path / "traffic"
    shutil.copytree(os.path.join(here, "data", "traffic"), traffic_dir)
    shutil.copy(traffic_dir / "tiny-backlog.json", traffic_dir / "dry-mix.json")
    config = tmp_path / "dry-config.json"
    shutil.copy(os.path.join(here, "data", "configs", "tiny-serve.json"), config)
    monkeypatch.setattr(manifest, "TRAFFIC_DIR", str(traffic_dir))
    new_metric = os.path.join(metric_dir, "dry_pool_peak.py")
    shutil.copy(os.path.join(metric_dir, "kv_pool_used_peak.py"), new_metric)
    try:
        bench = tiny_benchmark
        bench["configs"].append({
            "name": "dry-config", "source": "test", "reduced": [],
            "file": os.path.relpath(config, manifest.ROOT)})
        bench["workloads"].append({
            "name": "dry-cell", "config": "dry-config", "traffic": "dry-mix",
            "chips": 1, "why": "test"})
        bench["per_layer"].append({
            "name": "dry_pool_peak", "unit": "%", "better": "lower",
            "source": "program_counter", "layer": "KV manager",
            "moves": "tpot_p50_ms", "workloads": ["dry-cell"]})
        for metric in bench["end_to_end"]:
            if metric["name"] == "tpot_p50_ms":
                metric["workloads"].append("dry-cell")
        cell = manifest.cell("dry-cell")
        assert cell["traffic_file"]["kind"] == "serve_closed_loop"
        assert cell["config_file"]["name"] == "tiny-serve"
        assert {m["name"] for m in manifest.metrics_of("dry-cell", "end_to_end")} == {
            "tpot_p50_ms", "setup_s"}
        result = {"pool": [{"blocks_in_use": 3, "capacity": 4},
                           {"blocks_in_use": 1, "capacity": 4}]}
        line = cli._layer_metrics("dry-cell", result, {"tpot_p50_ms", "setup_s"})
        # the new reader is in the line; readers with nothing to read are not
        assert line == {"dry_pool_peak": {"value": 75.0, "unit": "%"}}
        assert json.dumps(line)
    finally:
        os.remove(new_metric)
