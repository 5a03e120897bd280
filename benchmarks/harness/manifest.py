"""BENCHMARK.json and the data files it names.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix; both are files found by name, so a later PR adds files and entries
and edits nothing that is here.
"""

from __future__ import annotations

import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
TRAFFIC_DIR = os.path.join(BENCH_DIR, "traffic")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell(name: str) -> dict:
    """The cell's entry with its configuration and traffic files loaded."""
    bench = benchmark()
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has: {known}")
    entry = dict(found[0])
    config_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    entry["config_file"] = load_json(os.path.join(ROOT, config_entry["file"]))
    entry["traffic_file"] = load_json(
        os.path.join(TRAFFIC_DIR, entry["traffic"] + ".json"))
    return entry


def metrics_of(cell_name: str, group: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports: those
    with no ``workloads`` key, or with the cell in it."""
    return [
        m for m in benchmark()[group]
        if "workloads" not in m or cell_name in m["workloads"]
    ]


def llama_kwargs(config: dict) -> dict:
    """A configuration file's published keys as ``LlamaConfig`` arguments
    (``ray_tpu.models.llama``): the one place the two vocabularies meet."""
    if config["hidden_size"] != config["num_attention_heads"] * config["head_dim"]:
        raise SystemExit(
            "LlamaConfig derives head_dim from hidden_size / num_attention_heads; "
            f"{config['name']} does not"
        )
    if config.get("sliding_window") or config.get("tie_word_embeddings"):
        raise SystemExit(f"{config['name']}: LlamaConfig has no window and no tied head")
    return dict(
        vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        intermediate=config["intermediate_size"],
        rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"],
    )
