"""What the Moonlight cell added to the benchmark, rehearsed on the CPU:
the ``serve_closed_loop_arch_blockwise`` kind end to end on a toy
latent-attention configuration, how the cell entered ``BENCHMARK.json``,
and each new reader on a hand-built result. Named to sort right behind
``test_arch_driver.py``, for its reason: ``cli.main`` refuses a harness
process that has initialised a JAX backend, so nothing here does (what
does is in ``test_mla_cell.py``, which sorts behind ``test_end_to_end.py``).

The toy is entered into ``benchmarks/conftest.py``'s tables from here, at
import: that file may not be edited by the PR that adds a cell, and
``tiny_moe_benchmark`` fails a cell without a toy by name. Collecting this
directory imports this module before any test runs; one of the older files
run alone misses the toy and says so (PERF.md, Open questions, B1).
"""

import json
import os

import pytest

from benchmarks import conftest as top
from benchmarks.harness import cli, flops_mla, manifest

HERE = os.path.dirname(os.path.abspath(__file__))
REAL, CELL = "moonlight-longctx-backlog", "tiny-longctx-mla"
top.TOYS[REAL] = CELL
top.TOY_CONFIGS["tiny-mla"] = "benchmarks/tests/data/configs/tiny-mla.json"
if not any(c["name"] == CELL for c in top.TOY_CELLS):
    top.TOY_CELLS.append({"name": CELL, "config": "tiny-mla", "traffic": CELL,
                          "chips": 1, "why": "test"})
NEW_METRICS = [
    ("mla_attention_roofline", "%", "device_trace", "kernel"),
    ("mla_experts_roofline", "%", "device_trace", "kernel"),
    ("mla_decode_roofline", "%", "device_trace", "kernel"),
    ("mla_attention_busy_share", "%", "device_trace", "jitted program"),
    ("kv_bytes_per_token", "bytes", "program_counter", "KV manager"),
]


def test_the_blockwise_driver_end_to_end_on_the_cpu(tiny_moe_benchmark, capsys):
    code = cli.main(["--workload", CELL, "--seed", str(2**31 + 7),
                     "--seconds", "4", "--trace", "0"])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    line, earlier = lines[-1], lines[:-1]
    assert code == 0
    assert line["correct"] is True, earlier
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"out_tok_per_s", "tpot_p50_ms", "setup_s"}
    check = next(e for e in earlier
                 if e.get("check") == "serve.engine_against_plain_reference")
    assert check["architecture"] == "deepseek_v3_arch" and check["ok"]
    assert [r["decoded"] for r in check["rows"]] == [18, 16]
    assert [r["positions"] for r in check["rows"]] == [16 + 17, 40 + 15]
    for row in check["rows"]:
        assert 0.25 <= row["routing_agree_share"] <= 1.0, row
        # (a float32 mean of ones need not be 1.0 to the last bit)
        assert (row["routing_slack_max"] > 0) == (row["routing_agree_share"] < 0.999), row
        assert row["routing_slack_max"] <= 0.1 and row["max_abs_logit_diff"] <= 0.125, row
        # the replay is the engine's own programs on the request's input, so
        # it makes the request's tokens, and the row the request's blocks hold
        assert row["replayed_tokens_equal"] == row["decoded"], row
        assert row["token_gap_max"] <= 0.125, row
    # the first answer filled a block of 16 past the prompt's: 16 + 17 -> 32 of 33
    assert [r["pool_positions"] for r in check["rows"]] == [32, 48]
    summary = next(e for e in earlier if "program_counters_kept" in e)
    assert summary["program_counters_kept"] == ["moe", "kv"]
    with open(os.path.join(manifest.BENCH_DIR, "out", CELL, "records.json")) as f:
        kept = json.load(f)["program_counters"]
    assert kept["after"]["moe"]["decode_steps"] > kept["before"]["moe"]["decode_steps"]
    assert len(kept["after"]["moe"]["assignments"]) == 2  # routed layers of 3
    assert kept["after"]["kv"] == {"cache_bytes_per_token": 3 * 40 * 2}


def test_a_traced_run_finds_the_new_scopes_in_the_compiled_program(
        tiny_moe_benchmark, capsys):
    with pytest.raises(SystemExit) as refused:  # a CPU trace has no device plane
        cli.main(["--workload", CELL, "--seed", "4", "--seconds", "4", "--trace", "1"])
    assert refused.value.code not in (0, None)
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    summary = next(e for e in lines if "scoped_instructions" in e)
    assert summary["scoped_instructions"] > 10 and summary["scopes"] is None
    records = os.path.join(manifest.BENCH_DIR, "out", CELL, "records.json")
    assert next(e for e in lines if e.get("check") == "serve.no_compilation_in_window")["ok"]
    assert os.path.exists(records)


def test_the_real_cell_entered_only_by_additions(tiny_moe_benchmark):
    names = [m["name"] for m in tiny_moe_benchmark["per_layer"]
             if m.get("workloads") == [CELL]]
    assert names == [name for name, *_ in NEW_METRICS]
    for name, unit, source, layer in NEW_METRICS:
        entry = next(m for m in tiny_moe_benchmark["per_layer"] if m["name"] == name)
        assert (entry["unit"], entry["source"], entry["layer"]) == (unit, source, layer)
    assert {m["name"] for m in manifest.metrics_of(CELL, "end_to_end")} == {
        "out_tok_per_s", "tpot_p50_ms", "setup_s"}
    per_layer = {m["name"] for m in manifest.metrics_of(CELL, "per_layer")}
    # their bytes are another family's
    assert not per_layer & {"decode_roofline", "moe_experts_roofline", "moe_decode_roofline"}
    assert {"decode_step_device_ms", "kv_copy_busy_share", "moe_experts_touched_mean",
            "moe_experts_busy_share", "sched_decode_batch_mean",
            "engine_decode_batch_mean", "prefill_device_ms_per_ktok"} <= per_layer
    real = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    assert real["workloads"][-1]["name"] == REAL and real["workloads"][-1]["chips"] == 1
    assert sum(w["chips"] == 4 for w in real["workloads"]) == 1
    config = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", real["workloads"][-1]["config"] + ".json"))
    mix = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "traffic", real["workloads"][-1]["traffic"] + ".json"))
    assert mix["kind"] == "serve_closed_loop_arch_blockwise"
    assert (mix["prompt_lens"], mix["output_tokens"], mix["clients_per_slot"]) == (
        {"1024": 0.5, "2048": 0.3, "4096": 0.2}, [256, 1024], 1)
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 27}
    from benchmarks.reference import deepseek_v3_arch

    arguments = deepseek_v3_arch.llm_arguments(config)["model_kwargs"]
    assert deepseek_v3_arch.llm_arguments(config)["model_family"] == "deepseek"
    assert (arguments["n_experts"], arguments["experts_per_token"],
            arguments["moe_intermediate"], arguments["n_shared_experts"]) == (64, 6, 1408, 2)
    assert (arguments["kv_lora_rank"], arguments["qk_rope_head_dim"]) == (512, 64)
    assert flops_mla.kv_bytes_per_token(config) == config["num_hidden_layers"] * 576 * 2 == 8064
    with pytest.raises(SystemExit, match="q_lora_rank"):
        deepseek_v3_arch.sizes_of(dict(config, q_lora_rank=768))


def _hand_built():
    config = manifest.load_json(os.path.join(HERE, "data", "configs", "tiny-mla.json"))
    experts = 8
    before = {"decode_steps": 10, "touched": [30, 40],
              "assignments": [[10] * experts, [10] * experts]}
    after = {"decode_steps": 110, "touched": [530, 640],
             "assignments": [[110] * experts, [110] * experts]}
    return {
        "config": config, "device": {"kind": "TPU v5 lite"},
        "program_counters": {
            "before": {"moe": before, "kv": {"cache_bytes_per_token": None}},
            "after": {"moe": after, "kv": {"cache_bytes_per_token": 240}}},
        "scopes": {"module": "_decode_impl", "executions": 50, "module_s": 0.5,
                   "scope_s": {"moe.route": 0.02, "moe.experts": 0.15, "moe.shared": 0.03},
                   "attention_scope_s": {"mla.absorb": 0.04},
                   "kernel_s": {"moe_experts": 0.12, "latent_decode_attention": 0.06}},
        "trace": {"busy_s": 0.6, "modules": {
            "jit__decode_impl": {"count": 50, "total_s": 0.5, "median_s": 0.01}}},
        "traced": {"start": 1.0, "stop": 3.0}, "window_s": 4.0, "pool": [],
        "records": [{"stamps": [0.5, 1.5, 2.5], "done": None, "prompt_len": 7,
                     "due": 0.1, "sent": 0.1, "asked": 9, "error": None}],
    }


def test_each_new_reader_on_a_hand_built_result(tiny_moe_benchmark, capsys):
    result = _hand_built()
    line = cli._layer_metrics(CELL, result, {"tpot_p50_ms"})
    config, layers, routed, live = result["config"], 3, 2, 7 + 2
    touched = (500 + 600) / (100 * routed)
    assert line["moe_experts_touched_mean"]["value"] == touched  # routed layers only
    assert line["kv_bytes_per_token"] == {"value": 240.0, "unit": "bytes"}
    assert flops_mla.kv_bytes_per_token(config) == layers * 40 * 2 == 240
    attention_bytes = live * 240
    assert line["mla_attention_roofline"]["value"] == pytest.approx(
        100 * (attention_bytes / 819e9) / (0.06 / 50))
    printed = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert printed[-1]["live_tokens"] == live
    assert printed[-1]["mla_attention_flop_share_pct"] == pytest.approx(
        100 * layers * live * 2 * 4 * (40 + 32) / 197e12 / (0.06 / 50))
    expert, shared, router = 3 * 64 * 32, 2 * 3 * 64 * 32, 65 * 8
    experts_bytes = routed * (touched * expert + shared + router) * 2
    assert line["mla_experts_roofline"]["value"] == pytest.approx(
        100 * (experts_bytes / 819e9) / (0.20 / 50))
    attention = 64 * 4 * 24 + 64 * 40 + 32 * 4 * 32 + 4 * 16 * 64
    weights = (layers * attention + 3 * 64 * 96 + 64 * 256) * 2
    assert line["mla_decode_roofline"]["value"] == pytest.approx(
        100 * ((experts_bytes + weights + attention_bytes) / 819e9) / 0.01)
    assert line["mla_attention_busy_share"]["value"] == pytest.approx(
        100 * (0.06 + 0.04) / 0.5)
    # the expert layers' scopes alone: the attention scope is kept apart
    assert line["moe_experts_busy_share"]["value"] == pytest.approx(
        100 * (0.02 + 0.15 + 0.03) / 0.5)


def test_new_readers_return_nothing_for_a_program_without_the_names(tiny_moe_benchmark):
    """The parent cannot build the family at all; were it to run, it has no
    ``kv`` counter, no latent kernel and no ``mla.absorb`` scope."""
    result = _hand_built()
    result["program_counters"] = {"before": {"moe": None, "kv": None},
                                  "after": {"moe": None, "kv": None}}
    result["scopes"] = {"module": "_decode_impl", "executions": 50, "module_s": 0.5,
                        "scope_s": {}, "kernel_s": {"latent_decode_attention": 0.0}}
    line = cli._layer_metrics(CELL, result, {"tpot_p50_ms"})
    assert not {name for name, *_ in NEW_METRICS} & set(line)
    result["scopes"] = None
    line = cli._layer_metrics(CELL, result, {"tpot_p50_ms"})
    assert not {name for name, *_ in NEW_METRICS} & set(line)
