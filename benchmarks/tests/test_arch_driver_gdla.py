"""What the Motif cell added to the benchmark, rehearsed on the CPU: the
``serve_closed_loop_arch_window_routed`` kind end to end on a toy of the
same shape (GDLA on window and full layers, a ring of 16, four streams,
PolyNorm, 8 of 16 routed experts held beside a shared one; a prompt the
check steps from position 0 and one it prefills), how the cell entered
``BENCHMARK.json``, ``harness/flops_gdla.py`` against shapes counted by
hand, and each new reader on a hand-built result and on one without the
spans (None). Named to sort beside ``test_arch_driver.py``, for its reason
(``cli.main`` refuses a harness process that has initialised a JAX
backend); nothing here initialises one.

The toy is entered into ``benchmarks/conftest.py``'s tables from here, at
import, as ``test_arch_driver_kda.py`` / ``_mla`` / ``_ssm`` enter theirs:
collect those three with this file.
"""

import importlib
import json
import os

import pytest

from benchmarks import conftest as top
from benchmarks.harness import cli, flops_gdla, manifest

REAL, CELL = "motif3-mixedlen-backlog", "tiny-mixedlen-gdla"
top.TOYS[REAL] = CELL
top.TOY_CONFIGS["tiny-gdla"] = "benchmarks/tests/data/configs/tiny-gdla.json"
if not any(c["name"] == CELL for c in top.TOY_CELLS):
    top.TOY_CELLS.append({"name": CELL, "config": "tiny-gdla", "traffic": CELL,
                          "chips": 1, "why": "test"})
NEW_METRICS = [
    ("gdla_attention_busy_share", "%", "device_trace", "jitted program"),
    ("gdla_attention_roofline", "%", "device_trace", "kernel"),
    ("gdla_experts_roofline", "%", "device_trace", "kernel"),
    ("gdla_decode_roofline", "%", "device_trace", "kernel"),
    ("mhc_busy_share", "%", "device_trace", "jitted program"),
    ("window_bytes_per_row", "bytes", "program_counter", "KV manager"),
]
# the toy: two rings of 16 positions and two full layers of a (32 + 8)-wide
# bf16 row
TOY_WINDOW_BYTES = 2 * 16 * 40 * 2
TOY_KV_BYTES = 2 * 40 * 2


def _real_config():
    real = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    entry = next(c for c in real["configs"] if c["name"] == "motif-3-beta-serve-1chip")
    return entry, manifest.load_json(os.path.join(manifest.ROOT, entry["file"]))


def test_the_window_routed_driver_end_to_end_on_the_cpu(tiny_moe_benchmark, capsys):
    code = cli.main(["--workload", CELL, "--seed", str(2**31 + 11),
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
    assert check["architecture"] == "motif_arch" and check["ok"]
    assert [r["decoded"] for r in check["rows"]] == [18, 16]
    short, long_ = check["rows"]
    # stepped from position 0 (16 + 17) and the request's own steps (17);
    # the long prompt's positions from the whole-prompt pass (48) and its 15
    assert short["from_zero"] and short["positions"] == 16 + 17 + 17
    assert not long_["from_zero"] and long_["positions"] == 48 + 15
    for row in check["rows"]:
        assert row["max_abs_logit_diff"] <= 0.25, row
        assert 0 < row["decode_rms_logit_diff"] <= 0.05, row
        assert 0 < row["first_part_rms_logit_diff"] <= 0.05, row
        assert row["replayed_tokens_equal"] == row["decoded"], row
        assert row["routing_slack_max"] <= 0.1, row
    summary = next(e for e in earlier if "program_counters_kept" in e)
    assert summary["program_counters_kept"] == ["moe", "kv"]
    assert summary["kvcache"]["hits"] == 0 and summary["kvcache"]["blocks_in_use"] == 0
    with open(os.path.join(manifest.BENCH_DIR, "out", CELL, "records.json")) as f:
        kept = json.load(f)["program_counters"]["after"]
    assert kept["kv"]["cache_bytes_per_token"] == TOY_KV_BYTES
    assert kept["kv"]["window_bytes_per_row"] == TOY_WINDOW_BYTES
    assert kept["kv"]["state_bytes_per_row"] == 0
    moe = kept["moe"]
    assert (moe["experts_routed"], moe["experts_held"]) == (16, 8)
    assert len(moe["assignments"]) == 3 and len(moe["assignments"][0]) == 8


def test_the_cell_entered_the_manifest_by_appending():
    bench = manifest.benchmark()
    entry, config = _real_config()
    cell = next(w for w in bench["workloads"] if w["name"] == REAL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "motif-3-beta-serve-1chip", "mixedlen-backlog-gdla", 1)
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "num_nextn_predict_layers"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, unit, source, layer in NEW_METRICS:
        assert by_name[name]["workloads"] == [REAL]
        assert (by_name[name]["unit"], by_name[name]["source"],
                by_name[name]["layer"]) == (unit, source, layer)
        assert importlib.import_module(
            f"benchmarks.layer_metrics.{name}").META["unit"] == unit
    reported = {m["name"] for g in ("end_to_end", "per_layer") for m in bench[g]
                if REAL in m.get("workloads", [REAL])}
    assert {"out_tok_per_s", "tpot_p50_ms", "kv_bytes_per_token",
            "moe_experts_touched_mean", "engine_decode_batch_mean"} <= reported
    assert not {n for n in reported if n.startswith(("kda_", "mla_", "ssm_"))}
    assert "state_bytes_per_row" not in reported
    mix = manifest.cell(REAL)["traffic_file"]
    assert mix["prompt_lens"] == {"256": 0.5, "2048": 0.3, "8192": 0.2}
    assert mix["output_tokens"] == [512, 1536] and mix["clients_per_slot"] == 1
    assert config["serving"]["max_batch_size"] == 16


def test_the_configuration_holds_every_published_width():
    _, config = _real_config()
    published = dict(config, **config["published"])
    assert (published["num_hidden_layers"], published["num_experts"],
            published["vocab_size"]) == (53, 384, 220160)
    assert (config["hidden_size"], config["num_attention_heads"], config["head_dim"],
            config["q_lora_rank"], config["kv_lora_rank"], config["moe_intermediate_size"],
            config["intermediate_size"], config["experts_top_k"]) == (
        4096, 80, 192, 1024, 512, 1280, 12288, 8)
    # counted by hand from the widths (ISSUE 50's arithmetic)
    assert flops_gdla.full_layers(config) == 2 and flops_gdla.window_layers(config) == 6
    assert flops_gdla.kv_bytes_per_token(config) == 2304
    assert flops_gdla.window_bytes_per_row(config) == 6 * 128 * 1152
    assert round(flops_gdla.attention_params(config) / 1e6, 2) == 91.75
    assert round(flops_gdla.expert_params(config) / 1e6, 2) == 15.73
    lengths = [300, 9000]
    assert flops_gdla.live_positions(config, lengths) == 2 * 9300 + 6 * 256
    per_byte = (flops_gdla.attention_step_flops(config, lengths)
                / flops_gdla.attention_step_min_bytes(config, lengths))
    assert round(per_byte) == 151


def _result(scopes=True, counters=True):
    """A traced run's result as the readers see it, built by hand: 100
    decode steps of 10 ms, two live rows of 300 and 8236 positions half way
    through the traced 0.87 s (44 tokens each by then)."""
    _, config = _real_config()
    records = [
        {"stamps": [1.0 + 0.01 * i for i in range(60)], "done": None, "prompt_len": 256},
        {"stamps": [1.0 + 0.01 * i for i in range(808)], "done": None, "prompt_len": 8192}]
    result = {
        "config": config, "device": {"kind": "TPU v5 lite"}, "records": records,
        "traced": {"start": 1.0, "stop": 1.87},
        "trace": {"modules": {"jit__decode_impl": {"count": 100, "median_s": 0.010}}},
        "program_counters": {"before": {}, "after": {}},
    }
    if scopes:
        result["scopes"] = {
            "executions": 100, "module_s": 1.0,
            "scope_s": {"moe.route": 0.02, "moe.experts": 0.4, "moe.shared": 0.05},
            "attention_scope_s": {"gdla.absorb": 0.03, "gdla.diff": 0.01,
                                  "mhc.maps": 0.04, "mhc.mix": 0.02},
            "kernel_s": {"latent_decode_attention": 0.06, "moe_experts": 0.4}}
    if counters:
        moe = lambda steps, touched: {  # noqa: E731
            "decode_steps": steps, "touched": [touched] * 6,
            "assignments": [[0] * 48] * 6}
        result["program_counters"] = {
            "before": {"moe": moe(0, 0)},
            "after": {"moe": moe(100, 1400),
                      "kv": {"cache_bytes_per_token": 2304,
                             "window_bytes_per_row": 884736}}}
    return result


def _read(name, result):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read(result)


def test_the_new_readers_on_a_hand_built_result():
    result = _result()
    _, config = _real_config()
    assert _read("window_bytes_per_row", result) == 884736
    assert _read("mhc_busy_share", result) == pytest.approx(6.0)
    assert _read("gdla_attention_busy_share", result) == pytest.approx(10.0)
    lengths = [256 + 44, 8192 + 44]
    peak = cli.peaks()["TPU v5 lite"]
    bytes_s = (2 * sum(lengths) + 6 * 256) * 1152 / peak["hbm_bytes_per_s"]
    flops_s = 151.1 * bytes_s * peak["hbm_bytes_per_s"] / peak["bf16_flops_per_s"]
    assert _read("gdla_attention_roofline", result) == pytest.approx(
        100 * max(bytes_s, flops_s) / 0.0006, rel=1e-3)
    experts = flops_gdla.experts_step_min_bytes(config, 14.0)
    assert _read("gdla_experts_roofline", result) == pytest.approx(
        100 * experts / peak["hbm_bytes_per_s"] / 0.0047)
    whole = flops_gdla.decode_step_min_bytes(config, 14.0, lengths)
    assert 4.5e9 < whole < 6e9
    assert _read("gdla_decode_roofline", result) == pytest.approx(
        100 * whole / peak["hbm_bytes_per_s"] / 0.010)
    for name, *_ in NEW_METRICS:
        assert 0 < _read(name, result) and (
            name == "window_bytes_per_row" or _read(name, result) <= 100)


@pytest.mark.parametrize("name", [m[0] for m in NEW_METRICS])
def test_a_reader_finds_nothing_where_the_program_has_no_such_span(name):
    """The parent's traced run, and every other family's: no ``gdla.*`` or
    ``mhc.*`` scope and no ``window_bytes_per_row``."""
    bare = _result(scopes=False, counters=False)
    assert _read(name, bare) is None
    other = _result()
    other["scopes"]["attention_scope_s"] = {"mla.absorb": 0.03}
    other["program_counters"]["after"]["kv"] = {"cache_bytes_per_token": 9216}
    assert _read(name, other) is None
    other["program_counters"]["after"]["kv"]["window_bytes_per_row"] = 0
    assert _read(name, other) is None
    assert _read(name, {"config": {}, "device": {"kind": "TPU v5 lite"}}) is None
