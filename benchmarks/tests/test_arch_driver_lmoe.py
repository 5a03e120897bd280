"""What the Nemotron-3-Super cell added to the benchmark, rehearsed on the
CPU: the ``serve_closed_loop_arch_stateful_routed`` kind, as it stands, end
to end on a toy of the same shape (pattern ``MEM*E``: layers that are a
Mamba-2 mixer, a NoPE GQA or a latent expert layer alone; 4 of 16 ungated
relu^2 experts held beside a shared one), how the cell entered
``BENCHMARK.json``, ``harness/flops_lmoe.py`` against shapes counted by
hand, each new reader on a hand-built result, and the controls: a program
that keeps a narrower state than the configuration guarantees, or that
drops a held expert's assignments, comes out not correct.
Named to sort beside ``test_arch_driver.py``, for its reason: ``cli.main``
refuses a harness process that has initialised a JAX backend, so nothing
here initialises one: the controls, which build an engine in-process, run
in a process of their own.

The toy is entered into ``benchmarks/conftest.py``'s tables from here, at
import, as the MLA, SSM, KDA and GDLA files enter theirs (collect them with
this file: each real cell needs its toy).
"""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import conftest as top
from benchmarks.harness import cli, flops_lmoe, hostplane, manifest

HERE = os.path.dirname(os.path.abspath(__file__))
REAL, CELL = "nemotron3s-reasoning-backlog", "tiny-backlog-lmoe"
top.TOYS[REAL] = CELL
top.TOY_CONFIGS["tiny-lmoe"] = "benchmarks/tests/data/configs/tiny-lmoe.json"
if not any(c["name"] == CELL for c in top.TOY_CELLS):
    top.TOY_CELLS.append({"name": CELL, "config": "tiny-lmoe", "traffic": CELL,
                          "chips": 1, "why": "test"})
NEW_METRICS = [
    ("lmoe_experts_roofline", "%", "device_trace", "kernel"),
    ("hssm_state_roofline", "%", "device_trace", "kernel"),
    ("hssm_mixer_busy_share", "%", "device_trace", "jitted program"),
    ("lmoe_decode_roofline", "%", "device_trace", "kernel"),
]
# two toy mixer layers: state 16 x 8 x 16 float32, tail 3 x 192 bf16; one
# attention layer: K and V 2 x 2 x 16 bf16; two expert layers keep nothing
TOY_STATE_BYTES = 2 * (16 * 8 * 16 * 4 + 3 * 192 * 2)
TOY_KV_BYTES = 2 * 2 * 16 * 2


def _real_config():
    real = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    entry = next(c for c in real["configs"]
                 if c["name"] == "nemotron-3-super-120b-serve-1chip")
    return manifest.load_json(os.path.join(manifest.ROOT, entry["file"]))


def test_the_accepted_kind_end_to_end_on_the_cpu(tiny_moe_benchmark, capsys):
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
    assert check["architecture"] == "nemotron_h_arch" and check["ok"]
    assert [r["decoded"] for r in check["rows"]] == [18, 16]
    assert [r["positions"] for r in check["rows"]] == [16 + 17 + 17, 40 + 15 + 15]
    for row in check["rows"]:
        assert row["max_abs_logit_diff"] <= 0.25, row
        assert 0 < row["decode_rms_logit_diff"] <= 0.05, row
        assert 0 < row["stepped_rms_logit_diff"] <= 0.05, row
        assert row["replayed_tokens_equal"] == row["decoded"], row
        assert row["routing_slack_max"] <= 0.1, row
    summary = next(e for e in earlier if "program_counters_kept" in e)
    assert summary["program_counters_kept"] == ["moe", "kv"]
    assert summary["kvcache"]["hits"] == 0 and summary["kvcache"]["blocks_in_use"] == 0
    with open(os.path.join(manifest.BENCH_DIR, "out", CELL, "records.json")) as f:
        kept = json.load(f)["program_counters"]["after"]
    assert kept["kv"]["cache_bytes_per_token"] == TOY_KV_BYTES
    assert kept["kv"]["state_bytes_per_row"] == TOY_STATE_BYTES
    moe = kept["moe"]
    assert (moe["experts_routed"], moe["experts_held"]) == (16, 4)
    # a row an *expert* layer (two of the five), a column an expert held
    assert len(moe["assignments"]) == 2 and len(moe["assignments"][0]) == 4
    assert all(sum(row) + gone > 0 for row, gone
               in zip(moe["assignments"], moe["assignments_absent"]))


def test_a_traced_run_finds_the_new_scopes(tiny_moe_benchmark, capsys):
    with pytest.raises(SystemExit) as refused:  # a CPU trace has no device plane
        cli.main(["--workload", CELL, "--seed", "4", "--seconds", "4", "--trace", "1"])
    assert refused.value.code not in (0, None)
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    summary = next(e for e in lines if "scoped_instructions" in e)
    assert summary["scoped_instructions"] > 10 and summary["scopes"] is None
    assert next(e for e in lines if e.get("check") == "serve.no_compilation_in_window")["ok"]


def test_the_real_cell_entered_only_by_additions(tiny_moe_benchmark):
    names = [m["name"] for m in tiny_moe_benchmark["per_layer"]
             if m.get("workloads") == [CELL]]
    assert names == [name for name, *_ in NEW_METRICS]
    for name, unit, source, layer in NEW_METRICS:
        entry = next(m for m in tiny_moe_benchmark["per_layer"] if m["name"] == name)
        assert (entry["unit"], entry["source"], entry["layer"], entry["moves"]) == (
            unit, source, layer, "tpot_p50_ms")
    assert {m["name"] for m in manifest.metrics_of(CELL, "end_to_end")} == {
        "out_tok_per_s", "tpot_p50_ms", "setup_s"}
    per_layer = {m["name"] for m in manifest.metrics_of(CELL, "per_layer")}
    # their counts are another family's
    assert not {m for m in per_layer if m.startswith(("mla_", "ssm_", "kda_", "gdla_"))
                or m in ("moe_experts_roofline", "moe_decode_roofline", "decode_roofline")}
    assert not {"kv_copy_busy_share", "kv_pool_used_peak"} & per_layer  # no pool
    assert {"decode_step_device_ms", "kv_bytes_per_token", "state_bytes_per_row",
            "moe_experts_busy_share", "moe_experts_touched_mean",
            "moe_expert_load_max_over_mean", "sched_decode_batch_mean",
            "engine_decode_ahead_share", "prefill_device_ms_per_ktok"} <= per_layer
    real = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    cell = next(w for w in real["workloads"] if w["name"] == REAL)
    assert cell["chips"] == 1 and sum(w["chips"] == 4 for w in real["workloads"]) == 1
    config = _real_config()
    mix = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "traffic", cell["traffic"] + ".json"))
    assert mix["kind"] == "serve_closed_loop_arch_stateful_routed"
    assert (mix["prompt_lens"], mix["output_tokens"], mix["ramp_s"], mix["trace_s"]) == (
        {"256": 0.5, "512": 0.3, "2048": 0.2}, [512, 2048], 16, 5)
    assert set(mix["tolerance"]) == {
        "prefill_logit", "rms_logit", "token_gap", "routing_agree_share",
        "routing_slack", "unfollowed_logit"}
    assert config["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert set(config["published"]) == set(config["reduced"])
    assert config["hybrid_override_pattern"] == config["published"][
        "hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    assert config["serving"]["max_batch_size"] * mix["clients_per_slot"] in (48, 64)
    # every number of the catalog's row, under its own key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(l) for l in f
                       if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in l)
        assert config["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
        assert differs == set(config["reduced"])
        assert config["published"] == {k: row["config"][k] for k in config["reduced"]}
    from benchmarks.reference import nemotron_h_arch

    arguments = nemotron_h_arch.llm_arguments(config)
    assert arguments["model_family"] == "nemotron_h"
    kwargs = arguments["model_kwargs"]
    assert (kwargs["vocab_size"], kwargs["dim"], kwargs["pattern"]) == (
        32768, 4096, "MEMEMEM*EME")
    assert (kwargs["n_experts"], kwargs["experts_held"], kwargs["experts_per_token"],
            kwargs["moe_latent"], kwargs["moe_intermediate"]) == (
        512, (0, 128), 22, 1024, 2688)
    assert (kwargs["mamba_n_heads"], kwargs["mamba_d_head"], kwargs["mamba_d_state"],
            kwargs["mamba_n_groups"], kwargs["n_heads"], kwargs["n_kv_heads"]) == (
        128, 64, 128, 8, 32, 2)
    assert nemotron_h_arch.sizes_of(config)["guaranteed"] == {
        "state_bytes_per_row": 21278720, "kv_bytes_per_token": 1024}
    with pytest.raises(SystemExit, match="num_nextn_predict_layers"):
        nemotron_h_arch.sizes_of(dict(config, num_nextn_predict_layers=1))


def test_flops_lmoe_against_shapes_counted_by_hand():
    config = _real_config()
    assert [flops_lmoe.layers(config, kind) for kind in "M*E"] == [5, 1, 5]
    assert flops_lmoe.state_elements(config) == 128 * 64 * 128
    assert flops_lmoe.conv_channels(config) == 8192 + 2 * 8 * 128 == 10240
    assert flops_lmoe.in_proj_columns(config) == 8192 + 10240 + 128 == 18560
    assert flops_lmoe.state_bytes_per_row(config) == 5 * (4194304 + 61440) == 21278720
    assert flops_lmoe.kv_bytes_per_token(config) == 2 * 2 * 128 * 2 == 1024
    assert flops_lmoe.mixer_params(config) == (
        4096 * 18560 + 8192 * 4096 + 5 * 10240 + 8192 + 3 * 128 + 4096) == 109640064
    assert flops_lmoe.attention_params(config) == (
        2 * 4096 * 4096 + 2 * 4096 * 256 + 4096) == 35655680
    assert flops_lmoe.expert_params(config) == 2 * 1024 * 2688 == 5505024
    assert flops_lmoe.shared_params(config) == (
        4097 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 4096) == 54530560
    assert flops_lmoe.state_step_bytes(config, 64) == 2 * 64 * 5 * 4194304
    assert flops_lmoe.state_step_flops(config, 64) == 6 * 64 * 5 * 1048576
    experts = 5 * (54530560 + 120.5 * 5505024) * 2
    assert flops_lmoe.experts_step_min_bytes(config, 120.5) == experts
    assert flops_lmoe.experts_step_flops(config, 64, 352) == 2 * 5 * (
        64 * (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376) + 352 * 5505024)
    weights = (5 * 109640064 + 35655680 + 4096 + 4096 * 32768) * 2
    assert flops_lmoe.decode_step_min_bytes(config, 63.5, 90000, 120.5) == (
        weights + 2 * 63.5 * 5 * 4194304 + 2 * 63.5 * 5 * 30720 * 2
        + 90000 * 1024 + experts)


def _hand_built():
    config = manifest.load_json(os.path.join(HERE, "data", "configs", "tiny-lmoe.json"))
    moe = {"decode_steps": 0, "touched": [0] * 2, "assignments": [[0] * 4] * 2,
           "experts_routed": 16, "experts_held": 4, "assignments_absent": [0] * 2}
    return {
        "config": config, "device": {"kind": "TPU v5 lite"},
        "program_counters": {
            "before": {"kv": {"cache_bytes_per_token": None, "state_bytes_per_row": None},
                       "moe": moe},
            "after": {"kv": {"cache_bytes_per_token": TOY_KV_BYTES,
                             "state_bytes_per_row": TOY_STATE_BYTES},
                      "moe": dict(moe, decode_steps=50, touched=[100, 150],
                                  assignments=[[10, 20, 30, 40]] * 2,
                                  assignments_absent=[300] * 2)}},
        "scopes": {"module": "_decode_impl", "executions": 50, "module_s": 0.5,
                   "scope_s": {"moe.route": 0.02, "moe.experts": 0.1, "moe.shared": 0.03,
                               "moe.latent": 0.01},
                   "attention_scope_s": {"ssm.proj": 0.05, "ssm.conv": 0.01,
                                         "ssm.scan": 0.14},
                   "kernel_s": {"decode_attention": 0.02, "kv_row_write": 0.01,
                                "moe_experts": 0.09}},
        "trace": {"busy_s": 0.6, "modules": {
            "jit__decode_impl": {"count": 50, "total_s": 0.5, "median_s": 0.01}}},
        "traced": {"start": 1.0, "stop": 3.0}, "window_s": 4.0, "pool": [],
        "records": [{"stamps": [0.5, 1.5, 2.5], "done": None, "prompt_len": 7,
                     "due": 0.1, "sent": 0.1, "asked": 9, "error": None}],
    }


def _dispatches(state_rows=True):
    """A loaded host plane (``hostplane.load``) of three decode dispatches
    of a 4-row pool, 2, 3 and 4 of its rows a request's."""
    return {"modules": [], "spans": [
        {"name": "engine.decode_dispatch", "thread": 0, "start": i, "end": i + 1,
         "stats": dict({"batch": batch}, **({"state_rows": 4} if state_rows else {}))}
        for i, batch in enumerate((2, 3, 4))]}


def test_each_new_reader_on_a_hand_built_result(tiny_moe_benchmark, capsys, monkeypatch):
    result = _hand_built()
    monkeypatch.setattr(hostplane, "of", lambda _: _dispatches())
    line = cli._layer_metrics(CELL, result, {"tpot_p50_ms"})
    config, carried, rows, tokens, touched = result["config"], 4, 3.0, 7 + 2, 2.5
    assert line["state_bytes_per_row"] == {"value": float(TOY_STATE_BYTES), "unit": "bytes"}
    assert line["kv_bytes_per_token"] == {"value": float(TOY_KV_BYTES), "unit": "bytes"}
    assert flops_lmoe.state_bytes_per_row(config) == TOY_STATE_BYTES
    assert flops_lmoe.kv_bytes_per_token(config) == TOY_KV_BYTES
    assert line["moe_experts_touched_mean"]["value"] == pytest.approx(touched)
    # the mixer's scopes are no part of the expert layers' share; moe.latent is
    assert line["moe_experts_busy_share"]["value"] == pytest.approx(100 * 0.16 / 0.5)
    assert line["hssm_mixer_busy_share"]["value"] == pytest.approx(100 * 0.20 / 0.5)
    state = 2 * 16 * 8 * 16 * 4  # two mixer layers' float32 state a row

    assert line["hssm_state_roofline"]["value"] == pytest.approx(
        100 * (2 * rows * state / 819e9) / (0.14 / 50))
    printed = {k: v for l in capsys.readouterr().out.splitlines()
               for k, v in json.loads(l).items()}
    assert (printed["state_rows"], printed["live_rows"]) == (carried, rows)
    assert printed["hssm_state_roofline_carried_rows_pct"] == pytest.approx(
        100 * (2 * carried * state / 819e9) / (0.14 / 50))
    assert printed["hssm_state_flop_share_pct"] == pytest.approx(
        100 * 6 * carried * 2 * 2048 / 197e12 / (0.14 / 50))
    expert = 2 * 32 * 48
    outside = 65 * 16 + 2 * 64 * 32 + 2 * 64 * 96 + 64
    experts = 2 * (outside + touched * expert) * 2
    assert line["lmoe_experts_roofline"]["value"] == pytest.approx(
        100 * (experts / 819e9) / (0.16 / 50))
    assert printed["held_assignments_a_layer_a_step"] == pytest.approx(100 / 50)
    assert printed["lmoe_experts_flop_share_pct"] == pytest.approx(
        100 * 2 * 2 * (rows * (64 * 16 + 2 * 64 * 32 + 2 * 64 * 96) + 2 * expert)
        / 197e12 / (0.16 / 50))
    mixer = 64 * (128 + 192 + 16) + 128 * 64 + 5 * 192 + 128 + 3 * 16 + 64
    attention = 2 * 64 * 64 + 2 * 64 * 32 + 64

    def step_bytes(stepped):
        return ((2 * mixer + attention + 64 + 64 * 256) * 2 + 2 * stepped * state
                + 2 * stepped * 2 * 3 * 192 * 2 + tokens * TOY_KV_BYTES + experts)

    assert line["lmoe_decode_roofline"]["value"] == pytest.approx(
        100 * (step_bytes(rows) / 819e9) / 0.01)
    assert printed["lmoe_decode_roofline_carried_rows_pct"] == pytest.approx(
        100 * (step_bytes(carried) / 819e9) / 0.01)


def test_new_readers_return_nothing_for_a_program_without_the_names(
        tiny_moe_benchmark, monkeypatch):
    """The parent cannot build the family at all; were it to run, it has no
    ``moe.latent`` scope; Falcon-H1's program has the mixer's scopes but
    keeps them among ``scope_s`` and has no expert layer."""
    new = {name for name, *_ in NEW_METRICS}
    result = _hand_built()
    monkeypatch.setattr(hostplane, "of", lambda _: _dispatches(state_rows=False))
    line = cli._layer_metrics(CELL, result, {"tpot_p50_ms"})
    assert new & set(line) == {"hssm_mixer_busy_share", "lmoe_experts_roofline"}
    monkeypatch.setattr(hostplane, "of", lambda _: _dispatches())
    del result["scopes"]["scope_s"]["moe.latent"]  # another family's experts
    assert not new & set(cli._layer_metrics(CELL, result, {"tpot_p50_ms"}))
    result = _hand_built()
    result["scopes"]["scope_s"].update(result["scopes"].pop("attention_scope_s"))
    assert not new & set(cli._layer_metrics(CELL, result, {"tpot_p50_ms"}))
    result = _hand_built()
    result["program_counters"] = {"before": {"kv": None, "moe": None},
                                  "after": {"kv": None, "moe": None}}
    assert new & set(cli._layer_metrics(CELL, result, {"tpot_p50_ms"})) == {
        "hssm_mixer_busy_share", "hssm_state_roofline"}
    result["scopes"] = None
    assert not new & set(cli._layer_metrics(CELL, result, {"tpot_p50_ms"}))


# -- the controls -------------------------------------------------------------

def check_facts(config: dict, seed: int, prompt_len: int, decoded: int,
                control: str = "") -> dict:
    """``RoutedStatefulReplica.bench_reference``'s facts for one request, in
    this process: the engine built as the replica builds it, the request
    through ``engine.generate``. ``control`` plants a fault in the
    *program*: ``state_bf16`` (the mixer's state stored in bf16:
    ``falcon_h1.STATE_DTYPE``, which this family's mixer shares),
    ``expert_dropped`` (the assignments to one held expert add nothing: its
    down matrix zeroed in the programs' weights, the reference's kept).
    Initialises a JAX backend and, for ``state_bf16``, leaves the program's
    module patched: for a process that ends with it."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.drivers import serve_arch_common as common
    from benchmarks.drivers import serve_closed_loop_arch_stateful_routed as kind
    from benchmarks.reference import nemotron_h_arch
    from ray_tpu import models
    from ray_tpu.kvcache import KVCacheManager
    from ray_tpu.llm.engine import ContinuousBatchingEngine, GenerationRequest
    from ray_tpu.models import falcon_h1
    from ray_tpu.parallel.sharding import unbox_params

    if control == "state_bf16":
        falcon_h1.STATE_DTYPE = jnp.bfloat16
    serving = config["serving"]
    model_config = common.llm_config(config, seed).build_model_config()
    params = unbox_params(models.init_params(model_config, jax.random.PRNGKey(seed)))
    engine = ContinuousBatchingEngine(
        model_config, params, num_slots=serving["max_batch_size"], seed=seed,
        kv_cache=KVCacheManager(serving["kv_cache_blocks"], serving["kv_block_size"]))
    if control == "expert_dropped":
        layer = f"layer_{model_config.routed_layers[0]}"
        moe = params[layer]["moe"]
        faulty = dict(params, **{layer: dict(params[layer], moe=dict(
            moe, w_down=moe["w_down"].at[1].set(0)))})
        for name in ("_prefill", "_decode"):
            honest = getattr(engine, name)
            setattr(engine, name, lambda _, *a, _honest=honest, **k: _honest(faulty, *a, **k))
    prompt = [int(t) for t in np.random.default_rng(seed).integers(
        0, config["vocab_size"], prompt_len)]
    answer = engine.generate(
        [GenerationRequest(token_ids=prompt, max_new_tokens=decoded)])[0].token_ids
    return kind.RoutedStatefulReplica.bench_reference(
        types.SimpleNamespace(_engine=engine), config["architecture"],
        nemotron_h_arch.sizes_of(config), prompt, answer)


def _toy_config() -> dict:
    return manifest.load_json(os.path.join(HERE, "data", "configs", "tiny-lmoe.json"))


def _facts_in_a_process_of_its_own(control: str) -> dict:
    code = (
        "import json, sys; sys.path[:0] = [%r, %r]; "
        "import test_arch_driver_lmoe as t; "
        "print('FACTS ' + json.dumps(t.check_facts(t._toy_config(), 2**31 + 5, 40, 16, %r)))"
        % (manifest.ROOT, HERE, control))
    ran = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    lines = [l for l in ran.stdout.splitlines() if l.startswith("FACTS ")]
    assert lines, ran.stderr[-2000:]
    return json.loads(lines[-1][len("FACTS "):])


@pytest.mark.parametrize("control, fails_by", [
    ("", None), ("state_bf16", "state_bytes_per_row"),
    ("expert_dropped", "rms_logit_diff")])
def test_a_planted_fault_is_not_correct(control, fails_by):
    """Each control through the kind's own ``within`` at the toy's
    tolerance: a narrower state by the bytes a row holds (no logit shows it
    at these lengths), a dropped expert by the logits."""
    from benchmarks.drivers import serve_closed_loop_arch_stateful_routed as kind

    tolerance = manifest.load_json(os.path.join(
        HERE, "data", "traffic", CELL + ".json"))["tolerance"]
    facts = _facts_in_a_process_of_its_own(control)
    assert kind.within(facts, tolerance) is (not control), facts
    if fails_by == "rms_logit_diff":
        assert facts[fails_by] > tolerance["rms_logit"], facts
    elif control:
        assert fails_by in facts["error"], facts
