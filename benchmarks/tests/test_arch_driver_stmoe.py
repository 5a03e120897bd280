"""What the SmallThinker cell added to the benchmark, rehearsed on the CPU:
the ``serve_closed_loop_arch_window_routed`` kind, as it stands, end to end
on a toy of the same shape (a router on the attention's input, a NoPE full
layer then three rotary window layers of 32, 7 query heads on one K/V head,
16 ReGLU experts all held, an untied head; a prompt the check steps from
position 0 and one it prefills, longer than the ring), how the cell entered
``BENCHMARK.json``, ``harness/flops_stmoe.py`` against shapes counted by
hand, each new reader on a hand-built result, and the controls: a reference
that routes on the post-attention norm's output, gates through silu or
holds its experts in an 8-bit float, and a program that keeps a shorter
ring than the configuration guarantees, comes out not correct. Named to
sort beside ``test_arch_driver.py``, for its reason: ``cli.main`` refuses a
harness process that has initialised a JAX backend, so nothing here
initialises one: the controls, which build an engine in-process, run in a
process of their own.

The toy is entered into ``benchmarks/conftest.py``'s tables from here, at
import, as the MLA, SSM, KDA, GDLA, LMOE and C2MOE files enter theirs
(collect them with this file: each real cell needs its toy).
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmarks import conftest as top
from benchmarks.harness import cli, flops_stmoe, manifest

HERE = os.path.dirname(os.path.abspath(__file__))
REAL, CELL = "smallthinker-chat-mixed-backlog", "tiny-backlog-stmoe"
REAL_CONFIG = "smallthinker-21ba3b-serve-1chip"
PARENT = "a09edc628b7805d4e461eac42ab73dd6f30a7292"
top.TOYS[REAL] = CELL
top.TOYS.setdefault("mistral7b-lora-fsdp4-filled", "tiny-lora")
top.TOY_CONFIGS["tiny-stmoe"] = "benchmarks/tests/data/configs/tiny-stmoe.json"
if not any(c["name"] == CELL for c in top.TOY_CELLS):
    top.TOY_CELLS.append({"name": CELL, "config": "tiny-stmoe", "traffic": CELL,
                          "chips": 1, "why": "test"})
NEW_METRICS = [
    ("stmoe_route_busy_share", "%", "lower", "jitted program"),
    ("stmoe_experts_roofline", "%", "higher", "kernel"),
    ("stmoe_attention_roofline", "%", "higher", "kernel"),
    ("stmoe_decode_roofline", "%", "higher", "kernel"),
]
# the toy: three rings of 32 positions and one full layer of K and V, 1
# head x 16, bf16
TOY_WINDOW_BYTES = 3 * 32 * 2 * 1 * 16 * 2
TOY_KV_BYTES = 1 * 2 * 1 * 16 * 2


def _real():
    real = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    entry = next(c for c in real["configs"] if c["name"] == REAL_CONFIG)
    return real, entry, manifest.load_json(os.path.join(manifest.ROOT, entry["file"]))


def test_the_accepted_kind_end_to_end_on_the_cpu(tiny_moe_benchmark, capsys):
    code = cli.main(["--workload", CELL, "--seed", str(2**31 + 13),
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
    assert check["architecture"] == "smallthinker_arch" and check["ok"]
    assert [r["decoded"] for r in check["rows"]] == [18, 16]
    short, long_ = check["rows"]
    # stepped from position 0 (16 + 17: through the ring's wrap at 32) and
    # the request's own steps (17); the long prompt's positions from the
    # whole-prompt pass under the band (48) and its 15
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
    assert (moe["experts_routed"], moe["experts_held"]) == (16, 16)
    assert len(moe["assignments"]) == 4 and len(moe["assignments"][0]) == 16
    assert moe["assignments_absent"] == [0] * 4


def test_a_traced_run_finds_the_new_scopes(tiny_moe_benchmark, capsys):
    with pytest.raises(SystemExit) as refused:  # a CPU trace has no device plane
        cli.main(["--workload", CELL, "--seed", "4", "--seconds", "4", "--trace", "1"])
    assert refused.value.code not in (0, None)
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    summary = next(e for e in lines if "scoped_instructions" in e)
    assert summary["scoped_instructions"] > 10 and summary["scopes"] is None
    assert next(e for e in lines if e.get("check") == "serve.no_compilation_in_window")["ok"]


def _without_the_additions(bench: dict) -> dict:
    """``BENCHMARK.json`` less what this cell's PR appended."""
    new = {name for name, *_ in NEW_METRICS}
    out = dict(
        bench,
        configs=[c for c in bench["configs"] if c["name"] != REAL_CONFIG],
        workloads=[w for w in bench["workloads"] if w["name"] != REAL])
    for group in ("end_to_end", "per_layer"):
        out[group] = [
            dict(m, workloads=[w for w in m["workloads"] if w != REAL])
            if "workloads" in m else m
            for m in bench[group] if m["name"] not in new]
    return out


def test_the_real_cell_entered_only_by_additions():
    bench, entry, config = _real()
    # at the end of every list it joined
    assert bench["configs"][-1] is entry and bench["workloads"][-1]["name"] == REAL
    assert [m["name"] for m in bench["per_layer"][-4:]] == [n for n, *_ in NEW_METRICS]
    for name, unit, better, layer in NEW_METRICS:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m == {"name": name, "unit": unit, "better": better,
                     "source": "device_trace", "layer": layer,
                     "moves": "tpot_p50_ms", "workloads": [REAL]}
        meta = importlib.import_module(f"benchmarks.layer_metrics.{name}").META
        assert (meta["unit"], meta["better"], meta["layer"]) == (unit, better, layer)
    joined = [m for g in ("end_to_end", "per_layer") for m in bench[g]
              if REAL in m.get("workloads", ())]
    assert all(m["workloads"][-1] == REAL for m in joined)
    # every shared metric Command A+'s cell reports, but its own family's
    sibling = {m["name"] for g in ("end_to_end", "per_layer") for m in bench[g]
               if "commandaplus-rag-backlog" in m.get("workloads", ())}
    assert {m["name"] for m in joined} - {n for n, *_ in NEW_METRICS} == {
        n for n in sibling if not n.startswith("c2moe_")}
    cell = bench["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        REAL_CONFIG, "chat-mixed-backlog-stmoe", 1)
    assert len(bench["workloads"]) == 12
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2
    # ... and the parent's file is this one less the additions, where git
    # can say what the parent's was
    shown = subprocess.run(
        ["git", "show", f"{PARENT}:BENCHMARK.json"], cwd=manifest.ROOT,
        capture_output=True, text=True)
    if shown.returncode == 0:
        assert _without_the_additions(bench) == json.loads(shown.stdout)
    mix = manifest.cell(REAL)["traffic_file"]
    assert mix["kind"] == "serve_closed_loop_arch_window_routed"
    assert (mix["prompt_lens"], mix["output_tokens"], mix["ramp_s"],
            mix["trace_s"], mix["clients_per_slot"]) == (
        {"512": 0.5, "2048": 0.3, "4096": 0.2}, [256, 1024], 16, 2.5, 1)
    assert set(mix["tolerance"]) == {
        "prefill_logit", "rms_logit", "token_gap", "routing_agree_share",
        "routing_slack", "unfollowed_logit"}
    assert config["serving"] == {
        "max_seq_len": 5120, "max_batch_size": 64, "kv_cache_blocks": 1,
        "kv_block_size": 128}
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 52}
    assert config["num_hidden_layers"] == 8
    assert {"router_input", "reglu_expert", "no_secondary_experts",
            "no_bias_no_qk_norm", "nope_full_layers"} <= set(config["assumed"])
    assert "ONE chip shares each layer" in config["stands_for"]
    # every number of the catalog's row, under its own key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(l) for l in f
                       if '"SmallThinker-21BA3B-Instruct"' in l)
        assert config["source"] == entry["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
        assert differs == set(config["reduced"])
        assert config["published"] == {k: row["config"][k] for k in config["reduced"]}


def test_the_configuration_holds_every_published_width():
    from benchmarks.reference import smallthinker_arch

    _, _, config = _real()
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["moe_ffn_hidden_size"], config["moe_num_primary_experts"],
            config["moe_num_active_primary_experts"], config["vocab_size"],
            config["sliding_window_size"], config["rope_theta"],
            config["rms_norm_eps"]) == (
        2560, 28, 4, 128, 768, 64, 6, 151936, 4096, 1500000, 1e-6)
    kwargs = smallthinker_arch.llm_arguments(config)["model_kwargs"]
    assert (kwargs["vocab_size"], kwargs["n_layers"], kwargs["n_experts"],
            kwargs["experts_held"]) == (151936, 8, 64, (0, 64))
    assert smallthinker_arch.sizes_of(config)["guaranteed"] == {
        "window_bytes_per_row": 50331648, "kv_bytes_per_token": 4096}
    with pytest.raises(SystemExit, match="tie_word_embeddings"):
        smallthinker_arch.sizes_of(dict(config, tie_word_embeddings=True))
    # counted by hand from the widths (ISSUE 61's arithmetic)
    assert flops_stmoe.full_layers(config) == 2 and flops_stmoe.window_layers(config) == 6
    assert flops_stmoe.attention_params(config) == 2 * 2560 * (3584 + 512)
    assert flops_stmoe.router_params(config) == 2560 * 64
    assert flops_stmoe.expert_params(config) * 2 == 11796480
    lengths = [1500, 4600]
    assert flops_stmoe.live_positions(config, lengths) == 2 * 6100 + 6 * (1500 + 4096)
    per_byte = (flops_stmoe.attention_step_flops(config, lengths)
                / flops_stmoe.attention_step_min_bytes(config, lengths))
    assert per_byte == 7
    # a step of 64 rows at ~2000 positions that touches every expert
    whole = flops_stmoe.decode_step_min_bytes(config, 64.0, 384.0, [2000] * 64)
    assert 8.5e9 < whole < 10.0e9
    assert flops_stmoe.experts_kernel_min_bytes(config, 63.9, 384.0) == (
        8 * (63.9 * 5898240 + 384 * 2 * 2560) * 2)


def _result(scopes=True, counters=True):
    """A traced run's result as the readers see it, built by hand: 100
    decode steps of 15 ms, two live rows of 556 and 4140 positions half way
    through the traced 0.87 s (44 tokens each by then)."""
    _, _, config = _real()
    records = [
        {"stamps": [1.0 + 0.01 * i for i in range(60)], "done": None, "prompt_len": 512},
        {"stamps": [1.0 + 0.01 * i for i in range(808)], "done": None, "prompt_len": 4096}]
    result = {
        "config": config, "device": {"kind": "TPU v5 lite"}, "records": records,
        "traced": {"start": 1.0, "stop": 1.87}, "window_s": 50.0,
        "trace": {"modules": {"jit__decode_impl": {"count": 100, "median_s": 0.015}}},
        "program_counters": {"before": {}, "after": {}},
    }
    if scopes:
        result["scopes"] = {
            "executions": 100, "module_s": 1.5,
            "scope_s": {"sthink.route": 0.03, "moe.sort": 0.06, "moe.experts": 0.8},
            "attention_scope_s": {"sthink.attn_window": 0.3, "sthink.attn_full": 0.12,
                                  "sthink.norm": 0.01},
            "kernel_s": {"decode_attention": 0.15, "kv_row_write": 0.01,
                         "moe_experts": 0.75}}
    if counters:
        moe = lambda steps, touched, each: {  # noqa: E731
            "decode_steps": steps, "touched": [touched] * 8,
            "assignments": [[each] * 64] * 8}
        result["program_counters"] = {
            "before": {"moe": moe(0, 0, 0)},
            "after": {"moe": moe(100, 6300, 600),
                      "kv": {"cache_bytes_per_token": 4096,
                             "window_bytes_per_row": 50331648}}}
    return result


def _read(name, result):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read(result)


def test_the_new_readers_on_a_hand_built_result():
    result = _result()
    _, _, config = _real()
    peak = cli.peaks()["TPU v5 lite"]
    # (0.03 under the router's scope + 0.06 of sort) of 1.5 s
    assert _read("stmoe_route_busy_share", result) == pytest.approx(6.0)
    lengths = [512 + 44, 4096 + 44]
    positions = 2 * sum(lengths) + 6 * (556 + 4096)
    bytes_s = positions * 2048 / peak["hbm_bytes_per_s"]
    assert _read("stmoe_attention_roofline", result) == pytest.approx(
        100 * bytes_s / 0.0015)
    experts = flops_stmoe.experts_kernel_min_bytes(config, 63.0, 384.0)
    assert _read("stmoe_experts_roofline", result) == pytest.approx(
        100 * experts / peak["hbm_bytes_per_s"] / 0.0075)
    whole = flops_stmoe.decode_step_min_bytes(config, 63.0, 384.0, lengths)
    assert _read("stmoe_decode_roofline", result) == pytest.approx(
        100 * whole / peak["hbm_bytes_per_s"] / 0.015)
    for name, *_ in NEW_METRICS:
        assert 0 < _read(name, result) <= 100, name
    # ... beside the shared counters the cell joined
    assert _read("window_bytes_per_row", result) == 50331648
    assert _read("kv_bytes_per_token", result) == 4096
    assert _read("moe_experts_touched_mean", result) == pytest.approx(63.0)


@pytest.mark.parametrize("name", [m[0] for m in NEW_METRICS])
def test_a_reader_finds_nothing_where_the_program_has_no_such_span(name):
    """The parent's traced run, and every other family's: no ``sthink.*``
    scope."""
    assert _read(name, _result(scopes=False, counters=False)) is None
    other = _result()
    other["scopes"]["scope_s"] = {"moe.route": 0.02, "moe.experts": 0.7}
    other["scopes"]["attention_scope_s"] = {"c2moe.attn_window": 0.3, "c2moe.norm": 0.02}
    assert _read(name, other) is None
    other["scopes"]["attention_scope_s"] = {}
    assert _read(name, other) is None
    assert _read(name, {"config": {}, "device": {"kind": "TPU v5 lite"}}) is None


# -- the controls -------------------------------------------------------------

def check_facts(config: dict, seed: int, prompt_len: int, decoded: int,
                control: str = "", faults=()) -> dict:
    """``WindowRoutedReplica.bench_reference``'s facts for one request, in
    this process: the engine built as the replica builds it, the request
    through ``engine.generate``. ``control`` plants a fault in the
    *program*: ``ring_halved`` (the window layers keep half the ring the
    configuration states), ``ring_e4m3`` (the rings a prefill leaves
    through an 8-bit float, a scale a position and head, before the row is
    inserted). ``faults``: the reference's own (``hidden_states``:
    ``route_on_n2``, ``swiglu``, ``experts_e4m3`` ...). Initialises a JAX
    backend: for a process that ends with it."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.drivers import serve_arch_common as common
    from benchmarks.drivers import serve_closed_loop_arch_window_routed as kind
    from benchmarks.reference import smallthinker_arch
    from ray_tpu import models
    from ray_tpu.kvcache import KVCacheManager
    from ray_tpu.llm.engine import ContinuousBatchingEngine, GenerationRequest
    from ray_tpu.parallel.sharding import unbox_params

    built = dict(config, sliding_window_size=config["sliding_window_size"] // 2) \
        if control == "ring_halved" else config
    serving = config["serving"]
    model_config = common.llm_config(built, seed).build_model_config()
    params = unbox_params(models.init_params(model_config, jax.random.PRNGKey(seed)))
    engine = ContinuousBatchingEngine(
        model_config, params, num_slots=serving["max_batch_size"], seed=seed,
        kv_cache=KVCacheManager(serving["kv_cache_blocks"], serving["kv_block_size"]))
    if control == "ring_e4m3":
        honest_prefill = engine._prefill

        def narrowed(leaf):
            wide = leaf.astype(jnp.float32)
            scale = jnp.max(jnp.abs(wide), axis=-1, keepdims=True) / 240.0
            scale = jnp.where(scale == 0, 1.0, scale)
            return (jax.lax.reduce_precision(
                wide / scale, exponent_bits=4, mantissa_bits=3) * scale).astype(leaf.dtype)

        def prefill(*args, **kwargs):
            logits, row = honest_prefill(*args, **kwargs)
            kinds = models.cache_kinds(row)
            return logits, jax.tree.map(
                lambda leaf, k: narrowed(leaf) if k == models.WINDOW else leaf,
                row, kinds)

        engine._prefill = prefill
    prompt = [int(t) for t in np.random.default_rng(seed).integers(
        0, config["vocab_size"], prompt_len)]
    answer = engine.generate(
        [GenerationRequest(token_ids=prompt, max_new_tokens=decoded)])[0].token_ids
    sizes = smallthinker_arch.sizes_of(config)
    if faults:
        sizes["faults"] = tuple(faults)
    facts = kind.WindowRoutedReplica.bench_reference(
        types.SimpleNamespace(_engine=engine), config["architecture"],
        sizes, prompt, answer)
    engine.close()
    return facts


def _toy_config() -> dict:
    return manifest.load_json(os.path.join(HERE, "data", "configs", "tiny-stmoe.json"))


def _facts_in_a_process_of_its_own(control: str, faults: tuple) -> dict:
    code = (
        "import json, sys; sys.path[:0] = [%r, %r]; "
        "import test_arch_driver_stmoe as t; "
        "print('FACTS ' + json.dumps(t.check_facts("
        "t._toy_config(), 2**31 + 5, 48, 16, %r, %r)))"
        % (manifest.ROOT, HERE, control, faults))
    ran = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    lines = [l for l in ran.stdout.splitlines() if l.startswith("FACTS ")]
    assert lines, ran.stderr[-2000:]
    return json.loads(lines[-1][len("FACTS "):])


@pytest.mark.parametrize("control, faults, fails_by", [
    ("", (), None),
    ("", ("route_on_n2",), "routing_agree_share"),
    ("", ("swiglu",), "rms_logit_diff"),
    ("", ("experts_e4m3",), "rms_logit_diff"),
    ("ring_halved", (), "window_bytes_per_row")])
def test_a_planted_fault_is_not_correct(control, faults, fails_by):
    """Each control through the kind's own ``within`` at the toy's
    tolerance: a router fed the wrong tensor by how seldom the program's
    choice is the reference's own, another gate and a lower precision by
    the logits, a shorter ring by the bytes a row holds."""
    from benchmarks.drivers import serve_closed_loop_arch_stateful_routed as kind

    tolerance = manifest.load_json(os.path.join(
        HERE, "data", "traffic", CELL + ".json"))["tolerance"]
    facts = _facts_in_a_process_of_its_own(control, faults)
    assert kind.within(facts, tolerance) is (not control and not faults), facts
    if fails_by == "rms_logit_diff":
        assert facts[fails_by] > tolerance["rms_logit"], facts
    elif fails_by == "routing_agree_share":
        assert facts[fails_by] < tolerance[fails_by], facts
    elif control:
        assert fails_by in facts["error"], facts
