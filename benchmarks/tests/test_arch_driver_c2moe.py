"""What the Command A+ cell added to the benchmark, rehearsed on the CPU:
the ``serve_closed_loop_arch_window_routed`` kind, as it stands, end to end
on a toy of the same shape (a parallel block, a ring of 32 K/V positions
every other layer with interleaved rotary pairs, a NoPE full layer between,
4 of 16 routed experts held beside two shared experts averaged, a tied
head; a prompt the check steps from position 0 and one it prefills, longer
than the ring), how the cell entered ``BENCHMARK.json``,
``harness/flops_c2moe.py`` against shapes counted by hand, each new reader
on a hand-built result, and the controls: a program that keeps a shorter
ring than the configuration guarantees, drops a held expert's assignments,
or reads a young row's ring past its live slots comes out not correct.
Named to sort beside ``test_arch_driver.py``, for its reason: ``cli.main``
refuses a harness process that has initialised a JAX backend, so nothing
here initialises one: the controls, which build an engine in-process, run
in a process of their own.

The toy is entered into ``benchmarks/conftest.py``'s tables from here, at
import, as the MLA, SSM, KDA, GDLA and LMOE files enter theirs (collect
them with this file: each real cell needs its toy). The second LoRA cell
has had no toy since PR 56; it takes the first's here, so that the
rehearsals collect again.
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmarks import conftest as top
from benchmarks.harness import cli, flops_c2moe, manifest

HERE = os.path.dirname(os.path.abspath(__file__))
REAL, CELL = "commandaplus-rag-backlog", "tiny-backlog-c2moe"
REAL_CONFIG = "command-a-plus-05-2026-serve-1chip"
PARENT = "0779a43e3c06c30ecf36dc7dee2d79efe659f5fe"
top.TOYS[REAL] = CELL
top.TOYS.setdefault("mistral7b-lora-fsdp4-filled", "tiny-lora")
top.TOY_CONFIGS["tiny-c2moe"] = "benchmarks/tests/data/configs/tiny-c2moe.json"
if not any(c["name"] == CELL for c in top.TOY_CELLS):
    top.TOY_CELLS.append({"name": CELL, "config": "tiny-c2moe", "traffic": CELL,
                          "chips": 1, "why": "test"})
NEW_METRICS = [
    ("c2moe_attention_busy_share", "%", "lower", "jitted program"),
    ("c2moe_attention_roofline", "%", "higher", "kernel"),
    ("c2moe_experts_roofline", "%", "higher", "kernel"),
    ("c2moe_dense_busy_share", "%", "lower", "jitted program"),
    ("c2moe_decode_roofline", "%", "higher", "kernel"),
]
# the toy: two rings of 32 positions and two full layers of K and V, 2
# heads x 16, bf16
TOY_WINDOW_BYTES = 2 * 32 * 2 * 2 * 16 * 2
TOY_KV_BYTES = 2 * 2 * 2 * 16 * 2


def _real():
    real = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    entry = next(c for c in real["configs"] if c["name"] == REAL_CONFIG)
    return real, entry, manifest.load_json(os.path.join(manifest.ROOT, entry["file"]))


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
    assert check["architecture"] == "cohere2_moe_arch" and check["ok"]
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
    assert (moe["experts_routed"], moe["experts_held"]) == (16, 4)
    assert len(moe["assignments"]) == 4 and len(moe["assignments"][0]) == 4


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
    assert [m["name"] for m in bench["per_layer"][-5:]] == [n for n, *_ in NEW_METRICS]
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
    # every shared metric Motif's cell reports, but its own family's
    motif = {m["name"] for g in ("end_to_end", "per_layer") for m in bench[g]
             if "motif3-mixedlen-backlog" in m.get("workloads", ())}
    assert {m["name"] for m in joined} - {n for n, *_ in NEW_METRICS} == {
        n for n in motif if not n.startswith(("gdla_", "mhc_"))}
    assert {"out_tok_per_s", "tpot_p50_ms", "window_bytes_per_row",
            "kv_bytes_per_token", "moe_experts_busy_share",
            "decode_step_device_ms", "prefill_device_ms_per_ktok",
            "client_itl_p99_ms", "startup_ready_s"} <= {m["name"] for m in joined}
    cell = bench["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        REAL_CONFIG, "rag-backlog-c2moe", 1)
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
            mix["clients_per_slot"]) == (
        {"2048": 0.3, "4096": 0.4, "8192": 0.3}, [512, 1536], 16, 1)
    assert set(mix["tolerance"]) == {
        "prefill_logit", "rms_logit", "token_gap", "routing_agree_share",
        "routing_slack", "unfollowed_logit"}
    assert config["serving"]["max_batch_size"] in (16, 24)
    assert config["serving"]["max_seq_len"] == 10240
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 32, "num_experts": 128, "vocab_size": 262144}
    assert {"expert_width", "shared_average", "nope_full_layers",
            "router", "tower"} <= set(config["assumed"])
    # every number of the catalog's row, under its own key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(l) for l in f
                       if '"command-a-plus-05-2026"' in l)
        assert config["source"] == entry["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
        assert differs == set(config["reduced"])
        assert config["published"] == {k: row["config"][k] for k in config["reduced"]}


def test_the_configuration_holds_every_published_width():
    from benchmarks.reference import cohere2_moe_arch

    _, _, config = _real()
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["intermediate_size"], config["num_experts_per_tok"],
            config["num_shared_experts"], config["sliding_window"],
            config["rope_theta"], config["layer_norm_eps"]) == (
        4096, 128, 8, 128, 4096, 8, 4, 4096, 50000, 1e-5)
    kwargs = cohere2_moe_arch.llm_arguments(config)["model_kwargs"]
    assert (kwargs["vocab_size"], kwargs["n_layers"], kwargs["n_experts"],
            kwargs["experts_held"]) == (32768, 4, 128, (0, 16))
    assert cohere2_moe_arch.sizes_of(config)["guaranteed"] == {
        "window_bytes_per_row": 50331648, "kv_bytes_per_token": 4096}
    with pytest.raises(SystemExit, match="logit_scale"):
        cohere2_moe_arch.sizes_of(dict(config, logit_scale=0.25))
    # counted by hand from the widths (ISSUE 59's arithmetic)
    assert flops_c2moe.full_layers(config) == 1 and flops_c2moe.window_layers(config) == 3
    assert flops_c2moe.attention_params(config) == 2 * 4096 * (16384 + 1024)
    assert flops_c2moe.shared_params(config) == 4 * 3 * 4096 * 4096
    assert round(flops_c2moe.dense_params(config) * 2 / 1e9, 3) == 0.688
    assert flops_c2moe.expert_params(config) * 2 == 100663296
    lengths = [2500, 9000]
    assert flops_c2moe.live_positions(config, lengths) == 11500 + 3 * (2500 + 4096)
    per_byte = (flops_c2moe.attention_step_flops(config, lengths)
                / flops_c2moe.attention_step_min_bytes(config, lengths))
    assert per_byte == 16
    # a step of 24 rows at ~6000 positions that touches 12.6 experts a layer
    whole = flops_c2moe.decode_step_min_bytes(config, 12.6, [6000] * 24)
    assert 9.0e9 < whole < 10.5e9
    assert flops_c2moe.experts_kernel_min_bytes(config, 12.6, 24.0) == (
        4 * (12.6 * 50331648 + 24 * 2 * 4096) * 2)


def _result(scopes=True, counters=True):
    """A traced run's result as the readers see it, built by hand: 100
    decode steps of 15 ms, two live rows of 2092 and 8236 positions half
    way through the traced 0.87 s (44 tokens each by then)."""
    _, _, config = _real()
    records = [
        {"stamps": [1.0 + 0.01 * i for i in range(60)], "done": None, "prompt_len": 2048},
        {"stamps": [1.0 + 0.01 * i for i in range(808)], "done": None, "prompt_len": 8192}]
    result = {
        "config": config, "device": {"kind": "TPU v5 lite"}, "records": records,
        "traced": {"start": 1.0, "stop": 1.87}, "window_s": 50.0,
        "trace": {"modules": {"jit__decode_impl": {"count": 100, "median_s": 0.015}}},
        "program_counters": {"before": {}, "after": {}},
    }
    if scopes:
        result["scopes"] = {
            "executions": 100, "module_s": 1.5,
            "scope_s": {"moe.route": 0.02, "moe.experts": 0.7, "moe.shared": 0.2},
            "attention_scope_s": {"c2moe.attn_window": 0.3, "c2moe.attn_full": 0.12,
                                  "c2moe.norm": 0.01},
            "kernel_s": {"decode_attention": 0.15, "kv_row_write": 0.01,
                         "moe_experts": 0.65}}
    if counters:
        moe = lambda steps, touched, each: {  # noqa: E731
            "decode_steps": steps, "touched": [touched] * 4,
            "assignments": [[each] * 16] * 4}
        result["program_counters"] = {
            "before": {"moe": moe(0, 0, 0)},
            "after": {"moe": moe(100, 1200, 150),
                      "kv": {"cache_bytes_per_token": 4096,
                             "window_bytes_per_row": 50331648}}}
    return result


def _read(name, result):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read(result)


def test_the_new_readers_on_a_hand_built_result():
    result = _result()
    _, _, config = _real()
    peak = cli.peaks()["TPU v5 lite"]
    assert _read("c2moe_attention_busy_share", result) == pytest.approx(10.0)
    # (0.42 - 0.15 - 0.01 of q/k/v/o + 0.2 of shared experts) of 1.5 s
    assert _read("c2moe_dense_busy_share", result) == pytest.approx(100 * 0.46 / 1.5)
    lengths = [2048 + 44, 8192 + 44]
    positions = sum(lengths) + 3 * (2092 + 4096)
    bytes_s = positions * 4096 / peak["hbm_bytes_per_s"]
    assert _read("c2moe_attention_roofline", result) == pytest.approx(
        100 * bytes_s / 0.0015)
    experts = flops_c2moe.experts_kernel_min_bytes(config, 12.0, 24.0)
    assert _read("c2moe_experts_roofline", result) == pytest.approx(
        100 * experts / peak["hbm_bytes_per_s"] / 0.0065)
    whole = flops_c2moe.decode_step_min_bytes(config, 12.0, lengths)
    assert _read("c2moe_decode_roofline", result) == pytest.approx(
        100 * whole / peak["hbm_bytes_per_s"] / 0.015)
    for name, *_ in NEW_METRICS:
        assert 0 < _read(name, result) <= 100, name
    # ... beside the two shared counters the cell joined
    assert _read("window_bytes_per_row", result) == 50331648
    assert _read("kv_bytes_per_token", result) == 4096


@pytest.mark.parametrize("name", [m[0] for m in NEW_METRICS])
def test_a_reader_finds_nothing_where_the_program_has_no_such_span(name):
    """The parent's traced run, and every other family's: no ``c2moe.*``
    scope."""
    assert _read(name, _result(scopes=False, counters=False)) is None
    other = _result()
    other["scopes"]["attention_scope_s"] = {"gdla.absorb": 0.03, "mhc.mix": 0.02}
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
    configuration states), ``expert_dropped`` (the assignments to one held
    expert of every layer add nothing: its down matrix zeroed in the
    programs' weights, the reference's kept), ``ring_past_live`` (a decode step reads a whole
    ring whatever the row's age), ``ring_e4m3`` (the rings a prefill leaves
    through an 8-bit float, a scale a position and head, before the row is
    inserted). ``faults``: the reference's own (``hidden_states``).
    Initialises a JAX backend and may leave the program's modules patched:
    for a process that ends with it."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.drivers import serve_arch_common as common
    from benchmarks.drivers import serve_closed_loop_arch_window_routed as kind
    from benchmarks.reference import cohere2_moe_arch
    from ray_tpu import models
    from ray_tpu.kvcache import KVCacheManager
    from ray_tpu.llm.engine import ContinuousBatchingEngine, GenerationRequest
    from ray_tpu.models import llama
    from ray_tpu.parallel.sharding import unbox_params

    if control == "ring_past_live":
        honest_kernel = llama.decode_attention
        ring = config["sliding_window"]

        def whole_ring(q, k, v, lengths, mesh=None):
            if k.shape[2] == ring:
                lengths = jnp.full_like(lengths, ring)
            return honest_kernel(q, k, v, lengths, mesh)

        llama.decode_attention = whole_ring
    built = dict(config, sliding_window=config["sliding_window"] // 2) \
        if control == "ring_halved" else config
    serving = config["serving"]
    model_config = common.llm_config(built, seed).build_model_config()
    params = unbox_params(models.init_params(model_config, jax.random.PRNGKey(seed)))
    engine = ContinuousBatchingEngine(
        model_config, params, num_slots=serving["max_batch_size"], seed=seed,
        kv_cache=KVCacheManager(serving["kv_cache_blocks"], serving["kv_block_size"]))
    if control == "expert_dropped":
        faulty = dict(params)
        for i in range(model_config.n_layers):
            layer = params[f"layer_{i}"]
            faulty[f"layer_{i}"] = dict(layer, moe=dict(
                layer["moe"], w_down=layer["moe"]["w_down"].at[1].set(0)))
        for name in ("_prefill", "_decode"):
            honest = getattr(engine, name)
            setattr(engine, name, lambda _, *a, _honest=honest, **k: _honest(faulty, *a, **k))
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
    sizes = cohere2_moe_arch.sizes_of(config)
    if faults:
        sizes["faults"] = tuple(faults)
    facts = kind.WindowRoutedReplica.bench_reference(
        types.SimpleNamespace(_engine=engine), config["architecture"],
        sizes, prompt, answer)
    engine.close()
    return facts


def _toy_config() -> dict:
    return manifest.load_json(os.path.join(HERE, "data", "configs", "tiny-c2moe.json"))


def _facts_in_a_process_of_its_own(control: str, prompt_len: int, ring: int) -> dict:
    code = (
        "import json, sys; sys.path[:0] = [%r, %r]; "
        "import test_arch_driver_c2moe as t; "
        "print('FACTS ' + json.dumps(t.check_facts("
        "dict(t._toy_config(), sliding_window=%d), 2**31 + 5, %d, 16, %r)))"
        % (manifest.ROOT, HERE, ring, prompt_len, control))
    ran = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    lines = [l for l in ran.stdout.splitlines() if l.startswith("FACTS ")]
    assert lines, ran.stderr[-2000:]
    return json.loads(lines[-1][len("FACTS "):])


@pytest.mark.parametrize("control, prompt_len, ring, fails_by", [
    ("", 48, 32, None), ("", 44, 64, None),
    ("ring_halved", 48, 32, "window_bytes_per_row"),
    ("expert_dropped", 48, 32, "rms_logit_diff"),
    ("ring_past_live", 44, 64, "rms_logit_diff")])
def test_a_planted_fault_is_not_correct(control, prompt_len, ring, fails_by):
    """Each control through the kind's own ``within`` at the toy's
    tolerance: a shorter ring by the bytes a row holds, a dropped expert
    and a ring read past its live slots (in a row younger than the ring: 44
    to 60 positions in 64 slots, the least the check's two-row replay has
    room in) by the logits."""
    from benchmarks.drivers import serve_closed_loop_arch_stateful_routed as kind

    tolerance = manifest.load_json(os.path.join(
        HERE, "data", "traffic", CELL + ".json"))["tolerance"]
    facts = _facts_in_a_process_of_its_own(control, prompt_len, ring)
    assert kind.within(facts, tolerance) is (not control), facts
    if fails_by == "rms_logit_diff":
        assert facts[fails_by] > tolerance["rms_logit"], facts
    elif control:
        assert fails_by in facts["error"], facts
