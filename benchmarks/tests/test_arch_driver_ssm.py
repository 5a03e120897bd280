"""What the Falcon-H1 cell added to the benchmark, rehearsed on the CPU: the
``serve_closed_loop_arch_stateful`` kind end to end on a toy of the same
shape (a Mamba-2 mixer beside attention in every block, every multiplier
live), how the cell entered ``BENCHMARK.json``, ``harness/flops_ssm.py``
against shapes counted by hand, each new reader on a hand-built result, and the
controls: a program that keeps less than the configuration guarantees
(a bf16 state, fp8 K/V, fp8 weights) comes out not correct. Named to sort beside ``test_arch_driver.py``, for its
reason: ``cli.main`` refuses a harness process that has initialised a JAX
backend, so nothing before the last tests here (the controls) does.

The toy is entered into ``benchmarks/conftest.py``'s tables from here, at
import, as ``test_arch_driver_mla.py`` enters its own and for its reason.
"""

import json
import os

import pytest

from benchmarks import conftest as top
from benchmarks.harness import cli, flops_ssm, hostplane, manifest

HERE = os.path.dirname(os.path.abspath(__file__))
REAL, CELL = "falconh1-chat-backlog", "tiny-backlog-ssm"
top.TOYS[REAL] = CELL
top.TOY_CONFIGS["tiny-ssm"] = "benchmarks/tests/data/configs/tiny-ssm.json"
if not any(c["name"] == CELL for c in top.TOY_CELLS):
    top.TOY_CELLS.append({"name": CELL, "config": "tiny-ssm", "traffic": CELL,
                          "chips": 1, "why": "test"})
NEW_METRICS = [
    ("ssm_mixer_busy_share", "%", "device_trace", "jitted program"),
    ("ssm_state_roofline", "%", "device_trace", "kernel"),
    ("ssm_decode_roofline", "%", "device_trace", "kernel"),
    ("state_bytes_per_row", "bytes", "program_counter", "KV manager"),
]
# two toy blocks: state 4 x 16 x 16 float32, tail 3 x 128 bf16; K and V 2 x 2 x 16 bf16
TOY_STATE_BYTES = 2 * (4 * 16 * 16 * 4 + 3 * 128 * 2)
TOY_KV_BYTES = 2 * 2 * 2 * 16 * 2


def _real_config():
    real = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    entry = next(c for c in real["configs"] if c["name"] == "falcon-h1-34b-serve-1chip")
    return manifest.load_json(os.path.join(manifest.ROOT, entry["file"]))


def test_the_stateful_driver_end_to_end_on_the_cpu(tiny_moe_benchmark, capsys):
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
    assert check["architecture"] == "falcon_h1_arch" and check["ok"]
    assert [r["decoded"] for r in check["rows"]] == [18, 16]
    # a fresh row stepped through prompt + answer, and the prefill's row
    # through the answer
    assert [r["positions"] for r in check["rows"]] == [16 + 17 + 17, 40 + 15 + 15]
    for row in check["rows"]:
        # bf16 weights and activations, float32 state: a few bf16 steps
        assert row["max_abs_logit_diff"] <= 0.125, row
        assert 0 < row["decode_rms_logit_diff"] <= 0.03, row
        assert 0 < row["stepped_rms_logit_diff"] <= 0.03, row
        # the replay is the engine's own programs on the request's input
        assert row["replayed_tokens_equal"] == row["decoded"], row
        assert row["token_gap_max"] <= 0.125, row
    summary = next(e for e in earlier if "program_counters_kept" in e)
    assert summary["program_counters_kept"] == ["kv"]
    # nothing of any request was matched, committed or pooled
    assert summary["kvcache"]["hits"] == 0 and summary["kvcache"]["blocks_in_use"] == 0
    assert summary["kvcache"]["requests"] > 2
    with open(os.path.join(manifest.BENCH_DIR, "out", CELL, "records.json")) as f:
        kept = json.load(f)["program_counters"]["after"]["kv"]
    assert kept["cache_bytes_per_token"] == TOY_KV_BYTES
    assert kept["state_bytes_per_row"] == TOY_STATE_BYTES
    assert kept["prefix_reuse"] is False and "no sequence axis" in kept[
        "prefix_reuse_refused"]


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
    assert not {m for m in per_layer
                if m.startswith(("moe_", "mla_")) or m == "decode_roofline"}
    # the family allocates no pool: nothing for the pool's readers to read
    assert not {"kv_copy_busy_share", "kv_pool_used_peak"} & per_layer
    assert {"decode_step_device_ms", "kv_bytes_per_token", "sched_decode_batch_mean", "client_itl_p99_ms",
            "engine_decode_batch_mean", "engine_decode_ahead_share",
            "prefill_device_ms_per_ktok"} <= per_layer
    real = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    assert real["workloads"][-1]["name"] == REAL and real["workloads"][-1]["chips"] == 1
    assert sum(w["chips"] == 4 for w in real["workloads"]) == 1
    config = _real_config()
    mix = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "traffic", real["workloads"][-1]["traffic"] + ".json"))
    assert mix["kind"] == "serve_closed_loop_arch_stateful"
    assert (mix["prompt_lens"], mix["output_tokens"], mix["clients_per_slot"]) == (
        {"128": 0.5, "256": 0.3, "512": 0.2}, [64, 320], 1)
    assert set(mix["tolerance"]) == {"prefill_logit", "rms_logit", "token_gap"}
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 72}
    assert config["serving"]["max_batch_size"] == 64
    # every number of the catalog's row, under its own key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(l) for l in f if '"Falcon-H1-34B-Instruct"' in l)
        assert config["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
        assert differs == {"num_hidden_layers"}
    from benchmarks.reference import falcon_h1_arch

    arguments = falcon_h1_arch.llm_arguments(config)
    assert arguments["model_family"] == "falcon_h1"
    kwargs = arguments["model_kwargs"]
    assert (kwargs["vocab_size"], kwargs["dim"], kwargs["intermediate"]) == (261120, 5120, 21504)
    assert (kwargs["mamba_n_heads"], kwargs["mamba_d_head"], kwargs["mamba_d_state"],
            kwargs["mamba_n_groups"]) == (32, 128, 256, 2)
    with pytest.raises(SystemExit, match="mamba_norm_before_gate"):
        falcon_h1_arch.sizes_of(dict(config, mamba_norm_before_gate=True))


def test_flops_ssm_against_shapes_counted_by_hand():
    config = _real_config()
    assert flops_ssm.state_elements(config) == 32 * 128 * 256
    assert flops_ssm.conv_channels(config) == 4096 + 2 * 2 * 256 == 5120
    assert flops_ssm.in_proj_columns(config) == 4096 + 5120 + 32 == 9248
    assert flops_ssm.attention_params(config) == (
        5120 * 2560 + 2 * 5120 * 512 + 2560 * 5120) == 31457280
    assert flops_ssm.mixer_params(config) == (
        5120 * 9248 + 4096 * 5120 + 5 * 5120 + 4096 + 3 * 32) == 68351072
    assert flops_ssm.mlp_params(config) == 3 * 5120 * 21504 == 330301440
    assert flops_ssm.block_params(config) == 31457280 + 68351072 + 330301440 + 2 * 5120
    layers = config["num_hidden_layers"]
    assert flops_ssm.state_bytes_per_row(config) == layers * (4194304 + 3 * 5120 * 2)
    assert flops_ssm.kv_bytes_per_token(config) == layers * 2 * 4 * 128 * 2 == layers * 2048
    assert flops_ssm.state_step_bytes(config, 64) == 2 * 64 * layers * 4194304
    assert flops_ssm.state_step_flops(config, 64) == 6 * 64 * layers * 1048576
    weights = (layers * flops_ssm.block_params(config) + 5120 * 261120) * 2
    assert flops_ssm.decode_step_min_bytes(config, 64, 28800) == (
        weights + 2 * 64 * layers * (4194304 + 3 * 5120 * 2) + 28800 * layers * 2048)
    # one chunk of 128: the chunked form's four products beside the projections
    one = 2 * 128 * (31457280 + 5120 * 9248 + 4096 * 5120 + 330301440)
    attention = 2 * 2 * 20 * 128 * 128 * 129 // 2
    scan = 2 * 128 * 128 * (2 * 256 + 4096) + 2 * 2 * 128 * 4096 * 256
    assert flops_ssm.prefill_flops(config, 128) == (
        layers * (one + attention + scan) + 2 * 5120 * 261120)


def _hand_built():
    config = manifest.load_json(os.path.join(HERE, "data", "configs", "tiny-ssm.json"))
    return {
        "config": config, "device": {"kind": "TPU v5 lite"},
        "program_counters": {
            "before": {"kv": {"cache_bytes_per_token": None, "state_bytes_per_row": None}},
            "after": {"kv": {"cache_bytes_per_token": TOY_KV_BYTES,
                             "state_bytes_per_row": TOY_STATE_BYTES}}},
        "scopes": {"module": "_decode_impl", "executions": 50, "module_s": 0.5,
                   "scope_s": {"ssm.proj": 0.05, "ssm.conv": 0.01, "ssm.scan": 0.14},
                   "kernel_s": {"decode_attention": 0.02, "kv_row_write": 0.01}},
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
    config, carried, rows, tokens = result["config"], 4, 3.0, 7 + 2
    assert line["state_bytes_per_row"] == {"value": float(TOY_STATE_BYTES), "unit": "bytes"}
    assert line["kv_bytes_per_token"] == {"value": float(TOY_KV_BYTES), "unit": "bytes"}
    assert flops_ssm.state_bytes_per_row(config) == TOY_STATE_BYTES
    assert flops_ssm.kv_bytes_per_token(config) == TOY_KV_BYTES
    assert line["ssm_mixer_busy_share"]["value"] == pytest.approx(100 * 0.20 / 0.5)

    def state_bytes(stepped):
        return 2 * stepped * 2 * (4 * 16 * 16) * 4

    # the least bytes are the live rows': the mean batch, not the pool
    assert line["ssm_state_roofline"]["value"] == pytest.approx(
        100 * (state_bytes(rows) / 819e9) / (0.14 / 50))
    printed = {k: v for l in capsys.readouterr().out.splitlines()
               for k, v in json.loads(l).items()}
    assert (printed["state_rows"], printed["live_rows"]) == (carried, rows)
    assert printed["ssm_state_roofline_carried_rows_pct"] == pytest.approx(
        100 * (state_bytes(carried) / 819e9) / (0.14 / 50))
    assert printed["ssm_state_flop_share_pct"] == pytest.approx(
        100 * 6 * carried * 2 * 1024 / 197e12 / (0.14 / 50))
    attention = 64 * 64 + 2 * 64 * 32 + 64 * 64
    mixer = 64 * (64 + 128 + 4) + 64 * 64 + 5 * 128 + 64 + 3 * 4
    block = attention + mixer + 3 * 64 * 96 + 2 * 64

    def step_bytes(stepped):
        return ((2 * block + 64 * 256) * 2 + state_bytes(stepped)
                + 2 * stepped * 2 * 3 * 128 * 2 + tokens * TOY_KV_BYTES)

    assert line["ssm_decode_roofline"]["value"] == pytest.approx(
        100 * (step_bytes(rows) / 819e9) / 0.01)
    assert printed["ssm_decode_roofline_carried_rows_pct"] == pytest.approx(
        100 * (step_bytes(carried) / 819e9) / 0.01)


def test_new_readers_return_nothing_for_a_program_without_the_names(
        tiny_moe_benchmark, monkeypatch):
    """The parent cannot build the family at all; were it to run, it has no
    state counter, no ``ssm.*`` scope and no ``state_rows`` on its spans."""
    result = _hand_built()
    monkeypatch.setattr(hostplane, "of", lambda _: _dispatches(state_rows=False))
    line = cli._layer_metrics(CELL, result, {"tpot_p50_ms"})
    assert {"ssm_mixer_busy_share", "state_bytes_per_row"} == {
        name for name, *_ in NEW_METRICS} & set(line)
    result["program_counters"] = {"before": {"kv": None}, "after": {"kv": None}}
    result["scopes"] = {"module": "_decode_impl", "executions": 50, "module_s": 0.5,
                        "scope_s": {}, "kernel_s": {"decode_attention": 0.02}}
    monkeypatch.setattr(hostplane, "of", lambda _: _dispatches())
    line = cli._layer_metrics(CELL, result, {"tpot_p50_ms"})
    assert not {name for name, *_ in NEW_METRICS} & set(line)
    result["scopes"] = None
    line = cli._layer_metrics(CELL, result, {"tpot_p50_ms"})
    assert not {name for name, *_ in NEW_METRICS} & set(line)


def test_rows_on_a_cut_trace():
    """``host_steps.xplane.pb`` (a recorded chip trace, cut) has decode
    dispatches with ``batch`` and, recorded before any family carried row
    state, no ``state_rows``: nothing to read."""
    from benchmarks.harness import ssm_counters

    loaded = hostplane.load(os.path.join(HERE, "data", "host_steps.xplane.pb"))
    assert hostplane.counts(loaded, ssm_counters.DISPATCH, "batch")
    assert not hostplane.counts(loaded, ssm_counters.DISPATCH, "state_rows")


# -- the controls: from here on this process holds a JAX backend --------------

def _fp8(weight):
    """A weight matrix through e4m3, one scale an output channel, kept in
    its own dtype: the nearest precision below the one the configuration
    states that the logits can see."""
    import jax.numpy as jnp

    if weight.ndim != 2:
        return weight
    w = weight.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 448.0
    return ((w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
            * scale).astype(weight.dtype)


def check_facts(config: dict, seed: int, prompt_len: int, decoded: int,
                control: str = "", monkeypatch=None) -> dict:
    """``StatefulReplica.bench_reference``'s facts for one request, in this
    process: the engine built as the replica builds it, the request through
    ``engine.generate``. ``control`` makes the *program* keep less than the
    configuration guarantees: ``state_bf16`` (the recurrent state stored in
    bf16: ``falcon_h1.STATE_DTYPE``), ``kv_fp8`` (the live rows' K/V stored
    in fp8), ``weights_fp8`` (every weight matrix of the programs through
    e4m3; the reference keeps the weights as they are). ``one_live_row``
    is a fault of the step's ``active`` mask that only rows coming and going
    beside a live one show: the first live row of the pool is kept, every
    other is restarted as a free row is."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.drivers import serve_arch_common as common
    from benchmarks.drivers import serve_closed_loop_arch_stateful as kind
    from benchmarks.reference import falcon_h1_arch
    from ray_tpu import models
    from ray_tpu.kvcache import KVCacheManager
    from ray_tpu.llm.engine import ContinuousBatchingEngine, GenerationRequest
    from ray_tpu.models import falcon_h1
    from ray_tpu.parallel.sharding import unbox_params

    if control == "state_bf16":
        monkeypatch.setattr(falcon_h1, "STATE_DTYPE", jnp.bfloat16)
    serving = config["serving"]
    model_config = common.llm_config(config, seed).build_model_config()
    params = unbox_params(models.init_params(model_config, jax.random.PRNGKey(seed)))
    engine = ContinuousBatchingEngine(
        model_config, params, num_slots=serving["max_batch_size"], seed=seed,
        kv_cache=KVCacheManager(serving["kv_cache_blocks"], serving["kv_block_size"]))
    if control == "weights_fp8":
        rounded = jax.tree.map(_fp8, params)
        for name in ("_prefill", "_decode"):
            honest = getattr(engine, name)
            setattr(engine, name, lambda _, *a, _honest=honest, **k: _honest(rounded, *a, **k))
    if control == "one_live_row":
        masked = engine._decode
        engine._decode = lambda *a, active, **k: masked(
            *a, active=np.asarray(active) & (np.cumsum(active) == 1), **k)
    prompt = [int(t) for t in np.random.default_rng(seed).integers(
        0, config["vocab_size"], prompt_len)]
    answer = engine.generate(
        [GenerationRequest(token_ids=prompt, max_new_tokens=decoded)])[0].token_ids
    if control == "kv_fp8":
        engine._cache = jax.tree.map(
            lambda leaf, k: leaf.astype(jnp.float8_e4m3fn)
            if k == models.SEQUENCE else leaf,
            engine._cache, models.cache_kinds(engine._cache))
    return kind.StatefulReplica.bench_reference(
        types.SimpleNamespace(_engine=engine), config["architecture"],
        falcon_h1_arch.sizes_of(config), prompt, answer)


@pytest.mark.parametrize("control, fails_by", [
    ("", None), ("state_bf16", "state_bytes_per_row"),
    ("kv_fp8", "kv_bytes_per_token"), ("weights_fp8", "rms_logit_diff"),
    ("one_live_row", "rms_logit_diff")])
def test_a_program_that_keeps_less_than_guaranteed_is_not_correct(
        control, fails_by, monkeypatch):
    """Each control through the kind's own ``within`` at the toy's
    tolerance: a narrower state or K/V by the bytes a row holds (no logit
    shows them: mix file, ``tolerance_why``), narrower weights by the
    logits, and a mask that restarts a live row by the logits of the two
    rows the replay keeps live (the request alone ran as it should)."""
    from benchmarks.drivers import serve_closed_loop_arch_stateful as kind

    config = manifest.load_json(os.path.join(HERE, "data", "configs", "tiny-ssm.json"))
    tolerance = manifest.load_json(os.path.join(
        HERE, "data", "traffic", CELL + ".json"))["tolerance"]
    facts = check_facts(config, 2**31 + 5, 40, 16, control, monkeypatch)
    assert kind.within(facts, tolerance) is (not control), facts
    if fails_by == "rms_logit_diff":
        assert facts[fails_by] > tolerance["rms_logit"], facts
        assert facts["replayed_tokens_equal"] == 16, facts
    elif control:
        assert fails_by in facts["error"], facts
