"""What the Solar-Open2 cell added to the benchmark, rehearsed on the CPU:
the ``serve_closed_loop_arch_stateful_routed`` kind end to end on a toy of
the same shape (a KDA mixer three layers in four, a gated NoPE GQA layer
the fourth, 4 of 16 routed experts held beside a shared one), how the cell
entered ``BENCHMARK.json``, ``harness/flops_kda.py`` against shapes counted
by hand, each new reader on a hand-built result, and the controls: a
program that keeps less than the configuration guarantees (a bf16 state,
fp8 K/V, fp8 weights, another share of the experts) comes out not correct.
Named to sort beside ``test_arch_driver.py``, for its reason: ``cli.main``
refuses a harness process that has initialised a JAX backend. This file
sorts before ``test_arch_driver_mla.py`` and ``test_arch_driver_ssm.py``,
whose rehearsals call ``cli.main`` too, so nothing here initialises one:
the controls, which build an engine in-process, run in a process of their
own.

The toy is entered into ``benchmarks/conftest.py``'s tables from here, at
import, as ``test_arch_driver_mla.py`` and ``test_arch_driver_ssm.py`` enter
theirs and for their reason.
"""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import conftest as top
from benchmarks.harness import cli, flops_kda, hostplane, manifest

HERE = os.path.dirname(os.path.abspath(__file__))
REAL, CELL = "solar2-longgen-backlog", "tiny-backlog-kda"
top.TOYS[REAL] = CELL
top.TOY_CONFIGS["tiny-kda"] = "benchmarks/tests/data/configs/tiny-kda.json"
if not any(c["name"] == CELL for c in top.TOY_CELLS):
    top.TOY_CELLS.append({"name": CELL, "config": "tiny-kda", "traffic": CELL,
                          "chips": 1, "why": "test"})
NEW_METRICS = [
    ("kda_mixer_busy_share", "%", "device_trace", "jitted program"),
    ("kda_state_roofline", "%", "device_trace", "kernel"),
    ("kda_experts_roofline", "%", "device_trace", "kernel"),
    ("kda_decode_roofline", "%", "device_trace", "kernel"),
]
# three toy KDA layers: state 4 x 16 x 16 float32, tail 3 x 192 bf16; one GQA
# layer: K and V 2 x 2 x 16 bf16
TOY_STATE_BYTES = 3 * (4 * 16 * 16 * 4 + 3 * 192 * 2)
TOY_KV_BYTES = 2 * 2 * 16 * 2


def _real_config():
    real = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    entry = next(c for c in real["configs"] if c["name"] == "solar-open2-250b-serve-1chip")
    return manifest.load_json(os.path.join(manifest.ROOT, entry["file"]))


def test_the_routed_stateful_driver_end_to_end_on_the_cpu(tiny_moe_benchmark, capsys):
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
    assert check["architecture"] == "solar_open2_arch" and check["ok"]
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
    assert len(moe["assignments"]) == 4 and len(moe["assignments"][0]) == 4
    # every live assignment fell on a held expert or on an absent one
    assert all(sum(row) + gone > 0 for row, gone
               in zip(moe["assignments"], moe["assignments_absent"]))


def test_the_clients_share_one_sequence_held_to_the_mix():
    """Whatever the clients' turns, every 20 consecutive requests sent hold the
    mix's proportions: a window's admissions are whole groups of the generator."""
    import threading
    import time
    from collections import Counter

    from benchmarks.drivers import serve_closed_loop_arch_stateful_routed as routed
    from benchmarks.harness import traffic

    mix = {"prompt_lens": {"256": 0.5, "512": 0.3, "1024": 0.2},
           "output_tokens": [256, 1024], "_clients": 8}

    class Clients:
        cut = threading.Event()
        sent = []

        def one(self, request, due):
            self.sent.append(request)
            time.sleep(0.0005 * (len(self.sent) % 3))

    clients, begun = Clients(), time.monotonic()
    threads = routed._load(clients, mix, 24576, 2**31 + 5, 0.0, 0.25,
                           lambda: time.monotonic() - begun)
    for t in threads:
        t.join(timeout=10)
    sent = clients.sent
    assert len(sent) >= 60
    one = traffic.requests(mix, 24576, 2**31 + 5)
    wanted = [next(one) for _ in sent]
    key = lambda r: (tuple(r["token_ids"][:4]), r["max_new_tokens"])
    assert sorted(map(key, sent)) == sorted(map(key, wanted))
    for at in range(0, len(wanted) - 19, 20):
        group = wanted[at:at + 20]
        assert Counter(len(r["token_ids"]) for r in group) == {256: 10, 512: 6, 1024: 4}
        assert sum(r["max_new_tokens"] for r in group) == 20 * 640


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
                if m.startswith(("mla_", "ssm_")) or m.endswith("decode_roofline")
                and m != "kda_decode_roofline" or m == "moe_experts_roofline"}
    # no pool
    assert not {"kv_copy_busy_share", "kv_pool_used_peak"} & per_layer
    assert {"decode_step_device_ms", "kv_bytes_per_token", "state_bytes_per_row",
            "moe_experts_busy_share", "moe_experts_touched_mean",
            "moe_expert_load_max_over_mean", "sched_decode_batch_mean",
            "engine_decode_ahead_share", "prefill_device_ms_per_ktok"} <= per_layer
    real = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    assert real["workloads"][-1]["name"] == REAL and real["workloads"][-1]["chips"] == 1
    assert sum(w["chips"] == 4 for w in real["workloads"]) == 1
    config = _real_config()
    mix = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "traffic", real["workloads"][-1]["traffic"] + ".json"))
    assert mix["kind"] == "serve_closed_loop_arch_stateful_routed"
    assert (mix["prompt_lens"], mix["output_tokens"], mix["clients_per_slot"],
            mix["ramp_s"]) == ({"256": 0.5, "512": 0.3, "1024": 0.2}, [256, 1024], 1, 16)
    assert set(mix["tolerance"]) == {
        "prefill_logit", "rms_logit", "token_gap", "routing_agree_share",
        "routing_slack", "unfollowed_logit"}
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 48, "n_routed_experts": 320, "vocab_size": 196608}
    assert (config["serving"]["max_batch_size"], config["serving"]["max_seq_len"]) == (
        32, 2048)
    # every number of the catalog's row, under its own key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(l) for l in f if '"Solar-Open2-250B"' in l)
        assert config["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
        assert differs == set(config["reduced"])
    from benchmarks.reference import solar_open2_arch

    arguments = solar_open2_arch.llm_arguments(config)
    assert arguments["model_family"] == "solar_open2"
    kwargs = arguments["model_kwargs"]
    assert (kwargs["vocab_size"], kwargs["dim"], kwargs["moe_intermediate"]) == (
        24576, 4096, 1280)
    assert (kwargs["n_experts"], kwargs["experts_held"], kwargs["experts_per_token"]) == (
        320, (0, 40), 8)
    assert (kwargs["kda_heads"], kwargs["kda_head_dim"], kwargs["n_heads"],
            kwargs["n_kv_heads"]) == (64, 128, 64, 8)
    assert solar_open2_arch.sizes_of(config)["guaranteed"] == {
        "state_bytes_per_row": 26050560, "kv_bytes_per_token": 8192}
    with pytest.raises(SystemExit, match="kda_use_full_proj"):
        solar_open2_arch.sizes_of(dict(config, kda_use_full_proj=True))


def test_flops_kda_against_shapes_counted_by_hand():
    config = _real_config()
    assert (flops_kda.kda_layers(config), flops_kda.gqa_layers(config)) == (6, 2)
    assert flops_kda.state_elements(config) == 64 * 128 * 128
    assert flops_kda.tail_elements(config) == 3 * 24576
    assert flops_kda.state_bytes_per_row(config) == 6 * (4194304 + 147456) == 26050560
    assert flops_kda.kv_bytes_per_token(config) == 2 * 2 * 8 * 128 * 2 == 8192
    assert flops_kda.kda_params(config) == (
        4096 * 24576 + 8192 * 4096 + 2 * (4096 * 128 + 128 * 8192) + 8192
        + 4096 * 64 + 4 * 24576 + 64 + 8192 + 128) == 137740480
    assert flops_kda.gqa_params(config) == (
        3 * 4096 * 8192 + 2 * 4096 * 1024) == 109051904
    assert flops_kda.expert_params(config) == 3 * 4096 * 1280 == 15728640
    assert flops_kda.router_params(config) == 4097 * 320
    assert flops_kda.state_step_bytes(config, 32) == 2 * 32 * 26050560
    assert flops_kda.state_step_flops(config, 32) == 9 * 32 * 6 * 1048576
    experts = 8 * (22.5 * 15728640 + 15728640 + 4097 * 320) * 2
    assert flops_kda.experts_step_min_bytes(config, 22.5) == experts
    weights = (6 * 137740480 + 2 * 109051904 + 2 * 8 * 4096 + 4096 * 24576) * 2
    assert flops_kda.decode_step_min_bytes(config, 31.5, 40000, 22.5) == (
        weights + 2 * 31.5 * 26050560 + 40000 * 8192 + experts)


def _hand_built():
    config = manifest.load_json(os.path.join(HERE, "data", "configs", "tiny-kda.json"))
    moe = {"decode_steps": 0, "touched": [0] * 4, "assignments": [[0] * 4] * 4,
           "experts_routed": 16, "experts_held": 4, "assignments_absent": [0] * 4}
    return {
        "config": config, "device": {"kind": "TPU v5 lite"},
        "program_counters": {
            "before": {"kv": {"cache_bytes_per_token": None, "state_bytes_per_row": None},
                       "moe": moe},
            "after": {"kv": {"cache_bytes_per_token": TOY_KV_BYTES,
                             "state_bytes_per_row": TOY_STATE_BYTES},
                      "moe": dict(moe, decode_steps=50, touched=[100, 150, 100, 150],
                                  assignments=[[10, 20, 30, 40]] * 4,
                                  assignments_absent=[300] * 4)}},
        "scopes": {"module": "_decode_impl", "executions": 50, "module_s": 0.5,
                   "scope_s": {"moe.route": 0.02, "moe.experts": 0.1, "moe.shared": 0.03},
                   "attention_scope_s": {"kda.proj": 0.05, "kda.conv": 0.01,
                                         "kda.state": 0.14, "attn.gate": 0.0},
                   "kernel_s": {"decode_attention": 0.02, "kv_row_write": 0.01,
                                "moe_experts": 0.09, "kda_step": 0.13}},
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
    assert flops_kda.state_bytes_per_row(config) == TOY_STATE_BYTES
    assert flops_kda.kv_bytes_per_token(config) == TOY_KV_BYTES
    assert line["moe_experts_touched_mean"]["value"] == pytest.approx(touched)
    # the mixers' scopes are no part of the expert layers' share
    assert line["moe_experts_busy_share"]["value"] == pytest.approx(100 * 0.15 / 0.5)
    assert line["kda_mixer_busy_share"]["value"] == pytest.approx(100 * 0.20 / 0.5)

    def state_bytes(stepped):  # in and out once: state f32 and tail bf16
        return 2 * stepped * TOY_STATE_BYTES

    assert line["kda_state_roofline"]["value"] == pytest.approx(
        100 * (state_bytes(rows) / 819e9) / (0.14 / 50))
    printed = {k: v for l in capsys.readouterr().out.splitlines()
               for k, v in json.loads(l).items()}
    assert (printed["state_rows"], printed["live_rows"]) == (carried, rows)
    assert printed["kda_state_roofline_carried_rows_pct"] == pytest.approx(
        100 * (state_bytes(carried) / 819e9) / (0.14 / 50))
    assert printed["kda_state_flop_share_pct"] == pytest.approx(
        100 * 9 * carried * 3 * 1024 / 197e12 / (0.14 / 50))
    expert = 3 * 64 * 32
    experts = 4 * (touched * expert + expert + 65 * 16) * 2
    assert line["kda_experts_roofline"]["value"] == pytest.approx(
        100 * (experts / 819e9) / (0.15 / 50))
    kda = (64 * 192 + 64 * 64 + 2 * (64 * 16 + 16 * 64) + 64 + 64 * 4 + 4 * 192
           + 4 + 64 + 16)
    gqa = 3 * 64 * 64 + 2 * 64 * 32

    def step_bytes(stepped):
        return ((3 * kda + gqa + 2 * 4 * 64 + 64 * 256) * 2 + state_bytes(stepped)
                + tokens * TOY_KV_BYTES + experts)

    assert line["kda_decode_roofline"]["value"] == pytest.approx(
        100 * (step_bytes(rows) / 819e9) / 0.01)
    assert printed["kda_decode_roofline_carried_rows_pct"] == pytest.approx(
        100 * (step_bytes(carried) / 819e9) / 0.01)


def test_new_readers_return_nothing_for_a_program_without_the_names(
        tiny_moe_benchmark, monkeypatch):
    """The parent cannot build the family at all; were it to run, it has no
    ``kda.*`` scope, no held-expert counter and no ``state_rows``."""
    new = {name for name, *_ in NEW_METRICS}
    result = _hand_built()
    monkeypatch.setattr(hostplane, "of", lambda _: _dispatches(state_rows=False))
    line = cli._layer_metrics(CELL, result, {"tpot_p50_ms"})
    assert new & set(line) == {"kda_mixer_busy_share", "kda_experts_roofline"}
    monkeypatch.setattr(hostplane, "of", lambda _: _dispatches())
    result["scopes"]["attention_scope_s"] = {}
    assert not new & set(cli._layer_metrics(CELL, result, {"tpot_p50_ms"}))
    del result["scopes"]["attention_scope_s"]
    assert not new & set(cli._layer_metrics(CELL, result, {"tpot_p50_ms"}))
    result["program_counters"] = {"before": {"kv": None, "moe": None},
                                  "after": {"kv": None, "moe": None}}
    assert not new & set(cli._layer_metrics(CELL, result, {"tpot_p50_ms"}))
    result["scopes"] = None
    assert not new & set(cli._layer_metrics(CELL, result, {"tpot_p50_ms"}))


# -- the controls -------------------------------------------------------------

def _fp8(weight):
    """A weight matrix through e4m3, one scale an output channel (an expert
    and output channel), kept in its own dtype: the nearest precision below
    the one the configuration states that the logits can see."""
    import jax.numpy as jnp

    if weight.ndim < 2:
        return weight
    w = weight.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
    return ((w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
            * scale).astype(weight.dtype)


def check_facts(config: dict, seed: int, prompt_len: int, decoded: int,
                control: str = "") -> dict:
    """``RoutedStatefulReplica.bench_reference``'s facts for one request, in
    this process: the engine built as the replica builds it, the request
    through ``engine.generate``. ``control`` makes the *program* keep less
    than the configuration guarantees: ``state_bf16`` (the delta rule's
    state stored in bf16: ``solar_open2.STATE_DTYPE``), ``kv_fp8`` (the live
    rows' K/V stored in fp8), ``weights_fp8`` (every weight matrix of the
    programs through e4m3; the reference keeps the weights as they are),
    ``held_other`` (the program holds experts 8..11 where the configuration
    says 4..7). Initialises a JAX backend and, for ``state_bf16``, leaves
    the program's module patched: for a process that ends with it."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.drivers import serve_arch_common as common
    from benchmarks.drivers import serve_closed_loop_arch_stateful_routed as kind
    from benchmarks.reference import solar_open2_arch
    from ray_tpu import models
    from ray_tpu.kvcache import KVCacheManager
    from ray_tpu.llm.engine import ContinuousBatchingEngine, GenerationRequest
    from ray_tpu.models import solar_open2
    from ray_tpu.parallel.sharding import unbox_params

    if control == "state_bf16":
        solar_open2.STATE_DTYPE = jnp.bfloat16
    serving = config["serving"]
    program = dict(config, experts_first=8) if control == "held_other" else config
    model_config = common.llm_config(program, seed).build_model_config()
    params = unbox_params(models.init_params(model_config, jax.random.PRNGKey(seed)))
    engine = ContinuousBatchingEngine(
        model_config, params, num_slots=serving["max_batch_size"], seed=seed,
        kv_cache=KVCacheManager(serving["kv_cache_blocks"], serving["kv_block_size"]))
    if control == "weights_fp8":
        rounded = jax.tree.map(_fp8, params)
        for name in ("_prefill", "_decode"):
            honest = getattr(engine, name)
            setattr(engine, name, lambda _, *a, _honest=honest, **k: _honest(rounded, *a, **k))
    prompt = [int(t) for t in np.random.default_rng(seed).integers(
        0, config["vocab_size"], prompt_len)]
    answer = engine.generate(
        [GenerationRequest(token_ids=prompt, max_new_tokens=decoded)])[0].token_ids
    if control == "kv_fp8":
        engine._cache = jax.tree.map(
            lambda leaf, k: leaf.astype(jnp.float8_e4m3fn)
            if k == models.SEQUENCE else leaf,
            engine._cache, models.cache_kinds(engine._cache))
    return kind.RoutedStatefulReplica.bench_reference(
        types.SimpleNamespace(_engine=engine), config["architecture"],
        solar_open2_arch.sizes_of(config), prompt, answer)


def _toy_config() -> dict:
    return manifest.load_json(os.path.join(HERE, "data", "configs", "tiny-kda.json"))


def _facts_in_a_process_of_its_own(control: str) -> dict:
    code = (
        "import json, sys; sys.path[:0] = [%r, %r]; "
        "import test_arch_driver_kda as t; "
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
    ("kv_fp8", "kv_bytes_per_token"), ("weights_fp8", "rms_logit_diff"),
    ("held_other", "rms_logit_diff")])
def test_a_program_that_keeps_less_than_guaranteed_is_not_correct(
        control, fails_by):
    """Each control through the kind's own ``within`` at the toy's
    tolerance: a narrower state or K/V by the bytes a row holds, narrower
    weights by the logits, and another share of the experts than the
    configuration's by the logits too (the counters say 4 of 16 either
    way: which four, only the reference's share can tell)."""
    from benchmarks.drivers import serve_closed_loop_arch_stateful_routed as kind

    tolerance = manifest.load_json(os.path.join(
        HERE, "data", "traffic", CELL + ".json"))["tolerance"]
    facts = _facts_in_a_process_of_its_own(control)
    assert kind.within(facts, tolerance) is (not control), facts
    if fails_by == "rms_logit_diff":
        assert facts[fails_by] > tolerance["rms_logit"], facts
        assert facts["replayed_tokens_equal"] == 16, facts
    elif control:
        assert fails_by in facts["error"], facts
