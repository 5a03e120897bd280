"""The ``serve_closed_loop_arch`` driver kind rehearsed on the CPU: end to
end on a toy OLMoE-shaped configuration, and its refusal of a program that
cannot build the family. Named to sort first: ``cli.main`` refuses a
harness process that has initialised a JAX backend, and later files do."""

import json
import os

import pytest

from benchmarks.conftest import TINY_MOE_CELL as CELL
from benchmarks.harness import cli, manifest


def test_the_arch_driver_end_to_end_on_the_cpu(tiny_moe_benchmark, capsys):
    code = cli.main(["--workload", CELL, "--seed", str(2**31 + 5),
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
    assert check["architecture"] == "olmoe_arch" and check["ok"]
    assert [r["decoded"] for r in check["rows"]] == [18, 16]
    # every position of prompt + answer is judged, the reference following
    # the experts the bf16 program chose
    assert [r["positions"] for r in check["rows"]] == [16 + 17, 24 + 15]
    for row in check["rows"]:
        assert 0.25 <= row["routing_agree_share"] <= 1.0, row
        assert (row["routing_slack_max"] > 0) == (row["routing_agree_share"] < 1), row
        assert row["routing_slack_max"] <= 0.1 and row["max_abs_logit_diff"] <= 0.125, row
    summary = next(e for e in earlier if "program_counters_kept" in e)
    assert summary["program_counters_kept"] == ["moe"]
    with open(os.path.join(manifest.BENCH_DIR, "out", CELL, "records.json")) as f:
        kept = json.load(f)["program_counters"]
    assert kept["after"]["moe"]["decode_steps"] > kept["before"]["moe"]["decode_steps"]
    assert len(kept["after"]["moe"]["assignments"]) == 2  # layers


def test_the_arch_driver_refuses_a_program_without_the_family(
        tiny_moe_benchmark, monkeypatch):
    """The parent of the PR that made ``model_family="moe"`` live has a
    ``MoEConfig`` without ``qk_norm``: the driver must say so before it
    starts a cluster."""
    import ray_tpu
    from benchmarks.drivers import serve_arch_common
    from ray_tpu.models import moe

    def old_config(**kwargs):
        kwargs.pop("qk_norm")  # TypeError in the parent; KeyError never
        raise TypeError("MoEConfig.__init__() got an unexpected keyword argument 'qk_norm'")

    monkeypatch.setattr(moe, "MoEConfig", old_config)
    monkeypatch.setattr(ray_tpu, "init", lambda *a, **k: pytest.fail("cluster started"))
    with pytest.raises(SystemExit) as refused:
        serve_arch_common.llm_config(manifest.cell(CELL)["config_file"], 1)
    assert "cannot build tiny-moe" in str(refused.value)


def test_a_traced_run_reads_the_scopes_and_prints_no_result_on_the_cpu(
        tiny_moe_benchmark, capsys):
    """The traced path up to the refusal (a CPU trace has no device plane):
    the replica lowers its decode program and finds instructions under both
    scopes in the compiled text."""
    with pytest.raises(SystemExit) as refused:
        cli.main(["--workload", CELL, "--seed", "4", "--seconds", "4", "--trace", "1"])
    assert refused.value.code not in (0, None)
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    assert '"metrics"' not in json.dumps(lines[-1])
    summary = next(e for e in lines if "scoped_instructions" in e)
    assert summary["scoped_instructions"] > 10 and summary["scopes"] is None
    check = next(e for e in lines if e.get("check") == "serve.no_compilation_in_window")
    assert check["ok"]
