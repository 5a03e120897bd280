"""The whole command at a tiny preset on the CPU, once for each driver kind
(the train job on four virtual CPU devices, fsdp=4), and the refusal to
print a result without a chip."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import cli

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run(capsys, workload, trace):
    code = cli.main(["--workload", workload, "--seed", "3", "--seconds", "4",
                     "--trace", str(trace)])
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    return code, json.loads(lines[-1]), [json.loads(l) for l in lines[:-1]]


@pytest.mark.parametrize("workload,metrics", [
    ("tiny-backlog", {"out_tok_per_s", "tpot_p50_ms", "setup_s"}),
    ("tiny-steady", {"tpot_p50_ms", "setup_s"}),
    ("tiny-lora", {"train_tok_per_s_per_chip", "setup_s"}),
])
def test_untraced_run_prints_the_result_line(tiny_benchmark, capsys, workload, metrics):
    code, line, earlier = _run(capsys, workload, 0)
    assert code == 0
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True, earlier
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == metrics
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    checks = [e for e in earlier if "check" in e]
    assert checks and all(c["ok"] for c in checks)


def test_traced_run_without_a_device_plane_prints_no_result(tiny_benchmark, capsys):
    """A CPU trace has no ``/device:TPU`` plane, so the traced run must end
    without a result: a number from a CPU run is never a device metric."""
    with pytest.raises(SystemExit) as refused:
        cli.main(["--workload", "tiny-backlog", "--seed", "3", "--seconds", "4",
                  "--trace", "1"])
    assert refused.value.code not in (0, None)
    out = capsys.readouterr().out
    assert '"metrics"' not in out.splitlines()[-1]


def test_no_chip_no_result():
    """The real cell, as the driver runs it, on a machine without a chip."""
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "mistral7b-chat-backlog",
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert done.returncode != 0
    assert '"metrics"' not in done.stdout
