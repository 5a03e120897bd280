"""The command: one cell, once, in a fresh process.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Starts a cluster, loads, checks correctness, warms the cell's shapes,
measures for ``--seconds``, shuts the cluster down and prints the result
line last. This process never initialises a JAX backend: the chip belongs to
the worker the raylet leases it to, and that worker reports the device.
Everything else worth keeping goes to earlier stdout lines (one JSON object
each) or to ``benchmarks/out/<workload>/``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import time

from . import manifest


def emit(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


def _pid_gone(pid: int) -> bool:
    """Exited, reaped or not: a zombie has released its devices."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] == "Z"
    except (FileNotFoundError, ProcessLookupError):
        return True


def wait_gone(pids, what: str, timeout_s: float = 60.0) -> None:
    deadline = time.time() + timeout_s
    while not all(_pid_gone(p) for p in pids):
        if time.time() > deadline:
            raise RuntimeError(f"{what}: chip worker pid(s) {pids} still alive")
        time.sleep(0.1)


def peaks() -> dict:
    return manifest.load_json(os.path.join(manifest.BENCH_DIR, "harness", "peaks.json"))


def require_chips(chips: int) -> None:
    """Refuse before anything is started: decided from the device files."""
    from ray_tpu._internal.accelerators import count_chip_devices

    platforms = os.environ.get("JAX_PLATFORMS", "")
    found = count_chip_devices()
    if found < chips or (platforms and "tpu" not in platforms.split(",")):
        raise SystemExit(
            f"benchmark: needs {chips} TPU chip(s); device files show {found}, "
            f"JAX_PLATFORMS={platforms!r}. Nothing was started, no result."
        )


def require_device(device: dict, chips: int) -> None:
    """Refuse a device the peaks table does not know, or too few chips."""
    if device["platform"] != "tpu" or device["kind"] not in peaks():
        raise SystemExit(f"benchmark: no peaks for device {device}; no result.")
    if device["count"] != chips:
        raise SystemExit(f"benchmark: cell needs {chips} chips, worker holds {device}")


def no_compilation(before: dict, after: dict) -> bool:
    """The worker's compile counters (``compile_cache.stats()``) did not
    move: nothing compiled, and nothing was read from the cache, between."""
    return all(after[k] == before[k] for k in ("programs", "cache_requests"))


class Run:
    """One run's arguments, clock and output directory."""

    def __init__(self, cell: dict, args, started_wall: float):
        self.cell, self.args = cell, args
        self.started_wall = started_wall
        self.setup_s = None
        self.out_dir = os.path.join(manifest.BENCH_DIR, "out", cell["name"])
        os.makedirs(self.out_dir, exist_ok=True)

    def check_device(self, device: dict) -> None:
        require_device(device, self.cell["chips"])

    def setup_done(self, opened_wall: float) -> None:
        """``opened_wall``: time.time() at which the measured window opens."""
        self.setup_s = opened_wall - self.started_wall


def _layer_metrics(cell_name: str, result: dict, reported: set) -> dict:
    """Every reader under ``layer_metrics/`` that BENCHMARK.json names for
    this cell and whose ``moves`` metric the cell reports. A reader that
    finds nothing to read returns None and is left out of the line."""
    out = {}
    for entry in manifest.metrics_of(cell_name, "per_layer"):
        if entry["moves"] not in reported:
            continue
        module = importlib.import_module(
            f"benchmarks.layer_metrics.{entry['name']}")
        value = module.read(result)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def main(argv=None, started_wall: float = None) -> int:
    started_wall = started_wall or time.time()
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if args.seconds is None:
        args.seconds = float(manifest.benchmark()["run_seconds"])

    cell = manifest.cell(args.workload)
    if not os.path.isdir(os.path.join(manifest.ROOT, "ray_tpu")):
        raise SystemExit("benchmark: no system under test beside benchmarks/ (ray_tpu/)")
    require_chips(cell["chips"])

    # the program honours JAX_COMPILATION_CACHE_DIR and workers inherit it:
    # a fixed path inside the checkout, unless the machine already names one
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(manifest.ROOT, ".jax_cache"))
    for key, value in cell["traffic_file"].get("environment", {}).items():
        os.environ.setdefault(key, str(value))

    run = Run(cell, args, started_wall)
    emit(start="benchmark", workload=cell["name"], seed=args.seed,
         seconds=args.seconds, trace=args.trace, pid=os.getpid(),
         compile_cache_dir=os.environ["JAX_COMPILATION_CACHE_DIR"])
    driver = importlib.import_module(
        f"benchmarks.drivers.{cell['traffic_file']['kind']}")
    result = driver.run(run)

    from ray_tpu._internal.platform import backend_initialized

    if backend_initialized():
        raise SystemExit("benchmark: the harness process initialised a JAX backend")
    end_to_end = dict(result["end_to_end"], setup_s=run.setup_s)
    wanted = manifest.metrics_of(cell["name"], "end_to_end")
    missing = [m["name"] for m in wanted if end_to_end.get(m["name"]) is None]
    if missing:
        raise SystemExit(f"benchmark: {cell['name']} could not measure {missing}")
    e2e_line = {
        m["name"]: {"value": float(end_to_end[m["name"]]), "unit": m["unit"]}
        for m in wanted
    }
    device = {k: result["device"][k]
              for k in ("platform", "kind", "count", "memory_peak_bytes")}
    line = {
        "correct": bool(result["correct"]), "attempted": int(result["attempted"]),
        "failed": int(result["failed"]), "metrics": e2e_line, "device": device,
    }
    if args.trace:
        trace = result.get("trace")
        if not trace or trace["busy_s"] <= 0:
            raise SystemExit("benchmark: traced run saw no operation on the device")
        emit(end_to_end_in_traced_run=e2e_line)
        line["metrics"] = _layer_metrics(cell["name"], result, set(e2e_line))
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = result["traced"]["stop"] - result["traced"]["start"]
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s in trace["ops"]],
            "idle_gaps": [[n, s] for n, s, _ in trace["gaps"]],
        }
        emit(trace_modules=trace["modules"], gaps=trace["gaps"])
    with open(os.path.join(run.out_dir, "result.json"), "w") as f:
        json.dump(line, f)
    print(json.dumps(line), flush=True)
    return 0
