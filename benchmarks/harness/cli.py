"""The command: one cell, once, in a fresh process.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Waits until no other process holds a chip, starts a cluster, loads, checks
correctness, warms the cell's shapes, measures for ``--seconds``, shuts the
cluster down, waits until every process the run started is out of ``/proc``
(``harness/boundaries.py``) and prints the result line last. This process
never initialises a JAX backend: the chip belongs to the worker the raylet
leases it to, and that worker reports the device. Everything else worth
keeping goes to earlier stdout lines (one JSON object each) or to
``benchmarks/out/<workload>/``. A run that ends any other way prints a
failure line last (``error``, ``phase``, and the holders or leftovers where
it has them) and exits non-zero.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
import traceback
from typing import Optional

from . import boundaries, manifest


def emit(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


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
    """One run's arguments, clock, output directory and how far it has come:
    ``phase`` is ``gate``, ``setup``, ``check``, ``window`` or ``teardown``,
    moved by ``gate``, ``setup_done`` and the driver, named by a failure line."""

    def __init__(self, cell: dict, args, started_wall: float):
        self.cell, self.args = cell, args
        self.started_wall = started_wall
        self.setup_s = None
        self.phase = "gate"
        self.teardown = None
        self.out_dir = os.path.join(manifest.BENCH_DIR, "out", cell["name"])

    def gate(self) -> None:
        """The run begins when no other process holds a chip. ``setup_s`` is
        this run's set-up, not the last run's death: the clock moves forward
        by the wait."""
        waited = boundaries.wait_chips_free()
        self.started_wall += waited["chips_wait_s"]
        emit(**waited)
        boundaries.become_subreaper()
        os.makedirs(self.out_dir, exist_ok=True)
        self.phase = "setup"

    def check_device(self, device: dict) -> None:
        require_device(device, self.cell["chips"])

    def setup_done(self, opened_wall: float) -> None:
        """``opened_wall``: time.time() at which the measured window opens."""
        self.setup_s = opened_wall - self.started_wall
        self.phase = "window"

    def reap(self, pids=()) -> None:
        """The run ends when nothing it started is left in ``/proc``. Called
        once ``ray_tpu.shutdown()`` has returned; ``pids`` are the ones the
        driver collected, for the line. Leaves ``phase`` as it is: a driver
        that is on its way out with an exception gets here too."""
        self.teardown = boundaries.reap()
        emit(**self.teardown, reported_pids=list(pids))


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


def _arguments(argv):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if args.seconds is None:
        args.seconds = float(manifest.benchmark()["run_seconds"])
    return args


def _result_line(run: Run, result: dict) -> dict:
    from ray_tpu._internal.platform import backend_initialized

    cell, args = run.cell, run.args
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
    return line


def _failure(run: Optional[Run], exc: BaseException) -> None:
    """The last stdout line of a run that prints no result: what failed, in
    which phase, and the holders or leftovers where the failure has them.
    A driver that raised before its own shutdown and reap gets both here, so
    a failed run leaves the machine as clean as a good one."""
    line = {"error": f"{type(exc).__name__}: {exc}",
            "phase": run.phase if run else "gate"}
    line.update(getattr(exc, "details", {}))
    started = run is not None and run.phase != "gate"
    if started and run.teardown is None and not isinstance(exc, boundaries.RunVoid):
        try:
            import ray_tpu

            ray_tpu.shutdown()
            line.update(boundaries.reap())
        except boundaries.RunVoid as left:
            line.update(left.details)
        except Exception:  # the failure asked about is exc: this one is said beside it
            line["teardown_error"] = traceback.format_exc(limit=4)
    emit(threads_alive_at_exit=boundaries.threads_alive())
    print(json.dumps(line, default=str), flush=True)


def main(argv=None, started_wall: float = None) -> int:
    started_wall = started_wall or time.time()
    args = _arguments(argv)
    run = None
    try:
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
        run.gate()
        driver = importlib.import_module(
            f"benchmarks.drivers.{cell['traffic_file']['kind']}")
        result = driver.run(run)
        line = _result_line(run, result)
    except BaseException as exc:
        _failure(run, exc)
        raise
    # the in-process GCS and raylet are the program's to stop (ROADMAP D20):
    # what they leave alive is named here, before the line that comes last
    emit(threads_alive_at_exit=boundaries.threads_alive())
    with open(os.path.join(run.out_dir, "result.json"), "w") as f:
        json.dump(line, f)
    print(json.dumps(line), flush=True)
    return 0


def main_then_leave(started_wall: float) -> None:
    """What ``run.py`` calls: ``main``, and then out of the process at once.
    An interpreter's ordinary exit joins every non-daemon thread, so a
    thread the program left alive (named on the ``threads_alive_at_exit``
    line) would hold this process, and the chips' next user, behind it. The
    benchmark's exit is not the product's."""
    try:
        code = main(started_wall=started_wall)
    except SystemExit as exc:
        code = exc.code if isinstance(exc.code, int) else int(exc.code is not None)
        if code and not isinstance(exc.code, int):
            print(exc.code, file=sys.stderr)
    except BaseException:
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    if boundaries.threads_alive(wait_s=0.0):
        os._exit(code)
    sys.exit(code)
