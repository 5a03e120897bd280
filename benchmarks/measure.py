#!/usr/bin/env python3
"""Runs of cells, one fresh process each, as the driver makes them: what a
builder uses on the chip to calibrate a rate and to read a spread.

    python benchmarks/measure.py --tag sets --runs mistral7b-chat-backlog:6 \
        mistral7b-chat-steady:6 [--traced mistral7b-chat-backlog] [--seed0 100]

``cell:n`` makes n untraced runs of the cell, each with another seed;
``--traced`` adds one traced run of each named cell. ``cell@mix`` is
calibration: a cell of that name, the cell's configuration and metrics under
another mix file of ``traffic/``, added to BENCHMARK.json in this program's
memory only (``--calibrate``), so the run says under which mix it was made
and ``run.py`` knows no such switch.
Every result line, with the run's wall seconds, goes to
``chiprun_out/<tag>/runs.jsonl``, full stdout and stderr beside it, and for
a traced run the trace's description (``xplane.describe``) and the
``.xplane.pb`` itself where it is under 24 MB. The last lines printed are a
table of medians and spreads (distance between the quartiles over the
median). This process never touches JAX.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

STARTED = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmarks.harness import manifest, stats, xplane  # noqa: E402


def calibrate(spec: str, argv: list) -> int:
    """``run.py``'s command for the cell ``<cell>@<mix>``, which exists only
    in this process's copy of BENCHMARK.json."""
    from benchmarks.harness import cli

    cell, _, mix = spec.partition("@")
    bench = manifest.benchmark()
    base = next(w for w in bench["workloads"] if w["name"] == cell)
    bench["workloads"].append(dict(base, name=spec, traffic=mix))
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if cell in metric.get("workloads", []):
            metric["workloads"].append(spec)
    manifest.benchmark = lambda: bench
    return cli.main(["--workload", spec] + argv, started_wall=STARTED)


def one(out_dir: str, spec: str, seed: int, seconds: float, trace: int) -> dict:
    tag = f"{spec.replace('@', '_')}.s{seed}.t{trace}"
    program = ([os.path.join(HERE, "measure.py"), "--calibrate", spec, "--"]
               if "@" in spec else
               [os.path.join(HERE, "run.py"), "--workload", spec])
    command = [sys.executable] + program + [
        "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.time()
    with open(os.path.join(out_dir, tag + ".out"), "w") as out, \
            open(os.path.join(out_dir, tag + ".err"), "w") as err:
        code = subprocess.run(command, cwd=ROOT, stdout=out, stderr=err,
                              timeout=1500).returncode
    wall = time.time() - t0
    with open(os.path.join(out_dir, tag + ".out")) as f:
        lines = [l for l in f.read().splitlines() if l.startswith("{")]
    last = json.loads(lines[-1]) if lines else {}
    row = {"cell": spec, "seed": seed, "trace": trace, "exit": code,
           "wall_s": round(wall, 1), "result": last if "metrics" in last else None}
    if trace:
        path = xplane.find_xplane(os.path.join(HERE, "out", spec, "trace"))
        if path:
            with open(os.path.join(out_dir, tag + ".trace_described.json"), "w") as f:
                json.dump(xplane.describe(path), f, indent=1)
            if os.path.getsize(path) < 24e6:
                shutil.copy(path, os.path.join(out_dir, tag + ".xplane.pb"))
    records = os.path.join(HERE, "out", spec, "records.json")
    if os.path.exists(records) and os.path.getsize(records) < 8e6:
        shutil.copy(records, os.path.join(out_dir, tag + ".records.json"))
    return row


def main() -> int:
    if sys.argv[1:2] == ["--calibrate"]:  # <cell>@<mix> -- <run.py's arguments>
        return calibrate(sys.argv[2], [a for a in sys.argv[3:] if a != "--"])
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tag", required=True)
    parser.add_argument("--runs", nargs="*", default=[])
    parser.add_argument("--traced", nargs="*", default=[])
    parser.add_argument("--seed0", type=int, default=100)
    parser.add_argument("--seconds", type=float,
                        default=float(manifest.benchmark()["run_seconds"]))
    args = parser.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out", args.tag)
    os.makedirs(out_dir, exist_ok=True)
    rows, seed = [], args.seed0
    plan = [(spec.rsplit(":", 1)[0], 0) for spec in args.runs
            for _ in range(int(spec.rsplit(":", 1)[1]))]
    plan += [(spec, 1) for spec in args.traced]
    with open(os.path.join(out_dir, "runs.jsonl"), "a") as log:
        for spec, trace in plan:
            row = one(out_dir, spec, seed, args.seconds, trace)
            seed += 1
            rows.append(row)
            log.write(json.dumps(row) + "\n")
            log.flush()
            print(json.dumps(row), flush=True)
    print("cell metric n median spread first")
    for spec in dict.fromkeys(r["cell"] for r in rows):
        good = [r["result"] for r in rows
                if r["cell"] == spec and r["trace"] == 0 and r["result"]]
        for name in (good[0]["metrics"] if good else {}):
            values = [g["metrics"][name]["value"] for g in good]
            # each side's first run compiles and is recorded apart
            steady = values[1:] if name == "setup_s" and len(values) > 1 else values
            print(spec, name, len(steady), round(stats.median(steady), 4),
                  round(stats.spread(steady) or 0.0, 4), round(values[0], 4))
    return 0 if all(r["exit"] == 0 for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
