"""CLI: ``python -m ray_tpu.scripts.cli <command>``.

Role-equivalent of the reference's ray CLI (python/ray/scripts/scripts.py —
ray start :684 / stop :1227 / status, plus `ray list ...` from the state
CLI util/state/state_cli.py). ``start --head`` runs a standalone head node
(GCS + raylet) that remote drivers join with
``ray_tpu.init(address="host:port")``; ``start --address`` joins an
existing head as a worker node.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time


def cmd_start(args):
    from .._internal.config import Config
    from ..runtime.node import Node

    resources = json.loads(args.resources) if args.resources else {}
    if args.num_cpus is not None:
        resources["CPU"] = float(args.num_cpus)
    if args.num_tpus is not None:
        resources["TPU"] = float(args.num_tpus)
    labels = json.loads(args.labels) if args.labels else {}

    config = Config()
    if args.head:
        config.client_server_port = args.ray_client_server_port
        config.client_server_host = args.ray_client_server_host
        node = Node(
            config,
            head=True,
            resources=resources,
            labels=labels,
            object_store_memory=args.object_store_memory,
        )
        host, port = node.gcs_address
        print(f"ray_tpu head started; connect with:")
        print(f'  ray_tpu.init(address="{host}:{port}")')
        if node.client_server is not None:
            chost, cport = node.client_server.address
            print(f'  ray_tpu.init(address="ray://{chost}:{cport}")  # client mode')
        if not args.no_dashboard:
            from ..dashboard import DashboardServer

            dash = DashboardServer(
                node.gcs_address, port=args.dashboard_port
            )
            dash.start()
            print(f"dashboard + job API at {dash.url}")
    else:
        if not args.address:
            print("worker nodes need --address host:port", file=sys.stderr)
            return 1
        host, port = args.address.rsplit(":", 1)
        node = Node(
            config,
            head=False,
            gcs_address=(host, int(port)),
            resources=resources,
            labels=labels,
            object_store_memory=args.object_store_memory,
        )
        print(f"ray_tpu node joined {args.address}")
    if args.block:
        stop = []
        signal.signal(signal.SIGTERM, lambda *_: stop.append(1))
        signal.signal(signal.SIGINT, lambda *_: stop.append(1))
        while not stop:
            time.sleep(0.5)
        node.stop()
        return 0
    print(f"(pid {os.getpid()} keeps the node alive; kill it to stop)")
    while True:  # non-daemonized v1: block regardless
        time.sleep(3600)


def cmd_stop(args):
    """Stop all local ray_tpu processes (reference: `ray stop` — scans for
    ray process cmdlines and terminates them)."""
    me = os.getpid()
    # exact argv-token matching (NUL-split), not substring over the joined
    # line: `grep worker_main ...` or an editor on that path must survive
    ray_modules = {
        "ray_tpu.scripts.cli", "ray_tpu.runtime.worker.worker_main",
    }
    killed = []
    for pid_dir in os.listdir("/proc"):
        if not pid_dir.isdigit() or int(pid_dir) == me:
            continue
        try:
            with open(f"/proc/{pid_dir}/cmdline", "rb") as f:
                argv = [
                    a.decode(errors="replace")
                    for a in f.read().split(b"\0") if a
                ]
        except OSError:
            continue
        is_ours = False
        for i, tok in enumerate(argv):
            if tok == "-m" and i + 1 < len(argv) and argv[i + 1] in ray_modules:
                # `cli` only counts when it is a `start` invocation
                if argv[i + 1].endswith("worker_main") or "start" in argv[i + 2 : i + 3]:
                    is_ours = True
                break
        if is_ours:
            try:
                os.kill(int(pid_dir), signal.SIGTERM)
                killed.append(int(pid_dir))
            except OSError:
                pass
    print(f"stopped {len(killed)} process(es): {killed}")
    return 0


def _connected(args):
    import ray_tpu

    # reuse a live driver when one exists in-process (tests drive commands
    # through main() against their own cluster)
    ray_tpu.init(address=args.address, ignore_reinit_error=True)
    return ray_tpu


def cmd_microbenchmark(args):
    from .._internal.perf import (
        json_results,
        print_results,
        run_microbenchmarks,
    )

    results = run_microbenchmarks(small=args.small)
    if getattr(args, "json", False):
        print(json_results(results))
    else:
        print_results(results)
    return 0


def cmd_status(args):
    _connected(args)
    from ..util import state

    summary = state.cluster_summary()
    print(json.dumps(summary, indent=2, default=str))
    return 0


def cmd_list(args):
    _connected(args)
    from ..util import state

    fn = {
        "nodes": state.list_nodes,
        "actors": state.list_actors,
        "tasks": state.list_tasks,
        "jobs": state.list_jobs,
        "placement-groups": state.list_placement_groups,
        "objects": state.list_objects,
        "weights": state.list_weights,
        "replicas": state.list_replicas,
    }[args.what]
    rows = fn()
    print(json.dumps(rows, indent=2, default=str))
    return 0


def cmd_logs(args):
    _connected(args)
    from ..util import state

    if args.filename:
        print(state.get_log(args.filename, node_id=args.node_id, tail=args.tail))
    else:
        print(json.dumps(state.list_logs(node_id=args.node_id), indent=2))
    return 0


def cmd_debug(args):
    _connected(args)
    from ..util import debug

    if not args.session:
        sessions = debug.list_sessions()
        if not sessions:
            print("no active debug sessions")
        else:
            for sid, info in sessions.items():
                print(
                    f"{sid}  pid={info.get('pid')}  {info.get('host')}:"
                    f"{info.get('port')}  {info.get('reason')}  "
                    f"task={info.get('task_id')}"
                )
        return 0
    if not debug.attach(args.session):
        print(f"unknown debug session: {args.session}", file=sys.stderr)
        return 1
    return 0


def cmd_summary(args):
    _connected(args)
    from ..util import state

    print(json.dumps(state.summarize_tasks(), indent=2))
    return 0


def cmd_metrics(args):
    _connected(args)
    if getattr(args, "summary", False):
        from ..util import state

        print(json.dumps(state.metrics_summary(), indent=2, default=str))
        return 0
    from ..util.metrics import prometheus_text

    print(prometheus_text())
    return 0


def cmd_kvcache(args):
    """`ray_tpu kvcache`: cluster-wide KV-cache plane stats — prefix-hit
    vs computed prefill tokens, block pool occupancy, evictions,
    admission backpressure, and TTFT by hit/miss (state API rollup of the
    `kvcache_*` metrics every paged engine pushes)."""
    _connected(args)
    from ..util import state

    print(json.dumps(state.metrics_summary()["kvcache"], indent=2, default=str))
    return 0


def cmd_kvtier(args):
    """`ray_tpu kvtier`: cluster KV-tier stats — resolution outcomes
    (hit / peer_pull / recompute), logical vs wire transfer bytes (the
    int8 shipment codec's compression split), and TTFT by tier
    (local / peer / miss) read off the kvcache histogram's tier tag."""
    _connected(args)
    from ..util import state

    print(json.dumps(state.metrics_summary()["kvtier"], indent=2, default=str))
    return 0


def cmd_adapters(args):
    """`ray_tpu adapters`: the multi-tenant LoRA adapter plane — lease
    hit rate vs cold attaches (is max_live sized right?), LRU evictions
    (thrash indicator), live slots, and cold-attach latency percentiles
    (the TTFT tax of a tenant's first request on a replica)."""
    _connected(args)
    from ..util import state

    print(json.dumps(
        state.metrics_summary()["adapters"], indent=2, default=str
    ))
    return 0


def cmd_autoscale(args):
    """`ray_tpu autoscale`: the SLO autoscaler's decision record.

    - ``log``: most recent scale-up/down decision events (direction,
      replica counts, triggering reasons, breach age, the signal snapshot
      at decision time) from the controller's GCS KV mirror.
    - ``status``: cluster rollup of the ``autoscale_*`` metrics —
      scale-up/down totals per deployment and decision-latency quantiles.
    """
    _connected(args)
    from ..util import state

    if args.autoscale_action == "log":
        print(json.dumps(
            state.autoscale_log(limit=args.limit), indent=2, default=str
        ))
    else:
        print(json.dumps(
            state.metrics_summary()["autoscale"], indent=2, default=str
        ))
    return 0


def cmd_events(args):
    """`ray_tpu events`: the cluster flight recorder — structured events
    (replica state transitions, autoscale decisions, collective epochs,
    admission blocks, retries, watchdog stack captures) streamed by every
    process into the GCS event store. Works post-mortem: a SIGKILLed
    process's last ~second of events is already in the store."""
    _connected(args)
    from ..util import state

    print(json.dumps(
        state.list_events(
            limit=args.limit, name=args.name,
            since=getattr(args, "since", None),
        ),
        indent=2, default=str,
    ))
    return 0


def cmd_top(args):
    """`ray_tpu top`: live per-worker training table, sorted by step-time
    deviation from the group median — the straggler hunt's first screen.
    Rows come from the GCS timeseries store's MAD verdicts; ``--watch``
    refreshes until interrupted."""
    _connected(args)
    import time as _time

    from ..util import state

    def _render():
        rows = state.straggler_verdicts()
        if getattr(args, "json", False):
            print(json.dumps(rows, indent=2, default=str))
            return
        if not rows:
            print("no step-time series yet (is a training run reporting?)")
            return
        header = (
            f"{'GROUP':<14} {'RANK':>4} {'WORKER':<14} {'STEP s':>9} "
            f"{'GROUP s':>9} {'DEV %':>8}  STATUS"
        )
        print(header)
        for v in rows:
            print(
                f"{str(v.get('group') or '?')[:14]:<14} "
                f"{str(v.get('rank') if v.get('rank') is not None else '?'):>4} "
                f"{str(v.get('worker_id') or '')[:14]:<14} "
                f"{v.get('median_s', 0.0):>9.4f} "
                f"{v.get('group_median_s', 0.0):>9.4f} "
                f"{100.0 * v.get('deviation', 0.0):>8.1f}  "
                f"{'STRAGGLER' if v.get('straggler') else 'ok'}"
            )

    if getattr(args, "watch", False):
        try:
            while True:
                print(f"\n-- {_time.strftime('%H:%M:%S')} --")
                _render()
                _time.sleep(args.interval)
        except KeyboardInterrupt:
            return 0
    else:
        _render()
    return 0


def cmd_alerts(args):
    """`ray_tpu alerts`: the alerting engine's surface — active alerts,
    declared rules, recent firing/resolved transitions, and straggler
    verdicts, straight off the GCS ``alerts_snapshot`` RPC. ``--events``
    tails the alert/straggler flight-recorder stream instead;
    ``--set-rule`` / ``--delete-rule`` manage the rule registry."""
    _connected(args)
    from ..util import state

    if getattr(args, "set_rule", None):
        rule = json.loads(args.set_rule)
        print(json.dumps(state.set_alert_rule(rule), indent=2, default=str))
        return 0
    if getattr(args, "delete_rule", None):
        ok = state.delete_alert_rule(args.delete_rule)
        print(json.dumps({"deleted": ok}))
        return 0 if ok else 1
    if getattr(args, "events", False):
        out = []
        for name in (
            "alert_firing", "alert_resolved",
            "straggler_detected", "straggler_resolved",
        ):
            out.extend(state.list_events(
                limit=args.limit, name=name,
                since=getattr(args, "since", None),
            ))
        out.sort(key=lambda e: e.get("ts", 0))
        print(json.dumps(out[-args.limit:], indent=2, default=str))
        return 0
    snapshot = state.alerts_snapshot()
    if getattr(args, "rules", False):
        print(json.dumps(snapshot["rules"], indent=2, default=str))
        return 0
    print(json.dumps(snapshot, indent=2, default=str))
    return 0


def cmd_proxies(args):
    """`ray_tpu proxies`: the ingress data plane — live proxy registry
    (``proxy:*`` GCS records: kind, host:port, pid, node) joined with the
    per-proxy traffic rollup (requests by outcome, inflight, latency
    p50/p99) from the pushed metrics plane."""
    _connected(args)
    from ..util import state
    from ..util.metrics import fetch_metric_payloads, ingress_summary

    proxies = state.list_proxies()
    try:
        traffic = ingress_summary(
            fetch_metric_payloads(state._gcs_call)
        ).get("proxies", {})
    except Exception:  # noqa: BLE001 — registry still prints without metrics
        traffic = {}
    for row in proxies:
        row["traffic"] = traffic.get(row.get("proxy_id"), {})
    print(json.dumps(proxies, indent=2, default=str))
    return 0


def cmd_chaos(args):
    """`ray_tpu chaos`: fault injection against a live cluster — the
    operator-facing face of the elastic-training chaos layer.

    - ``list``: live train runs (``trainrun:*`` records: state, group,
      epoch, per-rank pids) plus recovery counters.
    - ``kill-rank``: SIGKILL one rank's worker process (same-host pids
      only) — deterministic chip/host-loss injection.
    - ``abort-group``: write the collective abort key so every member
      blocked in a rendezvous raises CollectiveAbortedError within ~1 s.
    - ``delay-collective``: make every op of a group sleep N seconds at
      entry (straggler injection); 0 clears.
    - ``kill-replica`` / ``pause-replica``: SIGKILL / SIGSTOP one serve
      replica process (same-host pids only) — replica-loss / stuck-replica
      injection; the handle retry envelope plus controller health polling
      must absorb it.
    - ``kill-proxy``: SIGKILL one ingress proxy process (same-host pids
      only) — front-end-loss injection; surviving proxies on the shared
      SO_REUSEPORT listener keep accepting and the controller's health
      poll deregisters the corpse.
    - ``drain``: gracefully drain one serve replica through the
      controller's DRAINING state machine (rolling-restart injection).
    - ``net``: cluster-wide network chaos mesh. Writes a structured spec
      (seed + rules: fail/delay/jitter/blackhole/disconnect, optionally
      scoped by ``--method``/``--src``/``--dst``) to the GCS KV; every
      process polls it, so partitions apply — and heal — everywhere
      within ~1 poll period. ``--clear`` removes it; with no spec flags
      the current spec is printed.
    """
    _connected(args)
    from ..util import state

    if args.chaos_action in ("abort-group", "delay-collective") and not args.group:
        print(f"{args.chaos_action} needs --group", file=sys.stderr)
        return 1

    def _kv(method, *cargs):
        from .. import _worker_api

        worker = _worker_api.get_core_worker()
        client = worker.client_pool.get(*worker.gcs_address)
        return _worker_api.run_on_worker_loop(client.call(method, *cargs))

    if args.chaos_action == "net":
        from ..runtime.gcs import keys as gcs_keys

        if args.clear:
            _kv("kv_del", gcs_keys.CHAOS_NET_SPEC)
            print("chaos-net spec cleared; processes heal within ~1 poll "
                  "period")
            return 0
        spec = None
        if args.spec:
            spec = json.loads(args.spec)
        elif args.spec_file:
            with open(args.spec_file) as f:
                spec = json.load(f)
        elif any((args.fail, args.delay_ms, args.jitter_ms, args.blackhole,
                  args.disconnect)):
            rule = {"method": args.method, "src": args.src, "dst": args.dst}
            if args.fail:
                rule["fail"] = args.fail
            if args.delay_ms:
                rule["delay_ms"] = args.delay_ms
            if args.jitter_ms:
                rule["jitter_ms"] = args.jitter_ms
            if args.blackhole:
                rule["blackhole"] = True
            if args.disconnect:
                rule["disconnect"] = args.disconnect
            spec = {"seed": args.seed, "rules": [rule]}
        if spec is None:
            raw = _kv("kv_get", gcs_keys.CHAOS_NET_SPEC)
            if raw:
                print(bytes(raw).decode("utf-8", "replace"))
            else:
                print("no chaos-net spec set")
            return 0
        _kv("kv_put", gcs_keys.CHAOS_NET_SPEC,
            json.dumps(spec).encode(), True)
        print(f"chaos-net spec set ({len(spec.get('rules', []))} rule(s), "
              f"seed {spec.get('seed', 0)}); every process applies it "
              f"within ~1 poll period")
        return 0
    if args.chaos_action == "list":
        from ..testing import list_serve_replicas

        summary = state.metrics_summary()
        out = {
            "runs": state.list_train_runs(),
            "train_ft": summary["train_ft"],
            "serve_replicas": list_serve_replicas(args.app),
            "serve_ft": summary.get("serve_ft", {}),
        }
        print(json.dumps(out, indent=2, default=str))
        return 0
    if args.chaos_action in ("kill-replica", "pause-replica"):
        from ..testing import kill_serve_replica

        sig = signal.SIGKILL if args.chaos_action == "kill-replica" \
            else signal.SIGSTOP
        rid, pid = kill_serve_replica(
            args.app, deployment=args.deployment, replica_id=args.replica,
            sig=sig,
        )
        if rid is None:
            print(f"no matching RUNNING replica in app {args.app!r} "
                  f"(pids are same-host only; see `ray_tpu chaos list`)",
                  file=sys.stderr)
            return 1
        verb = "killed" if sig == signal.SIGKILL else "paused"
        print(f"{verb} replica {rid} (pid {pid}) of app {args.app!r}")
        return 0
    if args.chaos_action == "kill-proxy":
        from ..testing import kill_serve_proxy

        proxy_id, pid = kill_serve_proxy(args.proxy)
        if proxy_id is None:
            print("no matching live proxy (pids are same-host only; see "
                  "`ray_tpu proxies`)", file=sys.stderr)
            return 1
        print(f"killed proxy {proxy_id} (pid {pid}); survivors on the "
              f"shared listener keep serving")
        return 0
    if args.chaos_action == "drain":
        from .. import api
        from ..serve.controller import CONTROLLER_NAME

        if not args.replica:
            print("drain needs --replica (see `ray_tpu chaos list`)",
                  file=sys.stderr)
            return 1
        try:
            controller = api.get_actor(CONTROLLER_NAME)
            ok = api.get(
                controller.drain_replica.remote(args.app, args.replica),
                timeout=10,
            )
        except Exception as e:  # noqa: BLE001
            print(f"drain failed: {e}", file=sys.stderr)
            return 1
        if not ok:
            print(f"replica {args.replica!r} not found (or not RUNNING) in "
                  f"app {args.app!r}", file=sys.stderr)
            return 1
        print(f"draining replica {args.replica} of app {args.app!r}; the "
              f"controller replaces it once in-flight requests finish")
        return 0
    if args.chaos_action == "kill-rank":
        runs = {r["name"]: r for r in state.list_train_runs()}
        rec = runs.get(args.run)
        if rec is None:
            print(f"no live train run {args.run!r}; see `ray_tpu chaos list`",
                  file=sys.stderr)
            return 1
        for w in rec.get("workers", []):
            if w.get("rank") == args.rank:
                pid = w.get("pid")
                try:
                    os.kill(int(pid), signal.SIGKILL)
                except (OSError, TypeError, ValueError) as e:
                    print(f"kill pid {pid} failed: {e} (kill-rank only "
                          f"reaches same-host pids)", file=sys.stderr)
                    return 1
                print(f"killed run {args.run!r} rank {args.rank} (pid {pid})")
                return 0
        print(f"run {args.run!r} has no rank {args.rank}", file=sys.stderr)
        return 1
    if args.chaos_action == "abort-group":
        from ..collective import abort_collective_group

        advanced = abort_collective_group(
            args.group, args.epoch, reason="cli abort"
        )
        print(f"abort {'written' if advanced else 'already >= requested'} "
              f"for group {args.group!r} epoch {args.epoch}")
        return 0
    if args.chaos_action == "delay-collective":
        from ..runtime.gcs import keys as gcs_keys

        key = gcs_keys.COLLECTIVE_DELAY.key(args.group)
        if args.seconds > 0:
            _kv("kv_put", key, str(args.seconds).encode(), True)
            print(f"group {args.group!r}: every op now sleeps "
                  f"{args.seconds}s at entry (TTL-cached ~2s in members)")
        else:
            _kv("kv_del", key)
            print(f"group {args.group!r}: delay cleared")
        return 0
    return 1


def cmd_lint(args):
    """`ray_tpu lint`: the project-invariant static-analysis pass.

    Runs the RT001..RT012 checkers (ray_tpu/analysis/) over the package —
    or the given paths — subtracts the committed baseline, and reports
    what's left. Exit codes: 0 clean, 1 findings (new or stale baseline),
    2 internal error. ``--baseline-update`` rewrites the baseline from the
    current findings (shrink-only policy: do this only to *remove* fixed
    entries, never to grandfather new code).
    """
    import os as _os

    from .. import analysis

    try:
        rules = args.rules.split(",") if args.rules else None
        pkg_root = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
        repo_root = _os.path.dirname(pkg_root)
        targets = args.paths or [pkg_root]
        findings = []
        files = 0
        parse_errors = []
        for target in targets:
            analyzer = analysis.Analyzer(
                target, rules=rules,
                rel_to=repo_root if _os.path.abspath(target).startswith(repo_root)
                else None,
            )
            result = analyzer.run()
            findings.extend(result.findings)
            files += result.files_scanned
            parse_errors.extend(result.parse_errors)

        if args.baseline_update:
            path = analysis.write_baseline(findings, args.baseline)
            print(f"baseline rewritten with {len(findings)} finding(s): {path}")
            return 0

        entries = [] if args.no_baseline else analysis.load_baseline(args.baseline)
        new, suppressed, stale = analysis.apply_baseline(findings, entries)

        if getattr(args, "json", False):
            print(json.dumps({
                "files_scanned": files,
                "parse_errors": parse_errors,
                "findings": [f.to_dict() for f in new],
                "baselined": len(suppressed),
                "stale_baseline": stale,
                "counts": {
                    rule: sum(1 for f in new if f.rule == rule)
                    for rule in sorted({f.rule for f in new})
                },
            }, indent=2))
        else:
            for f in new:
                print(f"{f.path}:{f.line}: {f.rule} {f.message}")
            for e in stale:
                print(
                    f"stale baseline entry (finding fixed — shrink the "
                    f"baseline): {e.get('rule')} {e.get('path')}: "
                    f"{e.get('message')}"
                )
            for err in parse_errors:
                print(f"parse error: {err}", file=sys.stderr)
            print(
                f"{files} file(s) scanned: {len(new)} finding(s), "
                f"{len(suppressed)} baselined, {len(stale)} stale "
                f"baseline entr{'y' if len(stale) == 1 else 'ies'}"
            )
        return 1 if (new or stale or parse_errors) else 0
    except SystemExit:
        raise
    except Exception as e:  # noqa: BLE001 — exit code 2 contract
        print(f"lint internal error: {type(e).__name__}: {e}", file=sys.stderr)
        return 2


def cmd_timeline(args):
    """`ray_tpu timeline`: export the cluster-wide chrome trace — GCS
    task-state bars merged with every traced node's spans (reference:
    `ray timeline` writing chrome://tracing JSON)."""
    _connected(args)
    from ..util import tracing

    events = tracing.timeline(args.output)
    print(
        f"wrote {len(events)} trace events to {args.output} "
        f"(open in chrome://tracing or https://ui.perfetto.dev)"
    )
    return 0


def cmd_job(args):
    """`ray_tpu job submit|status|logs|stop|list` (reference: `ray job`
    subcommands, dashboard/modules/job/cli.py)."""
    from ..job_submission import JobSubmissionClient

    address = args.address
    if not address.startswith("http"):
        address = f"http://{address}"
    client = JobSubmissionClient(address)
    if args.action == "submit":
        entrypoint = " ".join(a for a in args.entrypoint if a != "--")
        if not entrypoint:
            print("job submit needs an entrypoint", file=sys.stderr)
            return 1
        runtime_env = (
            {"working_dir": args.working_dir} if args.working_dir else None
        )
        sid = client.submit_job(
            entrypoint=entrypoint,
            submission_id=args.submission_id,
            runtime_env=runtime_env,
        )
        print(sid)
    elif args.action == "list":
        print(json.dumps(client.list_jobs(), indent=2))
    else:
        if not args.submission_id:
            print(f"job {args.action} needs --submission-id", file=sys.stderr)
            return 1
        if args.action == "status":
            print(client.get_job_status(args.submission_id))
        elif args.action == "logs":
            print(client.get_job_logs(args.submission_id), end="")
        elif args.action == "stop":
            print(client.stop_job(args.submission_id))
    return 0


def main(argv=None):
    parser = argparse.ArgumentParser(prog="ray_tpu")
    sub = parser.add_subparsers(dest="command", required=True)

    p = sub.add_parser("start", help="start a head or worker node")
    p.add_argument("--head", action="store_true")
    p.add_argument("--address", default=None, help="head host:port to join")
    p.add_argument("--num-cpus", type=int, default=None)
    p.add_argument("--num-tpus", type=int, default=None)
    p.add_argument("--resources", default=None, help="JSON resource map")
    p.add_argument("--labels", default=None, help="JSON label map")
    p.add_argument("--object-store-memory", type=int, default=None)
    p.add_argument("--block", action="store_true")
    p.add_argument("--no-dashboard", action="store_true")
    p.add_argument("--dashboard-port", type=int, default=8265)
    p.add_argument(
        "--ray-client-server-port", type=int, default=10001,
        help="port for ray:// clients (head only); -1 disables",
    )
    p.add_argument(
        "--ray-client-server-host", default="127.0.0.1",
        help="bind host for ray:// clients; 0.0.0.0 accepts remote machines",
    )
    p.set_defaults(fn=cmd_start)

    p = sub.add_parser(
        "stop", help="stop all local ray_tpu processes (reference: ray stop)"
    )
    p.set_defaults(fn=cmd_stop)

    p = sub.add_parser("job", help="submit and manage jobs")
    p.add_argument(
        "action", choices=["submit", "status", "logs", "stop", "list"]
    )
    p.add_argument("--address", required=True, help="dashboard URL")
    p.add_argument("--submission-id", default=None)
    p.add_argument("--working-dir", default=None)
    p.add_argument("entrypoint", nargs=argparse.REMAINDER)
    p.set_defaults(fn=cmd_job)

    for name, fn in (
        ("status", cmd_status),
        ("summary", cmd_summary),
    ):
        p = sub.add_parser(name)
        p.add_argument("--address", required=True, help="head host:port")
        p.set_defaults(fn=fn)

    p = sub.add_parser(
        "metrics", help="Prometheus exposition dump (or --summary JSON)"
    )
    p.add_argument("--address", required=True, help="head host:port")
    p.add_argument(
        "--summary", action="store_true",
        help="aggregated collective/step/HBM JSON instead of raw exposition",
    )
    p.set_defaults(fn=cmd_metrics)

    p = sub.add_parser(
        "kvcache", help="KV-cache plane stats (prefix hits, blocks, TTFT)"
    )
    p.add_argument("--address", required=True, help="head host:port")
    p.set_defaults(fn=cmd_kvcache)

    p = sub.add_parser(
        "kvtier",
        help="cluster KV-tier stats (hit/peer_pull/recompute, wire bytes)",
    )
    p.add_argument("--address", required=True, help="head host:port")
    p.set_defaults(fn=cmd_kvtier)

    p = sub.add_parser(
        "adapters",
        help="LoRA adapter-plane stats (hit rate, cold attaches, evictions)",
    )
    p.add_argument("--address", required=True, help="head host:port")
    p.set_defaults(fn=cmd_adapters)

    p = sub.add_parser(
        "autoscale",
        help="SLO autoscaler decision log and scale-up/down counters",
    )
    p.add_argument("autoscale_action", choices=["log", "status"])
    p.add_argument("--address", required=True, help="head host:port")
    p.add_argument(
        "--limit", type=int, default=100,
        help="max decision events to show (log)",
    )
    p.set_defaults(fn=cmd_autoscale)

    p = sub.add_parser(
        "events",
        help="flight-recorder query: cluster-wide structured events "
             "(state transitions, retries, watchdog stack captures)",
    )
    p.add_argument("--address", required=True, help="head host:port")
    p.add_argument(
        "--limit", type=int, default=100, help="max events to show"
    )
    p.add_argument(
        "--name", default=None,
        help="filter to one event name (e.g. replica_state, request_retry)",
    )
    p.add_argument(
        "--since", type=float, default=None,
        help="only events with ts >= this unix timestamp",
    )
    p.set_defaults(fn=cmd_events)

    p = sub.add_parser(
        "top",
        help="live per-worker training table sorted by step-time "
             "deviation (straggler hunt)",
    )
    p.add_argument("--address", required=True, help="head host:port")
    p.add_argument("--json", action="store_true", help="raw verdict rows")
    p.add_argument(
        "--watch", action="store_true", help="refresh until interrupted"
    )
    p.add_argument(
        "--interval", type=float, default=2.0,
        help="seconds between --watch refreshes",
    )
    p.set_defaults(fn=cmd_top)

    p = sub.add_parser(
        "alerts",
        help="alerting engine: active alerts, rules, transitions, "
             "straggler verdicts",
    )
    p.add_argument("--address", required=True, help="head host:port")
    p.add_argument(
        "--rules", action="store_true", help="list declared rules only"
    )
    p.add_argument(
        "--events", action="store_true",
        help="tail alert/straggler flight-recorder events instead",
    )
    p.add_argument(
        "--limit", type=int, default=100, help="max events (--events)"
    )
    p.add_argument(
        "--since", type=float, default=None,
        help="only events with ts >= this unix timestamp (--events)",
    )
    p.add_argument(
        "--set-rule", default=None, metavar="JSON",
        help='declare/replace a rule, e.g. \'{"name": "slow_ttft", '
             '"series": "serve_ttft_s", "threshold": 0.5}\'',
    )
    p.add_argument(
        "--delete-rule", default=None, metavar="NAME",
        help="remove a rule from the registry",
    )
    p.set_defaults(fn=cmd_alerts)

    p = sub.add_parser(
        "proxies",
        help="ingress data plane: live proxy registry + per-proxy "
             "traffic rollup",
    )
    p.add_argument("--address", required=True, help="head host:port")
    p.set_defaults(fn=cmd_proxies)

    p = sub.add_parser(
        "chaos",
        help="fault injection: kill ranks/replicas/proxies, abort/delay "
             "collectives, drain replicas, network chaos mesh",
    )
    p.add_argument(
        "chaos_action",
        choices=["list", "kill-rank", "abort-group", "delay-collective",
                 "kill-replica", "pause-replica", "kill-proxy", "drain",
                 "net"],
    )
    p.add_argument("--address", required=True, help="head host:port")
    p.add_argument("--run", default=None, help="train run name (kill-rank)")
    p.add_argument(
        "--app", default="default",
        help="serve app name (kill-replica/pause-replica/drain)",
    )
    p.add_argument(
        "--deployment", default=None,
        help="restrict kill-replica/pause-replica to one deployment",
    )
    p.add_argument(
        "--replica", default=None,
        help="replica id (required for drain; optional filter for "
             "kill-replica/pause-replica)",
    )
    p.add_argument(
        "--proxy", default=None,
        help="proxy id (optional filter for kill-proxy; see "
             "`ray_tpu proxies`)",
    )
    p.add_argument("--rank", type=int, default=0, help="world rank to kill")
    p.add_argument("--group", default=None, help="collective group name")
    p.add_argument(
        "--epoch", type=int, default=0,
        help="abort epochs <= this (abort-group)",
    )
    p.add_argument(
        "--seconds", type=float, default=0.0,
        help="per-op delay for delay-collective; 0 clears",
    )
    p.add_argument(
        "--spec", default=None,
        help="chaos-net: full structured spec as inline JSON",
    )
    p.add_argument(
        "--spec-file", default=None,
        help="chaos-net: path to a JSON spec file",
    )
    p.add_argument(
        "--clear", action="store_true",
        help="chaos-net: remove the cluster spec (heal all partitions)",
    )
    p.add_argument(
        "--method", default="*",
        help="chaos-net single-rule: RPC method to match (default: all)",
    )
    p.add_argument(
        "--src", default="*",
        help="chaos-net single-rule: caller node-id hex prefix "
             "(directional partition source; default: all)",
    )
    p.add_argument(
        "--dst", default="*",
        help="chaos-net single-rule: destination host:port (default: all)",
    )
    p.add_argument(
        "--fail", type=float, default=0.0,
        help="chaos-net single-rule: per-call failure probability",
    )
    p.add_argument(
        "--delay-ms", type=float, default=0.0,
        help="chaos-net single-rule: fixed per-call delay",
    )
    p.add_argument(
        "--jitter-ms", type=float, default=0.0,
        help="chaos-net single-rule: uniform extra delay on top of "
             "--delay-ms",
    )
    p.add_argument(
        "--blackhole", action="store_true",
        help="chaos-net single-rule: calls hang until the caller's "
             "deadline instead of erroring",
    )
    p.add_argument(
        "--disconnect", type=float, default=0.0,
        help="chaos-net single-rule: probability of mid-call transport "
             "disconnect",
    )
    p.add_argument(
        "--seed", type=int, default=0,
        help="chaos-net: deterministic rng seed for the spec",
    )
    p.set_defaults(fn=cmd_chaos)

    p = sub.add_parser(
        "lint",
        help="run the RT001..RT012 static-analysis pass "
             "(exit 0 clean / 1 findings / 2 internal error)",
    )
    p.add_argument(
        "paths", nargs="*",
        help="files or directories to scan (default: the ray_tpu package)",
    )
    p.add_argument(
        "--json", action="store_true", help="machine-readable report"
    )
    p.add_argument(
        "--rules", default=None,
        help="comma-separated rule ids to run (default: all)",
    )
    p.add_argument(
        "--baseline", default=None,
        help="baseline file (default: ray_tpu/analysis/baseline.json)",
    )
    p.add_argument(
        "--no-baseline", action="store_true",
        help="report every finding, ignoring the baseline",
    )
    p.add_argument(
        "--baseline-update", action="store_true",
        help="rewrite the baseline from current findings (shrink-only "
             "policy: use to drop fixed entries)",
    )
    p.set_defaults(fn=cmd_lint)

    p = sub.add_parser(
        "timeline", help="export the cluster chrome trace (ray timeline)"
    )
    p.add_argument("--address", required=True, help="head host:port")
    p.add_argument(
        "-o", "--output", default="/tmp/ray_tpu_timeline.json",
        help="output chrome-trace JSON path",
    )
    p.set_defaults(fn=cmd_timeline)

    p = sub.add_parser(
        "logs", help="list or tail session log files (reference: ray logs)"
    )
    p.add_argument("filename", nargs="?", help="log file name; omit to list")
    p.add_argument("--address", required=True, help="head host:port")
    p.add_argument("--node-id", default=None, help="node id hex prefix filter")
    p.add_argument("--tail", type=int, default=1000)
    p.set_defaults(fn=cmd_logs)

    p = sub.add_parser(
        "debug", help="list or attach to remote pdb sessions (ray debug)"
    )
    p.add_argument("session", nargs="?", help="session id prefix; omit to list")
    p.add_argument("--address", required=True, help="head host:port")
    p.set_defaults(fn=cmd_debug)

    p = sub.add_parser("list", help="list cluster state")
    p.add_argument(
        "what",
        choices=[
            "nodes", "actors", "tasks", "jobs", "placement-groups",
            "objects", "weights", "replicas",
        ],
    )
    p.add_argument("--address", required=True, help="head host:port")
    p.set_defaults(fn=cmd_list)

    # `perf` is the canonical name; `microbenchmark` stays as the
    # backward-compatible alias from earlier rounds
    for bench_name in ("perf", "microbenchmark"):
        p = sub.add_parser(
            bench_name, help="core-ops throughput suite "
            "(reference: release/microbenchmark)",
        )
        p.add_argument("--small", action="store_true")
        p.add_argument(
            "--json", action="store_true",
            help="emit one machine-readable JSON line",
        )
        p.set_defaults(fn=cmd_microbenchmark)

    args = parser.parse_args(argv)
    return args.fn(args) or 0


if __name__ == "__main__":
    sys.exit(main())
