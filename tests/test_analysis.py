"""Tests for the static-analysis plane (ray_tpu.analysis, `ray_tpu lint`).

Three layers:

- per-checker fixture tests: each rule fires on a minimal positive fixture
  and stays silent on the matching negative one (the contract ISSUE 9's
  acceptance criteria name);
- framework tests: baseline split/round-trip, fingerprint stability, CLI
  exit codes (0 clean / 1 findings or stale / 2 internal error);
- the repo gate: the analyzer over the real ray_tpu package plus the
  committed baseline must report zero new findings and zero stale entries,
  and every exception class must survive a pickle round-trip with its typed
  fields intact (the dynamic twin of RT006).
"""

import inspect
import json
import pickle
import textwrap

import pytest

from ray_tpu import analysis, exceptions
from ray_tpu.analysis import (
    Analyzer,
    apply_baseline,
    checker_catalog,
    load_baseline,
    write_baseline,
)
from ray_tpu.scripts import cli


def _run(tmp_path, files, rules=None):
    """Write a fixture package under tmp_path/pkg and analyze it.

    Findings come back with paths like ``pkg/runtime/mod.py`` so the
    path-scoped rules (RT001's asyncio planes, RT004/RT005 home files) see
    the same shapes they see in the real repo.
    """
    pkg = tmp_path / "pkg"
    for rel, src in files.items():
        p = pkg / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return Analyzer(pkg, rules=rules, rel_to=tmp_path).run()


def _rules(result):
    return [f.rule for f in result.findings]


# ---------------------------------------------------------------- RT001


def test_rt001_flags_blocking_calls_in_async_def(tmp_path):
    result = _run(tmp_path, {
        "runtime/mod.py": """
            import time

            async def bad_sleep():
                time.sleep(1)

            async def bad_result(fut):
                return fut.result()

            async def bad_result_none(fut):
                return fut.result(timeout=None)
        """,
    }, rules=["RT001"])
    assert _rules(result) == ["RT001", "RT001", "RT001"]
    assert "time.sleep" in result.findings[0].message


def test_rt001_silent_on_sync_defs_and_bounded_result(tmp_path):
    result = _run(tmp_path, {
        "runtime/mod.py": """
            import time

            def sync_sleep_is_fine():
                time.sleep(1)

            async def bounded_result_is_fine(fut):
                return fut.result(timeout=5)

            async def nested_sync_def_is_fine():
                def helper():
                    time.sleep(1)
                return helper
        """,
    }, rules=["RT001"])
    assert result.findings == []


def test_rt001_scoped_to_asyncio_planes(tmp_path):
    # collective rendezvous loops legitimately sleep in sync threads; the
    # rule only patrols the asyncio planes (runtime/serve/dag/client/...)
    result = _run(tmp_path, {
        "collective/mod.py": """
            import time

            async def out_of_scope():
                time.sleep(1)
        """,
    }, rules=["RT001"])
    assert result.findings == []


# ---------------------------------------------------------------- RT002


def test_rt002_flags_raw_run_in_executor_and_global_trace_state(tmp_path):
    result = _run(tmp_path, {
        "runtime/worker/core_worker.py": """
            _current_trace = None

            class CoreWorker:
                async def bad(self, fn):
                    return await self.loop.run_in_executor(self._pool, fn)

                async def _run_traced(self, fn):
                    return await self.loop.run_in_executor(self._pool, fn)
        """,
    }, rules=["RT002"])
    msgs = [f.message for f in result.findings]
    assert len(result.findings) == 2
    assert any("run_in_executor" in m for m in msgs)
    assert any("ContextVar" in m for m in msgs)


def test_rt002_silent_on_run_traced_and_contextvar(tmp_path):
    result = _run(tmp_path, {
        "runtime/worker/core_worker.py": """
            import contextvars

            _current_trace = contextvars.ContextVar("trace", default=None)

            class CoreWorker:
                async def good(self, fn):
                    return await self._run_traced(fn)

                async def _run_traced(self, fn):
                    return await self.loop.run_in_executor(self._pool, fn)
        """,
        # run_in_executor outside core_worker.py is other planes' business
        "serve/proxy.py": """
            async def fine(loop, fn):
                return await loop.run_in_executor(None, fn)
        """,
    }, rules=["RT002"])
    assert result.findings == []


# ---------------------------------------------------------------- RT003


def test_rt003_flags_bare_write_to_lock_guarded_attr(tmp_path):
    result = _run(tmp_path, {
        "mod.py": """
            class S:
                def __init__(self):
                    self._count = 0  # exempt: no concurrency yet

                def guarded(self):
                    with self._lock:
                        self._count += 1

                def racy(self):
                    self._count = 0
        """,
    }, rules=["RT003"])
    assert len(result.findings) == 1
    f = result.findings[0]
    assert "S.racy" in f.message and "_count" in f.message


def test_rt003_silent_when_every_write_holds_the_lock(tmp_path):
    result = _run(tmp_path, {
        "mod.py": """
            class S:
                def guarded(self):
                    with self._lock:
                        self._count += 1

                def also_guarded(self):
                    with self._lock:
                        self._count = 0

                def read_only(self):
                    return self._count  # bare reads are not flagged
        """,
    }, rules=["RT003"])
    assert result.findings == []


# ---------------------------------------------------------------- RT004


def test_rt004_flags_registry_violations(tmp_path):
    result = _run(tmp_path, {
        "util/metrics.py": """
            class Counter:
                def __init__(self, name, description="", tag_keys=()):
                    pass

            a = Counter("tasks_total", tag_keys=("node",))
            b = Counter("tasks_total", tag_keys=("replica",))
            c = Counter("BadName")
        """,
        "serve/mod.py": """
            from ..util.metrics import Counter

            d = Counter("stray_metric")
        """,
    }, rules=["RT004"])
    msgs = " | ".join(f.message for f in result.findings)
    assert "declared 2 times" in msgs
    assert "conflicting" in msgs
    assert "not snake_case" in msgs
    assert "outside util/metrics.py" in msgs


def test_rt004_ignores_collections_counter(tmp_path):
    result = _run(tmp_path, {
        "serve/mod.py": """
            from collections import Counter

            votes = Counter("abracadabra")
        """,
    }, rules=["RT004"])
    assert result.findings == []


# ---------------------------------------------------------------- RT005


def test_rt005_flags_stray_key_literals_once_each(tmp_path):
    result = _run(tmp_path, {
        "mod.py": '''
            def keys(group, epoch, rank):
                plain = "colabort:" + group
                fstr = f"colmember:{group}:{epoch}:{rank}"
                return plain, fstr
        ''',
    }, rules=["RT005"])
    # one finding per literal — the f-string head must not double-report
    assert len(result.findings) == 2
    assert {f.line for f in result.findings} == {3, 4}


def test_rt005_exempts_registry_and_docstrings(tmp_path):
    result = _run(tmp_path, {
        "runtime/gcs/keys.py": """
            COLLECTIVE_ABORT = "colabort:"
        """,
        "mod.py": '''
            def sweeper():
                """Sweeps colabort:<group> keys (prose is fine)."""
                return None
        ''',
    }, rules=["RT005"])
    assert result.findings == []


# ---------------------------------------------------------------- RT006


def test_rt006_flags_custom_init_without_reduce(tmp_path):
    result = _run(tmp_path, {
        "exceptions.py": """
            class Bad(Exception):
                def __init__(self, code, detail):
                    self.code = code
                    super().__init__(f"error {code}: {detail}")
        """,
    }, rules=["RT006"])
    assert len(result.findings) == 1
    assert "Bad" in result.findings[0].message


def test_rt006_silent_with_reduce_or_default_init(tmp_path):
    result = _run(tmp_path, {
        "exceptions.py": """
            class Good(Exception):
                def __init__(self, code):
                    self.code = code
                    super().__init__(f"error {code}")

                def __reduce__(self):
                    return (type(self), (self.code,))

            class AlsoGood(Exception):
                pass
        """,
    }, rules=["RT006"])
    assert result.findings == []


# ---------------------------------------------------------------- RT007


def test_rt007_flags_event_registry_violations(tmp_path):
    result = _run(tmp_path, {
        "util/events.py": """
            class EventName(str):
                pass

            A = EventName("replica_state")
            B = EventName("replica_state")
            C = EventName("BadName")
            D = EventName("dyn_" + "amic")
        """,
        "serve/mod.py": """
            from ..util.events import EventName

            E = EventName("stray_event")
        """,
    }, rules=["RT007"])
    msgs = " | ".join(f.message for f in result.findings)
    assert "declared 2 times" in msgs
    assert "not snake_case" in msgs
    assert "literal string" in msgs
    assert "outside util/events.py" in msgs


def test_rt007_ignores_unrelated_classes(tmp_path):
    result = _run(tmp_path, {
        "serve/mod.py": """
            class EventName(str):
                pass

            local = EventName("Whatever Goes")
        """,
    }, rules=["RT007"])
    # an unimported local class of the same name is not the registry
    assert result.findings == []


# ---------------------------------------------------------------- RT009


def test_rt009_flags_hot_path_host_roundtrips(tmp_path):
    result = _run(tmp_path, {
        "llm/engine.py": """
            import jax
            import jax.numpy as jnp
            import numpy as np

            def decode_step(logits, x):
                a = jax.device_get(logits)
                logits.block_until_ready()
                b = np.asarray(jnp.argmax(logits, axis=-1))
                c = float(jnp.max(logits))
                return a, b, c
        """,
    }, rules=["RT009"])
    assert _rules(result) == ["RT009"] * 4
    msgs = " ".join(f.message for f in result.findings)
    assert "host_sync" in msgs


def test_rt009_host_sync_chokepoint_and_host_values_exempt(tmp_path):
    result = _run(tmp_path, {
        "kvcache/manager.py": """
            import jax.numpy as jnp
            import numpy as np

            def host_sync(x):
                return np.asarray(x.block_until_ready())

            def admit(token_ids, row):
                ids = np.asarray(token_ids, np.int32)  # host list: fine
                tok = int(row[0])                      # host array: fine
                dev = jnp.asarray(ids)                 # host->device: fine
                return ids, tok, dev
        """,
        "serve/router.py": """
            import jax

            def off_hot_path(x):
                return jax.device_get(x)  # out of scope for RT009
        """,
    }, rules=["RT009"])
    assert result.findings == []


# ---------------------------------------------------------------- RT010


def test_rt010_flags_direct_reduce_in_train(tmp_path):
    result = _run(tmp_path, {
        "train/loop.py": """
            from ray_tpu.collective import allreduce, reducescatter

            def train_step(group, grads, tensor):
                summed = group.allreduce(grads)
                shard = group.reducescatter(tensor)
                also = allreduce(grads)
                scattered = reducescatter(tensor)
                return summed, shard, also, scattered
        """,
    }, rules=["RT010"])
    assert _rules(result) == ["RT010"] * 4
    msgs = " ".join(f.message for f in result.findings)
    assert "reduce_gradients" in msgs


def test_rt010_wrapper_and_non_train_exempt(tmp_path):
    result = _run(tmp_path, {
        "train/collective.py": """
            from .. import collective as _collective

            def allreduce(value, op=None):
                kwargs = {} if op is None else {"op": op}
                return _collective.allreduce(value, **kwargs)

            def reduce_gradients(grads):
                return gradient_scheduler().step(grads)
        """,
        "collective/scheduler.py": """
            def reduce(self, group, flat):
                return group.allreduce(flat)  # scheduler internals: fine
        """,
        "rllib/learner.py": """
            def sync(group, grads):
                return group.allreduce(grads)  # not train/: out of scope
        """,
    }, rules=["RT010"])
    assert result.findings == []


# ---------------------------------------------------------------- RT011


def test_rt011_flags_raw_puts_in_serving_kv_paths(tmp_path):
    result = _run(tmp_path, {
        "kvtier/tier.py": """
            async def export(worker, meta, bufs):
                oid, _ = await worker.put_serialized(meta, bufs)
                return oid
        """,
        "kvcache/spill.py": """
            def spill(client, key, blob):
                return client.call("store_put", key, blob)
        """,
        "llm/engine.py": """
            async def stash(worker, meta, bufs):
                return await worker.put_serialized(meta, bufs)
        """,
    }, rules=["RT011"])
    assert _rules(result) == ["RT011"] * 3
    msgs = " ".join(f.message for f in result.findings)
    assert "_internal/transfer.py" in msgs
    assert "store_put" in msgs


def test_rt011_transfer_layer_and_other_planes_exempt(tmp_path):
    result = _run(tmp_path, {
        # the chokepoint itself: outside the patrolled paths
        "_internal/transfer.py": """
            async def put_chunks(worker, meta, bufs):
                return await worker.put_serialized(meta, bufs)
        """,
        # object plane proper: put_serialized is ITS primitive
        "runtime/worker/core_worker.py": """
            async def put(self, meta, bufs):
                return await self.put_serialized(meta, bufs)
        """,
        # other GCS RPCs in serving paths are fine, as is going through
        # the transfer layer
        "kvtier/registry.py": """
            from ray_tpu._internal import transfer

            async def register(client, shipment, worker, values):
                refs = await transfer.put_chunks(worker, values)
                return client.call("kvtier_register", shipment), refs
        """,
    }, rules=["RT011"])
    assert result.findings == []


# ---------------------------------------------------------------- RT013


def test_rt013_flags_bank_mutation_outside_store(tmp_path):
    result = _run(tmp_path, {
        "llm/engine.py": """
            def attach(self, store, tree, slot):
                store._bank = rebuild(store._bank, tree, slot)
        """,
        "serve/replica.py": """
            def hot_swap(self, store, tree, slot):
                store._write_slot(store._bank, tree, slot)
        """,
        "kvcache/manager.py": """
            def steal(self, pool):
                self._adapter_bank = pool
        """,
    }, rules=["RT013"])
    assert _rules(result) == ["RT013"] * 3  # 2 bank assigns + 1 raw call
    msgs = " ".join(f.message for f in result.findings)
    assert "AdapterStore" in msgs


def test_rt013_store_itself_and_other_planes_exempt(tmp_path):
    result = _run(tmp_path, {
        # the chokepoint itself: outside the patrolled paths
        "lora/store.py": """
            def acquire(self, adapter_id):
                self._bank = self._write_slot(self._bank, tree, slot)
        """,
        # leasing through the store API in serving paths is fine
        "llm/serving.py": """
            def resolve(self, store, adapter_id):
                lease = store.acquire(adapter_id)
                return lease
        """,
        # unrelated trains-plane code with its own _bank attr name is
        # out of scope by path
        "train/optim.py": """
            def init(self):
                self._bank = {}
        """,
    }, rules=["RT013"])
    assert result.findings == []


# ------------------------------------------------------------- framework


def test_catalog_has_all_thirteen_rules():
    assert sorted(checker_catalog()) == [
        "RT001", "RT002", "RT003", "RT004", "RT005", "RT006", "RT007",
        "RT008", "RT009", "RT010", "RT011", "RT012", "RT013",
    ]


def test_unknown_rule_id_rejected(tmp_path):
    with pytest.raises(ValueError, match="RT999"):
        Analyzer(tmp_path, rules=["RT999"])


def test_parse_error_reported_not_fatal(tmp_path):
    result = _run(tmp_path, {
        "broken.py": "def oops(:\n",
        "fine.py": "x = 1\n",
    })
    assert result.files_scanned == 1
    assert len(result.parse_errors) == 1
    assert "broken.py" in result.parse_errors[0]


def test_fingerprint_excludes_line_number():
    a = analysis.Finding(rule="RT001", path="p.py", line=3, message="m")
    b = analysis.Finding(rule="RT001", path="p.py", line=300, message="m")
    assert a.fingerprint == b.fingerprint


def test_baseline_split_and_round_trip(tmp_path):
    old = analysis.Finding(rule="RT003", path="a.py", line=1, message="old")
    fixed = analysis.Finding(rule="RT003", path="b.py", line=2, message="gone")
    fresh = analysis.Finding(rule="RT001", path="c.py", line=3, message="new")
    path = write_baseline([old, fixed], tmp_path / "baseline.json")
    entries = load_baseline(path)

    new, suppressed, stale = apply_baseline([old, fresh], entries)
    assert new == [fresh]
    assert suppressed == [old]
    assert [e["message"] for e in stale] == ["gone"]


def test_malformed_baseline_raises(tmp_path):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps({"version": 99}))
    with pytest.raises(ValueError, match="unsupported baseline"):
        load_baseline(p)
    assert load_baseline(tmp_path / "missing.json") == []


# ------------------------------------------------------------------ CLI


def _write_fixture(tmp_path, src):
    d = tmp_path / "scan"
    d.mkdir()
    (d / "mod.py").write_text(textwrap.dedent(src))
    return d


def test_cli_lint_exit_0_on_clean_tree(tmp_path, capsys):
    d = _write_fixture(tmp_path, "x = 1\n")
    assert cli.main(["lint", "--no-baseline", str(d)]) == 0
    assert "0 finding(s)" in capsys.readouterr().out


def test_cli_lint_exit_1_on_findings_and_json_shape(tmp_path, capsys):
    d = _write_fixture(tmp_path, """
        class Bad(Exception):
            def __init__(self, code):
                self.code = code
    """)
    (d / "mod.py").rename(d / "exceptions.py")
    assert cli.main(["lint", "--no-baseline", "--json", str(d)]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["files_scanned"] == 1
    assert doc["counts"] == {"RT006": 1}
    assert doc["findings"][0]["rule"] == "RT006"
    assert doc["baselined"] == 0 and doc["stale_baseline"] == []


def test_cli_lint_exit_1_on_stale_baseline_entry(tmp_path, capsys):
    d = _write_fixture(tmp_path, "x = 1\n")
    baseline = tmp_path / "baseline.json"
    write_baseline(
        [analysis.Finding(rule="RT001", path="gone.py", line=1, message="m")],
        baseline,
    )
    assert cli.main(["lint", "--baseline", str(baseline), str(d)]) == 1
    assert "stale baseline" in capsys.readouterr().out


def test_cli_lint_exit_2_on_internal_error(tmp_path, capsys):
    d = _write_fixture(tmp_path, "x = 1\n")
    assert cli.main(["lint", "--rules", "RT999", str(d)]) == 2
    assert "internal error" in capsys.readouterr().err


def test_cli_lint_baseline_update_writes_file(tmp_path, capsys):
    d = _write_fixture(tmp_path, """
        class Bad(Exception):
            def __init__(self, code):
                self.code = code
    """)
    (d / "mod.py").rename(d / "exceptions.py")
    baseline = tmp_path / "baseline.json"
    assert cli.main(
        ["lint", "--baseline-update", "--baseline", str(baseline), str(d)]
    ) == 0
    assert len(load_baseline(baseline)) == 1
    # and with the baseline applied the same tree now gates clean
    capsys.readouterr()
    assert cli.main(["lint", "--baseline", str(baseline), str(d)]) == 0


# -------------------------------------------------------------- the gate


def test_repo_gate_zero_new_findings_zero_stale():
    """The committed invariant: the live tree minus the committed baseline
    is clean, and the baseline holds no entries for already-fixed findings
    (shrink-only policy). A failure here means either fix the new finding
    or—only for pre-existing debt—run `ray_tpu lint --baseline-update`."""
    pkg_root = analysis.DEFAULT_BASELINE_PATH.parents[1]
    repo_root = pkg_root.parent
    result = Analyzer(pkg_root, rel_to=repo_root).run()
    assert result.parse_errors == []
    assert result.files_scanned > 150

    new, _suppressed, stale = apply_baseline(
        result.findings, load_baseline()
    )
    assert new == [], "\n".join(
        f"{f.path}:{f.line}: {f.rule} {f.message}" for f in new
    )
    assert stale == [], (
        "baseline entries for fixed findings — shrink the baseline: "
        + json.dumps(stale, indent=2)
    )


# ------------------------------------------------- exception pickle gate


_EXC_INSTANCES = [
    exceptions.RayTpuError("boom"),
    exceptions.TaskError("f", "tb text", ValueError("root cause")),
    exceptions.ActorError("actor failed"),
    exceptions.ActorDiedError("abc123", "oom killed"),
    exceptions.ActorUnschedulableError("no feasible node"),
    exceptions.WorkerCrashedError("sigsegv"),
    exceptions.NodeDiedError("node-2 heartbeat lost"),
    exceptions.ObjectLostError("obj1", "all copies gone"),
    exceptions.OwnerDiedError("obj2", "owner died"),
    exceptions.ObjectStoreFullError("store full"),
    exceptions.OutOfMemoryError("rss over limit"),
    exceptions.TaskCancelledError("task-7"),
    exceptions.GetTimeoutError("timed out after 5s"),
    exceptions.RuntimeEnvSetupError("pip env failed"),
    exceptions.PlacementGroupSchedulingError("infeasible bundle"),
    exceptions.CollectiveAbortedError("ring0", 3, "member died"),
    exceptions.BackPressureError("replica-1", 4, 9, 0.25),
    exceptions.DeadlineExceededError("deploy", 1.5, 1.0, "handle"),
    exceptions.ReplicaDrainingError("replica-2"),
    exceptions.NodeFencedError("node-3", "gcs unreachable"),
    exceptions.MeshValidationError("tp=3 does not divide 8 devices"),
    exceptions.NoAcceleratorError("a train worker needs 1 TPU chip(s)"),
    exceptions.RpcError("connection reset"),
    exceptions.PendingCallsLimitExceeded("queue cap"),
]


@pytest.mark.parametrize(
    "exc", _EXC_INSTANCES, ids=lambda e: type(e).__name__
)
def test_exception_pickle_round_trip(exc):
    """Every framework exception travels as an object value; pickling must
    preserve its concrete type, message, and typed fields (the serve retry
    envelope reads retry_after_s/deadline off the instance caller-side)."""
    back = pickle.loads(pickle.dumps(exc))
    assert type(back) is type(exc)
    assert str(back) == str(exc)
    assert set(back.__dict__) == set(exc.__dict__)
    for key, want in exc.__dict__.items():
        got = back.__dict__[key]
        if isinstance(want, BaseException):
            # exceptions compare by identity; structural check instead
            assert type(got) is type(want) and got.args == want.args
        else:
            assert got == want, key


def test_every_exception_class_is_round_tripped():
    """Coverage guard: adding an exception class without extending the
    round-trip list above fails here, not in production."""
    declared = {
        obj
        for obj in vars(exceptions).values()
        if inspect.isclass(obj) and issubclass(obj, exceptions.RayTpuError)
    }
    covered = {type(e) for e in _EXC_INSTANCES}
    assert declared <= covered, sorted(
        c.__name__ for c in declared - covered
    )
