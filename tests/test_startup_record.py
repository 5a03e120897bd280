"""``worker.startup``: the record a process keeps of its own start
(``tracing.startup_reached`` / ``startup_phase`` / ``startup_ready``) and
writes, with the compile totals of the moment, into whatever profiler
session is open (``tracing.replay_program_facts``), read back with
``jax.profiler.ProfileData`` as ``tests/test_step_spans.py`` reads the step
spans.

One tiny ``_LLMReplica`` and one profile session serve the cases about the
trace: two writes with a compile between them, then 40 tokens stepped by the
engine's own thread. The compile counters are fed by hand there (the
listeners' own functions), so that the session's cases do not place a
persistent cache under the test process; a process of its own shows that
this JAX passes the compiled function's name. Two cases start workers: a
serve replica on the weight plane and a training worker leased a (pretend)
chip, for the milestones only a worker process has.
"""

import glob
import subprocess
import sys
import time

import jax
import pytest

from ray_tpu._internal import compile_cache
from ray_tpu.util import tracing

TILES = ("main_us", "register_us", "wait_us", "backend_us", "weights_us",
         "engine_us", "other_us")
NEW = 40  # tokens of the stepped request: the thread's turns pass 32 once


@pytest.fixture
def fresh(monkeypatch):
    """The record of a process that has reached nothing yet."""
    monkeypatch.setattr(tracing, "_startup", {})
    monkeypatch.setattr(tracing, "_startup_clock", {})


def _tiles(record):
    return sum(record.get(k, 0) for k in TILES)


def test_the_phases_and_other_add_up_to_ready(fresh):
    import psutil

    tracing.startup_reached("main")
    tracing.startup_reached("register")
    time.sleep(0.01)
    tracing.startup_reached("wait")
    with tracing.startup_phase("backend") as phase:
        time.sleep(0.02)
        phase.count(devices=4)
    with tracing.startup_phase("weights") as weights:
        weights.count(weights_source="init")
        time.sleep(0.01)
    time.sleep(0.03)  # a phase nobody named
    with tracing.startup_phase("engine"):
        pass
    tracing.startup_ready()
    record = tracing.startup_record()
    assert _tiles(record) == record["ready_us"]
    assert record["wait_us"] >= 10_000 and record["backend_us"] >= 20_000
    assert record["weights_us"] == weights.us >= 10_000
    assert record["other_us"] >= 30_000  # shows as a number, not as a gap
    assert record["devices"] == 4 and record["weights_source"] == "init"
    # the kernel's start of this process, to its clock's tick (10 ms), and
    # the record laid beside the wall clock
    born = psutil.Process().create_time()
    assert abs(record["process_start_wall_us"] / 1e6 - born) < 0.05
    now = (record["process_start_wall_us"] + record["ready_us"]) / 1e6
    assert abs(now - time.time()) < 1.0
    assert all(isinstance(v, int) for k, v in record.items() if k.endswith("_us"))


def test_a_phase_that_did_not_happen_is_absent_not_zero(fresh):
    assert tracing.startup_record() is None  # not ready: nothing to write
    tracing.replay_program_facts()
    tracing.startup_ready()
    record = tracing.startup_record()
    # no worker's milestones, no chip attach, no weights, no engine; and a
    # process whose compiles nothing counts has no compile totals
    assert set(record) == {"process_start_wall_us", "other_us", "ready_us"}
    assert record["other_us"] == record["ready_us"] > 0


def test_a_ready_process_leaves_its_record_alone(fresh):
    tracing.startup_reached("wait")
    first = tracing._startup["wait_us"]
    tracing.startup_reached("wait")  # a worker's second task
    assert tracing._startup["wait_us"] == first
    tracing.startup_ready()
    before = tracing.startup_record()
    with tracing.startup_phase("weights") as later:
        later.count(weights_source="plane")
        time.sleep(0.005)
    tracing.startup_reached("register")
    tracing.startup_ready()
    # a second replica of the process still reads its own stopwatch
    assert later.us >= 5_000
    assert tracing.startup_record() == before


def test_a_phase_that_raised_is_not_in_the_record(fresh):
    with pytest.raises(RuntimeError):
        with tracing.startup_phase("weights") as weights:
            weights.count(weights_source="plane")
            raise RuntimeError("no such weights")
    tracing.startup_ready()
    assert "weights_us" not in tracing.startup_record()
    assert "weights_source" not in tracing.startup_record()


# -- the record in a profiler session ----------------------------------------


def _read(logdir):
    """[{name, thread, start, end, stats}] of the host plane, nanoseconds,
    in start order."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")
    (plane,) = [p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU"]
    spans = []
    for thread, line in enumerate(plane.lines):
        for ev in line.events:
            if ev.name.split(".")[0] in ("engine", "worker", "train", "test"):
                spans.append({
                    "name": ev.name, "thread": thread, "start": ev.start_ns,
                    "end": ev.start_ns + ev.duration_ns,
                    "stats": dict(ev.stats)})
    return sorted(spans, key=lambda s: (s["start"], -s["end"]))


def _compiled(name, seconds, hit):
    """What JAX's monitoring tells ``compile_cache`` about one program."""
    compile_cache._on_event(compile_cache._CACHE_REQUEST)
    if hit:
        compile_cache._on_event(compile_cache._CACHE_HIT)
    compile_cache._on_duration(
        compile_cache._BACKEND_COMPILE, seconds, fun_name=name)
    compile_cache._on_duration(compile_cache._TRACE_LOWER[0], 0.25, fun_name=name)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.engine import GenerationRequest
    from ray_tpu.llm.serving import _LLMReplica

    patch = pytest.MonkeyPatch()
    patch.setattr(tracing, "_startup", {})
    patch.setattr(tracing, "_startup_clock", {})
    patch.setattr(tracing, "_program_facts", {})
    patch.setattr(tracing, "_enabled", False)
    patch.setattr(compile_cache, "_counting", True)
    patch.setattr(compile_cache, "_stats", dict.fromkeys(compile_cache._stats, 0))
    patch.setattr(compile_cache, "_compile_s_by_name", {})
    jax.devices()  # this process's backend is up long before its replica
    replica = _LLMReplica(LLMConfig(
        model_id="llama-tiny", max_seq_len=128, max_batch_size=2,
        kv_cache_blocks=8, kv_block_size=16, seed=0))
    tracing.program_fact("train.remat_plan", kept="attn_k+attn_v", budget_bytes=7)
    _compiled("jit(_prefill_impl)", 2.0, hit=False)
    logdir = str(tmp_path_factory.mktemp("startup"))
    try:
        with tracing.device_profile(logdir):
            with tracing.annotate_device_trace("test.replay"):
                tracing.replay_program_facts()
                _compiled("jit(_decode_impl)", 3.5, hit=True)
                _compiled("jit(_prefill_impl)", 2.5, hit=True)
                _compiled("jit(commit_impl)", 0.5, hit=True)
                _compiled("jit(_insert_row)", 0.125, hit=False)
                tracing.replay_program_facts()
            with tracing.annotate_device_trace("test.stepper"):
                (answer,) = replica._engine.generate([GenerationRequest(
                    token_ids=[5, 6, 7, 8], max_new_tokens=NEW, temperature=0.0)])
            info = replica.runtime_info()
        spans = _read(logdir)
    finally:
        replica.shutdown()
        patch.undo()

    def scenario(name):
        (mark,) = [s for s in spans if s["name"] == f"test.{name}"]
        return [s for s in spans if mark["start"] <= s["start"]
                and s["end"] <= mark["end"] and s is not mark]

    return {"replay": scenario("replay"), "stepper": scenario("stepper"),
            "spans": spans, "info": info, "answer": answer,
            "steps": replica._engine.stepper_stats()["steps"]}


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def test_replay_writes_the_record_and_the_kept_facts(recorded):
    names = [s["name"] for s in recorded["replay"]]
    assert names == ["train.remat_plan", "worker.startup"] * 2
    plan = recorded["replay"][0]["stats"]
    assert plan == {"kept": "attn_k+attn_v", "budget_bytes": 7}
    record = recorded["replay"][1]["stats"]
    assert _tiles(record) == record["ready_us"]
    assert record["weights_source"] == "init" and record["weights_us"] > 0
    assert record["weights_bytes"] > 100_000 and record["engine_us"] > 0
    # what did not happen in this process is absent: it is no worker, and
    # its backend was up already
    for key in ("main_us", "register_us", "wait_us", "backend_us", "devices"):
        assert key not in record


def test_a_second_write_has_newer_compile_totals_and_the_same_phases(recorded):
    first, second = [s["stats"] for s in _named(recorded["replay"], "worker.startup")]
    assert (first["compile_us"], first["trace_lower_us"]) == (2_000_000, 250_000)
    # nothing had been counted when the replica was built: what a write's
    # totals say came after ``ready_us``
    assert first["compile_at_ready_us"] == second["compile_at_ready_us"] == 0
    assert (first["programs"], first["cache_requests"], first["cache_hits"]) == (1, 1, 0)
    assert first["slowest"] == "jit(_prefill_impl):2.0"
    assert (second["compile_us"], second["trace_lower_us"]) == (8_625_000, 1_250_000)
    assert (second["programs"], second["cache_requests"], second["cache_hits"]) == (5, 5, 3)
    # by name, summed: a prompt length's programs share one
    assert second["slowest"] == (
        "jit(_prefill_impl):4.5+jit(_decode_impl):3.5+jit(commit_impl):0.5")
    compile_keys = {"compile_us", "trace_lower_us", "programs", "cache_requests",
                    "cache_hits", "slowest"}
    assert ({k: v for k, v in first.items() if k not in compile_keys}
            == {k: v for k, v in second.items() if k not in compile_keys})


def test_the_stepping_thread_writes_on_its_turns_0_and_32_and_not_between(recorded):
    spans = recorded["stepper"]
    assert len(recorded["answer"].token_ids) == NEW
    assert 32 < recorded["steps"] <= 64
    steps = _named(spans, "engine.step")
    writes = _named(spans, "worker.startup")
    assert len(steps) == recorded["steps"] and len(writes) == 2
    (thread,) = {s["thread"] for s in steps}
    assert {w["thread"] for w in writes} == {thread}
    before = [sum(1 for s in steps if s["end"] <= w["start"]) for w in writes]
    assert before == [0, 32]
    # no step is open around a write: the attribution of idle time sees an
    # instant on a thread with no step
    for w in writes:
        assert not [s for s in steps if s["start"] < w["end"] and w["start"] < s["end"]]
    # and the kept facts go with it
    assert len(_named(spans, "train.remat_plan")) == 2


def test_runtime_info_startup_is_the_last_record_written(recorded):
    last = _named(recorded["spans"], "worker.startup")[-1]["stats"]
    assert recorded["info"]["startup"] == last
    # "compile" stays what the accepted drivers read
    assert set(recorded["info"]["compile"]) == {
        "compile_s", "trace_lower_s", "programs", "cache_requests", "cache_hits"}


# -- the counters' source, and processes that are not this one ---------------


def test_this_jax_names_the_function_it_compiled(tmp_path):
    """``slowest`` rests on ``fun_name`` arriving with the backend-compile
    event; a JAX that stops passing it leaves the key out."""
    done = subprocess.run(
        [sys.executable, "-c", (
            "import jax, jax.numpy as jnp\n"
            "from ray_tpu._internal import compile_cache\n"
            "from ray_tpu.util import tracing\n"
            "assert compile_cache.totals_us() == {}\n"
            "compile_cache.configure()\n"
            "def a_step_of_mine(x):\n"
            "    return jnp.tanh(x) @ x\n"
            "jax.jit(a_step_of_mine)(jnp.ones((8, 8))).block_until_ready()\n"
            "tracing.startup_ready()\n"
            "record = tracing.startup_record()\n"
            "assert record['programs'] >= 1 and record['compile_us'] > 0, record\n"
            "assert record['cache_requests'] >= record['cache_hits'] >= 0\n"
            "assert 'a_step_of_mine' in record['slowest'], record\n"
            "at_ready = record['compile_at_ready_us']\n"
            "assert at_ready == record['compile_us'] + record['trace_lower_us']\n"
            "jax.jit(lambda x: x * 3)(jnp.ones(3)).block_until_ready()\n"
            "later = tracing.startup_record()\n"
            "assert later['compile_at_ready_us'] == at_ready\n"
            "assert later['compile_us'] + later['trace_lower_us'] > at_ready\n")],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "PYTHONPATH": ":".join(sys.path),
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert done.returncode == 0, done.stderr


def test_a_process_without_jax_is_not_made_to_import_it():
    """Every worker keeps the record, a plain Python one too: neither the
    milestones nor a write may cost it an ``import jax``."""
    done = subprocess.run(
        [sys.executable, "-c", (
            "import sys\n"
            "from ray_tpu.util import tracing\n"
            "tracing.startup_reached('main')\n"
            "tracing.startup_reached('register')\n"
            "tracing.startup_reached('wait')\n"
            "tracing.startup_ready()\n"
            "tracing.replay_program_facts()\n"
            "record = tracing.startup_record()\n"
            "assert record['main_us'] > 0 and 'compile_us' not in record\n"
            "assert sum(record[k] for k in ('main_us', 'register_us', 'wait_us',"
            " 'other_us')) == record['ready_us']\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n")],
        capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr


def test_weights_resolve_s_is_weights_us_in_a_weight_plane_replica(ray_start_regular):
    """A serve replica on the weight plane, in a worker of its own: the
    worker's milestones, the phases that tile its start, and one stopwatch
    behind ``weights_resolve_s`` and ``weights_us``."""
    from ray_tpu import serve, weights
    from ray_tpu.llm.config import LLMConfig
    from ray_tpu.llm.serving import build_llm_deployment
    from ray_tpu.models.llama import init_params
    from ray_tpu.parallel.sharding import unbox_params

    llm_config = LLMConfig(
        model_id="llama-tiny", max_seq_len=64, max_new_tokens=4,
        resources_per_replica={"CPU": 1.0})
    weights.publish("t/startup", unbox_params(
        init_params(llm_config.build_model_config(), jax.random.PRNGKey(0))))
    serve.start(proxy=False)
    handle = serve.run(build_llm_deployment(llm_config, weights_name="t/startup"),
                       name="llm-startup", route_prefix=None, _proxy=False)
    try:
        record = handle.runtime_info.remote().result(timeout_s=120)["startup"]
        warm = handle.warmup.remote().result(timeout_s=60)
    finally:
        serve.shutdown()
    assert record["weights_source"] == "plane"
    assert round(warm["weights_resolve_s"] * 1e6) == record["weights_us"] > 0
    assert _tiles(record) == record["ready_us"]
    for key in ("main_us", "register_us", "wait_us", "backend_us", "weights_us",
                "engine_us", "other_us"):
        assert record[key] > 0, key
    assert record["devices"] == jax.device_count()
    assert "compile_us" not in record  # a CPU worker: no cache was placed


def _loop_reports_its_record(config):
    from ray_tpu import train
    from ray_tpu.util import tracing

    from ray_tpu._internal.platform import backend_initialized

    train.report({"startup": tracing.startup_record(),
                  "backend": backend_initialized()})


@pytest.mark.parametrize("chips", [0, 1])
def test_a_training_worker_is_ready_when_its_loop_is_entered(ray_start_regular, chips):
    """With chips leased, ``_jax_worker_setup`` attaches them in a phase of
    its own before the loop; a CPU worker's backend is left to its loop."""
    from ray_tpu import train

    resources = {"CPU": 1.0, **({"TPU": float(chips)} if chips else {})}
    fitted = train.JaxTrainer(
        _loop_reports_its_record,
        scaling_config=train.ScalingConfig(
            num_workers=1, use_tpu=bool(chips), resources_per_worker=resources),
        run_config=train.RunConfig(name=f"startup-{chips}"),
    ).fit()
    assert fitted.error is None
    record = fitted.metrics["startup"]
    assert _tiles(record) == record["ready_us"]
    assert record["main_us"] > 0 and record["register_us"] > 0
    assert "weights_us" not in record and "engine_us" not in record
    if chips:
        assert record["backend_us"] > 0 and record["devices"] == jax.device_count()
        assert record["cache_requests"] >= 0  # a chip owner counts its compiles
    else:
        assert "backend_us" not in record and not fitted.metrics["backend"]
