"""The host-plane reduction against a trace built by hand: four decode steps
by two threads, with every state of the attribution in one of three idle
gaps of the device. Times below are microseconds; every expected number was
added up by hand from this table, not with the code under test.

    device   decode [100,200) sample [200,202) | decode [230,330) sample [330,332)
             | decode [400,500) | decode [520,620)       idle: 28 + 68 + 20 = 116
    thread A next [90,215): lock_wait [91,95) step0 [95,212): dispatch [96,101)
             sync [101,204) emit [204,211): commit(tail) [206,210): extract [207,208)
             next [359,505.5): lock_wait [360,365) step2 [365,505): admit [366,396):
             acquire [367,369) prefill [369,390) commit [390,393) insert [393,395);
             dispatch [397,402) sync [402,503) emit [503,504)
             next [505.8,626): lock_wait [506,507) step3 [507,625): dispatch [508,521)
             sync [521,622) emit [622,624)
    thread B next [149,345): lock_wait [150,222) step1 [222,340): dispatch [223,231)
             sync [231,333) emit [333,339)
"""

import os

import pytest

from benchmarks.harness import hostplane, manifest
from benchmarks.layer_metrics import (
    engine_decode_batch_mean, engine_lock_handoff_p50_ms, engine_lock_wait_p50_ms,
    engine_step_gap_host_ms)

US = 1_000_000  # picoseconds


def executor_wait_p50_ms(result):
    """A span's count straight from the host plane, as the reader retired in
    PR 47 took it (the program still opens ``replica.stream_next`` for a
    synchronous generator)."""
    waits = hostplane.counts(
        hostplane.of(result), "replica.stream_next", "executor_wait_us")
    return hostplane.median_or_none([us / 1000.0 for us in waits])

DEVICE = [
    (100, 200, "jit__decode_impl(11)"), (200, 202, "jit__greedy_sample(12)"),
    (230, 330, "jit__decode_impl(11)"), (330, 332, "jit__greedy_sample(12)"),
    (400, 500, "jit__decode_impl(11)"), (520, 620, "jit__decode_impl(11)"),
]
THREAD_A = [
    (90, 215, "replica.stream_next", {"executor_wait_us": 1000}),
    (91, 95, "engine.lock_wait", {}),
    (95, 212, "engine.step", {"step": 0, "pending": 0, "prefilling": 0, "wall_us": 5}),
    (96, 101, "engine.decode_dispatch", {"batch": 2}),
    (101, 204, "engine.sample_sync", {}),
    (204, 211, "engine.emit", {}),
    (206, 210, "kv.commit", {"tail": 1, "blocks": 1}),
    (207, 208, "kv.extract_row", {"blocks": 1}),
    (359, 505.5, "replica.stream_next", {"executor_wait_us": 2000}),
    (360, 365, "engine.lock_wait", {}),
    (365, 505, "engine.step", {"step": 2, "pending": 1, "prefilling": 0, "wall_us": 275}),
    (366, 396, "engine.admit",
     {"request_id": 7, "queue_wait_us": 250000, "prompt_tokens": 128}),
    (367, 369, "kv.acquire", {}),
    (369, 390, "engine.prefill", {"computed_tokens": 128, "cached_tokens": 0}),
    (390, 393, "kv.commit", {"blocks": 8}),
    (393, 395, "kv.insert_row", {}),
    (397, 402, "engine.decode_dispatch", {"batch": 4}),
    (402, 503, "engine.sample_sync", {}),
    (503, 504, "engine.emit", {}),
    (505.8, 626, "replica.stream_next", {"executor_wait_us": 500}),
    (506, 507, "engine.lock_wait", {}),
    (507, 625, "engine.step", {"step": 3, "pending": 0, "prefilling": 0, "wall_us": 417}),
    (508, 521, "engine.decode_dispatch", {"batch": 4}),
    (521, 622, "engine.sample_sync", {}),
    (622, 624, "engine.emit", {}),
    (10, 700, "some.other_host_event", {}),
]
THREAD_B = [
    (149, 345, "replica.stream_next", {"executor_wait_us": 3000}),
    (150, 222, "engine.lock_wait", {}),
    (222, 340, "engine.step", {"step": 1, "pending": 1, "prefilling": 0, "wall_us": 132}),
    (223, 231, "engine.decode_dispatch", {"batch": 3}),
    (231, 333, "engine.sample_sync", {}),
    (333, 339, "engine.emit", {}),
]
# the three gaps, piece by piece, as the docstring's table gives them
EXPECTED_US = {
    "engine.sample_sync": 2 + 1 + 3,
    "engine.emit": 2 + 1 + 6 + 1,
    "kv.commit": 1 + 2 + 3,
    "kv.extract_row": 1,
    "engine.step": 1 + 1 + 1 + 1 + 1 + 1 + 1,
    "lock_handoff": 10 + 5 + 1,
    "engine_idle": 20 + 1,
    "engine.decode_dispatch": 7 + 3 + 12,
    "engine.admit": 1 + 1,
    "kv.acquire": 2,
    "engine.prefill": 21,
    "kv.insert_row": 2,
}


def write_trace(path, device=DEVICE, threads=(THREAD_A, THREAD_B)):
    """An ``.xplane.pb`` with one device plane and one host plane (test
    tooling: TensorFlow's copy of ``xplane.proto``, as ``cut_xplane.py``)."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()

    def fill(plane, line_name, events, line_id):
        names = {m.name: i for i, m in plane.event_metadata.items()}
        stats = {m.name: i for i, m in plane.stat_metadata.items()}
        line = plane.lines.add(id=line_id, name=line_name, timestamp_ns=0)
        for start, end, name, counts in events:
            if name not in names:
                names[name] = len(names) + 1
                plane.event_metadata[names[name]].id = names[name]
                plane.event_metadata[names[name]].name = name
            ev = line.events.add(
                metadata_id=names[name], offset_ps=round(start * US),
                duration_ps=round((end - start) * US))
            for key, value in counts.items():
                if key not in stats:
                    stats[key] = len(stats) + 1
                    plane.stat_metadata[stats[key]].id = stats[key]
                    plane.stat_metadata[stats[key]].name = key
                ev.stats.add(metadata_id=stats[key], int64_value=value)

    tpu = space.planes.add(id=1, name="/device:TPU:0")
    fill(tpu, "XLA Modules", [(s, e, n, {}) for s, e, n in device], 1)
    host = space.planes.add(id=2, name="/host:CPU")
    for i, events in enumerate(threads):
        fill(host, f"replica-{i}", events, 100 + i)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())
    return str(path)


@pytest.fixture
def loaded(tmp_path):
    return hostplane.load(write_trace(tmp_path / "hand.xplane.pb"))


def test_load_keeps_the_step_spans_with_their_counts(loaded):
    assert len(loaded["spans"]) == len(THREAD_A) - 1 + len(THREAD_B)
    assert not hostplane.named(loaded, "some.other_host_event")
    (admit,) = hostplane.named(loaded, "engine.admit")
    assert admit["stats"] == {
        "request_id": 7, "queue_wait_us": 250000, "prompt_tokens": 128}
    assert (admit["start"], admit["end"]) == (366 * US, 396 * US)
    assert [m[2] for m in loaded["modules"]] == [
        "jit__decode_impl", "jit__greedy_sample"] * 2 + ["jit__decode_impl"] * 2
    assert hostplane.idle_intervals(loaded) == [
        (202 * US, 230 * US), (332 * US, 400 * US), (500 * US, 520 * US)]


def test_attribution_adds_up_to_the_idle_time_exactly(loaded):
    got = hostplane.attribution(loaded)
    assert got == {name: us / 1e6 for name, us in EXPECTED_US.items()}
    assert sum(EXPECTED_US.values()) == 116
    ps = sum(round(seconds * 1e12) for seconds in got.values())
    assert ps == 116 * US == round(hostplane.idle_s(loaded) * 1e12)


def test_lock_handoff_and_engine_idle_are_told_apart(tmp_path):
    """Between two steps nobody steps. With a thread asking for the lock
    the gap is the hand-over's; with none it is the engine's own idleness."""
    device = [(0, 10, "jit__decode_impl(1)"), (40, 50, "jit__decode_impl(1)")]
    step = lambda s, e, n: (s, e, "engine.step", {"step": n})
    wait = (12, 38, "engine.lock_wait", {})
    asked = hostplane.load(write_trace(
        tmp_path / "asked.xplane.pb", device,
        ([step(0, 12, 0)], [wait, step(38, 52, 1)])))
    assert hostplane.attribution(asked) == {
        "engine.step": 4e-6, "lock_handoff": 26e-6}
    nobody = hostplane.load(write_trace(
        tmp_path / "nobody.xplane.pb", device,
        ([step(0, 12, 0)], [step(38, 52, 1)])))
    assert hostplane.attribution(nobody) == {
        "engine.step": 4e-6, "engine_idle": 26e-6}
    # seven threads waiting are one hand-over, and a waiter is never the cause
    crowd = hostplane.load(write_trace(
        tmp_path / "crowd.xplane.pb", device,
        ([step(0, 12, 0)], [wait, step(38, 52, 1)], [(5, 60, "engine.lock_wait", {})])))
    assert hostplane.attribution(crowd) == {
        "engine.step": 4e-6, "lock_handoff": 26e-6}
    assert hostplane.lock_handoffs_ms(crowd) == [0.026]
    assert hostplane.lock_handoffs_ms(nobody) == [0.0]


def test_self_times_and_series(loaded):
    own = hostplane.self_times(loaded)
    assert own["engine.lock_wait"] == pytest.approx(82e-6)
    assert own["kv.commit"] == pytest.approx((3 + 3) * 1e-6)
    assert own["engine.admit"] == pytest.approx((30 - 2 - 21 - 3 - 2) * 1e-6)
    assert own["replica.stream_next"] == pytest.approx(
        (125 - 4 - 117 + 146.5 - 5 - 140 + 120.2 - 1 - 118 + 196 - 72 - 118) * 1e-6)
    assert sorted(hostplane.durations_ms(loaded, "engine.lock_wait")) == [
        0.001, 0.004, 0.005, 0.072]
    assert hostplane.lock_handoffs_ms(loaded) == [0.010, 0.005, 0.001]
    # dispatch starts 223, 397, 508 after syncs ending 204, 333, 503
    assert hostplane.step_gaps_host_ms(loaded) == [0.019, 0.064, 0.005]
    # syncs end at 204, 333, 503, 622 after decode modules ending 200, 330, 500, 620
    assert hostplane.sync_lags_ms(loaded) == [0.004, 0.003, 0.003, 0.002]
    assert "lock_handoff" in hostplane.table(loaded)


def test_the_four_readers_on_the_hand_built_trace(tmp_path, monkeypatch):
    path = write_trace(tmp_path / "hand.xplane.pb")
    monkeypatch.setattr(hostplane, "path_of",
                        lambda result: path if result.get("trace") else None)
    traced = {"trace": {"busy_s": 1.0}}
    assert executor_wait_p50_ms(traced) == 1.5   # 0.5 1 2 3
    assert engine_lock_wait_p50_ms.read(traced) == pytest.approx(0.0045)  # 1 4 5 72 us
    assert engine_lock_handoff_p50_ms.read(traced) == 0.005   # 10 5 1 us
    assert engine_step_gap_host_ms.read(traced) == 0.019      # 19 64 5 us
    assert engine_decode_batch_mean.read(traced) == 3.25      # 2 3 4 4
    readers = (engine_lock_wait_p50_ms, engine_lock_handoff_p50_ms,
               engine_step_gap_host_ms, engine_decode_batch_mean)
    for reader in readers:
        assert reader.read({"trace": None}) is None
        assert reader.read({}) is None
    # a program that opens no span (this PR's parent): nothing, not a raise
    bare = write_trace(tmp_path / "bare.xplane.pb", DEVICE,
                       ([(10, 700, "some.other_host_event", {})],))
    monkeypatch.setattr(hostplane, "path_of", lambda result: bare)
    for reader in readers:
        assert reader.read(traced) is None


def test_a_result_finds_its_own_cells_trace(tiny_benchmark, tmp_path, monkeypatch):
    real_cell = manifest.cell  # loads files relative to ROOT and TRAFFIC_DIR
    backlog = real_cell("tiny-backlog")
    monkeypatch.setattr(manifest, "BENCH_DIR", str(tmp_path))
    result = {"trace": {"busy_s": 1.0}, "config": backlog["config_file"],
              # the closed-loop driver adds its own key to the mix it was given
              "mix": dict(backlog["traffic_file"], _clients=4)}
    assert hostplane.path_of(result) is None  # no trace directory yet
    run_dir = tmp_path / "out" / "tiny-backlog" / "trace" / "plugins" / "profile" / "t0"
    os.makedirs(run_dir)
    path = write_trace(run_dir / "host.xplane.pb")
    assert hostplane.path_of(result) == path
    assert engine_decode_batch_mean.read(result) == 3.25
    assert hostplane.path_of(dict(result, trace=None)) is None
    assert hostplane.path_of(dict(result, mix={"kind": "unknown"})) is None


RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "host_steps.xplane.pb")


def test_the_recorded_trace_as_read_from_the_protobuf(monkeypatch):
    """0.55 s of PR 23's first traced ``mistral7b-chat-backlog`` run on a TPU
    v5 lite (five decode steps, three admissions, one retirement), cut by
    ``cut_hostplane.py``. The expected numbers were read straight from the
    protobuf with TensorFlow's ``xplane_pb2`` and a plain sweep, not with the
    code under test. Steps that began before the cut lost their spans and
    kept their modules, so ``engine_idle`` is larger here than in a whole
    trace."""
    loaded = hostplane.load(RECORDED)
    assert len(loaded["modules"]) == 142 and len(loaded["spans"]) == 102
    assert len({s["thread"] for s in loaded["spans"]}) == 6
    assert sum("_decode_impl" in m[2] for m in loaded["modules"]) == 5
    got = hostplane.attribution(loaded)
    # ProfileData hands times over in whole nanoseconds: 141 gaps, each
    # within a nanosecond of the protobuf's picoseconds
    idle_ps = round(hostplane.idle_s(loaded) * 1e12)
    assert abs(idle_ps - 98356191794) <= 141_000
    assert sum(round(s * 1e12) for s in got.values()) == idle_ps
    assert max(got, key=got.get) == "kv.commit"  # 16 + 4 + 4 blocks and a tail of 9
    named_share = 1 - got["engine_idle"] / hostplane.idle_s(loaded)
    assert named_share == pytest.approx(0.7934, abs=1e-3)
    assert sorted(hostplane.counts(loaded, "engine.decode_dispatch", "batch")) == [
        12, 13, 14, 14, 15]
    (tail,) = [s for s in hostplane.named(loaded, "kv.commit") if s["stats"].get("tail")]
    assert tail["stats"]["blocks"] == 9
    monkeypatch.setattr(hostplane, "path_of", lambda result: RECORDED)
    traced = {"trace": {"busy_s": 1.0}}
    # 1.25 1.43 1.52 us (the lock was free) and 502.2 506.6 523.9 601.7 897.1 ms
    assert engine_lock_wait_p50_ms.read(traced) == pytest.approx(504.407681, rel=1e-9)
    assert executor_wait_p50_ms(traced) == 77.616  # the 29th of 57
    assert engine_decode_batch_mean.read(traced) == 13.6
    # an admission precedes four of the five dispatches: prefill, not the host
    assert engine_step_gap_host_ms.read(traced) == pytest.approx(24.94, abs=0.01)
    assert 0.08 < engine_lock_handoff_p50_ms.read(traced) < 0.2
    # host and device planes: a sync ends 2.5-2.7 ms after its decode module
    assert all(2.4 < lag < 2.8 for lag in hostplane.sync_lags_ms(loaded))
