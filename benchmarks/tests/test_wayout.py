"""The way out's readers (``harness/wayout.py`` and the four metrics built on
it) against a trace built by hand (``test_hostplane.write_trace``): one
thread steps, the replica's loop delivers on another. Times are
microseconds, counts as the program writes them.

    thread S step0 [90,212): admit r1 [91,95) queue 1000, admit r2 [95,99)
             queue 3000
             step1 [222,340): admit r7 [223,226) queue 9000 (no blocks for
             it: tried again below)
             step2 [365,505): admit r7 [366,396) queue 50000 free 150000
    thread L open stream 3 [80,80.1) admit_wait 50
             fan_out [213,215) 2 streams lag 400
             item stream 1 [236,236.1) rtt 20000, stream 2 [242,242.1) 26000
             fan_out [341,343) 2 streams lag 200
             item 1 [367,367.1) 24000
             end r1 [367.2,367.3) wait 900000, item 1 [397,397.1) 30000
             fan_out [506,508) 1 stream lag 1200
             end r2 [508,508.1) wait 1500000, item 2 [530,530.1) 22000
    the parent's program writes the admissions' ``queue_wait_us`` and
    nothing on the loop's thread
"""

import pytest
from test_hostplane import DEVICE, EXPECTED_US, THREAD_A, THREAD_B, US, write_trace

from benchmarks.harness import hostplane, wayout
from benchmarks.layer_metrics import (
    engine_queue_wait_p50_ms, replica_loop_lag_p50_ms, stream_end_lag_p50_ms,
    stream_item_rtt_p50_ms)

NEW_SPANS = (stream_item_rtt_p50_ms, stream_end_lag_p50_ms, replica_loop_lag_p50_ms)


def _admit(start, end, rid, queue_us, free_us=0):
    return (start, end, "engine.admit", {
        "request_id": rid, "queue_wait_us": queue_us, "prompt_tokens": 128,
        "slot_free_us": free_us})


def _step(start, end, n):
    return (start, end, "engine.step",
            {"step": n, "pending": 1, "prefilling": 0, "wall_us": start})


def _fan_out(start, streams, lag_us):
    return (start, start + 2, "replica.fan_out",
            {"streams": streams, "post_lag_us": lag_us})


def _end(start, rid, wait_us):
    return (start, start + 0.1, "replica.stream_end", {
        "request_id": rid, "inbox_wait_us": wait_us, "tokens": 1})


def _item(start, stream, rtt_us):
    return (start, start + 0.1, "replica.stream_item",
            {"stream": stream, "rtt_us": rtt_us})


STEPPER = [
    _step(90, 212, 0), _admit(91, 95, 1, 1000), _admit(95, 99, 2, 3000),
    _step(222, 340, 1), _admit(223, 226, 7, 9000),
    _step(365, 505, 2), _admit(366, 396, 7, 50000, 150000),
]
LOOP = [
    (80, 80.1, "replica.stream_open", {"stream": 3, "admit_wait_us": 50}),
    _fan_out(213, 2, 400), _item(236, 1, 20000), _item(242, 2, 26000),
    _fan_out(341, 2, 200), _item(367, 1, 24000),
    _end(367.2, 1, 900000), _item(397, 1, 30000),
    _fan_out(506, 1, 1200), _end(508, 2, 1500000), _item(530, 2, 22000),
]
TRACED = {"trace": "change", "traced": {"start": 20.0, "stop": 22.5}}


@pytest.fixture
def traces(tmp_path, monkeypatch):
    paths = {
        "change": write_trace(tmp_path / "change.xplane.pb", DEVICE, (STEPPER, LOOP)),
        "parent": write_trace(tmp_path / "parent.xplane.pb", DEVICE, (STEPPER,)),
    }
    monkeypatch.setattr(hostplane, "path_of", lambda result: paths.get(result.get("trace")))
    return paths


def test_the_four_readers_on_values_known_by_construction(traces):
    assert stream_item_rtt_p50_ms.read(TRACED) == 24.0      # 20 22 24 26 30
    # r1's result lay 900 ms, r2's 1500; r7 has not ended
    assert stream_end_lag_p50_ms.read(TRACED) == 1200.0
    assert replica_loop_lag_p50_ms.read(TRACED) == 0.4      # 0.2 0.4 1.2
    # r1 1 ms, r2 3, r7 50 (its last try; the 9 ms one got no blocks)
    assert engine_queue_wait_p50_ms.read(TRACED) == 3.0
    assert stream_end_lag_p50_ms.META["moves"] == "out_tok_per_s"


def test_a_program_without_the_spans_reads_none_and_the_old_count_still_reads(traces):
    parent = dict(TRACED, trace="parent")
    for reader in NEW_SPANS:
        assert reader.read(parent) is None
    # the parent's admissions carry the count already
    assert engine_queue_wait_p50_ms.read(parent) == 3.0
    for reader in NEW_SPANS + (engine_queue_wait_p50_ms,):
        assert reader.read({"trace": None}) is None
        assert reader.read({}) is None


def test_the_chain_and_the_order_checks(traces):
    loaded = hostplane.load(traces["change"])
    links = dict(wayout.chain(loaded))
    assert links["loop lag (fan_out.post_lag_us)"] == [0.4, 0.2, 1.2]
    assert links["the end's inbox wait (stream_end.inbox_wait_us)"] == [900.0, 1500.0]
    # an end with one token leaves two items behind it, 24.4 ms each
    assert links["its items' round trips"] == pytest.approx([48.8, 48.8])
    assert links["replica admission (stream_open.admit_wait_us)"] == [0.05]
    assert links["engine queue (admit.queue_wait_us)"] == [1.0, 3.0, 50.0]
    assert wayout.slot_frees_ms(loaded) == [150.0]
    # stream 1: 24 + 30 ms of round trips in the 0.161 ms between its first
    # and last acknowledgement, as no real stream could
    assert wayout.rtt_over_life_ms(loaded) == pytest.approx(54.0 - 0.161)
    # three clients, no decode dispatch in this trace: no turn without rows
    assert wayout.turn(loaded, 3) is None
    assert "left to the caller" not in wayout.table(loaded, 3)
    assert "none" in wayout.table(hostplane.load(traces["parent"]))


def test_a_turn_by_littles_law(tmp_path):
    """Three clients on two live rows a step: one client is outside the
    engine at a time, three requests were admitted in the 415 us the steps
    span, so a turn's mean is 415 / 3 us; the links account for far more
    here (their counts are not of this toy's scale), and the rest is
    negative with its sign."""
    stepper = list(STEPPER) + [
        (100, 104, "engine.decode_dispatch", {"batch": 2}),
        (370, 374, "engine.decode_dispatch", {"batch": 2})]
    loaded = hostplane.load(write_trace(
        tmp_path / "rows.xplane.pb", DEVICE, (sorted(stepper), LOOP)))
    whole = wayout.turn(loaded, 3)
    assert whole["mean_ms"] == pytest.approx(0.415 / 3)
    # 0.6 + 1200 + 48.8 + 0.05 + 18
    assert whole["seen_ms"] == pytest.approx(1267.45)
    assert whole["left_ms"] == pytest.approx(0.415 / 3 - 1267.45)
    assert "3 clients: a turn's mean 0.138" in wayout.table(loaded, 3)


def test_attribution_is_the_stepping_threads_whatever_the_loop_opens(tmp_path):
    """The loop's regions on a thread of their own take no idle time from
    the spans of the thread that steps: the values are what they were, and
    add up to the idle time exactly."""
    loaded = hostplane.load(write_trace(
        tmp_path / "three.xplane.pb", DEVICE, (THREAD_A, THREAD_B, LOOP)))
    got = hostplane.attribution(loaded)
    assert got == {name: us / 1e6 for name, us in EXPECTED_US.items()}
    ps = sum(round(seconds * 1e12) for seconds in got.values())
    assert ps == 116 * US == round(hostplane.idle_s(loaded) * 1e12)
    assert hostplane.self_times(loaded)["replica.fan_out"] == pytest.approx(6e-6)
