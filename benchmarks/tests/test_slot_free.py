"""``engine_slot_free_p50_ms`` against a trace built by hand
(``test_hostplane.write_trace``): one thread steps, and six admissions say
how long the slot each took had been nobody's.

    thread S step0 [90,212): admit r1 [91,95) free 0 (the slot's first),
             admit r2 [95,99) free 0
             step1 [222,340): admit r3 [223,226) free 4000, admit r7 [226,229)
             free 9000 (the pool has no blocks for it: tried again below)
             step2 [365,505): admit r7 [366,396) free 150000, admit r4
             [396,399) free 2000
    the parent's program writes no ``slot_free_us`` on its admissions
"""

from test_hostplane import DEVICE, write_trace

from benchmarks.harness import hostplane
from benchmarks.layer_metrics import engine_slot_free_p50_ms


def _admit(start, end, rid, free_us=None):
    counts = {"request_id": rid, "queue_wait_us": 10, "prompt_tokens": 128}
    if free_us is not None:
        counts["slot_free_us"] = free_us
    return (start, end, "engine.admit", counts)


def _step(start, end, n):
    return (start, end, "engine.step",
            {"step": n, "pending": 1, "prefilling": 0, "wall_us": start})


def _thread(free):
    return [
        _step(90, 212, 0), _admit(91, 95, 1, free and 0), _admit(95, 99, 2, free and 0),
        _step(222, 340, 1), _admit(223, 226, 3, free and 4000),
        _admit(226, 229, 7, free and 9000),
        _step(365, 505, 2), _admit(366, 396, 7, free and 150000),
        _admit(396, 399, 4, free and 2000),
    ]


def test_the_median_over_last_admissions_that_were_not_a_slots_first(
        tmp_path, monkeypatch):
    paths = {
        "change": write_trace(tmp_path / "change.xplane.pb", DEVICE, (_thread(True),)),
        "parent": write_trace(tmp_path / "parent.xplane.pb", DEVICE, (_thread(None),)),
    }
    monkeypatch.setattr(hostplane, "path_of", lambda result: paths.get(result.get("trace")))
    # r3 4 ms, r7 150 ms (its last try; the 9 ms one got no blocks), r4 2 ms;
    # r1 and r2 took slots never used
    assert engine_slot_free_p50_ms.read({"trace": "change"}) == 4.0
    # a program that writes no such count: nothing, not a raise
    assert engine_slot_free_p50_ms.read({"trace": "parent"}) is None
    assert engine_slot_free_p50_ms.read({"trace": None}) is None
    assert engine_slot_free_p50_ms.read({}) is None
    assert engine_slot_free_p50_ms.META["moves"] == "out_tok_per_s"
