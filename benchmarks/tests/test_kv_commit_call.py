"""``kv_commit_call_p50_ms`` against traces built by hand
(``test_hostplane.write_trace``): the median over every ``kv.commit`` span,
tails and admissions alike, whatever counts the program writes on them.

    thread S step0 [90,212): emit [204,211): commit(tail) [206,210): extract [207,208)
             step1 [222,340): admit [223,300): commit [260,272)
             step2 [365,505): admit [366,396): commit [390,393);
             emit [503,504.9): commit(tail) [503.1,504.6)
    durations 4, 12, 3 and 1.5 us: the median is 3.5 us
    a program that opens no ``kv.commit`` span (a family with state: prefix
    reuse refused, no commit) gives nothing
"""

from test_hostplane import DEVICE, write_trace

from benchmarks.harness import hostplane
from benchmarks.layer_metrics import kv_commit_call_p50_ms


def _step(start, end, n):
    return (start, end, "engine.step",
            {"step": n, "pending": 1, "prefilling": 0, "wall_us": start})


def _commit(start, end, blocks, counted, tail=False):
    counts = {"blocks": blocks, **({"tail": 1} if tail else {})}
    if counted:  # what the program counts since one program writes a call's blocks
        counts.update(dispatches=(1 if blocks else 0) + tail, evictions=blocks)
    return (start, end, "kv.commit", counts)


def _thread(counted, commits=True):
    events = [
        _step(90, 212, 0), (204, 211, "engine.emit", {}),
        _commit(206, 210, 1, counted, tail=True),
        (207, 208, "kv.extract_row", {"blocks": 1}),
        _step(222, 340, 1),
        (223, 300, "engine.admit", {"request_id": 3, "queue_wait_us": 10,
                                    "prompt_tokens": 384}),
        _commit(260, 272, 12, counted),
        _step(365, 505, 2),
        (366, 396, "engine.admit", {"request_id": 4, "queue_wait_us": 10,
                                    "prompt_tokens": 128}),
        _commit(390, 393, 4, counted),
        (503, 504.9, "engine.emit", {}),
        _commit(503.1, 504.6, 0, counted, tail=True),
    ]
    return [e for e in events
            if commits or e[2] not in ("kv.commit", "kv.extract_row")]


def test_the_median_over_every_commit_span(tmp_path, monkeypatch):
    paths = {
        "change": write_trace(tmp_path / "change.xplane.pb", DEVICE, (_thread(True),)),
        "parent": write_trace(tmp_path / "parent.xplane.pb", DEVICE, (_thread(False),)),
        "stateful": write_trace(tmp_path / "stateful.xplane.pb", DEVICE,
                                (_thread(False, commits=False),)),
    }
    monkeypatch.setattr(hostplane, "path_of", lambda result: paths.get(result.get("trace")))
    assert kv_commit_call_p50_ms.read({"trace": "change"}) == 0.0035
    # the parent opens the same span without the counts: the same number
    assert kv_commit_call_p50_ms.read({"trace": "parent"}) == 0.0035
    # no such span, no trace, no traced run: nothing, not a raise
    assert kv_commit_call_p50_ms.read({"trace": "stateful"}) is None
    assert kv_commit_call_p50_ms.read({"trace": None}) is None
    assert kv_commit_call_p50_ms.read({}) is None
    assert kv_commit_call_p50_ms.META["moves"] == "tpot_p50_ms"
    assert kv_commit_call_p50_ms.META["layer"] == "KV manager"
