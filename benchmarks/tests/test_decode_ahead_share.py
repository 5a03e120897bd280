"""``engine_decode_ahead_share`` against traces built by hand
(``test_hostplane.write_trace``): a program whose decode step runs one
ahead of the host writes ``ahead`` on every ``engine.decode_dispatch``, and
a call dispatches step N+1 before it reads step N.

    device   decode [100,200) sample [200,202) merge [202,203) | decode [203,303)
             sample [303,305) merge [305,306) | decode [306,406) sample [406,408)
             merge [420,421) | decode [421,521) sample [521,523)
    thread A step0 [90,212): dispatch [91,96) ahead 0, dispatch [96,101) ahead 1,
             sync [101,204) emit [204,211)
             step2 [330,420): dispatch [331,336) ahead 1, sync [336,410) emit [410,419)
    thread B step1 [222,320): dispatch [223,228) ahead 1, sync [228,307) emit [307,319)
"""

import pytest
from test_hostplane import THREAD_A, THREAD_B, write_trace

from benchmarks.harness import hostplane
from benchmarks.layer_metrics import engine_decode_ahead_share, engine_decode_batch_mean

DEVICE = [
    (100, 200, "jit__decode_impl(11)"), (200, 202, "jit__greedy_sample(12)"),
    (202, 203, "jit__merge_last(13)"), (203, 303, "jit__decode_impl(11)"),
    (303, 305, "jit__greedy_sample(12)"), (305, 306, "jit__merge_last(13)"),
    (306, 406, "jit__decode_impl(11)"), (406, 408, "jit__greedy_sample(12)"),
    (420, 421, "jit__merge_last(13)"), (421, 521, "jit__decode_impl(11)"),
    (521, 523, "jit__greedy_sample(12)"),
]
AHEAD_A = [
    (90, 212, "engine.step", {"step": 0, "pending": 0, "prefilling": 0, "wall_us": 5}),
    (91, 96, "engine.decode_dispatch", {"batch": 2, "live_tokens": 40, "ahead": 0}),
    (96, 101, "engine.decode_dispatch", {"batch": 2, "live_tokens": 42, "ahead": 1}),
    (101, 204, "engine.sample_sync", {}),
    (204, 211, "engine.emit", {}),
    (330, 420, "engine.step", {"step": 3, "pending": 0, "prefilling": 0, "wall_us": 245}),
    (331, 336, "engine.decode_dispatch", {"batch": 1, "live_tokens": 23, "ahead": 1}),
    (336, 410, "engine.sample_sync", {}),
    (410, 419, "engine.emit", {}),
]
AHEAD_B = [
    (222, 320, "engine.step", {"step": 2, "pending": 0, "prefilling": 0, "wall_us": 137}),
    (223, 228, "engine.decode_dispatch", {"batch": 2, "live_tokens": 44, "ahead": 1}),
    (228, 307, "engine.sample_sync", {}),
    (307, 319, "engine.emit", {}),
]


@pytest.fixture
def traced(monkeypatch):
    paths = {}
    monkeypatch.setattr(hostplane, "path_of", lambda result: paths.get(result.get("trace")))
    return paths


def test_three_of_four_dispatches_found_a_step_in_flight(tmp_path, traced):
    traced["ahead"] = write_trace(
        tmp_path / "ahead.xplane.pb", DEVICE, (AHEAD_A, AHEAD_B))
    result = {"trace": "ahead"}
    assert engine_decode_ahead_share.read(result) == 75.0
    # the count is one of the span's, beside those the older readers take
    assert engine_decode_batch_mean.read(result) == pytest.approx(7 / 4)
    loaded = hostplane.of(result)
    assert hostplane.counts(loaded, "engine.decode_dispatch", "ahead") == [0, 1, 1, 1]
    # a step is read while its successor runs: the device idles 12 us of
    # 423, between the sampler at 408 and the merge the late dispatch queued
    assert hostplane.idle_intervals(loaded) == [(408 * 10**6, 420 * 10**6)]
    assert hostplane.attribution(loaded) == {"engine.sample_sync": 2e-6, "engine.emit": 9e-6,
                                             "engine.step": 1e-6}


def test_none_where_the_program_counts_no_step_ahead(tmp_path, traced):
    """The parent of the PR that added the count: the spans are there, the
    count is not; and a run that was not traced, or opened no span at all."""
    traced["parent"] = write_trace(tmp_path / "parent.xplane.pb")
    assert engine_decode_batch_mean.read({"trace": "parent"}) is not None
    assert engine_decode_ahead_share.read({"trace": "parent"}) is None
    assert engine_decode_ahead_share.read({"trace": None}) is None
    bare = [(s, e, n, c) for s, e, n, c in THREAD_A + THREAD_B
            if not n.startswith(hostplane.PREFIXES)]
    traced["bare"] = write_trace(tmp_path / "bare.xplane.pb", threads=(bare,))
    assert engine_decode_ahead_share.read({"trace": "bare"}) is None


def test_an_engine_that_never_got_ahead_reads_zero(tmp_path, traced):
    behind = [(s, e, n, dict(c, ahead=0) if n == "engine.decode_dispatch" else c)
              for s, e, n, c in THREAD_A]
    traced["behind"] = write_trace(tmp_path / "behind.xplane.pb", threads=(behind, THREAD_B))
    # thread B's spans carry no count and are left out, not read as 0
    assert engine_decode_ahead_share.read({"trace": "behind"}) == 0.0
    assert len(hostplane.counts(
        hostplane.of({"trace": "behind"}), "engine.decode_dispatch", "ahead")) == 3
