"""The trace reduction against a small recorded trace: 0.4 s of PR 22's
first traced ``mistral7b-chat-backlog`` run on a TPU v5 lite (five decode
steps, two retirements), cut by ``cut_xplane.py``. The expected numbers were
read by hand straight from the protobuf (TensorFlow's ``xplane_pb2``, a
plain sweep), not with the code under test."""

import os

import pytest

from benchmarks.harness import xplane
from benchmarks.layer_metrics import (
    decode_step_device_ms, kv_copy_busy_share, sched_prefill_busy_share)

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "decode_steps.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce(TRACE)


def test_pure_helpers():
    assert xplane.union_s([(0, 1), (0.5, 2), (3, 4)]) == 3.0
    own = xplane.self_times(
        [(0, 10, "while"), (1, 3, "a"), (4, 6, "a"), (6, 7, "b"), (11, 12, "c")])
    assert own == {"a": 4.0, "b": 1.0, "while": 5.0, "c": 1.0}
    assert xplane.module_name("jit__decode_impl(4016089970500199467)") == "jit__decode_impl"
    assert xplane.op_label(
        "%broadcast.548 = f32[16,8,4,4096,128]{4,3,2,1,0:T(8,128)} "
        "broadcast(bf16[16,8,4096,128]{3,2,1,0} %gte.157), dimensions={0,1,3,4}"
    ) == "broadcast f32[16,8,4,4096,128]"
    assert xplane.op_label("%all-gather-done.3 = bf16[8,4]{1,0} all-gather-done(%x)"
                           ).startswith("all-gather-done")
    assert xplane.COLLECTIVE.match("all-gather-done bf16[8,4]")
    assert not xplane.COLLECTIVE.match("fusion f32[8]")


def test_modules_as_read_by_hand(reduced):
    assert reduced["devices"] == 1
    counts = {name: m["count"] for name, m in reduced["modules"].items()}
    assert counts == {
        "jit__decode_impl": 5, "jit__lambda": 6, "jit__threefry_fold_in": 4,
        "jit_commit_impl": 4, "jit_convert_element_type": 14}
    decode = reduced["modules"]["jit__decode_impl"]
    # 74088.046 + 73991.143 + 74097.106 + 73991.729 + 74086.411 microseconds
    assert decode["total_s"] == pytest.approx(0.370254433, rel=1e-6)
    assert decode["median_s"] == pytest.approx(0.074086411, rel=1e-6)
    assert reduced["modules"]["jit_commit_impl"]["median_s"] == pytest.approx(22.12e-6, rel=1e-2)


def test_busy_share_and_top_ops_as_read_by_hand(reduced):
    # the union of 17,658 op intervals, 118 of them nested in a `while`
    assert reduced["busy_s"] == pytest.approx(0.363846917, rel=1e-4)
    assert reduced["extent_s"] == pytest.approx(0.400784069, rel=1e-6)
    assert reduced["busy_s"] / reduced["extent_s"] == pytest.approx(0.9078, abs=1e-3)
    name, seconds = reduced["ops"][0]
    # 59 broadcasts of the f32 GQA-expanded cache, 1.93 ms each
    assert name == "jit__decode_impl/broadcast f32[16,8,4,4096,128]"
    assert seconds == pytest.approx(0.113755075, rel=1e-5)
    assert [n for n, _ in reduced["ops"][1:3]] == [
        "jit__decode_impl/multiply_reduce_fusion bf16[16,32,128]",
        "jit__decode_impl/copy bf16[16,8,4096,128]"]
    gap, seconds, count = reduced["gaps"][0]
    assert gap == "jit__lambda->jit__decode_impl" and count == 2
    assert reduced["collective_self_s"] == [0.0]


def test_layer_metric_readers_on_the_recorded_trace(reduced):
    result = {"trace": reduced}
    assert decode_step_device_ms.read(result) == pytest.approx(74.086411, rel=1e-6)
    assert sched_prefill_busy_share.read(result) == 0.0
    # 2 x 0.624 ms of row extraction, 4 x 22 us of block commits, and the
    # sampler's 4 us lambdas, over 363.8 ms busy
    assert kv_copy_busy_share.read(result) == pytest.approx(0.3717, rel=1e-2)


def test_a_trace_without_a_device_plane_reduces_to_nothing(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jax.jit(lambda x: x + 1)(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    path = xplane.find_xplane(str(tmp_path))
    assert path is not None and xplane.reduce(path) is None
