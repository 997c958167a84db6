"""The trace reduction on a small trace recorded on a TPU v5e chip (16
clients, one chunk of two co-train rounds of the linear model at full
width, per-example chunk 12, and its evaluation, with the harness's host
annotations around them), committed gzipped beside this test."""
import os

import pytest

from chipbench import trace_reduce
from chipbench.metrics import _dp_clip

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "p4_linear_m16.xplane.pb.gz")


@pytest.fixture(scope="module")
def red():
    out = trace_reduce.reduce_file(TRACE, window_annotation="window")
    assert out is not None
    return out


def test_busy_within_the_slice(red):
    assert red["devices"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["window_s"] < 60


def test_ops_and_breakdown(red):
    # self times of nested ops add up to the busy time
    assert sum(red["ops"].values()) == pytest.approx(red["busy_s"], rel=1e-6)
    assert not any(n.startswith("while") for n, _ in red["top_ops"])
    assert 0 < len(red["top_ops"]) <= trace_reduce.TOP
    assert all(isinstance(n, str) and s > 0 for n, s in red["top_ops"])
    assert 0 < len(red["idle_gaps"]) <= trace_reduce.TOP
    idle = red["window_s"] - red["busy_s"]
    assert sum(s for _, s in red["idle_gaps"]) == pytest.approx(idle, rel=1e-6)


def test_dp_clip_kernels_found(red):
    # 2 rounds x 192 / 12 example chunks: the metrics step's DP gradient
    # is dead code (its update is dropped) and XLA removes it
    assert _dp_clip.launches(red) == 32
    assert 0 < _dp_clip.seconds(red) < red["busy_s"]


def test_self_times_of_nested_ops():
    events = [(0, 10, "while"), (1, 4, "a"), (5, 9, "b"), (6, 7, "c")]
    assert trace_reduce._self_times(events) == [
        ["while", 3], ["a", 3], ["b", 3], ["c", 1]]


def test_merge_and_label():
    assert trace_reduce._merge([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    host = {"eval": [(0, 10)], "dispatch": [(2, 4)]}
    assert trace_reduce._label(3, host, ("dispatch", "eval")) == "dispatch"
    assert trace_reduce._label(8, host, ("dispatch", "eval")) == "eval"
    assert trace_reduce._label(12, host, ("dispatch", "eval")) == "other"
