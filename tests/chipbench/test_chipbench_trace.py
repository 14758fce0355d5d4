"""The trace reduction on a small recorded trace with known times."""

from pathlib import Path

import pytest

from chipbench import xtrace

TRACE = Path(__file__).resolve().parents[2] / "chipbench" / "testdata" / \
    "small_trace.json"


@pytest.fixture(scope="module")
def red():
    events = xtrace.load_json(str(TRACE))
    win = xtrace.window_of(events, "chipbench.window")
    assert win == (1000000, 1010000)
    return xtrace.reduce(events, win)


def test_window_and_devices(red):
    assert red.window_s == pytest.approx(1e-5)
    assert red.devices == ["/device:TPU:0", "/device:TPU:1"]


def test_busy_is_the_union_of_ops_in_the_window(red):
    # TPU:0: [1000,2500) + [3000,4000) + [6000,7500) = 4000 ns
    assert red.busy_s["/device:TPU:0"] == pytest.approx(4000e-9)
    # TPU:1: [0,500) clipped + [1000,3000) = 2500 ns
    assert red.busy_s["/device:TPU:1"] == pytest.approx(2500e-9)
    assert red.mean_busy_s == pytest.approx(3250e-9)


def test_module_and_op_seconds(red):
    assert red.module_s["/device:TPU:0"]["jit_mapper"] == pytest.approx(
        2500e-9)
    assert red.module_seconds(lambda n: "mapper" in n) == pytest.approx(
        (2500e-9 + 2000e-9) / 2)
    assert red.module_seconds(lambda n: "delta" in n) == pytest.approx(
        1500e-9 / 2)
    assert red.op_seconds(lambda n: "all-reduce" in n) == pytest.approx(
        (500e-9 + 1000e-9) / 2)


def test_gaps_and_their_names(red):
    assert red.gaps == [(1000000, 1001000), (1002500, 1003000),
                        (1004000, 1006000), (1007500, 1010000)]
    spans = [("mine.gen", 1003900, 1006100), ("mine.run", 1000000, 1010000)]
    assert xtrace.idle_gaps(red, spans, n=2) == [
        ["mine.run", 2500e-9], ["mine.gen", 2000e-9]]
    top = xtrace.top_ops(red, n=1)
    assert top[0][0] == "fusion.1"
    assert top[0][1] == pytest.approx((2000e-9 + 1500e-9) / 2)
