"""The readers of the four-chip exchange on a recorded four-device trace.

``chipbench/testdata/mesh4_trace.json`` is cut from a ``--trace 1`` run of
``t40x4.mine`` on four TPU v5e chips: the first mine's level-1 and level-2
count jobs on every chip, with the operations' names and times as the
trace recorded them, and the window cut down to the two jobs.
"""

from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import exchange, run, xtrace
from chipbench.peaks import peaks

ROOT = Path(__file__).resolve().parents[2] / "chipbench"
V5E = peaks("TPU v5 lite")
# the two jobs' rows (1,024 singletons, 417,792 padded pairs), 4 B a count,
# from each of the four chips
EXCHANGE_BYTES = (1024 + 417792) * 4 * 4
# per chip: (level-1 psum, level-2 psum, both count programs), in ns
RECORDED = {0: (4953, 33836, 730558 + 26155132),
            1: (4186, 33218, 479245 + 25863080),
            2: (4320, 33395, 83523 + 26562357),
            3: (2925, 31326, 101276 + 25771102)}


def _reader(name: str):
    return run.load_module(ROOT / "metrics" / f"{name}.py")


def _reduced(name: str):
    events = xtrace.load_json(str(ROOT / "testdata" / name))
    return xtrace.reduce(events, xtrace.window_of(events, "chipbench.window"))


def _ctx(trace, exchange_bytes=EXCHANGE_BYTES, chips=4):
    mine = SimpleNamespace(exchange_bytes=exchange_bytes)
    return SimpleNamespace(window={"mines": [mine]}, trace=trace,
                           chips=chips, peaks=V5E)


def _mean_psum_ns():
    return sum(a + b for a, b, _ in RECORDED.values()) / 4


def test_the_exchange_is_the_psum_ops_of_the_trace():
    red = _reduced("mesh4_trace.json")
    assert len(red.devices) == 4
    for dev in red.devices:
        found = sorted(n for n in red.op_s[dev] if exchange.is_exchange(n))
        assert found == ["%psum.7 = s32[1024]{0:T",
                         "%psum.7 = s32[417792]{0:T"], dev
    assert red.op_seconds(exchange.is_exchange) == pytest.approx(
        _mean_psum_ns() / 1e9)


def test_collective_share_on_the_recorded_trace():
    got = _reader("collective_share.mine4").read(_ctx(_reduced(
        "mesh4_trace.json")))
    count_ns = sum(c for _, _, c in RECORDED.values()) / 4
    assert got == pytest.approx(100.0 * _mean_psum_ns() / count_ns)
    assert got == pytest.approx(0.14010801, rel=1e-6)


def test_exchange_roofline_on_the_recorded_trace():
    got = _reader("exchange_roofline.mine4").read(_ctx(_reduced(
        "mesh4_trace.json")))
    # each chip puts in a quarter of the bytes and sends 2·3/4 of that
    least_s = 2 * 3 / 4 * (EXCHANGE_BYTES / 4) / (1600e9 / 8)
    assert least_s == pytest.approx(12.56448e-6)
    assert got == pytest.approx(100.0 * least_s / (_mean_psum_ns() / 1e9))
    assert got == pytest.approx(33.9216112, rel=1e-6)


@pytest.mark.parametrize("case", ["untraced", "no_all_reduce",
                                  "no_exchange_counted", "one_chip"])
def test_exchange_readers_read_nothing_they_cannot_see(case):
    trace = {"untraced": None,
             "no_all_reduce": _reduced("host_gaps_trace.json")}.get(
        case, _reduced("mesh4_trace.json"))
    ctx = _ctx(trace, exchange_bytes=None if case == "no_exchange_counted"
               else 0 if case == "one_chip" else EXCHANGE_BYTES)
    assert _reader("exchange_roofline.mine4").read(ctx) is None
    share = _reader("collective_share.mine4").read(ctx)
    # the share reads the trace alone: a program that counts no exchange
    # still shows its all-reduce ops
    assert (share is None) == (case in ("untraced", "no_all_reduce"))


@pytest.mark.parametrize("exchange_bytes", [64, 16 * 1024, 6701056, 1 << 30])
@pytest.mark.parametrize("chips", [2, 4, 8])
@pytest.mark.parametrize("slower", [1.0, 1.0001, 3.0, 1e4])
def test_exchange_roofline_cannot_pass_100(exchange_bytes, chips, slower):
    """At the v5e peaks, an all-reduce that takes its least time or longer
    reads at most 100%."""
    least = exchange.least_seconds(exchange_bytes, chips,
                                   V5E["ici_bits_per_s"])
    trace = SimpleNamespace(op_seconds=lambda match: least * slower)
    got = _reader("exchange_roofline.mine4").read(
        _ctx(trace, exchange_bytes, chips))
    assert got == pytest.approx(100.0 / slower)
    assert got <= 100.0 + 1e-9


def test_one_chip_exchanges_in_no_time():
    assert exchange.least_seconds(6701056, 1, V5E["ici_bits_per_s"]) == 0.0
