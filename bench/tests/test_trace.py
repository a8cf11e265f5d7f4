"""The trace reduction on a small recorded trace with known answers.

Window [800, 4200) ns.  Device ops clipped to it: [800, 1100) (the op that
starts at 500), [1000, 1200), [1300, 1800), [1700, 1900), [2600, 2700),
[3500, 4200).  Busy union: [800, 1200) + [1300, 1900) + [2600, 2700) +
[3500, 4200) = 400 + 600 + 100 + 700 = 1800 ns.  Idle gaps: [1200, 1300)
(100, in the first refresh), [1900, 2600) (700, midpoint 2250 in the first
refresh), [2700, 3500) (800, midpoint 3100 in the second refresh).
"""
import json
import pathlib

import pytest

from bench import trace

DATA = pathlib.Path(__file__).with_name("data") / "small_trace.json"


@pytest.fixture(scope="module")
def red():
    return trace.reduce(json.loads(DATA.read_text()))


def test_busy_union_and_window(red):
    assert red["window_s"] == pytest.approx(3400e-9)
    assert red["busy_s"] == pytest.approx(1800e-9)


def test_device_time_per_op(red):
    assert red["op_s"]["dp_kernel"] == pytest.approx(1200e-9)   # 500 + 700
    assert red["op_s"]["fusion.1"] == pytest.approx(700e-9)     # 300+200+200


def test_gaps_named_by_span(red):
    gaps = red["breakdown"]["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([800e-9, 700e-9, 100e-9])
    assert [g[0] for g in gaps] == ["bench.refresh"] * 3
    outside = trace.reduce({"devices": {"/device:TPU:0": {
        "ops": [["a", 0, 10], ["a", 90, 10]]}},
        "spans": [["bench.window", 0, 100], ["bench.job", 0, 20]]})
    assert outside["breakdown"]["idle_gaps"] == [["bench.window",
                                                  pytest.approx(80e-9)]]


def test_span_host_time(red):
    first, second = red["span_host"]["bench.refresh"]
    # [900, 2400): busy 300 + 600 = 900 of 1500
    assert first == pytest.approx((1500e-9, 600e-9))
    # [2450, 4150): busy 100 + 650 of 1700
    assert second == pytest.approx((1700e-9, 950e-9))


def test_top_device_ops(red):
    ops = red["breakdown"]["device_ops"]
    assert ops[0][0] == "dp_kernel" and len(ops) == 3


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "spans": []})
