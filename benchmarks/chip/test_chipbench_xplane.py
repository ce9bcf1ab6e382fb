"""The trace reduction: busy time as a union of device op intervals
inside the window, programs counted, idle gaps attributed to the
innermost host span; on a hand-made trace in the layout of a TPU
profile ("XLA Ops" and "XLA Modules" lines on a /device: plane)."""

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE)]

import xplane  # noqa: E402

MS = 1e6   # ns


def hand_trace():
    return {
        "/host:CPU": {"python": [
            ["chipbench.window", 0, 100 * MS],
            ["chipbench.tick", 0, 60 * MS],
            ["cb-base.extend", 10 * MS, 5 * MS],
            ["chipbench.tick", 60 * MS, 40 * MS],
        ]},
        "/device:TPU:0": {
            "XLA Ops": [["fusion.1", -5 * MS, 15 * MS],     # clipped to 10
                        ["fusion.2", 5 * MS, 10 * MS],      # overlaps .1
                        ["dot.3", 30 * MS, 20 * MS],
                        ["fusion.1", 95 * MS, 20 * MS]],    # clipped to 5
            "XLA Modules": [["jit_a", -5 * MS, 20 * MS],
                            ["jit_b", 30 * MS, 20 * MS],
                            ["jit_a", 95 * MS, 20 * MS]],
        },
    }


def test_busy_programs_and_ops():
    red = xplane.reduce(hand_trace())
    assert red["window_s"] == pytest.approx(0.1)
    # busy: [0, 15) + [30, 50) + [95, 100) = 40 ms
    assert red["busy_s"] == pytest.approx(0.040)
    assert red["programs"] == 2                 # starts inside the window
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.015)
    assert ops["dot.3"] == pytest.approx(0.020)


def test_idle_gaps_go_to_the_innermost_host_span():
    red = xplane.reduce(hand_trace())
    gaps = dict(red["idle_gaps"])
    # [15, 30) mid 22.5 and [50, 60) mid 55: first tick; [60, 95): second
    assert gaps["chipbench.tick"] == pytest.approx(0.060)
    assert sum(gaps.values()) == pytest.approx(0.060)
    tr = hand_trace()
    tr["/host:CPU"]["python"].append(["cb-base.decode", 14 * MS, 20 * MS])
    gaps = dict(xplane.reduce(tr)["idle_gaps"])
    assert gaps["cb-base.decode"] == pytest.approx(0.015)


def test_no_device_no_reduction():
    tr = hand_trace()
    del tr["/device:TPU:0"]
    assert xplane.reduce(tr) is None
