"""The reduction from a trace to busy time, per-span device time and the
breakdown, on a hand-made trace and on a small one recorded on the chip."""
import json

import pytest

from benchlib import trace
from tiny import BENCH

MS = 1_000_000


def hand_trace():
    # window 0..10 ms; each span holds its ops exactly, so the device's
    # clock needs no shift; the head's host work runs past the window
    return {
        "window": [0, 10 * MS],
        "spans": [["hi/layer", 1 * MS, 2 * MS], ["lo/layer", 5 * MS, 4 * MS],
                  ["lo/head.host", 9_500_000, 1_500_000]],
        "ops": [["fusion.1", 1 * MS, 1 * MS], ["fusion.2", 2 * MS, 1 * MS],
                ["fusion.1", 5 * MS, 4 * MS],
                ["argmax", 9_500_000, 1_500_000]],
    }


def test_busy_and_window():
    r = trace.reduce(hand_trace())
    assert r["device_shift_s"] == 0
    assert r["window_s"] == pytest.approx(0.010)
    # 1..3, 5..9 and 9.5..10 ms (clipped at the window's end)
    assert r["busy_s"] == pytest.approx(0.0065)


def test_device_time_per_call_counts_spans_inside_the_window():
    r = trace.reduce(hand_trace())
    assert r["spans"]["hi/layer"] == {"calls": 1,
                                      "device_s": pytest.approx(0.002)}
    assert r["spans"]["lo/layer"] == {"calls": 1,
                                      "device_s": pytest.approx(0.004)}
    assert "lo/head.host" not in r["spans"]     # ends past the window


def test_breakdown_labels_ops_and_gaps_by_host_span():
    r = trace.reduce(hand_trace())
    ops = dict(map(tuple, r["device_ops"]))
    assert ops == pytest.approx({"lo/layer:fusion.1": 0.004,
                                 "hi/layer:fusion.1": 0.001,
                                 "hi/layer:fusion.2": 0.001,
                                 "lo/head.host:argmax": 0.0005})
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps == pytest.approx({"start > hi/layer": 0.001,
                                  "hi/layer > lo/layer": 0.002,
                                  "lo/layer > lo/head.host": 0.0005})


def test_alignment_recovers_a_shifted_device_clock():
    ex = hand_trace()
    for op in ex["ops"]:
        op[1] -= 700_000                 # device clock 0.7 ms behind
    assert trace.align(ex) == 700_000
    r = trace.reduce(ex)
    assert r["device_shift_s"] == pytest.approx(0.0007)
    assert r["spans"]["hi/layer"]["device_s"] == pytest.approx(0.002)


def test_recorded_chip_trace():
    """60 ms of a traced qwen-stablelm window, measured on one v5e chip: lo
    layer calls only. By hand from the raw events: the device plane runs
    ~1.04 ms behind the host spans; each lo layer call is one burst of 47
    operations that sum to 1.817 ms."""
    ex = json.loads((BENCH / "tests" / "data"
                     / "trace_sample.json").read_text())
    r = trace.reduce(ex)
    assert 0.0005 < r["device_shift_s"] < 0.0015
    assert r["window_s"] == pytest.approx(0.060)
    assert 0 < r["busy_s"] <= r["window_s"]
    lo = r["spans"]["lo/layer"]
    assert lo["calls"] == 18
    assert lo["device_s"] / lo["calls"] == pytest.approx(0.001817, rel=2e-3)
    # after alignment every device operation lies in some span
    assert not any(k.startswith("no span") for k, _ in r["device_ops"])
