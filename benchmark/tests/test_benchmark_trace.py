"""trace_reduce.py on the CPU: a hand-made trace with hand-worked answers
(union of busy intervals, self time under a nesting op, gap attribution,
mean over devices), and the small trace recorded on the v5e under
``fixtures/`` against an independent sweep."""

import json
import os

import pytest

from conftest import BENCH
from harness import trace_reduce as tr

HAND = {
    "device": {
        # one while of 100..600 holding two fusions; a copy overlapping
        # nothing; on device 1 a single all-reduce
        "0": [["while.1", "", 100, 500], ["fusion.3", "jit(f)/zoo_flash_fwd",
                                          150, 100],
              ["fusion.4", "", 300, 200], ["copy.9", "", 800, 100]],
        "1": [["all-reduce.2", "", 200, 300]],
    },
    "host": [["zb:window", 0, 1000], ["zb:engine.step", 50, 650],
             ["zb:engine.join", 720, 60]],
}


def test_hand_worked_trace():
    r = tr.reduce(HAND)
    # device 0 busy 100..600 and 800..900 = 600 ns; device 1 300 ns
    assert r["busy_s"] == pytest.approx((600 + 300) / 2 / 1e9)
    assert r["window_s"] == pytest.approx(1000 / 1e9)
    ops = dict(r["device_ops"])
    assert ops["while"] == pytest.approx((500 - 100 - 200) / 2 / 1e9)
    assert ops["fusion"] == pytest.approx(300 / 2 / 1e9)
    assert ops["all-reduce"] == pytest.approx(300 / 2 / 1e9)
    gaps = dict(r["idle_gaps"])
    # the step span is open 50..700, the join span 720..780. Device 0 idles
    # 0..100 (50 of it under the step), 600..800 (100 step, 60 join, 40
    # nothing) and 900..1000; device 1 idles 0..200 (150 step) and
    # 500..1000 (200 step, 60 join)
    assert gaps["engine.step"] == pytest.approx((50 + 100 + 150 + 200) / 2e9)
    assert gaps["engine.join"] == pytest.approx((60 + 60) / 2e9)
    assert gaps["unannotated"] == pytest.approx(
        (50 + 40 + 100 + 50 + 240) / 2e9)
    assert tr.seconds_matching(r, "zoo_flash_") == pytest.approx(
        100 / 2 / 1e9)
    assert tr.seconds_matching(r, "^all-reduce") == pytest.approx(
        300 / 2 / 1e9)
    # ops started inside engine.step: all of device 0's but the copy, and
    # device 1's all-reduce
    assert tr.seconds_matching(r, ".", within="engine.step") == \
        pytest.approx((500 + 300) / 2 / 1e9)


def test_host_spans_from_the_program_are_attributed_too():
    r = tr.reduce(HAND, host_spans=[("train/device_sync", 880, 1000)])
    assert dict(r["idle_gaps"])["train/device_sync"] == \
        pytest.approx((100 + 120) / 2e9)     # 900..1000 on 0, 880..1000 on 1


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce({"device": {}, "host": []})


def sweep(events, w0, w1):
    """Busy ns by stepping over every boundary: the slow, obvious way."""
    cuts = sorted({w0, w1} | {min(max(x, w0), w1) for e in events
                              for x in (e[2], e[2] + e[3])})
    busy = 0
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        if any(e[2] <= mid < e[2] + e[3] for e in events):
            busy += b - a
    return busy


def test_recorded_v5e_trace():
    path = os.path.join(BENCH, "fixtures", "trace_v5e_small.json")
    with open(path) as f:
        events = json.load(f)
    assert events["device"] and events["host"]
    r = tr.reduce(events)
    (w0, dur), = [(s, d) for n, s, d in events["host"]
                  if n == tr.WINDOW_MARK]
    want = sum(sweep(evs, w0, w0 + dur) for evs in
               events["device"].values()) / len(events["device"])
    assert r["busy_s"] == pytest.approx(want / 1e9, rel=1e-9)
    assert r["window_s"] == pytest.approx(dur / 1e9)
    assert 0 < r["busy_s"] <= r["window_s"]
    # self times add up to no more than busy time per device
    total = sum(t for _, _, t, _ in r["ops"]) / r["devices"]
    assert total <= r["busy_s"] * (1 + 1e-9)
    assert sum(g for _, g in r["idle_gaps"]) <= \
        r["window_s"] - r["busy_s"] + 1e-12
    assert tr.seconds_matching(r, events["expect"]["kernel_pattern"]) > 0
