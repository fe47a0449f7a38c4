"""The trace reducer on a small synthetic trace whose answers are known."""
import pytest

from chipbench import tracing

MS = 1_000_000   # ns


def synthetic():
    """Three ticks of 10 ms: collect 0-2, forecast 2-7, decide 7-10 (ms,
    tick-relative).  On the device each tick runs one program from 3 to 6:
    a copy 3-4 and the kernel 4-6, plus an overlapping async op 3.5-4.5."""
    spans = {"collect": [], "forecast": [], "decide": []}
    ops = []
    for k in range(3):
        t = 10 * MS * k
        spans["collect"].append((t, t + 2 * MS))
        spans["forecast"].append((t + 2 * MS, t + 7 * MS))
        spans["decide"].append((t + 7 * MS, t + 10 * MS))
        d = t
        ops.append(("%copy.1 = f32[8,5]{1,0} copy(f32[8,5]{0,1} %p)",
                    d + 3 * MS, d + 4 * MS))
        ops.append(("%copy-start = (f32[8,5]) copy-start(f32[8,5] %q)",
                    d + int(3.5 * MS), d + int(4.5 * MS)))
        ops.append(("%body.1 = f32[8,5]{1,0} custom-call(f32[8,1,5] %a)",
                    d + 4 * MS, d + 6 * MS))
    return tracing.Raw(spans, {"/device:TPU:0": ops})


def test_union_and_overlap():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tracing.overlap([(0, 3), (5, 8)], [(2, 6)]) == 2


def test_window_busy_idle_and_spans():
    s = tracing.reduce(synthetic(), 1)
    assert s.ticks == 3
    assert s.window_ns == 30 * MS
    # busy: the union of the ops, 3 ms per tick (the async op overlaps)
    assert s.busy_ns == pytest.approx(9 * MS)
    assert 100 * (1 - s.busy_ns / s.window_ns) == pytest.approx(70.0)
    assert [d / MS for d in s.span_ns["forecast"]] == [5, 5, 5]


def test_idle_gaps_split_by_host_span():
    # the device runs 3-6 ms of every tick: idle 0-2 in collect, 2-3 and
    # 6-7 in forecast, 7-10 in decide
    s = tracing.reduce(synthetic(), 1)
    idle = {k: v / MS for k, v in s.idle_by_span.items()}
    assert idle["collect"] == pytest.approx(6)
    assert idle["forecast"] == pytest.approx(6)
    assert idle["decide"] == pytest.approx(9)
    assert idle[tracing.BETWEEN] == pytest.approx(0)


def test_window_clips_device_work_outside_it():
    raw = synthetic()
    raw.ops["/device:TPU:0"].append(("%late = f32[1] copy(f32[1] %x)",
                                     29 * MS, 33 * MS))
    s = tracing.reduce(raw, 1)
    assert s.busy_ns == pytest.approx(10 * MS)


def test_kernel_calls_by_name_and_top_ops():
    s = tracing.reduce(synthetic(), 1)
    calls = tracing.kernel_calls(
        s, lambda n: 8 if "custom-call(" in n else None)
    assert [n for n, _ in calls] == [8, 8, 8]
    assert sum(ns for _, ns in calls) == pytest.approx(6 * MS)
    top = dict(tracing.top_ops(s))
    assert top["body.1 custom-call f32[8,5]"] == pytest.approx(6e-3)
    assert top["copy.1 copy f32[8,5]"] == pytest.approx(3e-3)


def test_no_spans_gives_nothing():
    assert tracing.reduce(tracing.Raw({}, {}), 1) is None


def test_planes_outside_the_cell_are_left_out():
    # a one-chip cell on a host of four: the other chips' planes, one busy
    # all window long and one with a plane of no ops, change nothing
    alone = tracing.reduce(synthetic(), 1)
    raw = synthetic()
    raw.ops["/device:TPU:1"] = [("%x = f32[1] copy(f32[1] %y)", 0, 30 * MS)]
    raw.ops["/device:TPU:3"] = []
    s = tracing.reduce(raw, 1)
    assert s.n_devices == alone.n_devices == 1
    assert s.busy_ns == alone.busy_ns
    assert s.idle_by_span == alone.idle_by_span
    assert s.ops == alone.ops
    # a two-chip cell counts its second chip
    two = tracing.reduce(raw, 2)
    assert two.n_devices == 2
    assert two.busy_ns == pytest.approx((9 * MS + 30 * MS) / 2)
