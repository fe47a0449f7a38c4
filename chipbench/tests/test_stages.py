"""The reduction of the program's stage spans (``stages.py``) on a small
synthetic trace whose answers are known, and one traced run on the CPU."""
import sys
import time

import pytest

from chipbench import stages, tracing
from chipbench.layout import Layout

from .test_tracing import synthetic

MS = 1_000_000   # ns
T = ("/host:CPU", 0)
KERNEL = ('%lstm_seq_stacked.1 = f32[8,5]{1,0} custom-call(f32[8,1,5]{2,1,0}'
          ' %copy.12, f32[8,5,200]{2,1,0} %copy.14), custom_call_target='
          '"tpu_custom_call"')


def program_trace():
    """Three ticks of 10 ms, as in ``test_tracing.synthetic``, with the
    program's spans inside the benchmark's (ms, tick-relative):

    collect 0-2:  ppa.collect 0.1-1.9 > upload 0.5-1.5 (100 B)
    forecast 2-7: ppa.forecast 2.1-6.9 > install 2.1-2.2, snapshot
                  2.2-2.3, device 2.3-6.5 > dispatch 2.3-3, readback
                  3-6.5 (100 B)
    decide 7-10:  ppa.decide 7-9 > join 7-7.1, evaluate 7.1-8, stabilise
                  8-8.5, record 8.5-8.9, epilogue 8.9-9; ppa.readout 9-9.5

    On the device: the ring push 1.0-1.2, then the forecast module 3-6: a
    relayout copy 3-4 and the kernel 4-6."""
    bench = {"collect": [], "forecast": [], "decide": []}
    spans, mods, ops = [], [], []

    def ms(k, a, b):
        return 10 * MS * k + int(a * MS), 10 * MS * k + int(b * MS)

    layout = [("ppa.collect", 0.1, 1.9, {"tick": 0}),
              ("ppa.collect.upload", 0.5, 1.5, {"bytes": 100}),
              ("ppa.forecast", 2.1, 6.9, {"tick": 0}),
              ("ppa.forecast.install", 2.1, 2.2, {}),
              ("ppa.forecast.snapshot", 2.2, 2.3, {}),
              ("ppa.forecast.device", 2.3, 6.5, {"tick": 0}),
              ("ppa.forecast.dispatch", 2.3, 3.0, {}),
              ("ppa.forecast.readback", 3.0, 6.5, {"bytes": 100}),
              ("ppa.decide", 7.0, 9.0, {"tick": 0}),
              ("ppa.decide.join", 7.0, 7.1, {}),
              ("ppa.decide.evaluate", 7.1, 8.0, {}),
              ("ppa.decide.stabilise", 8.0, 8.5, {}),
              ("ppa.decide.record", 8.5, 8.9, {}),
              ("ppa.decide.epilogue", 8.9, 9.0, {}),
              ("ppa.readout", 9.0, 9.5, {"tick": 0})]
    for k in range(3):
        for name, (a, b) in (("collect", (0, 2)), ("forecast", (2, 7)),
                             ("decide", (7, 10))):
            s, e = ms(k, a, b)
            bench[name].append((s, e))
            spans.append((tracing.SPAN_PREFIX + name, s, e, {}, T))
        for name, a, b, stats in layout:
            spans.append((name, *ms(k, a, b), dict(stats), T))
        mods.append(ms(k, 3, 6))
        ops.append(("%fusion = f32[8,1,5]{2,1,0} fusion(f32[8,5] %r)",
                    *ms(k, 1.0, 1.2)))
        ops.append(("%copy.14 = f32[8,5,200]{2,1,0} copy(f32[8,5,200] %w)",
                    *ms(k, 3, 4)))
        ops.append((KERNEL, *ms(k, 4, 6)))
    raw = tracing.Raw(bench, {"/device:TPU:0": ops})
    return raw, stages.Stages(spans, {"/device:TPU:0": mods})


def test_old_numbers_unchanged_by_the_program_spans():
    # the stage reduction reads the benchmark's trace and leaves it as is
    raw = synthetic()
    before = tracing.reduce(raw, 1)
    st = stages.Stages([], {})
    assert stages.reduce(raw, st, 1, 5, 1)["metrics"] == {}
    after = tracing.reduce(raw, 1)
    assert before == after
    assert after.busy_ns == pytest.approx(9 * MS)
    assert {k: v / MS for k, v in after.idle_by_span.items()} == \
        pytest.approx({"collect": 6, "forecast": 6, "decide": 9,
                       tracing.BETWEEN: 0})


def test_stage_metrics_per_tick():
    raw, st = program_trace()
    out = stages.reduce(raw, st, 1, 5, 1)
    assert out["ticks"] == 3
    assert out["metrics"] == pytest.approx({
        "upload_ms": 1.0, "dispatch_ms": 0.7, "device_wait_ms": 3.0,
        "download_ms": 0.5, "evaluate_ms": 0.9, "stabilise_ms": 0.5,
        "record_ms": 0.4, "transfer_bytes_per_tick": 200.0,
        "forecast_copy_ms": 1.0})
    # the module: its kernel and the copy, not the ring push outside it
    assert out["forecast_module_ms"] == pytest.approx(3.0)
    assert out["forecast_kernel_ms"] == pytest.approx(2.0)
    assert out["forecast_kernel_ms"] + out["metrics"]["forecast_copy_ms"] \
        == pytest.approx(out["forecast_module_ms"])
    assert out["copy_ops_ms"] == pytest.approx(1.0)
    assert out["coverage"] == pytest.approx(
        {"collect": 0.9, "forecast": 0.96, "decide": 2.5 / 3})
    assert out["builds_in_window"] == []
    # self time: the parent's time outside its stages
    assert out["self_ms"]["ppa.forecast"] == pytest.approx(0.4)
    assert out["self_ms"]["ppa.decide.evaluate"] == pytest.approx(0.9)
    assert out["self_ms"]["ppa.collect"] == pytest.approx(0.8)


def test_readback_split_at_the_module_end():
    # readbacks 10-20 (module ends at 15), 30-40 (the last module ended at
    # 25, before it began) and 50-60 (a module still running at 60)
    rb = [(stages.READBACK, 10, 20, {}, T), (stages.READBACK, 30, 40, {}, T),
          (stages.READBACK, 50, 60, {}, T)]
    mods = {"/device:TPU:0": [(5, 15), (22, 25), (55, 70)],
            "/device:TPU:1": [(5, 12), (22, 24)]}
    assert stages.readback_split(rb, mods) == (5 + 0 + 10, 5 + 10 + 0)
    assert stages.readback_split(rb, {}) is None
    assert stages.readback_split([], mods) is None


def test_idle_by_stage_known_answers():
    # device busy 1.0-1.2 and 3-6 of every tick: idle 0-1, 1.2-3, 6-10
    raw, st = program_trace()
    idle = stages.idle_by_stage(raw, st, 0, 30 * MS, T)
    per_tick = {k: v / MS / 3 for k, v in idle.items()}
    assert per_tick == pytest.approx({
        "collect.other": 0.2, "ppa.collect": 0.8, "ppa.collect.upload": 0.8,
        "forecast.other": 0.2, "ppa.forecast.install": 0.1,
        "ppa.forecast.snapshot": 0.1, "ppa.forecast.dispatch": 0.7,
        "ppa.forecast.readback": 0.5, "ppa.forecast": 0.4,
        "ppa.decide.join": 0.1, "ppa.decide.evaluate": 0.9,
        "ppa.decide.stabilise": 0.5, "ppa.decide.record": 0.4,
        "ppa.decide.epilogue": 0.1, "ppa.readout": 0.5,
        "decide.other": 0.5, tracing.BETWEEN: 0.0})


def test_idle_by_stage_sums_to_the_idle_gaps():
    raw, st = program_trace()
    out = stages.reduce(raw, st, 1, 5, 1)
    assert len(out["idle_by_stage"]) == stages.TOP
    assert out["idle_by_stage"][-1][0] == "rest"
    total = sum(v for _, v in out["idle_by_stage"])
    assert total == pytest.approx(sum(v for _, v in out["idle_gaps"]))
    assert total == pytest.approx(3 * 6.8e-3)


def test_labelled_segments_follow_the_innermost_span():
    spans = [("a", 0, 10, {}, T), ("b", 2, 4, {}, T), ("c", 3, 4, {}, T),
             ("d", 6, 7, {}, T), ("e", 12, 13, {}, T)]
    assert stages.labelled_segments(spans) == [
        (0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 6, "a"), (6, 7, "d"),
        (7, 10, "a"), (12, 13, "e")]


def test_a_named_kernel_is_still_found():
    assert tracing.forecast_call_targets(KERNEL, 1, 5) == 8
    assert tracing.forecast_call_targets(KERNEL, 8, 5) is None


@pytest.mark.parametrize("spans", [True, False])
def test_cpu_stage_run(tiny_root, spans):
    layout = Layout(tiny_root, tiny_root / "chipbench")
    out = stages.stage_run(layout, "attn-tiny", 2**33 + 11, 0.5, spans,
                           time.perf_counter(), require_tpu=False,
                           log=sys.stdout)
    got = out["stages"]["metrics"]
    if not spans:
        assert got == {}
        return
    # no TPU plane, so no forecast module to split the readback at
    assert set(got) == set(stages.STAGE_METRICS) | {
        "transfer_bytes_per_tick"}
    # the tiny mix has 8 targets: one (8, 5) f32 batch each way a tick
    assert got["transfer_bytes_per_tick"] == 2 * 8 * 5 * 4
    assert out["stages"]["builds_in_window"] == []
    assert out["stages"]["coverage"]["forecast"] > 0.5
    after = out["tick_stats"]["after"]
    assert after["program_builds"] == 2      # the forecast and the shift
    assert after["h2d_bytes"] > 0 and after["d2h_bytes"] > 0
    assert out["decision_log_bytes_per_tick"] > 0
    assert {"collect_ms", "forecast_ms", "decide_ms"} <= set(
        out["per_layer"])
