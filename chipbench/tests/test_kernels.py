"""FLOP and byte counts against hand counts at tiny shapes."""
import importlib.util
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]


def load(name):
    spec = importlib.util.spec_from_file_location(
        f"k_{name}", BENCH / "kernels" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


lstm = load("lstm_seq_stacked")
attn = load("attn_lstm_seq_stacked")


def test_lstm_window_one_needs_no_recurrent_weights():
    # H=2, M=1, W=1: gates x@Wx are 4H=8 outputs of 1 MAC each -> 16 FLOPs
    # (no h@Wh: h0 = 0); head relu(h)@Wo is 1 output of 2 MACs -> 4 FLOPs
    assert lstm.flops_per_target(2, 1, 1) == 16 + 4
    # Wx 1x8 + b 8 + Wo 2x1 + bo 1 = 19 floats, no Wh; in 1 + out 1
    assert lstm.bytes_per_target(2, 1, 1) == 4 * (19 + 2)


def test_lstm_later_steps_add_the_recurrent_term():
    # W=2: step 2 has x@Wx (8 MACs) and h@Wh (16 MACs) -> 48 more FLOPs
    assert lstm.flops_per_target(2, 1, 2) == 16 + 48 + 4
    # Wh 2x8 = 16 floats more; the window in is 2 values
    assert lstm.bytes_per_target(2, 1, 2) == 4 * (19 + 16 + 2 + 1)


def test_lstm_paper_sizes():
    # H=50, M=5, W=1: 2,500 FLOP and 5,860 B per target
    assert lstm.flops_per_target(50, 5, 1) == 2500
    assert lstm.bytes_per_target(50, 5, 1) == 5860


def test_attention_counts():
    # H=2, M=1, W=1: LSTM1 16, query Wa 2x2 -> 8, scores 2*1*2 = 4,
    # reweight 1*2 = 2, LSTM2 first step a@Wx2 (2x8) -> 32, head 4
    assert attn.flops_per_target(2, 1, 1) == 16 + 8 + 4 + 2 + 32 + 4
    # Wx1 8 + b1 8 + Wa 4 + Wx2 16 + b2 8 + Wo 2 + bo 1 = 47, no Wh1/Wh2
    assert attn.bytes_per_target(2, 1, 1) == 4 * (47 + 2)
    # W=2 adds a step to each LSTM: LSTM1 8*2*(1+2) = 48, LSTM2
    # 8*2*(2+2) = 64, scores and reweight double; Wh1, Wh2 are needed
    assert attn.flops_per_target(2, 1, 2) == (16 + 48) + 8 + 8 + 4 \
        + (32 + 64) + 4
    assert attn.bytes_per_target(2, 1, 2) == 4 * (47 + 32 + 2 + 1)
    assert attn.flops_per_target(50, 5, 8) == 462700
    assert attn.bytes_per_target(50, 5, 8) == 136800


def call(window, operands, n=4096, pad_out=None):
    """An HLO custom-call line as the TPU profiler names it: the window
    first, then ``operands`` (trailing dims after the target axis)."""
    ops = [f"f32[{n},{window},5]{{2,1,0:T(1,128)}} %fusion"] + [
        f"f32[{n}," + ",".join(map(str, t)) + "]{2,1,0} %copy"
        for t in operands]
    out = pad_out or n
    return (f"%body.1 = f32[{out},5]{{1,0:T(8,128)}} custom-call("
            + ", ".join(ops)
            + '), custom_call_target="tpu_custom_call", '
            "frontend_attributes={kernel_metadata={}}")


LSTM_W = [(5, 200), (50, 200), (200,), (50, 5), (5,)]
ATTN_W = [(5, 200), (50, 200), (200,), (50, 50), (50, 200), (50, 200),
          (200,), (50, 5), (5,)]


@pytest.mark.parametrize("mod,window,weights", [
    (lstm, 1, LSTM_W), (attn, 8, ATTN_W)])
def test_trace_names(mod, window, weights):
    name = call(window, weights)
    assert mod.call_targets(name, 50, 5, window) == 4096
    # another window is another kernel's call
    assert mod.call_targets(name, 50, 5, window + 1) is None
    assert mod.call_targets(
        "%copy.3 = f32[4096,5]{1,0} copy(f32[4096,5]{0,1} %b)",
        50, 5, window) is None
    # a custom call that is not Mosaic's, or writes no forecast
    assert mod.call_targets(name.replace("tpu_custom_call", "other"),
                            50, 5, window) is None
    assert mod.call_targets(call(window, weights, pad_out=4095),
                            50, 5, window) is None


@pytest.mark.parametrize("weights", [
    [t for t in LSTM_W if t != (50, 200)],          # no Wh at window 1
    [(8, 5, 256), (256,)],                          # weights re-laid out
    []])                                            # weights fused in
def test_kernel_found_whatever_weights_it_is_passed(weights):
    assert lstm.call_targets(call(1, weights), 50, 5, 1) == 4096
