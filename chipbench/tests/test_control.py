"""The control -- the plain reference computed in bfloat16, put in the
program's place -- comes out as not correct, while the program, judged by
the same check on the same run, is correct.  On the chip the same
readings come from ``calibrate.py`` at each cell's own size."""
import sys
import time

import pytest

from chipbench.calibrate import readings
from chipbench.harness import drive_cell
from chipbench.layout import Layout


@pytest.mark.parametrize("cell", ["lstm-tiny", "attn-tiny"])
def test_control_fails_the_forecast_limit(tiny_root, cell):
    layout = Layout(tiny_root, tiny_root / "chipbench")
    run = drive_cell(layout, cell, 5, 0.3, False, time.perf_counter(),
                     require_tpu=False, log=sys.stdout)
    r = readings(run)
    lim = r["limits"]["forecast_gap_z"]
    assert r["program"]["correct"] is True
    assert r["control"]["correct"] is False
    assert r["program"]["checks"]["forecast_gap_z"] <= lim \
        < r["control"]["checks"]["forecast_gap_z"]
    # the control fails the forecasts alone: it forecasts every target,
    # and the decisions checked are the program's
    assert r["control"]["checks"]["missing_forecasts"] == 0
    assert r["control"]["checks"]["decision_mismatches"] == 0
