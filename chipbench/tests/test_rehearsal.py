"""The whole run on the CPU at a tiny size, and the refusal to report
without a TPU."""
import json
import os
import subprocess
import sys
import time

import pytest

from chipbench.harness import run_cell
from chipbench.layout import Layout

from .conftest import ROOT


@pytest.mark.parametrize("cell", ["lstm-tiny", "attn-tiny"])
@pytest.mark.parametrize("trace", [False, True])
def test_cpu_rehearsal_drives_the_whole_path(tiny_root, cell, trace):
    layout = Layout(tiny_root, tiny_root / "chipbench")
    out = run_cell(layout, cell, 2**33 + 7, 0.5, trace, time.perf_counter(),
                   require_tpu=False, log=sys.stdout)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["decision_mismatches"]["value"] == 0
    assert out["checks"]["missing_forecasts"]["value"] == 0
    kind = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in layout.metrics_for(cell, kind)}
    assert set(out["metrics"]) <= names
    if trace:
        # host spans are read; device metrics are left out, never 0
        assert {"collect_ms", "forecast_ms", "decide_ms"} <= set(
            out["metrics"])
        assert "device_idle_share" not in out["metrics"]
        assert out["device"]["window_s"] > 0
    else:
        assert set(out["metrics"]) == names
        assert out["metrics"]["decisions_per_s"]["value"] > 0


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "lstm50-nasa-z16", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert "needs a TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_benchmark_alone_is_not_enough(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files (no
    program) gives no result."""
    import shutil
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "lstm50-nasa-z16", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
