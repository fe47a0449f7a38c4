"""The whole run on the CPU at a tiny size, on one device and on four,
and the refusal to report without a TPU or for a cell whose config runs
on another number of chips."""
import json
import os
import subprocess
import sys
import time

import pytest

from chipbench import harness
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


# one run of a cell in a process that sees four CPU devices (JAX fixes its
# device count when it starts, so the run is a process of its own)
FOUR_DEVICES = """
import json, sys, time
from pathlib import Path
from chipbench.harness import run_cell
from chipbench.layout import Layout
root = Path(sys.argv[1])
out = run_cell(Layout(root, root / "chipbench"), sys.argv[2], 2**33 + 5,
               0.5, sys.argv[3] == "1", time.perf_counter(),
               require_tpu=False, log=sys.stderr)
print(json.dumps(out))
"""


@pytest.mark.parametrize("trace", [False, True])
def test_four_device_rehearsal(tiny_root, trace):
    """A four-chip cell (the plane's mesh over four devices) runs the whole
    path on four forced CPU devices and reports its four chips."""
    from repro.core.device_plane import force_host_devices_env
    env = force_host_devices_env(4)
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), str(ROOT / "src")])
    p = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICES, str(tiny_root),
         "attn-tiny-mesh4", str(int(trace))],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.splitlines()[-1])
    assert out["correct"] is True
    assert out["checks"]["missing_forecasts"]["value"] == 0
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["count"] == 4
    assert out["device"]["platform"] == "cpu"
    if trace:
        assert {"collect_ms", "forecast_ms", "decide_ms"} <= set(
            out["metrics"])
    else:
        assert out["metrics"]["decisions_per_s"]["value"] > 0


def test_config_and_chips_that_differ_are_refused_first(tiny_root,
                                                         monkeypatch):
    """A cell whose config's device_mesh is not its chips is refused before
    the look for a chip and before any input is made."""
    def no_work(*a, **k):
        raise AssertionError("work done before the refusal")
    monkeypatch.setattr(harness, "find_chips", no_work)
    monkeypatch.setattr(harness.traffic, "generate", no_work)
    monkeypatch.setattr(harness.weights, "make", no_work)
    layout = Layout(tiny_root, tiny_root / "chipbench")
    with pytest.raises(harness.BadCell, match="device_mesh=4"):
        run_cell(layout, "attn-tiny-mesh-mismatch", 3, 0.5, False,
                 time.perf_counter(), log=sys.stdout)
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "attn-tiny-mesh-mismatch", "--seed", "3", "--seconds", "1",
         "--trace", "0"],
        cwd=tiny_root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert "device_mesh=4" in p.stderr
    assert p.stdout.strip() == ""
