"""A new configuration, traffic mix and metric are found by name from
BENCHMARK.json, with no edit to the harness."""
import json
import sys
import time

from chipbench.harness import run_cell
from chipbench.layout import Layout

from .conftest import tiny_mix


def test_new_files_are_found_by_name(tiny_root):
    bench = tiny_root / "chipbench"
    cfg = json.loads((bench / "configs" / "ppa-lstm50.json").read_text())
    cfg.update(name="new-config", hidden=8)
    (bench / "configs" / "new-config.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "new-mix.json").write_text(
        json.dumps(tiny_mix("new-mix", 4, level=[900.0, 1100.0])))
    (bench / "metrics" / "ticks_seen.py").write_text(
        "def read(ctx):\n    return float(ctx.trace.ticks)\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "new-cell", "config": "new-config",
                              "traffic": "new-mix", "chips": 1,
                              "why": "added by files alone"})
    spec["per_layer"].append({"name": "ticks_seen", "unit": "ticks",
                              "better": "higher", "source": "device_trace",
                              "layer": "entry", "moves": "decisions_per_s",
                              "workloads": ["new-cell"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))

    layout = Layout(tiny_root, bench)
    assert "ticks_seen" not in {
        m["name"] for m in layout.metrics_for("lstm-tiny", "per_layer")}
    out = run_cell(layout, "new-cell", 11, 0.3, True, time.perf_counter(),
                   require_tpu=False, log=sys.stdout)
    assert out["correct"] is True
    assert out["attempted"] % 4 == 0
    assert out["metrics"]["ticks_seen"]["value"] > 0
