"""Shared set-up of the harness's tests: everything runs on the CPU, with
the Pallas kernels in interpret mode, at tiny sizes."""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def tiny_mix(name: str, targets: int, **kw) -> dict:
    mix = json.loads((BENCH / "traffic" / "nasa-z16.json").read_text())
    mix.update(name=name, targets=targets, pool_ticks=64, history_ticks=48)
    mix.update(kw)
    return mix


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout-like directory: a copy of the benchmark's files, a tiny
    traffic mix and a BENCHMARK.json whose cells run at that size."""
    bench = tmp_path / "chipbench"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (bench / "traffic" / "tiny.json").write_text(
        json.dumps(tiny_mix("tiny", 8)))
    # the attention deployment with its plane over four devices
    cfg = json.loads((bench / "configs" / "ppa-attn50-w8.json").read_text())
    cfg.update(name="ppa-attn50-w8-mesh4", device_mesh=4)
    (bench / "configs" / "ppa-attn50-w8-mesh4.json").write_text(
        json.dumps(cfg))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"] = [
        {"name": "lstm-tiny", "config": "ppa-lstm50", "traffic": "tiny",
         "chips": 1, "why": "tiny"},
        {"name": "attn-tiny", "config": "ppa-attn50-w8", "traffic": "tiny",
         "chips": 1, "why": "tiny"},
        {"name": "attn-tiny-mesh4", "config": "ppa-attn50-w8-mesh4",
         "traffic": "tiny", "chips": 4, "why": "tiny, four devices"},
        {"name": "attn-tiny-mesh-mismatch", "config": "ppa-attn50-w8-mesh4",
         "traffic": "tiny", "chips": 1, "why": "config and chips differ"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path
