"""Where the benchmark's parts live, found by the names in BENCHMARK.json.

Everything that belongs to one configuration, traffic mix, metric, kernel
or model architecture is a file of its own under this directory, so a new
cell, mix or metric is added by adding files and entries, never by editing
the harness:

* ``configs/<config>.json``  -- the deployment as it is run (sizes, policy,
  plane layout, the limits of the correctness check);
* ``models/<arch>.py``       -- the plain reference forward of one forecaster
  architecture, its weight shapes and the program class that serves it;
* ``kernels/<kernel>.py``    -- the FLOPs and bytes one forecast needs, and
  the names by which the kernel is found in a device trace;
* ``traffic/<traffic>.json`` -- the parameters of one traffic mix, read by
  the one generator in ``traffic.py``;
* ``metrics/<metric>.py``    -- a reader that takes one metric from a run.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class Layout:
    """The benchmark definition (``BENCHMARK.json``) and the files it names.
    ``root`` holds ``BENCHMARK.json``; ``bench_dir`` holds the parts."""

    def __init__(self, root: Path = ROOT, bench_dir: Path = BENCH_DIR):
        self.root = Path(root)
        self.bench_dir = Path(bench_dir)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self._modules: dict[Path, object] = {}

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def model(self, arch: str):
        return self._module("models", arch)

    def kernel(self, name: str):
        return self._module("kernels", name)

    def reader(self, metric: str):
        return self._module("metrics", metric)

    def metrics_for(self, cell: str, kind: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports:
        those without a ``workloads`` key, and those that list the cell."""
        return [m for m in self.spec[kind]
                if "workloads" not in m or cell in m["workloads"]]

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.bench_dir / "peaks.json").read_text())
        if device_kind not in table["devices"]:
            raise KeyError(f"device kind {device_kind!r} is not in "
                           "peaks.json: add its published peaks first")
        return table["devices"][device_kind]

    def _json(self, sub: str, name: str) -> dict:
        path = self.bench_dir / sub / f"{name}.json"
        if not path.is_file():
            raise FileNotFoundError(f"{sub}/{name}.json not found under "
                                    f"{self.bench_dir}")
        return json.loads(path.read_text())

    def _module(self, sub: str, name: str):
        path = self.bench_dir / sub / f"{name}.py"
        if path not in self._modules:
            if not path.is_file():
                raise FileNotFoundError(f"{sub}/{name}.py not found under "
                                        f"{self.bench_dir}")
            spec = importlib.util.spec_from_file_location(
                f"chipbench_{sub}_{name.replace('-', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return self._modules[path]
