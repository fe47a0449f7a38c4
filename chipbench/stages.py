#!/usr/bin/env python3
"""The control plane's own stage spans in a traced window.

The plane marks every stage of its tick with a ``ppa.*`` span when its
spans are on (``repro.core.obs``; the tree is in docs/architecture.md,
"Observability"), and names its device programs (``jit_ppa_forecast``,
``jit_ppa_ring_push``) and kernels.  From the same ``.xplane.pb`` file that
``tracing.py`` reduces, this module takes:

* the ``ppa.*`` spans with their stats, on every host thread;
* the intervals of the forecast module on each device's ``XLA Modules``
  line;

and reduces them, over the window ``tracing.reduce`` uses, to the stage
times per tick (``STAGE_METRICS``), the forecast's readback split at the
end of the forecast module into waiting for the device and copying, the
device time the forecast module spends outside its kernel (the weight
relayout copies), the bytes moved
between host and device per tick, how far the program's spans cover the
benchmark's own, and each idle gap of the device put under the innermost
span the tick's thread was in.

    python3 chipbench/stages.py --workload <cell> --seed <n> \
        --seconds <s> [--spans 0|1]

runs one cell as ``run.py --trace 1`` does, with the plane's spans on
(``--spans 0``: off, to price them), and prints one JSON line: the cell's
per-layer metrics as ``run.py`` reads them, the stage figures, and the
plane's ``tick_stats()`` before and after the run.  It runs no correctness
check.  Without a TPU it exits with code 2.
"""
from __future__ import annotations

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import tracing  # noqa: E402

PREFIX = "ppa."
FORECAST_MODULE = "jit_ppa_forecast"
# stage metric -> the span whose time per tick it is
STAGE_METRICS = {
    "upload_ms": "ppa.collect.upload",
    "dispatch_ms": "ppa.forecast.dispatch",
    "evaluate_ms": "ppa.decide.evaluate",
    "stabilise_ms": "ppa.decide.stabilise",
    "record_ms": "ppa.decide.record",
}
# one np.asarray waits for the forecast and copies it: split at the end of
# the forecast module on the device into device_wait_ms and download_ms
READBACK = "ppa.forecast.readback"
TRANSFER_SPANS = ("ppa.collect.upload", READBACK)
# the benchmark's span -> the program's spans that should cover it
COVERS = {"collect": ("ppa.collect",), "forecast": ("ppa.forecast",),
          "decide": ("ppa.decide", "ppa.readout")}
TOP = 10


@dataclasses.dataclass
class Stages:
    """Events in ns: the program's spans ``[(name, start, end, stats,
    thread)]`` and per device the forecast module's intervals
    ``[(start, end)]``."""
    spans: list
    modules: dict


def read(path: str) -> Stages:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    spans, modules = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[plane.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                        if e.name.startswith(FORECAST_MODULE)]
        elif plane.name.startswith("/host:"):
            for li, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(PREFIX) or e.name.startswith(
                            tracing.SPAN_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      {k: v for k, v in e.stats},
                                      (plane.name, li)))
    return Stages(spans, modules)


def window(raw: tracing.Raw) -> tuple[float, float] | None:
    """The window ``tracing.reduce`` takes: first collect start to last
    decide end."""
    c, d = raw.spans.get("collect", []), raw.spans.get("decide", [])
    if not c or not d:
        return None
    return min(s for s, _ in c), max(e for _, e in d)


def labelled_segments(spans) -> list[tuple[float, float, str]]:
    """The time of one thread cut into disjoint segments, each named after
    the innermost span open there (spans of one thread nest)."""
    out, stack, t = [], [], None
    events = sorted(spans, key=lambda s: (s[1], -s[2]))

    def emit(upto):
        if stack and upto > t:
            out.append((t, upto, stack[-1][0]))

    for name, s, e, *_ in events:
        while stack and stack[-1][2] <= s:
            emit(stack[-1][2])
            t = stack[-1][2]
            stack.pop()
        if stack:
            emit(s)
        stack.append((name, s, e))
        t = s
    while stack:
        emit(stack[-1][2])
        t = stack[-1][2]
        stack.pop()
    return out


def readback_split(readbacks, modules: dict) -> tuple[float, float] | None:
    """The readback spans' ns split into waiting for the device and
    copying: each span's time up to the end of the last forecast module
    that started before the span ended (on the latest device), and the
    rest.  None without module intervals."""
    per_dev = [sorted(iv) for iv in modules.values() if iv]
    if not per_dev or not readbacks:
        return None
    starts = [[a for a, _ in iv] for iv in per_dev]
    wait = copy = 0.0
    for _, s, e, *_ in readbacks:
        done = s
        for iv, st in zip(per_dev, starts):
            i = bisect.bisect_left(st, e) - 1
            if i >= 0:
                done = max(done, min(iv[i][1], e))
        wait += done - s
        copy += e - done
    return wait, copy


def _label(name: str) -> str:
    if name.startswith(tracing.SPAN_PREFIX):
        return name[len(tracing.SPAN_PREFIX):] + ".other"
    return name


def idle_by_stage(raw: tracing.Raw, st: Stages, w0: float, w1: float,
                  thread) -> dict:
    """Idle ns of the device, averaged over devices, under the innermost
    span of the tick's thread (``<benchmark span>.other`` where no program
    span is open, ``between_spans`` where none is)."""
    segs = labelled_segments([s for s in st.spans if s[4] == thread])
    idle = defaultdict(float)
    n = len(raw.ops)
    for dev_ops in raw.ops.values():
        bu = tracing.union((max(s, w0), min(e, w1)) for _, s, e in dev_ops
                           if e > w0 and s < w1)
        gaps, t = [], w0
        for s, e in bu:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < w1:
            gaps.append((t, w1))
        i = 0
        for gs, ge in gaps:
            covered = 0.0
            while i < len(segs) and segs[i][1] <= gs:
                i += 1
            j = i
            while j < len(segs) and segs[j][0] < ge:
                ov = min(ge, segs[j][1]) - max(gs, segs[j][0])
                if ov > 0:
                    idle[_label(segs[j][2])] += ov / n
                    covered += ov
                j += 1
            idle[tracing.BETWEEN] += (ge - gs - covered) / n
    return dict(idle)


def tick_thread(st: Stages):
    """The host thread that ran the benchmark's tick spans."""
    count = defaultdict(int)
    for name, *_, thread in st.spans:
        if name.startswith(tracing.SPAN_PREFIX):
            count[thread] += 1
    return max(count, key=count.get) if count else None


def reduce(raw: tracing.Raw, st: Stages, window_m: int, n_metrics: int,
           chips: int) -> dict | None:
    """The stage figures of one traced window of a cell on the first
    ``chips`` devices; None without tick spans.  ``window_m`` and
    ``n_metrics`` find the forecast kernel's calls."""
    raw = tracing.Raw(raw.spans, tracing.on_chips(raw.ops, chips))
    st = Stages(st.spans, tracing.on_chips(st.modules, chips))
    summary = tracing.reduce(raw, chips)
    w = window(raw)
    if summary is None or w is None:
        return None
    w0, w1 = w
    ticks = summary.ticks
    inside = [s for s in st.spans
              if s[0].startswith(PREFIX) and s[2] > w0 and s[1] < w1]
    total, count = defaultdict(float), defaultdict(int)
    for name, s, e, *_ in inside:
        total[name] += min(e, w1) - max(s, w0)
        count[name] += 1
    out: dict = {"ticks": ticks}
    metrics = {m: total[span] / ticks * 1e-6
               for m, span in STAGE_METRICS.items() if count[span]}
    moved = [s[3].get("bytes", 0) for s in inside
             if s[0] in TRANSFER_SPANS]
    if moved:
        metrics["transfer_bytes_per_tick"] = sum(moved) / ticks
    split = readback_split([s for s in inside if s[0] == READBACK],
                           st.modules)
    if split is not None:
        metrics["device_wait_ms"] = split[0] / ticks * 1e-6
        metrics["download_ms"] = split[1] / ticks * 1e-6
    # the forecast module's device time: its kernel, and every other op
    # (the relayout copies of the weights, the standardisation)
    n_dev = len(raw.ops)
    mod_ns = kern_ns = other_ns = 0.0
    for dev, dev_ops in raw.ops.items():
        mods = sorted(tracing.clip(st.modules.get(dev, []), w0, w1))
        mod_ns += sum(e - s for s, e in mods)
        i = 0
        for name, s, e in sorted(dev_ops, key=lambda o: o[1]):
            while i < len(mods) and mods[i][1] <= s:
                i += 1
            if i == len(mods) or s < mods[i][0] or not (w0 <= s < w1):
                continue
            if tracing.forecast_call_targets(name, window_m, n_metrics):
                kern_ns += e - s
            else:
                other_ns += e - s
    if mod_ns > 0:
        metrics["forecast_copy_ms"] = other_ns / n_dev / ticks * 1e-6
        out["forecast_module_ms"] = mod_ns / n_dev / ticks * 1e-6
        out["forecast_kernel_ms"] = kern_ns / n_dev / ticks * 1e-6
    copies = [(n, s, e) for n, s, e in summary.ops
              if tracing.op_label(n).startswith("copy")]
    if n_dev:
        out["copy_ops_ms"] = (sum(e - s for _, s, e in copies)
                              / n_dev / ticks * 1e-6)
    out["metrics"] = metrics
    out["span_ms"] = {n: total[n] / ticks * 1e-6 for n in sorted(total)}
    out["span_count"] = dict(sorted(count.items()))
    thread = tick_thread(st)
    # self time: where a span is the innermost one open on the tick thread
    own = defaultdict(float)
    for s0, e0, name in labelled_segments(
            [s for s in st.spans if s[4] == thread]):
        if name.startswith(PREFIX) and e0 > w0 and s0 < w1:
            own[name] += min(e0, w1) - max(s0, w0)
    out["self_ms"] = {n: own[n] / ticks * 1e-6 for n in sorted(own)}
    out["builds_in_window"] = [s[3].get("program") for s in inside
                               if s[0] == "ppa.build"]
    cover = {}
    for bench, progs in COVERS.items():
        b = tracing.union(tracing.clip(raw.spans.get(bench, []), w0, w1))
        p = tracing.union(tracing.clip(
            [(s, e) for name, s, e, _, th in st.spans
             if name in progs and th == thread], w0, w1))
        bt = sum(e - s for s, e in b)
        if bt > 0:
            cover[bench] = tracing.overlap(b, p) / bt
    out["coverage"] = cover
    if n_dev:
        idle = idle_by_stage(raw, st, w0, w1, thread)
        ranked = sorted(idle.items(), key=lambda kv: -kv[1])
        top = ranked[:TOP - 1]
        rest = sum(v for _, v in ranked[TOP - 1:])
        if rest:
            top.append(("rest", rest))
        out["idle_by_stage"] = [[k, v * 1e-9] for k, v in top]
        out["idle_gaps"] = [[k, v * 1e-9] for k, v in sorted(
            summary.idle_by_span.items(), key=lambda kv: -kv[1])]
    return out


def stage_run(layout, workload: str, seed: int, seconds: float,
              spans: bool, t_proc0: float, *, require_tpu: bool = True,
              log=sys.stderr) -> dict:
    """One traced run of a cell with the plane's spans on or off: the
    harness's set-up and window, then the per-layer metrics and the stage
    figures of the trace."""
    import glob
    import shutil
    import tempfile

    from chipbench import harness
    run, clock = harness.start(layout, workload, seed,
                               require_tpu=require_tpu, log=log)
    try:
        from repro.core import obs
    except ImportError:           # a program without spans
        obs = None
    run.make_inputs()
    run.build_plane(run.leaves)
    stats = getattr(run.plane, "tick_stats", None)
    before = stats() if stats else None
    if obs is not None:
        obs.enable(spans)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-stages-")
    try:
        run.drive(seconds, trace_dir, t_proc0, clock)
        after = stats() if stats else None
        path = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")[0]
        raw = tracing.read(path)
        run.trace = tracing.reduce(raw, run.n_chips)
        st = read(path)
    finally:
        if obs is not None:
            obs.enable(False)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run.release()
    cfg = run.config
    out = {"workload": workload, "seed": seed, "spans": bool(spans),
           "window_ticks": len(run.tick_s),
           "tick_ms_p50": sorted(run.tick_s)[len(run.tick_s) // 2] * 1e3,
           "compiles_in_window": run.compiles_in_window}
    per_layer = {}
    for m in layout.metrics_for(workload, "per_layer"):
        value = layout.reader(m["name"]).read(run)
        if value is not None:
            per_layer[m["name"]] = value
    out["per_layer"] = per_layer
    out["stages"] = reduce(raw, st, int(cfg["window"]),
                           int(cfg["n_metrics"]), run.n_chips)
    if before is not None:
        n = after["ticks"] - before["ticks"]
        out["tick_stats"] = {"before": before, "after": after}
        out["decision_log_bytes_per_tick"] = (
            (after["decision_log_bytes"] - before["decision_log_bytes"]) / n
            if n else None)
    return out


def main(argv=None) -> int:
    from chipbench.layout import Layout
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--spans", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    from chipbench.harness import BadCell, NoChip
    try:
        out = stage_run(Layout(), args.workload, args.seed, args.seconds,
                        bool(args.spans), T_PROC0)
    except (NoChip, BadCell) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
