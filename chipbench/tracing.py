"""Reduction of one profiler trace to the numbers the per-layer readers
use.

The harness wraps the calls of each tick in host spans (``collect``,
``forecast``, ``decide``, as ``jax.profiler.TraceAnnotation`` with the
prefix ``chipbench.``).  From the ``.xplane.pb`` file of a traced window
this module takes:

* the window: first span start to last span end, on the host clock;
* each span's durations;
* the device ops (line ``XLA Ops`` of every ``/device:TPU:<n>`` plane),
  clipped to the window; busy time is the union of their intervals;
  only the planes of the cell's chips count: the plane's mesh is the first
  ``chips`` devices, ``/device:TPU:0`` to ``chips - 1``, and a one-chip
  cell on a host of four leaves the other three out;
* the idle gaps between them, each split among the host spans it overlaps.

The profiler puts device events on the host's clock itself.  What skew
remains (tens of microseconds on a v5e) moves an idle gap across a span
boundary by that much, and leaves busy time as it is.

``read`` parses the file; ``reduce`` works on plain tuples, so tests can
feed it a synthetic trace.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

SPAN_PREFIX = "chipbench."
SPANS = ("collect", "forecast", "decide")
BETWEEN = "between_spans"


@dataclasses.dataclass
class Raw:
    """Events in ns: host spans ``{name: [(start, end)]}`` (prefix
    stripped), and per device its ops ``[(name, start, end)]``."""
    spans: dict
    ops: dict


def read(path: str) -> Raw:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    spans, ops = defaultdict(list), {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans[e.name[len(SPAN_PREFIX):]].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    return Raw(dict(spans), ops)


def union(intervals) -> list[tuple[float, float]]:
    """Sorted disjoint union of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def overlap(a, b) -> float:
    """Total length of the overlap of two sorted disjoint interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


_OP = re.compile(r"^%?(\S+) = (\S+) ([\w-]+)\(")


def op_label(name: str) -> str:
    """``'body.1 custom-call f32[4096,5]'`` from an HLO instruction line."""
    m = _OP.match(name)
    if not m:
        return name[:80]
    return f"{m.group(1)} {m.group(3)} {m.group(2).split('{')[0]}"


@dataclasses.dataclass
class Summary:
    window_ns: float
    ticks: int
    span_ns: dict          # name -> list of durations
    n_devices: int
    busy_ns: float         # mean over devices of the busy union
    ops: list              # (name, start, end) on the host clock, clipped
    idle_by_span: dict     # span name -> idle ns while the host was in it


_DEVICE = re.compile(r"^/device:TPU:(\d+)")


def on_chips(planes: dict, chips: int) -> dict:
    """The entries of ``planes`` (keyed by device plane name) that belong
    to the first ``chips`` devices."""
    return {name: v for name, v in planes.items()
            if (m := _DEVICE.match(name)) and int(m.group(1)) < chips}


def reduce(raw: Raw, chips: int) -> Summary | None:
    """The trace of a cell that runs on the first ``chips`` devices; None
    when it holds no tick spans."""
    raw = Raw(raw.spans, on_chips(raw.ops, chips))
    ticks = sorted(zip(sorted(raw.spans.get("collect", [])),
                       sorted(raw.spans.get("decide", []))))
    ticks = [(c[0], d[1]) for c, d in ticks]
    if not ticks:
        return None
    w0, w1 = ticks[0][0], ticks[-1][1]
    span_iv = {name: union(clip(raw.spans.get(name, []), w0, w1))
               for name in SPANS}
    busy, ops = [], []
    idle = defaultdict(float)
    for dev_ops in raw.ops.values():
        inside = [(n, max(s, w0), min(e, w1)) for n, s, e in dev_ops
                  if e > w0 and s < w1]
        ops.extend(inside)
        bu = union((s, e) for _, s, e in inside)
        busy.append(sum(e - s for s, e in bu))
        gaps, t = [], w0
        for s, e in bu:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < w1:
            gaps.append((t, w1))
        covered = 0.0
        for name in SPANS:
            ov = overlap(gaps, span_iv[name])
            idle[name] += ov / len(raw.ops)
            covered += ov
        idle[BETWEEN] += (sum(e - s for s, e in gaps) - covered) / len(raw.ops)
    return Summary(
        window_ns=w1 - w0, ticks=len(ticks),
        span_ns={n: [e - s for s, e in clip(raw.spans.get(n, []), w0, w1)]
                 for n in SPANS},
        n_devices=len(raw.ops),
        busy_ns=(sum(busy) / len(busy)) if busy else 0.0,
        ops=ops, idle_by_span=dict(idle))


def top_ops(summary: Summary, n: int = 10) -> list:
    """The ``n`` device ops that took most time, ``[label, seconds]``,
    summed over their calls and averaged over devices."""
    tot = defaultdict(float)
    for name, s, e in summary.ops:
        tot[op_label(name)] += (e - s) / max(summary.n_devices, 1)
    return [[k, v * 1e-9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


_CALL = re.compile(r"^%?\S+ = f32\[(\d+),(\d+)\]\S* custom-call\("
                   r"f32\[(\d+),(\d+),(\d+)\]")


def forecast_call_targets(event_name: str, window: int,
                          n_metrics: int) -> int | None:
    """Targets in one call of a forecast kernel, or None where the op is
    none.  A forecast kernel is the Mosaic custom call that takes the
    window (n, W, M) as its first operand and returns the forecast (n, M):
    it is known by what it reads and writes, not by the weights it is
    passed, which a kernel may lay out or leave out as it needs."""
    if 'custom_call_target="tpu_custom_call"' not in event_name:
        return None
    m = _CALL.match(event_name)
    if not m:
        return None
    n_out, m_out, n_in, w_in, m_in = map(int, m.groups())
    if n_out != n_in or (w_in, m_in) != (window, n_metrics) \
            or m_out != n_metrics:
        return None
    return n_out


def kernel_calls(summary: Summary, targets_of) -> list[tuple[int, float]]:
    """``(targets, ns)`` of every op for which ``targets_of(name)`` gives a
    number of targets."""
    out = []
    for name, s, e in summary.ops:
        n = targets_of(name)
        if n:
            out.append((n, e - s))
    return out
