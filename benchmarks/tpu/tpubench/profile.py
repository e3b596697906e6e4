"""Reduction of a ``jax.profiler`` trace to device busy time, kernel time
by name scope, the heaviest device operations and the host's activity in
the device's idle gaps.

``load_events`` turns the ``.xplane.pb`` into plain lists, so the
reduction below runs the same on a trace read back from a file of
recorded events (the tests do that):

  {"device": {chip: [[op name, start ns, duration ns, stats text], ...]},
   "host":   [[thread, event name, start ns, duration ns], ...]}
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"


def load_events(logdir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    prof = ProfileData.from_file(paths[-1])
    out = {"device": {}, "host": []}
    for plane in prof.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev and line.name == OPS_LINE:
                out["device"].setdefault(int(dev.group(1)), []).extend(
                    [ev.name, float(ev.start_ns), float(ev.duration_ns),
                     " ".join(str(v) for _, v in ev.stats if isinstance(v, str))]
                    for ev in line.events)
            elif plane.name.startswith("/host:"):
                out["host"].extend([line.name, ev.name, float(ev.start_ns),
                                    float(ev.duration_ns)] for ev in line.events)
    return out


def window(events: dict):
    """(start, end) ns of the benchmark's own ``bench.window`` span."""
    spans = [(s, s + d) for _, name, s, d in events["host"] if name == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def union(intervals, lo: float, hi: float):
    """Merged [start, end) intervals clipped to [lo, hi)."""
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_ns(ops, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(((o[1], o[1] + o[2]) for o in ops), lo, hi))


def device_busy_s(events: dict, lo: float, hi: float) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    chips = events["device"]
    if not chips:
        return 0.0
    return sum(busy_ns(ops, lo, hi) for ops in chips.values()) / len(chips) / 1e9


def op_name(op) -> str:
    """An op's own HLO name (``%fusion.12``): the event name is the whole
    HLO instruction, whose operands name other ops."""
    return op[0].split(" ", 1)[0]


def ops_s(events: dict, prefix: str, lo: float, hi: float) -> float:
    """Seconds of device time, averaged over the chips, of the operations
    whose own HLO name starts with ``prefix`` (overlaps counted once)."""
    chips = events["device"]
    if not chips:
        return 0.0
    total = sum(busy_ns([o for o in ops if op_name(o).startswith(prefix)], lo, hi)
                for ops in chips.values())
    return total / len(chips) / 1e9


def top_ops(events: dict, lo: float, hi: float, n: int = 10):
    """[[op name, seconds], ...] of chip 0's operations by total time (an
    op nested in a loop is counted in the loop's time too)."""
    ops = events["device"].get(min(events["device"], default=0), [])
    tot = {}
    for op in ops:
        d = min(op[1] + op[2], hi) - max(op[1], lo)
        if d > 0:
            tot[op_name(op)] = tot.get(op_name(op), 0.0) + d / 1e9
    return sorted(([k, v] for k, v in tot.items()), key=lambda kv: -kv[1])[:n]


def idle_gaps(events: dict, lo: float, hi: float, n: int = 10):
    """[[host activity, seconds], ...]: chip 0's idle time inside the
    window, each gap named by the innermost host event at its midpoint
    (``idle`` where the host shows none), summed by name."""
    ops = events["device"].get(min(events["device"], default=0), [])
    busy = union(((o[1], o[1] + o[2]) for o in ops), lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    host = [h for h in events["host"] if h[1] != WINDOW and h[3] > 0]
    starts = np.array([h[2] for h in host], np.float64)
    durs = np.array([h[3] for h in host], np.float64)
    tot = {}
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        mid = (s + e) / 2
        cover = np.flatnonzero((starts <= mid) & (starts + durs > mid))
        name = host[cover[np.argmin(durs[cover])]][1] if len(cover) else "idle"
        tot[name] = tot.get(name, 0.0) + (e - s) / 1e9
    return sorted(([k, v] for k, v in tot.items()), key=lambda kv: -kv[1])[:n]
