"""Stretch (D): the program's own round spans on the device trace's clock.

For 0.30 x ``--seconds`` of whole rounds after the span stretch (S), the
profiler records device operations only (host tracer off, as in stretch
P), and the federation's ``repro.obs`` facade, attached at level
``round``, records non-blocking spans inside every round: ``sample``,
``dispatch.<phase>`` and ``sync``.  Nothing blocks between phases, so
the rounds run the schedule the untraced window runs.

Two clocks, one shift: span ``ts`` is epoch microseconds, and the
trace's events count nanoseconds from its ``profile_start_time`` (epoch
ns, a stat of the ``Task Environment`` plane), so a span starts at
``ts_us * 1000 - profile_start_time`` on the trace's clock.  Each chip's
``XLA Modules`` line names the program every execution belongs to
(``jit_client_round(12)``: the function name given to ``jax.jit``), so
device time is split by program without reading any op names.

The reduced trace, which the tests read back from a recorded excerpt:

  {"start_ns": profile_start_time,
   "ops":     {chip: [[op name, start ns, duration ns], ...]},   # XLA Ops
   "modules": {chip: [[module name, start ns, duration ns], ...]}}

The harness (``run.py``, ``traced_stretches``) runs stretch D after S
and hands it to the readers as ``ctx["stretch_d"]``; a reader that finds
no stretch there returns nothing.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile

import numpy as np

from tpubench.profile import DEVICE_PLANE, OPS_LINE, union

SHARE = 0.30          # of --seconds
MODULES_LINE = "XLA Modules"
ENV_PLANE = "Task Environment"

CLIENT = ("jit_client_round",)
EVAL = ("jit_eval_round",)
STORE = ("jit_store_gather", "jit_store_scatter")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load(logdir: str) -> dict:
    """The reduced trace of the ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    prof = ProfileData.from_file(paths[-1])
    out = {"start_ns": None, "ops": {}, "modules": {}}
    for plane in prof.planes:
        if plane.name == ENV_PLANE:
            for name, value in plane.stats:
                if name == "profile_start_time":
                    out["start_ns"] = int(value)
            continue
        dev = DEVICE_PLANE.match(plane.name)
        if not dev:
            continue
        chip = int(dev.group(1))
        for line in plane.lines:
            key = {MODULES_LINE: "modules", OPS_LINE: "ops"}.get(line.name)
            if key:
                out[key].setdefault(chip, []).extend(
                    [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                    for ev in line.events)
    if out["start_ns"] is None:
        raise ValueError(f"the trace under {logdir} has no profile_start_time")
    return out


def module_name(name: str) -> str:
    """``jit_client_round(12)`` -> ``jit_client_round``."""
    return name.split("(", 1)[0]


def shift(spans, start_ns: int):
    """[[name, start, end], ...] on the trace's clock (ns from its start)
    of program spans stamped in epoch microseconds."""
    return [[s["name"], s["ts"] * 1000 - start_ns, (s["ts"] + s["dur"]) * 1000 - start_ns]
            for s in spans]


def module_ms(stretch: dict, prefixes) -> float | None:
    """Device time per round, ms, of the modules whose name starts with
    one of ``prefixes``, averaged over the chips; None where no such
    module ran."""
    chips = stretch["modules"]
    totals = [sum(d for name, _, d in mods if module_name(name).startswith(tuple(prefixes)))
              for mods in chips.values()]
    if not any(totals) or stretch["rounds"] <= 0:
        return None
    return sum(totals) / len(totals) / stretch["rounds"] / 1e6


def span_ms(stretch: dict, name: str) -> float | None:
    """Host time per round, ms, of the program spans named ``name``."""
    d = [e - s for n, s, e in stretch["spans"] if n == name]
    if not d or stretch["rounds"] <= 0:
        return None
    return sum(d) / stretch["rounds"] / 1e6


def idle_by_span(stretch: dict) -> dict:
    """{span name: ns}: chip 0's idle time in the stretch's rounds, each
    gap given to the innermost program span holding its midpoint
    (``none`` where no span holds it).  Busy is the union of chip 0's
    ``XLA Ops``."""
    lo, hi = stretch["lo"], stretch["hi"]
    ops = stretch["ops"].get(min(stretch["ops"], default=0), [])
    busy = union(((s, s + d) for _, s, d in ops), lo, hi)
    edges = np.array([lo] + [x for iv in busy for x in iv] + [hi], np.float64)
    gap_s, gap_e = edges[::2], edges[1::2]
    keep = gap_e > gap_s
    gap_s, gap_e = gap_s[keep], gap_e[keep]
    mid = (gap_s + gap_e) / 2
    owner = np.full(len(mid), -1)
    spans = stretch["spans"]
    # longest first, so a span nested in another takes its own gaps
    for i in sorted(range(len(spans)), key=lambda i: spans[i][1] - spans[i][2]):
        owner[(spans[i][1] <= mid) & (mid < spans[i][2])] = i
    out = {}
    for i, d in zip(owner.tolist(), (gap_e - gap_s).tolist()):
        name = spans[i][0] if i >= 0 else "none"
        out[name] = out.get(name, 0.0) + d
    return out


def driver_idle_ms(stretch: dict) -> float | None:
    """Chip 0's idle per round, ms, whose midpoint lies in a program span
    other than ``sync``: idle caused by the driver's own host work."""
    if stretch["rounds"] <= 0 or not stretch["ops"] or not stretch["spans"]:
        return None
    idle = idle_by_span(stretch)
    return sum(v for k, v in idle.items() if k not in ("sync", "none")) / stretch["rounds"] / 1e6


def clock_check(stretch: dict):
    """(inside, executions): chip 0's ``jit_client_round`` executions that
    start after their round's ``dispatch.client`` span begins and end
    before its ``sync`` span ends, the i-th execution paired with the
    i-th round."""
    chip = min(stretch["modules"], default=None)
    if chip is None:
        return 0, 0
    runs = sorted((s, s + d) for n, s, d in stretch["modules"][chip]
                  if module_name(n).startswith(CLIENT))
    starts = [s for n, s, _ in stretch["spans"] if n == "dispatch.client"]
    ends = [e for n, _, e in stretch["spans"] if n == "sync"]
    inside = sum(1 for (s, e), a, b in zip(runs, starts, ends) if a <= s and e <= b)
    return inside, len(runs)


def run_stretch(cell, seconds: float, timed_rounds) -> dict:
    """Whole rounds for ``seconds`` under a device-only profile with the
    program's obs attached at level ``round``; returns the stretch:
    ``rounds``, the reduced trace's ``start_ns``, ``ops``, ``modules``,
    the shifted program ``spans``, each round's [start, end] on the trace
    clock (``round_bounds``), the stretch's ``lo``/``hi`` and its rounds
    with a non-finite loss or accuracy (``bad``)."""
    import jax
    from repro.obs import NOOP, Obs, ObsConfig, read_events

    logdir = tempfile.mkdtemp(prefix="bench_xplane_d_")
    spandir = tempfile.mkdtemp(prefix="bench_spans_d_")
    obs = cell.fed.attach_obs(Obs(ObsConfig(trace_dir=spandir, level="round", quiet=True)))
    rounds = []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        n, _, bad, _ = timed_rounds(cell, seconds,
                                    on_round=lambda ts, d: rounds.append((ts, d)),
                                    label="stretch D (round spans, device trace)")
    finally:
        jax.profiler.stop_trace()
        obs.close()
        cell.fed.attach_obs(NOOP)
    trace = load(logdir)
    raw = [e for e in read_events(spandir) if e.get("k") == "span"]
    shutil.rmtree(logdir, ignore_errors=True)
    shutil.rmtree(spandir, ignore_errors=True)
    start = trace["start_ns"]
    bounds = [[ts * 1000 - start, ts * 1000 + d * 1e9 - start] for ts, d in rounds]
    return {**trace, "rounds": n, "bad": bad, "spans": shift(raw, start),
            "round_bounds": bounds, "lo": bounds[0][0], "hi": bounds[-1][1]}


def describe(stretch: dict) -> None:
    """Log what the metrics do not carry: the clock check, chip 0's idle
    by span, each span's mean host time, chip 0's device time by module
    and the slowest round's spans."""
    inside, runs = clock_check(stretch)
    log(f"stretch D clock check: {inside} of {runs} jit_client_round executions inside "
        "their round's [dispatch.client start, sync end]")
    n = max(stretch["rounds"], 1)
    idle = idle_by_span(stretch)
    log("stretch D chip-0 idle per round (ms) by span: "
        + ", ".join(f"{k} {v / n / 1e6:.4f}" for k, v in sorted(idle.items(), key=lambda kv: -kv[1])))
    names = sorted({s[0] for s in stretch["spans"]})
    log("stretch D host time per round (ms) by span: "
        + ", ".join(f"{k} {span_ms(stretch, k):.4f}" for k in names))
    mods = {}
    for name, _, d in stretch["modules"].get(min(stretch["modules"], default=0), []):
        mods[module_name(name)] = mods.get(module_name(name), 0.0) + d
    log("stretch D chip-0 device time per round (ms) by module: "
        + ", ".join(f"{k} {v / n / 1e6:.4f}" for k, v in sorted(mods.items(), key=lambda kv: -kv[1])))
    if stretch["round_bounds"]:
        a, b = max(stretch["round_bounds"], key=lambda r: r[1] - r[0])
        held = [f"{k} {(min(e, b) - max(s, a)) / 1e6:.3f}" for k, s, e in stretch["spans"]
                if min(e, b) > max(s, a)]
        log(f"stretch D slowest round {(b - a) / 1e9:.4f}s, spans (ms): {', '.join(held)}")
