"""Summarize obs traces: per-phase breakdown, stragglers, bytes, dists.

Reads the ``events.jsonl`` / ``metrics.jsonl`` a traced run leaves under
its ``--trace-dir`` (DESIGN.md §13) and prints the questions the trace
exists to answer:

- **phase breakdown** — wall-clock per server phase (gather / client /
  all_gather / eval / aggregate / scatter, plus the async dispatch
  pipeline), warm means with the compile round excluded, as a share of
  round time.  A ``round``-level trace (non-blocking) splits the round's
  host time instead: ``sample`` (the host's draws and gathers), one
  ``dispatch.<phase>`` per program call, and ``sync`` (the wait for the
  device's results); a ``phase``-level trace holds ``sample`` and
  ``sync`` beside its blocking phase spans.  Pointed at several runs at once (e.g. the per-backend
  subdirs ``benchmarks/run.py --only multipod-engine --trace-dir ...``
  leaves behind) it prints a side-by-side comparison — the
  shard_map-vs-mesh gap decomposes into per-phase deltas, with the
  round-boundary all-gather visible as its own line.
- **stragglers** — top-k clients by total in-flight sim time (the async
  scheduler's dispatch→completion spans).
- **bytes moved** — the cohort store's h2d/d2h counters from the final
  metrics snapshot.
- **distributions** — the recorded histograms (pFedSOP angle θ, β,
  client loss, async staleness τ and its Gompertz discount).

  PYTHONPATH=src python scripts/trace_report.py <trace-dir> [...] \
      [--top-k 5] [--json report.json]

A directory without its own ``events.jsonl`` is searched for traced runs
beneath it, so pointing at a bench harness --trace-dir root reports every
run it contains.
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.obs import read_events, read_metrics  # noqa: E402

# server phases in pipeline order; anything else recorded lands after
_PIPELINE = ["gather", "client", "all_gather", "eval", "aggregate",
             "aggregate_stale", "scatter"]
PHASE_ORDER = (["sample"] + _PIPELINE + [f"dispatch.{p}" for p in _PIPELINE]
               + ["sync", "train_step", "round"])


def discover(paths):
    """Expand each path to the traced runs at or beneath it."""
    runs = []
    for p in paths:
        p = Path(p)
        if (p / "events.jsonl").exists():
            runs.append(p)
        else:
            runs.extend(sorted(q.parent for q in p.rglob("events.jsonl")))
    return runs


def _phase_stats(events):
    """name -> {count, total_us, warm_mean_us} over span records.

    The first occurrence of each phase carries jit compilation, so the
    warm mean (all occurrences after the first) is the honest per-round
    figure; ``total`` keeps compile time so shares still add up.
    """
    durs = defaultdict(list)
    for rec in events:
        if rec.get("k") == "span" and "dur" in rec:
            durs[rec["name"]].append(int(rec["dur"]))
    out = {}
    for name, ds in durs.items():
        warm = ds[1:] if len(ds) > 1 else ds
        out[name] = {
            "count": len(ds),
            "total_us": sum(ds),
            "warm_mean_us": sum(warm) / len(warm),
        }
    return out


def _stragglers(events, top_k):
    """Top-k clients by total in-flight sim time (+ dispatch count)."""
    total = defaultdict(float)
    count = defaultdict(int)
    for rec in events:
        if rec.get("k") == "cspan" and rec.get("name") == "inflight":
            total[rec["client"]] += rec["sim1"] - rec["sim0"]
            count[rec["client"]] += 1
    ranked = sorted(total, key=total.get, reverse=True)[:top_k]
    return [{"client": c, "inflight_sim_s": total[c], "dispatches": count[c]}
            for c in ranked]


def _compile_events(events):
    return [
        {"name": r["name"], **r.get("args", {})}
        for r in events
        if r.get("k") == "ev" and r.get("cat") == "compile"
    ]


def _last_snapshot(run):
    path = run / "metrics.jsonl"
    if not path.exists():
        return None
    snaps = read_metrics(path)
    return snaps[-1] if snaps else None


def _fmt_hist(name, h, width=28):
    if not h.get("count"):
        # registered but never observed (e.g. a sharded-output run records
        # no replicate-phase histogram): min/max are None — render, don't
        # crash on the float format
        return [f"  {name}: n=0 mean=— min=— max=—"]
    lines = [f"  {name}: n={h['count']} mean={h['sum'] / max(h['count'], 1):.4g} "
             f"min={h['min']:.4g} max={h['max']:.4g}"]
    edges = h["edges"]
    labels = ([f"<{edges[0]:g}"]
              + [f"[{a:g},{b:g})" for a, b in zip(edges, edges[1:])]
              + [f">={edges[-1]:g}"])
    peak = max(h["counts"]) or 1
    for label, n in zip(labels, h["counts"]):
        if n:
            bar = "#" * max(1, round(width * n / peak))
            lines.append(f"    {label:>14} {n:>7} {bar}")
    return lines


def report_run(run, top_k):
    events = read_events(run)
    meta_path = run / "meta.json"
    meta = json.loads(meta_path.read_text()) if meta_path.exists() else {}
    phases = _phase_stats(events)
    snap = _last_snapshot(run)
    rep = {
        "trace_dir": str(run),
        "fingerprint": meta.get("fingerprint"),
        "events": len(events),
        "resumes": sum(1 for r in events
                       if r.get("k") == "ev" and r.get("name") == "resume"),
        "phases": phases,
        "stragglers": _stragglers(events, top_k),
        "compile_events": _compile_events(events),
    }
    if snap is not None:
        gauges = snap.get("gauges", {})
        rep["bytes_moved"] = {
            k.split(".", 1)[1]: gauges[k]
            for k in ("store.h2d_bytes", "store.d2h_bytes") if k in gauges}
        rep["histograms"] = snap.get("histograms", {})
        rep["counters"] = snap.get("counters", {})
    return rep


def print_run(rep):
    fp = rep["fingerprint"] or {}
    tag = " ".join(f"{k}={fp[k]}" for k in ("driver", "backend", "method")
                   if isinstance(fp, dict) and k in fp)
    print(f"\n== {rep['trace_dir']} {('(' + tag + ')') if tag else ''}")
    print(f"  {rep['events']} events, {rep['resumes']} resume(s), "
          f"{len(rep['compile_events'])} compile event(s)")

    phases = rep["phases"]
    if phases:
        round_warm = phases.get("round", {}).get("warm_mean_us", 0)
        print(f"  {'phase':>16} {'count':>6} {'warm mean ms':>13} "
              f"{'total s':>8} {'% of round':>10}")
        names = ([n for n in PHASE_ORDER if n in phases]
                 + sorted(set(phases) - set(PHASE_ORDER)))
        for name in names:
            st = phases[name]
            share = (100 * st["warm_mean_us"] / round_warm
                     if round_warm and name != "round" else None)
            print(f"  {name:>16} {st['count']:>6} "
                  f"{st['warm_mean_us'] / 1e3:>13.2f} "
                  f"{st['total_us'] / 1e6:>8.2f} "
                  + (f"{share:>9.1f}%" if share is not None else f"{'—':>10}"))

    if rep["stragglers"]:
        print("  stragglers (total in-flight sim time):")
        for s in rep["stragglers"]:
            print(f"    client {s['client']:>6}: {s['inflight_sim_s']:>8.2f}s "
                  f"over {s['dispatches']} dispatches")

    if rep.get("bytes_moved"):
        moved = ", ".join(f"{k}={v / 1e6:.1f}MB"
                          for k, v in rep["bytes_moved"].items())
        print(f"  bytes moved: {moved}")
    if rep.get("counters"):
        print("  counters: " + ", ".join(
            f"{k}={v}" for k, v in sorted(rep["counters"].items())))
    for name, h in sorted(rep.get("histograms", {}).items()):
        for line in _fmt_hist(name, h):
            print(line)


def print_comparison(reps):
    """Side-by-side warm phase means — the cross-backend gap, attributed."""
    all_phases = set()
    for rep in reps:
        all_phases |= set(rep.get("phases", {}))
    names = ([n for n in PHASE_ORDER if n in all_phases]
             + sorted(all_phases - set(PHASE_ORDER)))
    cols = [Path(rep["trace_dir"]).name[:22] for rep in reps]
    print("\n== phase comparison (warm mean ms) ==")
    print(f"  {'phase':>16} " + " ".join(f"{c:>22}" for c in cols))
    for name in names:
        # a run may simply not record a phase (sharded-output runs have no
        # all_gather/replicate span) — render "—", never KeyError
        row = []
        for rep in reps:
            st = rep.get("phases", {}).get(name)
            row.append(f"{st['warm_mean_us'] / 1e3:>22.2f}" if st
                       else f"{'—':>22}")
        print(f"  {name:>16} " + " ".join(row))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dirs", nargs="+",
                    help="trace dir(s), or roots containing traced runs")
    ap.add_argument("--top-k", type=int, default=5,
                    help="stragglers to list per run")
    ap.add_argument("--json", default="",
                    help="also write the full structured report here")
    args = ap.parse_args()

    runs = discover(args.trace_dirs)
    if not runs:
        raise SystemExit(f"no events.jsonl found under {args.trace_dirs}")
    reps = [report_run(run, args.top_k) for run in runs]
    for rep in reps:
        print_run(rep)
    if len(reps) > 1:
        print_comparison(reps)
    if args.json:
        Path(args.json).write_text(json.dumps(reps, indent=1, default=str))
        print(f"\nwrote {args.json}")


if __name__ == "__main__":
    main()
