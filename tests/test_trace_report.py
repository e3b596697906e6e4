"""trace_report.py rendering against partial traces (ISSUE 10).

Sharded-output runs record no ``all_gather``/``replicate`` span and a run
may register histograms that never observe a value; the report script must
render those as ``—`` rather than raise.  The script is exercised through
its public entry points (``report_run`` / ``print_run`` /
``print_comparison``) on synthetic trace dirs.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "src"))

_spec = importlib.util.spec_from_file_location(
    "trace_report", REPO / "scripts" / "trace_report.py")
trace_report = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(trace_report)


def _write_run(tmp_path, name, phases, histograms=None):
    """Synthesize a traced run: span events + optional metrics snapshot."""
    run = tmp_path / name
    run.mkdir()
    with open(run / "events.jsonl", "w") as f:
        for phase, durs in phases.items():
            for d in durs:
                f.write(json.dumps({"k": "span", "name": phase, "dur": d})
                        + "\n")
    if histograms is not None:
        with open(run / "metrics.jsonl", "w") as f:
            f.write(json.dumps({"histograms": histograms, "gauges": {},
                                "counters": {}}) + "\n")
    (run / "meta.json").write_text(json.dumps(
        {"fingerprint": {"driver": "sync", "backend": "mesh",
                         "method": "pfedsop"}}))
    return run


class TestMissingPhaseRendering:
    def test_comparison_renders_dash_for_absent_phase(self, tmp_path, capsys):
        replicated = _write_run(tmp_path, "replicated", {
            "round": [900, 800], "client": [500, 450],
            "all_gather": [200, 180], "aggregate": [100, 90]})
        sharded = _write_run(tmp_path, "sharded", {
            "round": [700, 600], "client": [500, 450],
            "aggregate": [100, 90]})  # no all_gather span at all
        reps = [trace_report.report_run(r, top_k=3)
                for r in (replicated, sharded)]
        for rep in reps:
            trace_report.print_run(rep)
        trace_report.print_comparison(reps)
        out = capsys.readouterr().out
        assert "all_gather" in out
        assert "—" in out  # the sharded column renders a dash, not a crash

    def test_comparison_with_no_phases_at_all(self, tmp_path, capsys):
        empty = _write_run(tmp_path, "empty", {})
        rep = trace_report.report_run(empty, top_k=3)
        trace_report.print_run(rep)
        trace_report.print_comparison([rep, rep])
        assert rep["phases"] == {}

    def test_share_column_dash_without_round_phase(self, tmp_path, capsys):
        run = _write_run(tmp_path, "noround", {"client": [500, 450]})
        trace_report.print_run(trace_report.report_run(run, top_k=3))
        out = capsys.readouterr().out
        assert "client" in out and "—" in out


class TestRoundLevelSpans:
    def test_round_level_spans_render_in_pipeline_order(self, tmp_path, capsys):
        """A non-blocking (level ``round``) trace: sample, the program
        calls' dispatch spans in pipeline order, then sync."""
        run = _write_run(tmp_path, "round_level", {
            "sync": [400, 300], "dispatch.scatter": [5, 5],
            "dispatch.client": [50, 40], "sample": [30, 20],
            "dispatch.gather": [10, 8], "round": [600, 500]})
        rep = trace_report.report_run(run, top_k=3)
        trace_report.print_run(rep)
        rows = [line.split()[0] for line in capsys.readouterr().out.splitlines()
                if line.strip().split(" ")[0] in rep["phases"]]
        assert rows == ["sample", "dispatch.gather", "dispatch.client",
                        "dispatch.scatter", "sync", "round"]
        assert rep["phases"]["sync"]["warm_mean_us"] == 300


class TestHistogramRendering:
    def test_unobserved_histogram_renders(self):
        # Histogram.snapshot() of a never-observed histogram: min/max None
        h = {"edges": [0.0, 1.0], "counts": [0, 0, 0], "count": 0,
             "sum": 0.0, "min": None, "max": None}
        lines = trace_report._fmt_hist("beta", h)
        assert lines == ["  beta: n=0 mean=— min=— max=—"]

    def test_observed_histogram_renders_bars(self):
        h = {"edges": [0.0, 1.0], "counts": [0, 3, 1], "count": 4,
             "sum": 2.5, "min": 0.1, "max": 1.4}
        lines = trace_report._fmt_hist("beta", h)
        assert "n=4" in lines[0]
        assert any("#" in ln for ln in lines[1:])

    def test_print_run_with_unobserved_histogram(self, tmp_path, capsys):
        run = _write_run(
            tmp_path, "hist", {"round": [100, 90]},
            histograms={"fl.beta": {"edges": [0.0, 1.0],
                                    "counts": [0, 0, 0], "count": 0,
                                    "sum": 0.0, "min": None, "max": None}})
        trace_report.print_run(trace_report.report_run(run, top_k=3))
        out = capsys.readouterr().out
        assert "fl.beta: n=0" in out
