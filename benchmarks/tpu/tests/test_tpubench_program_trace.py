"""Stretch (D)'s reduction: program spans shifted onto the device trace's
clock, device time by XLA module, and chip 0's idle time given to the
program span that holds each gap's midpoint."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from tpubench import program_trace as pt, spec  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "stretch_d_excerpt.json"

NEW = ("client.device_ms", "eval.device_ms", "store.device_ms", "driver.sample_ms",
       "driver.idle_ms")


def synthetic():
    """Two rounds on two chips, on the trace's clock (ns).  Chip 0 idles
    in [0, 150) (midpoint in `sample`), [450, 620) (midpoint 535 in round
    2's `sample`, though the gap starts in round 1's `sync`), [700, 720)
    (midpoint 710 in no span) and [900, 1000) (in `sync`)."""
    spans = [["sample", 0, 100], ["dispatch.gather", 100, 120],
             ["dispatch.client", 120, 300], ["dispatch.eval", 300, 320],
             ["dispatch.aggregate", 320, 330], ["dispatch.scatter", 330, 340],
             ["sync", 340, 500],
             ["sample", 500, 560], ["dispatch.gather", 560, 570],
             ["dispatch.client", 570, 600], ["dispatch.eval", 600, 700],
             ["sync", 720, 1000]]
    ops = {0: [["%while.1", 150.0, 200.0], ["%fusion.2", 200.0, 100.0],
               ["%fusion.3", 350.0, 100.0], ["%while.1", 620.0, 80.0],
               ["%fusion.4", 720.0, 180.0]],
           1: [["%while.1", 150.0, 300.0]]}
    modules = {0: [["jit_store_gather(1)", 150.0, 10.0], ["jit_client_round(2)", 160.0, 250.0],
                   ["jit_eval_round(3)", 410.0, 40.0], ["jit_store_scatter(5)", 620.0, 5.0],
                   ["jit_client_round(2)", 625.0, 75.0], ["jit_eval_round(3)", 720.0, 180.0]],
               1: [["jit_client_round(2)", 150.0, 300.0]]}
    return {"rounds": 2, "lo": 0.0, "hi": 1000.0, "spans": spans, "ops": ops,
            "modules": modules, "round_bounds": [[0.0, 500.0], [500.0, 1000.0]]}


def test_module_time_per_round_mean_over_chips():
    st = synthetic()
    # client: chip 0 250 + 75, chip 1 300 -> 312.5 ns over 2 rounds
    assert pt.module_ms(st, pt.CLIENT) == pytest.approx(312.5 / 2 / 1e6)
    # eval on chip 0 only: (40 + 180) / 2 chips / 2 rounds
    assert pt.module_ms(st, pt.EVAL) == pytest.approx(55.0 / 1e6)
    assert pt.module_ms(st, pt.STORE) == pytest.approx(15.0 / 4 / 1e6)
    assert pt.module_ms(st, ("jit_no_such",)) is None


def test_idle_goes_to_the_span_holding_its_midpoint():
    idle = pt.idle_by_span(synthetic())
    assert idle == {"sample": 150.0 + 170.0, "none": 20.0, "sync": 100.0}
    # sync and no span are not the driver's: (150 + 170) / 2 rounds
    assert pt.driver_idle_ms(synthetic()) == pytest.approx(160.0 / 1e6)


def test_nested_span_takes_its_own_gaps():
    st = synthetic()
    st["spans"].append(["outer", -10, 2000])
    idle = pt.idle_by_span(st)
    assert idle == {"sample": 320.0, "outer": 20.0, "sync": 100.0}


def test_clock_check_pairs_client_runs_with_their_rounds():
    st = synthetic()
    assert pt.clock_check(st) == (2, 2)
    st["modules"][0][4][1] = 990.0          # round 2's client ends after its sync
    assert pt.clock_check(st) == (1, 2)


def test_shift_puts_epoch_spans_on_the_trace_clock():
    start = 1_792_341_133_589_984_021
    spans = [{"name": "sample", "ts": 1_792_341_133_590_000, "dur": 250}]
    assert pt.shift(spans, start) == [["sample", 15_979, 265_979]]


def test_new_readers_read_a_stretch_in_the_context():
    ctx = {"stretch_d": synthetic()}
    got = {m: spec.load_reader(m)(ctx) for m in NEW}
    assert got["client.device_ms"] == pytest.approx(312.5 / 2 / 1e6)
    assert got["eval.device_ms"] == pytest.approx(55.0 / 1e6)
    assert got["store.device_ms"] == pytest.approx(15.0 / 4 / 1e6)
    assert got["driver.sample_ms"] == pytest.approx(160.0 / 2 / 1e6)
    assert got["driver.idle_ms"] == pytest.approx(160.0 / 1e6)


def test_readers_outside_the_harness_run_no_stretch():
    ctx = {}
    for m in NEW:
        assert spec.load_reader(m)(ctx) is None
    assert ctx == {"stretch_d": None}


class _Fed:
    """A federation without ``attach_obs``, as the parent program's."""


class _Cell:
    fed = _Fed()


def run_cell(cell, seconds, ctx):
    """Stands in for the harness's frame."""
    return pt.ensure(ctx)


def test_a_program_without_attach_obs_gets_no_stretch(capsys):
    ctx = {}
    assert run_cell(_Cell(), 40.0, ctx) is None
    assert ctx == {"stretch_d": None}
    assert "no Federation.attach_obs" in capsys.readouterr().err


def recorded():
    """Two rounds of stretch (D) in the cross-device cell on a TPU v5e:
    the program's raw spans (epoch us), the trace's profile_start_time
    and chip 0's modules and longest ops, as recorded."""
    ex = json.loads(RECORDED.read_text())
    chips = lambda d: {int(c): v for c, v in d.items()}
    bounds = ex["round_bounds"]
    return ex, {"rounds": ex["rounds"], "spans": pt.shift(ex["spans"], ex["start_ns"]),
                "modules": chips(ex["modules"]), "ops": chips(ex["ops"]),
                "round_bounds": bounds, "lo": bounds[0][0], "hi": bounds[-1][1]}


def test_recorded_spans_and_modules_share_one_clock():
    """After the shift, every program runs after the span that launched
    it begins, and the round's client and eval programs end inside its
    `sync` span, where the host waits for them."""
    ex, st = recorded()
    assert ex["device"] == "TPU v5 lite"
    assert pt.clock_check(st) == (2, 2)
    spans = st["spans"]
    per_round = len(spans) // 2
    launcher = {"jit_store_gather": "dispatch.gather", "jit_client_round": "dispatch.client",
                "jit_eval_round": "dispatch.eval", "jit_aggregate": "dispatch.aggregate",
                "jit_store_scatter": "dispatch.scatter"}
    mods = st["modules"][0]
    assert [pt.module_name(m[0]) for m in mods] == list(launcher) * 2
    for i, (name, start, dur) in enumerate(mods):
        rnd = spans[per_round * (i // 5):per_round * (i // 5 + 1)]
        launched = next(s for n, s, _ in rnd if n == launcher[pt.module_name(name)])
        assert launched < start
        if pt.module_name(name) in ("jit_client_round", "jit_eval_round"):
            sync = next((a, b) for n, a, b in rnd if n == "sync")
            assert sync[0] < start + dur <= sync[1]
    # the rounds' spans lie inside the rounds the harness timed
    for (a, b), i in zip(st["round_bounds"], (0, per_round)):
        assert all(a - 1e3 <= s <= e <= b + 1e3 for _, s, e in spans[i:i + per_round])


def test_recorded_module_time():
    _, st = recorded()
    assert pt.module_ms(st, pt.CLIENT) == pytest.approx(118.656, abs=0.01)
    assert pt.module_ms(st, pt.EVAL) == pytest.approx(11.350, abs=0.01)
    assert pt.module_ms(st, pt.STORE) == pytest.approx(32.743, abs=0.01)
    # the excerpt keeps chip 0's longest ops: each lies inside a module
    # execution, so attribution by module needs no op names
    runs = [(s, s + d) for _, s, d in st["modules"][0]]
    assert all(any(a <= s and s + d <= b for a, b in runs) for _, s, d in st["ops"][0])
