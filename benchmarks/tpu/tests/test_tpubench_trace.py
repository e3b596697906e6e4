"""The trace reduction on a small recorded trace: busy time as the union
of device-op intervals, kernel time by name scope, the heaviest ops and
the host's activity in the device's idle gaps."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from tpubench import profile, spec, work  # noqa: E402

RESNET9 = {"model": dict(image_size=32, channels=[64, 128, 256], in_channels=3,
                         n_classes=100)}

RECORDED = Path(__file__).resolve().parent / "data" / "trace_excerpt.json"


def synthetic():
    """Two chips; on chip 0 a while loop with ops nested inside it, the
    update kernel's two Pallas calls and an op that reads their output,
    and a gap while the host transposes the next round's batch."""
    return {
        "device": {
            0: [["%while.4 = while(...)", 100.0, 400.0, ""],
                ["%fusion.1 = fusion(...)", 150.0, 100.0, ""],
                ["%fusion.2 = fusion(...)", 300.0, 150.0, ""],
                ["%pfedsop_update_batched.3 = f32[20,20,1,128] custom-call(...)",
                 700.0, 50.0, ""],
                ["%pfedsop_update_batched.5 = f32[20,10240,128] custom-call(...)",
                 740.0, 30.0, ""],
                ["%fusion.9 = f32[20] fusion(%pfedsop_update_batched.5)", 770.0, 0.0, ""]],
            1: [["%while.4 = while(...)", 100.0, 200.0, ""]],
        },
        "host": [["python", profile.WINDOW, 0.0, 1000.0],
                 ["python", "bench.round", 0.0, 1000.0],
                 ["pjrt", "Transpose", 500.0, 180.0],
                 ["pjrt", "Transpose", 520.0, 20.0]],
    }


def test_busy_is_the_union_of_op_intervals():
    ev = synthetic()
    # chip 0: [100, 500) and [700, 770) -> 470 ns; chip 1: 200 ns
    assert profile.busy_ns(ev["device"][0], 0.0, 1000.0) == 470.0
    assert profile.device_busy_s(ev, 0.0, 1000.0) == pytest.approx(335e-9)
    assert profile.busy_ns(ev["device"][0], 200.0, 720.0) == 320.0


def test_device_only_trace_reduces_over_all_its_ops():
    """Stretch (P) records no host events: busy time and kernel time are
    read over every op the trace holds, against the stretch's host-clock
    length."""
    ev = synthetic()
    ev["host"] = []
    inf = float("inf")
    assert profile.device_busy_s(ev, -inf, inf) == pytest.approx(335e-9)
    assert profile.ops_s(ev, "%pfedsop_update", -inf, inf) == pytest.approx(35e-9)
    assert profile.top_ops(ev, -inf, inf, n=1) == [["%while.4", pytest.approx(400e-9)]]
    ctx = {"window_s": 1000e-9, "busy_s": profile.device_busy_s(ev, -inf, inf),
           "events": ev}
    assert spec.load_reader("device.idle_frac")(ctx) == pytest.approx(66.5)


def test_kernel_time_by_op_name_counts_overlaps_once():
    ev = synthetic()
    # [700, 750) and [740, 770) on chip 0, not the op that reads them;
    # none on chip 1
    assert profile.ops_s(ev, "%pfedsop_update", 0.0, 1000.0) == pytest.approx(35e-9)
    assert profile.ops_s(ev, "%no_such_op", 0.0, 1000.0) == 0.0


def test_window_is_the_benchmarks_own_span():
    assert profile.window(synthetic()) == (0.0, 1000.0)
    with pytest.raises(ValueError):
        profile.window({"device": {}, "host": []})


def test_idle_gaps_named_by_the_innermost_host_event():
    gaps = dict(profile.idle_gaps(synthetic(), 0.0, 1000.0))
    # [500, 700): midpoint 600 inside the 180 ns Transpose, not the 20 ns one
    assert gaps["Transpose"] == pytest.approx(200e-9)
    # [0, 100) and [770, 1000) fall inside bench.round only
    assert gaps["bench.round"] == pytest.approx(330e-9)


def test_top_ops_sum_by_name_on_chip_0():
    ops = profile.top_ops(synthetic(), 0.0, 1000.0, n=2)
    assert ops[0] == ["%while.4", pytest.approx(400e-9)]
    assert len(ops) == 2


def test_recorded_trace_reduces_within_bounds():
    """An excerpt of a traced round of the program's ResNet-9 over a
    cohort of K'=10 on a TPU v5e, around the update kernel's three Pallas
    calls."""
    ev = json.loads(RECORDED.read_text())
    ev["device"] = {int(k): v for k, v in ev["device"].items()}
    lo, hi = profile.window(ev)
    busy = profile.device_busy_s(ev, lo, hi)
    assert 0 < busy <= (hi - lo) / 1e9
    kernel = profile.ops_s(ev, "%pfedsop_update", lo, hi)
    assert 0 < kernel < busy
    nested_sum = sum(min(s + d, hi) - max(s, lo) for _, s, d, _ in ev["device"][0]
                     if min(s + d, hi) > max(s, lo)) / 1e9
    assert busy < nested_sum          # nested ops counted once
    ctx = {"events": ev, "lo": lo, "hi": hi, "rounds": 1, "window_s": (hi - lo) / 1e9,
           "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "update_work": work.update_work(10, spec.load_family("resnet").param_count(RESNET9))}
    share = spec.load_reader("pfedsop_update_roofline")(ctx)
    # 156 MB at 819 GB/s over the 216 us the three calls took
    assert share == pytest.approx(88.0, abs=1.0)
