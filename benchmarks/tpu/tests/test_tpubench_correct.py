"""The comparison that decides `correct`, at a size a CPU test run holds:
a sound run passes the committed limits of the cell, while the bfloat16
control and each fault planted under the timed path fail them.  The
harness's look for a chip is skipped; the rest of a run is driven as on
the chip."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[3] / "src"))

import calibrate  # noqa: E402
import run  # noqa: E402
from tpubench import check, spec  # noqa: E402

CELL = "resnet18-cifar100.crossdevice"
SPECS = [{"name": "rounds_per_s", "unit": "rounds/s"}, {"name": "setup_s", "unit": "s"}]
SEED = 2**31 + 12345


def tiny():
    """The cell's configuration and traffic at a size a test holds."""
    bench = spec.load_benchmark()
    wl = spec.workload(bench, CELL)
    config = json.loads(json.dumps(spec.load_config(bench, wl["config"])))
    config["program_config"] = "repro.configs.resnet_cifar:SMALL_CNN"
    config["model"] = {"channels": [16, 32], "blocks_per_stage": 1, "image_size": 16,
                       "in_channels": 3, "n_classes": 10}
    config["data"]["samples"] = 400
    traffic = dict(spec.load_traffic(wl["traffic"]), clients=8, participation=0.5,
                   batch=8, local_iters=3, samples=400)
    return config, traffic, spec.load_limits(CELL)


def run_tiny():
    import jax

    config, traffic, limits = tiny()
    return run.run_cell(CELL, config, traffic, limits, SEED, 0.01, False,
                        jax.devices(), SPECS)


def test_sound_run_is_correct():
    res = run_tiny()
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"rounds_per_s", "setup_s"}


def test_bfloat16_control_is_not_correct():
    config, traffic, limits = tiny()
    _, (images, labels, parts, weights) = calibrate.program_side(config, traffic, SEED)
    ref = run.reference_readings(config, traffic, SEED, images, labels, parts, weights)
    ctrl = run.reference_readings(config, traffic, SEED, images, labels, parts, weights,
                                  dtype="bfloat16")
    correct, table = check.judge(check.readings(ctrl, ref), limits)
    assert not correct, table


def unchanged_state(monkeypatch):
    from repro.core import baselines

    orig = baselines.PFedSOP.client_round

    def client_round(self, loss_fn, state, broadcast, batches):
        _, delta, metrics = orig(self, loss_fn, state, broadcast, batches)
        return state, delta, metrics

    monkeypatch.setattr(baselines.PFedSOP, "client_round", client_round)


def planted(fault):
    def plant(monkeypatch):
        cm = calibrate.FAULTS[fault]()
        cm.__enter__()
        monkeypatch.undo = (lambda undo=monkeypatch.undo: (cm.__exit__(None, None, None),
                                                           undo()))
    return plant


@pytest.mark.parametrize("plant", [unchanged_state, planted("half_batch"),
                                   planted("altered_answers")],
                         ids=["unchanged_state", "half_batch", "altered_answers"])
def test_fault_under_the_timed_path_is_not_correct(plant, monkeypatch):
    plant(monkeypatch)
    res = run_tiny()
    assert not res["correct"], res["checks"]
