"""A model family is a file: a toy family kept with the tests, a
two-layer MLP on a seeded feature bank, runs a cell through the harness's
own ``run_cell`` to ``correct``, and the faults planted under its timed
path are not ``correct``.  The harness's look for a chip is skipped."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[3] / "src"))

import calibrate  # noqa: E402
import run  # noqa: E402
from tpubench import check, devices as devs, spec  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
SPECS = [{"name": "rounds_per_s", "unit": "rounds/s"}, {"name": "setup_s", "unit": "s"}]
SEED = 2**31 + 777
CONFIG = {
    "name": "mlp-toy", "family": "mlp",
    "model": {"features": 16, "hidden": 32, "n_classes": 4},
    "data": {"samples": 240, "train_frac": 0.8, "noise": 0.5},
    "method": {"name": "pfedsop", "eta1": 0.01, "eta2": 0.05, "rho": 1.0, "lam": 1.0,
               "update_impl": "auto"},
    "precision": {"matmul": "highest"},
}
TRAFFIC = {"clients": 4, "participation": 0.5, "batch": 8, "local_iters": 3,
           "partition": {"kind": "dirichlet", "alpha": 1.0, "seed": 0},
           "run": {"backend": "vmap", "mesh": "", "store": "device"}}
# program and reference both in f32 on the CPU part by rounding only: on
# five seeds at most 6e-8 in loss_rel and 6e-7 in state3_leaf, against the
# bfloat16 control's 2.4e-3 in loss_rel and 3.5e-3 in update1_leaf
LIMITS = {"loss_rel": 1e-4, "acc_gap": 0.05, "update1_leaf": 1e-4, "state3_leaf": 1e-4,
          "rounds_seen_diff": 0}


@pytest.fixture
def cell(tmp_path):
    """(config as read back from its file, family found by its name)."""
    (tmp_path / "mlp-toy.json").write_text(json.dumps(CONFIG))
    bench = {"configs": [{"name": "mlp-toy", "file": "mlp-toy.json"}]}
    config = spec.load_config(bench, "mlp-toy", root=tmp_path)
    return config, spec.load_family(config["family"], bench_dir=DATA)


def run_toy(config, family, trace=False, specs=SPECS, readers=None, seconds=0.01):
    import jax

    return run.run_cell("mlp-toy.toy", config, TRAFFIC, LIMITS, SEED, seconds, trace,
                        jax.devices(), specs, family, readers)


def test_toy_family_runs_a_cell_to_correct(cell):
    res = run_toy(*cell)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == set(LIMITS)
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_toy_family_control_is_not_correct(cell):
    config, family = cell
    _, (bank, parts, weights) = calibrate.program_side(config, TRAFFIC, SEED, family)
    ref = run.reference_readings(config, TRAFFIC, SEED, family, bank, parts, weights)
    ctrl = run.reference_readings(config, TRAFFIC, SEED, family, bank, parts, weights,
                                  dtype="bfloat16")
    correct, table = check.judge(check.readings(ctrl, ref), LIMITS)
    assert not correct, table


def unchanged_state(monkeypatch, family):
    """Each client's step returns the state it was given."""
    from repro.core import baselines

    orig = baselines.PFedSOP.client_round

    def client_round(self, loss_fn, state, broadcast, batches):
        _, delta, metrics = orig(self, loss_fn, state, broadcast, batches)
        return state, delta, metrics

    monkeypatch.setattr(baselines.PFedSOP, "client_round", client_round)


def planted(fault):
    def plant(monkeypatch, family):
        cm = calibrate.FAULTS[fault](family)
        cm.__enter__()
        monkeypatch.undo = (lambda undo=monkeypatch.undo: (cm.__exit__(None, None, None),
                                                           undo()))
    return plant


@pytest.mark.parametrize("plant", [unchanged_state, planted("half_batch"),
                                   planted("altered_answers")],
                         ids=["unchanged_state", "half_batch", "altered_answers"])
def test_fault_in_the_toy_family_is_not_correct(cell, plant, monkeypatch):
    config, family = cell
    plant(monkeypatch, family)
    res = run_toy(config, family)
    assert not res["correct"], res["checks"]


def test_traced_toy_run_reads_stretch_d(cell, monkeypatch):
    """``traced_stretches`` runs stretch D itself: the readers of D find
    it in their context.  The CPU trace has no device plane, so the
    device-time readers find nothing and the span reader reads."""
    import jax

    config, family = cell
    monkeypatch.setitem(devs.PEAKS, jax.devices()[0].device_kind,
                        {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    names = ["driver.sample_ms", "client.device_ms", "round.mfu"]
    specs = [{"name": n, "unit": "ms/round"} for n in names]
    res = run_toy(config, family, trace=True, specs=specs, seconds=0.3,
                  readers={n: spec.load_reader(n) for n in names})
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"driver.sample_ms", "round.mfu"}
    assert res["metrics"]["driver.sample_ms"]["value"] > 0
