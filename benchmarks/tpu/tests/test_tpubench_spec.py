"""BENCHMARK.json names files that exist, and the harness finds each by name."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[3] / "src"))

from tpubench import check, spec  # noqa: E402

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load_by_name(cell):
    wl = spec.workload(BENCH, cell)
    config = spec.load_config(BENCH, wl["config"])
    traffic = spec.load_traffic(wl["traffic"])
    limits = spec.load_limits(cell)
    assert config["name"] == wl["config"]
    assert set(limits) <= set(check.NUMBERS) and "rounds_seen_diff" in limits
    assert traffic["clients"] > 0 and 0 < traffic["participation"] <= 1
    assert traffic["run"]["backend"] in ("vmap", "mesh")


@pytest.mark.parametrize("cell", CELLS)
def test_configuration_matches_program_model(cell):
    config = spec.load_config(BENCH, spec.workload(BENCH, cell)["config"])
    cfg = spec.load_family(config["family"]).program_model(config)
    model = config["model"]
    assert cfg.family == "cnn" and cfg.name == config["name"]
    assert (list(cfg.cnn_channels), cfg.cnn_image_size, cfg.cnn_in_channels, cfg.n_classes) \
        == (model["channels"], model["image_size"], model["in_channels"], model["n_classes"])


def test_mismatched_configuration_is_refused():
    """The program's ResNet has one block per stage: a file that states
    two cannot be run as stated."""
    config = spec.load_config(BENCH, "resnet18-cifar10")
    config = json.loads(json.dumps(config))
    config["model"]["blocks_per_stage"] = 2
    with pytest.raises(spec.SpecError):
        spec.load_family(config["family"]).program_model(config)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    read = spec.load_reader(metric)
    assert callable(read)


def test_readers_find_nothing_in_an_empty_context():
    ctx = {"spans": [], "obs_phases": [], "window_s": 0.0, "events": {"device": {}, "host": []},
           "lo": 0.0, "hi": 0.0, "rounds": 0, "busy_s": 0.0, "model_flops": 0, "chips": 1,
           "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}, "update_work": (1, 1)}
    for m in BENCH["per_layer"]:
        assert spec.load_reader(m["name"])(ctx) is None, m["name"]


def test_cell_metrics_filter_by_workloads_key():
    names = lambda cell, trace: {m["name"] for m in spec.cell_metrics(BENCH, cell, trace)}
    assert names(CELLS[0], False) == {"rounds_per_s", "setup_s"}
    restricted = {m["name"]: m["workloads"] for m in BENCH["per_layer"] if "workloads" in m}
    for cell in CELLS:
        for metric, cells in restricted.items():
            assert (metric in names(cell, True)) == (cell in cells)


def test_span_readers_average_over_rounds():
    rounds = [dict(gather=1.0, client=10.0, all_gather=0.0, eval=2.0, aggregate=0.5,
                   scatter=0.25, round=20.0),
              dict(gather=3.0, client=12.0, all_gather=0.0, eval=4.0, aggregate=0.5,
                   scatter=0.25, round=30.0)]
    ctx = {"spans": rounds, "obs_phases": ["aggregate", "client", "eval", "gather", "scatter"]}
    assert spec.load_reader("client.ms")(ctx) == 11.0
    assert spec.load_reader("eval.ms")(ctx) == 3.0
    assert spec.load_reader("store.ms")(ctx) == 2.25
    assert spec.load_reader("driver.host_ms")(ctx) == pytest.approx((6.25 + 10.25) / 2)


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_every_configuration_names_a_family(config):
    family = spec.load_family(spec.load_config(BENCH, config)["family"])
    assert all(callable(getattr(family, f)) for f in spec.FAMILY_API)


@pytest.mark.parametrize("name", [None, "no-such-family", "../families/resnet", ""])
def test_unknown_family_is_a_spec_error(name):
    with pytest.raises(spec.SpecError):
        spec.load_family(name)


def test_missing_family_file_is_a_spec_error(tmp_path):
    with pytest.raises(spec.SpecError, match="missing"):
        spec.load_family("resnet", bench_dir=tmp_path)


def test_family_without_the_whole_interface_is_a_spec_error(tmp_path):
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "half.py").write_text("def program(config):\n    pass\n")
    with pytest.raises(spec.SpecError, match="lacks generate"):
        spec.load_family("half", bench_dir=tmp_path)


def test_missing_files_are_errors(tmp_path):
    with pytest.raises(spec.SpecError):
        spec.load_traffic("no-such-mix", bench_dir=tmp_path)
    with pytest.raises(spec.SpecError):
        spec.load_reader("no.such.metric", bench_dir=tmp_path)
    with pytest.raises(spec.SpecError):
        spec.workload(BENCH, "no-such-cell")
