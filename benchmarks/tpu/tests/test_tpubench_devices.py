"""The benchmark refuses a host without a TPU, too few chips, and a chip
missing from the peak table."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[3] / "src"))

from tpubench import devices as devs  # noqa: E402


def dev(platform="tpu", kind="TPU v5 lite"):
    return SimpleNamespace(platform=platform, device_kind=kind,
                           memory_stats=lambda: {"peak_bytes_in_use": 7})


def test_cpu_host_is_refused():
    with pytest.raises(devs.DeviceError, match="needs a TPU"):
        devs.cell_devices([dev("cpu", "cpu")], 1)


def test_unknown_device_kind_is_refused():
    with pytest.raises(devs.DeviceError, match="peak table"):
        devs.cell_devices([dev(kind="TPU v9 giant")], 1)


def test_too_few_chips_are_refused():
    with pytest.raises(devs.DeviceError, match="4 chips"):
        devs.cell_devices([dev()], 4)


def test_cell_takes_the_first_chips_and_names_them():
    got = devs.cell_devices([dev(), dev(), dev(), dev()], 1)
    assert len(got) == 1
    assert devs.describe(got) == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert devs.memory_peak_bytes(got) == 7


def test_run_exits_without_a_result_off_the_chip(capsys):
    import run

    rc = run.main(["--workload", "resnet18-cifar10.paper", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc == 3
    assert capsys.readouterr().out == ""
