"""Work counts of the benchmark (the ResNet family's, ``families/resnet.py``,
and the round's) against hand counts and the program's shapes."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[3] / "src"))

from tpubench import spec, work  # noqa: E402

RESNET = spec.load_family("resnet")
RESNET9 = {"model": dict(image_size=32, channels=[64, 128, 256], in_channels=3,
                         n_classes=100)}
RESNET18 = {"model": dict(image_size=32, channels=[64, 128, 256, 512], in_channels=3,
                          n_classes=10)}


@pytest.mark.parametrize("model,gflop", [(RESNET9, 0.389), (RESNET18, 0.507)])
def test_forward_flops_match_hand_count(model, gflop):
    assert RESNET.forward_flops(model) / 1e9 == pytest.approx(gflop, abs=5e-4)


def test_resnet9_flops_exact():
    # stem 1.77M MACs; stage 1 2x37.7M; stages 2-3 18.9M + 37.7M + 2.1M each; head
    macs = (32 * 32 * 27 * 64 + 2 * 32 * 32 * 576 * 64
            + 16 * 16 * 576 * 128 + 16 * 16 * 1152 * 128 + 16 * 16 * 64 * 128
            + 8 * 8 * 1152 * 256 + 8 * 8 * 2304 * 256 + 8 * 8 * 128 * 256 + 256 * 100)
    assert RESNET.forward_flops(RESNET9) == 2 * macs


@pytest.mark.parametrize("name,model", [("RESNET9_CIFAR100", RESNET9),
                                        ("RESNET18_CIFAR10", RESNET18)])
def test_param_count_matches_program_model(name, model):
    import jax

    from repro.configs import resnet_cifar
    from repro.models import cnn

    params = jax.eval_shape(lambda: cnn.init_params(jax.random.PRNGKey(0),
                                                    getattr(resnet_cifar, name)))
    n = sum(x.size for x in jax.tree.leaves(params))
    assert RESNET.param_count(model) == n


@pytest.mark.parametrize("config", [c["name"] for c in spec.load_benchmark()["configs"]])
def test_param_count_matches_each_configuration_as_run(config):
    import jax

    from repro.models import cnn

    cfg = spec.load_config(spec.load_benchmark(), config)
    family = spec.load_family(cfg["family"])
    params = jax.eval_shape(lambda: cnn.init_params(jax.random.PRNGKey(0),
                                                    family.program_model(cfg)))
    n = sum(x.size for x in jax.tree.leaves(params))
    assert family.param_count(cfg) == n
    # the weights the family makes are as large
    weights = jax.eval_shape(lambda: family.init_params(jax.random.PRNGKey(0), cfg["model"]))
    assert sum(x.size for x in jax.tree.leaves(weights)) == n


def test_round_model_flops_counts_sgd_three_times_and_real_eval_once():
    fwd = RESNET.forward_flops(RESNET9)
    assert work.round_model_flops(fwd, 20, 10, 50, 0) == 3 * fwd * 20 * 10 * 50
    assert (work.round_model_flops(fwd, 20, 10, 50, 2400)
            - work.round_model_flops(fwd, 20, 10, 50, 0)) == fwd * 2400
    # the paper round: 11.7 TFLOP of local SGD
    assert work.round_model_flops(fwd, 20, 10, 50, 0) / 1e12 == pytest.approx(11.68, abs=0.01)


def test_update_work_reads_shared_delta_once():
    flops, nbytes = work.update_work(20, 1_249_956)
    assert nbytes == 4 * (3 * 20 * 1_249_956 + 1_249_956)
    assert flops == 11 * 20 * 1_249_956
    # HBM time at 819 GB/s: about 0.37 ms; the byte bound dominates
    assert nbytes / 819e9 > flops / 197e12
