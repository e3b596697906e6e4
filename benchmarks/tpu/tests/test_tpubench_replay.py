"""The benchmark against a small live federation on the CPU: its replay of
the driver's client draw (``Cell.next_eval_samples``, which ``round.mfu``
counts eval FLOPs from) agrees with the cohort each round reports, and
stretch (D) runs the harness's own round loop with the program's spans
attached, on the trace's clock."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402
from tpubench import program_trace as pt  # noqa: E402


class SmallCell:
    """The harness's ``Cell`` around a small federation: its own replay
    and sync, unchanged."""

    next_eval_samples = run.Cell.next_eval_samples
    sync = run.Cell.sync

    def __init__(self, seed: int):
        import jax
        from repro.configs.resnet_cifar import SMALL_CNN as CFG
        from repro.core.baselines import METHODS
        from repro.data import (FederatedData, dirichlet_partition,
                                make_class_conditional_images)
        from repro.fl import Federation, FLRunConfig
        from repro.fl.runtime import masked_accuracy
        from repro.models import cnn

        images, labels = make_class_conditional_images(300, CFG.n_classes,
                                                       CFG.cnn_image_size, seed=0)
        parts = dirichlet_partition(labels, 10, alpha=0.3, seed=0)
        data = FederatedData.from_partition(images, labels, parts, seed=0)
        self.run_cfg = FLRunConfig(n_clients=10, participation=0.3, rounds=1, batch=8,
                                   local_iters=1, seed=seed)
        self.fed = Federation(
            METHODS["pfedsop"](), lambda p, b: cnn.loss_fn(p, CFG, b),
            masked_accuracy(lambda p, t: cnn.apply(p, CFG, t["images"])),
            cnn.init_params(jax.random.PRNGKey(0), CFG), data, self.run_cfg)
        self.test_counts = data.test_counts


@pytest.mark.parametrize("seed", [0, 2_147_483_659 % 2**32])
def test_replay_matches_the_cohort_each_round_reports(seed):
    cell = SmallCell(seed)
    for _ in range(5):
        replayed = cell.next_eval_samples()
        m = cell.fed.run_round()
        assert replayed == m["eval_samples"]
        assert m["eval_samples"] == int(cell.test_counts[m["clients"]].sum())


def test_stretch_d_attaches_round_spans_and_detaches(capsys):
    """On the CPU the trace has no TPU plane, so nothing is read from the
    device; the stretch still runs whole rounds with the program's spans,
    shifts them onto the trace's clock and detaches the facade."""
    from repro.obs import NOOP

    cell = SmallCell(1)
    cell.fed.run_round()                      # compile outside the stretch
    st = pt.run_stretch(cell, 0.5, run.timed_rounds)
    pt.describe(st)
    assert st["rounds"] >= 1 and st["bad"] == 0
    assert cell.fed.obs is NOOP and cell.fed.programs.obs is NOOP
    names = [s[0] for s in st["spans"]]
    per_round = ["sample", "dispatch.gather", "dispatch.client", "dispatch.eval",
                 "dispatch.aggregate", "dispatch.scatter", "sync"]
    assert names == per_round * st["rounds"]
    # every span inside its round, the rounds inside the stretch
    for (a, b), i in zip(st["round_bounds"], range(0, len(names), len(per_round))):
        for _, s, e in st["spans"][i:i + len(per_round)]:
            assert a - 1e3 <= s <= e <= b + 1e3
    assert 0 <= st["lo"] < st["hi"]
    assert st["modules"] == {} and st["ops"] == {}
    assert pt.module_ms(st, pt.CLIENT) is None
    assert pt.span_ms(st, "sample") > 0
    assert "stretch D clock check" in capsys.readouterr().err
