"""The §13 hard contract: observability NEVER changes traced values.

For every engine backend x driver combination — {vmap, shard_map,
multi-pod mesh} x {sync, async} — the full training history of a traced
run (phase level, metrics on) must be bitwise identical to the untraced
run, except ``round_time`` (wall clock is the one documented cost of the
``timed`` block-until-ready boundaries).  Subprocess on a forced
8-device mesh, like tests/test_multipod.py: the mesh backend needs the
device count forced before jax initialises.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

_INVARIANCE_SCRIPT = textwrap.dedent(
    """
    import tempfile
    from pathlib import Path

    import jax
    assert len(jax.devices()) == 8, jax.devices()

    from repro.configs.resnet_cifar import SMALL_CNN as CFG
    from repro.core.baselines import METHODS
    from repro.data import (FederatedData, dirichlet_partition,
                            make_class_conditional_images)
    from repro.fl import AsyncFederation, Federation, FLRunConfig
    from repro.fl.runtime import masked_accuracy
    from repro.models import cnn
    from repro.obs import ObsConfig, read_events, read_metrics

    images, labels = make_class_conditional_images(600, CFG.n_classes,
                                                   CFG.cnn_image_size, seed=0)
    parts = dirichlet_partition(labels, 8, alpha=0.3, seed=0)
    data = FederatedData.from_partition(images, labels, parts, seed=0)
    params = cnn.init_params(jax.random.PRNGKey(0), CFG)
    loss = lambda p, b: cnn.loss_fn(p, CFG, b)
    acc = masked_accuracy(lambda p, t: cnn.apply(p, CFG, t["images"]))
    tmp = Path(tempfile.mkdtemp())

    def run(backend, mesh, driver, obs):
        cfg = FLRunConfig(n_clients=8, participation=0.5, rounds=2, batch=8,
                          local_iters=2, seed=1, backend=backend, mesh=mesh,
                          update_impl="kernel_interpret", obs=obs)
        cls = AsyncFederation if driver == "async" else Federation
        return cls(METHODS["pfedsop"](), loss, acc, params, data, cfg).run()

    for backend, mesh in [("vmap", ""), ("shard_map", ""),
                          ("mesh", "pods:2x2x2")]:
        for driver in ["sync", "async"]:
            tdir = tmp / f"{backend}_{driver}"
            h_off = run(backend, mesh, driver, None)
            h_on = run(backend, mesh, driver,
                       ObsConfig(trace_dir=str(tdir), level="phase",
                                 quiet=True))
            for key in h_off:
                if key == "round_time":
                    continue
                assert h_off[key] == h_on[key], (
                    backend, driver, key, h_off[key], h_on[key])
            # the traced run actually traced: spans + per-round metrics
            evs = read_events(tdir)
            assert any(e.get("k") == "span" and e["name"] == "client"
                       for e in evs), (backend, driver)
            snaps = read_metrics(tdir / "metrics.jsonl")
            assert len(snaps) == 2, (backend, driver, len(snaps))
            assert (tdir / "trace.json").exists()
            print(f"INVARIANT_OK {backend}/{driver}")
    print("ALL_INVARIANT_OK")
    """
)


def test_traced_equals_untraced_all_backends_forced_8_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "-c", _INVARIANCE_SCRIPT],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    for backend in ["vmap", "shard_map", "mesh"]:
        for driver in ["sync", "async"]:
            assert f"INVARIANT_OK {backend}/{driver}" in res.stdout, res.stdout
    assert "ALL_INVARIANT_OK" in res.stdout


_ATTACH_SCRIPT = textwrap.dedent(
    """
    import sys, tempfile
    from pathlib import Path

    import jax
    assert len(jax.devices()) == 2, jax.devices()

    from repro.configs.resnet_cifar import SMALL_CNN as CFG
    from repro.core.baselines import METHODS
    from repro.data import (FederatedData, dirichlet_partition,
                            make_class_conditional_images)
    from repro.fl import Federation, FLRunConfig
    from repro.fl.runtime import masked_accuracy
    from repro.models import cnn
    from repro.obs import NOOP, Obs, ObsConfig, read_events

    images, labels = make_class_conditional_images(300, CFG.n_classes,
                                                   CFG.cnn_image_size, seed=0)
    parts = dirichlet_partition(labels, 4, alpha=0.3, seed=0)
    data = FederatedData.from_partition(images, labels, parts, seed=0)
    params = cnn.init_params(jax.random.PRNGKey(0), CFG)
    loss = lambda p, b: cnn.loss_fn(p, CFG, b)
    acc = masked_accuracy(lambda p, t: cnn.apply(p, CFG, t["images"]))
    tmp = Path(tempfile.mkdtemp())
    backend = sys.argv[1]

    def fed():
        cfg = FLRunConfig(n_clients=4, participation=0.5, rounds=4, batch=8,
                          local_iters=2, seed=3, backend=backend)
        return Federation(METHODS["pfedsop"](), loss, acc, params, data, cfg)

    plain, traced = fed(), fed()
    h_plain = [plain.run_round() for _ in range(4)]
    h_traced = [traced.run_round() for _ in range(2)]
    obs = traced.attach_obs(Obs(ObsConfig(trace_dir=str(tmp / "t"),
                                          level="round", quiet=True)))
    h_traced += [traced.run_round() for _ in range(2)]
    obs.close()
    traced.attach_obs(NOOP)
    h_traced.append(traced.run_round())
    h_plain.append(plain.run_round())
    for a, b in zip(h_plain, h_traced):
        assert (a["loss"], a["acc"]) == (b["loss"], b["acc"]), (a, b)
        assert list(a["clients"]) == list(b["clients"])
    names = [e["name"] for e in read_events(tmp / "t") if e.get("k") == "span"]
    gather = ["dispatch.all_gather"] if backend != "vmap" else []
    want = (["sample", "dispatch.gather", "dispatch.client"] + gather
            + ["dispatch.eval", "dispatch.aggregate", "dispatch.scatter", "sync"])
    assert names == want * 2, names
    print("ATTACH_OK", backend)
    """
)


def test_attach_obs_mid_run_keeps_histories_bitwise_forced_2_devices():
    """``Federation.attach_obs`` at level ``round`` part-way through a run
    (and ``NOOP`` after it) leaves every round's loss, accuracy and cohort
    bitwise equal to an untraced run, and the attached rounds record the
    round-level spans in order: with the round-boundary all-gather on
    the mesh engine, without it on vmap."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
    ).strip()
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get("PYTHONPATH", "")
    for backend in ("vmap", "shard_map"):
        res = subprocess.run(
            [sys.executable, "-c", _ATTACH_SCRIPT, backend],
            capture_output=True, text=True, env=env, timeout=600,
        )
        assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
        assert f"ATTACH_OK {backend}" in res.stdout, res.stdout
