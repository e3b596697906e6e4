"""Multi-pod mesh engine parity tests (DESIGN.md §11).

The acceptance anchor of the mesh refactor: on a forced 8-device
`(pod=2, data=2, model=2)` mesh, bitwise parity must hold in all three
degenerate directions —

  multi-pod ``MeshBackend`` == 1-D ``ShardMapBackend`` == ``VmapBackend``
  loss/accuracy histories (sync), and always-on/uniform/buffer=K'
  multi-pod async == sync history

— with the model-sharded batched ``pfedsop_update`` kernel active on the
hot path (``kernel_interpret`` so the kernel body actually runs on CPU).
Subprocess: the XLA device count must be forced before jax initialises,
and the rest of the suite needs the single real CPU device (cf.
tests/test_engine.py).
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

_MULTIPOD_SCRIPT = textwrap.dedent(
    """
    import jax, numpy as np, jax.numpy as jnp
    assert len(jax.devices()) == 8, jax.devices()
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    from repro.configs.resnet_cifar import SMALL_CNN as CFG
    from repro.core.baselines import METHODS
    from repro.data import (FederatedData, dirichlet_partition,
                            make_class_conditional_images)
    from repro.fl import AsyncFederation, Federation, FLRunConfig
    from repro.fl.runtime import masked_accuracy
    from repro.kernels.pfedsop_update.ops import (
        pfedsop_update_batched, pfedsop_update_batched_sharded)
    from repro.launch.mesh import MeshSpec, resolve_mesh
    from repro.models import cnn

    # -- 1. model-sharded kernel op: bitwise vs the unsharded kernel ------
    mesh = resolve_mesh(MeshSpec.multi_pod(2, 2, 2))
    k = jax.random.PRNGKey(0)
    # sub-tile N, a non-tile-multiple N, and an N of five 512-row tiles,
    # so that shard 1 holds real tiles (3, 4) as well as padding
    for n in [130, 4096 + 7, 2 * 2 * 512 * 128 + 7]:
        x, di = (jax.random.normal(jax.random.fold_in(k, i), (4, n))
                 for i in (1, 2))
        dg = jax.random.normal(jax.random.fold_in(k, 3), (n,))
        ref, beta_ref = pfedsop_update_batched(x, di, dg, interpret=True)
        out, beta = shard_map(
            lambda x, di, dg: pfedsop_update_batched_sharded(
                x, di, dg, "model", 2, interpret=True),
            mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
            check_vma=False)(x, di, dg)
        assert np.array_equal(np.asarray(ref), np.asarray(out)), n
        assert np.array_equal(np.asarray(beta_ref), np.asarray(beta)), n
    print("KERNEL_SHARDED_BITWISE_OK")

    # -- shared federation fixtures ---------------------------------------
    images, labels = make_class_conditional_images(600, CFG.n_classes,
                                                   CFG.cnn_image_size, seed=0)
    parts = dirichlet_partition(labels, 8, alpha=0.3, seed=0)
    data = FederatedData.from_partition(images, labels, parts, seed=0)
    params = cnn.init_params(jax.random.PRNGKey(0), CFG)
    loss = lambda p, b: cnn.loss_fn(p, CFG, b)
    acc = masked_accuracy(lambda p, t: cnn.apply(p, CFG, t["images"]))

    def run_cfg(backend, mesh="", rounds=2):
        # K' = 4: divisible by the 2 pods AND by 4/8-way client splits
        return FLRunConfig(n_clients=8, participation=0.5, rounds=rounds,
                           batch=8, local_iters=2, seed=1, backend=backend,
                           mesh=mesh, update_impl="kernel_interpret")

    # -- 2. sync three-way bitwise parity, model-sharded kernel active ----
    hists = {}
    for backend, mesh_spec in [("vmap", ""), ("shard_map", ""),
                               ("mesh", "pods:2x2x2")]:
        fed = Federation(METHODS["pfedsop"](), loss, acc, params, data,
                         run_cfg(backend, mesh_spec))
        hists[backend] = fed.run()
    eng = hists["mesh"]["engine"]
    assert eng["mesh"].startswith("pod=2,data=2,model=2"), eng
    assert eng["shards"] == 2 and eng["model_shards"] == 2, eng
    assert hists["shard_map"]["engine"]["shards"] == 4
    for b in ["shard_map", "mesh"]:
        assert hists["vmap"]["loss"] == hists[b]["loss"], (b, hists)
        assert hists["vmap"]["acc"] == hists[b]["acc"], (b, hists)
    print("SYNC_THREEWAY_BITWISE_OK")

    # -- 3. degenerate multi-pod async == sync (per-pod streams) ----------
    h_sync = Federation(METHODS["pfedsop"](), loss, acc, params, data,
                        run_cfg("vmap", rounds=3)).run()
    fed = AsyncFederation(METHODS["pfedsop"](), loss, acc, params, data,
                          run_cfg("mesh", "pods:2x2x2", rounds=3))
    assert fed.n_pods == 2, fed.n_pods
    h_async = fed.run()
    assert h_sync["loss"] == h_async["loss"]
    assert h_sync["acc"] == h_async["acc"]
    assert h_sync["sim_time"] == h_async["sim_time"]
    assert h_async["staleness"] == [0.0] * 3
    # per-pod delivery streams: the K'/2-sized pod cohorts actually ran
    assert 2 in h_async["engine"]["cohort_sizes"], h_async["engine"]
    print("ASYNC_MULTIPOD_DEGENERATE_OK")

    # -- 4. non-divisor micro-cohorts fall back (async lenient mode) -----
    from repro.fl import AsyncConfig
    from repro.fl.availability import AvailabilityConfig
    acfg = AsyncConfig(buffer_size=1, concurrency=3,
                       availability=AvailabilityConfig(speed="lognormal",
                                                       sigma=1.0))
    h = AsyncFederation(METHODS["pfedsop"](), loss, acc, params, data,
                        run_cfg("mesh", "pods:2x2x2", rounds=3), acfg).run()
    assert len(h["loss"]) == 3
    assert any(c % 2 for c in h["engine"]["cohort_sizes"]), h["engine"]
    print("ASYNC_FALLBACK_OK")

    # -- 5. mid-drain checkpoint resume: with buffer_size = K'/pods, each
    # pod-0 delivery flushes (and checkpoints) while pod 1's same-time
    # completions are still in the heap; resuming from that checkpoint
    # must deliver pod 1 BEFORE the next dispatch draw, or the RNG stream
    # (and history) diverges from the uninterrupted run
    import dataclasses, tempfile
    ckdir = tempfile.mkdtemp()
    cfg5 = dataclasses.replace(run_cfg("mesh", "pods:2x2x2", rounds=4),
                               ckpt_every=1, ckpt_dir=ckdir)
    h_full = AsyncFederation(METHODS["pfedsop"](), loss, acc, params, data,
                             cfg5, AsyncConfig(buffer_size=2)).run()
    fed_r = AsyncFederation(METHODS["pfedsop"](), loss, acc, params, data,
                            cfg5, AsyncConfig(buffer_size=2))
    assert fed_r.restore(ckdir, step=1) == 1  # written mid-drain
    h_res = fed_r.run()
    assert h_full["loss"] == h_res["loss"]
    assert h_full["acc"] == h_res["acc"]
    assert h_full["sim_time"] == h_res["sim_time"]
    print("ASYNC_MULTIPOD_RESUME_OK")
    """
)


def test_multipod_parity_forced_8_devices():
    """Three-way sync bitwise parity + degenerate multi-pod async == sync
    + model-sharded kernel bitwise + lenient micro-cohort fallback, all on
    a forced 8-device (2,2,2) mesh (one subprocess to amortize compiles).
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "-c", _MULTIPOD_SCRIPT],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    for marker in ["KERNEL_SHARDED_BITWISE_OK", "SYNC_THREEWAY_BITWISE_OK",
                   "ASYNC_MULTIPOD_DEGENERATE_OK", "ASYNC_FALLBACK_OK",
                   "ASYNC_MULTIPOD_RESUME_OK"]:
        assert marker in res.stdout, res.stdout
