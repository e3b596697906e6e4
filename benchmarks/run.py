"""Benchmark harness - one benchmark per paper table/figure + the kernel
microbenches and the roofline summary.

  PYTHONPATH=src python -m benchmarks.run                 # everything (CPU-sized)
  PYTHONPATH=src python -m benchmarks.run --only table2   # one table
  PYTHONPATH=src python -m benchmarks.run --rounds 30     # bigger federation

Mapping to the paper (Sen & Mohan 2025):
  table1   per-round computation cost across methods (Table I analog:
           measured wall-clock per round, same model/partition for all)
  table2   best personalized accuracy, Dirichlet + pathological partitions
           (Table II analog on synthetic class-conditional images)
  table3   personalization-component ablation (Table III)
  table4   rho / lambda sensitivity (Table IV)
  figures  round-wise loss/accuracy histories (Figs. 2-4) -> JSON
  kernels  pfedsop_update / flash_gqa / rmsnorm microbench (interpret mode
           on CPU: validates + times the kernel bodies; TPU wall-times come
           from the roofline terms, not this box)
  engine   federation-engine throughput: rounds/sec for the vmap vs the
           shard_map backend across federation sizes (DESIGN.md §3; on a
           1-device box both run the same program - run under
           XLA_FLAGS=--xla_force_host_platform_device_count=N to see the
           multi-shard split)
  pfedsop-update  round-start-update impl shootout (DESIGN.md §9):
           rounds/sec for the pytree reference vs the fused Pallas kernel
           under both backends, with a per-backend parity assertion;
           --interpret forces the interpreter kernel (automatic off-TPU)
  async-engine  simulated wall-clock to a fixed target accuracy, sync vs
           async (DESIGN.md §10): heterogeneous client speeds (lognormal)
           + 30% availability; the bulk-synchronous server waits for
           stragglers while the async driver dispatches to online clients
           and applies FedBuff-style staleness-weighted buffered updates.
           Asserts async reaches the target in less simulated time AND
           that the staleness-weighted pFedSOP path still matches the
           fused-kernel dispatch (--interpret / automatic off-TPU)
  cohort-store  fleet-scale store sweep (DESIGN.md §12): rounds/sec and
           host<->device bytes moved vs fleet size K per store kind
           (device / host / mmap / LRU-cached host), K' fixed at 64,
           K = 10^3..10^5, with a bitwise parity assertion against the
           all-on-device baseline at the smallest K
  multipod-engine  mesh-engine shootout (DESIGN.md §11): rounds/sec and
           simulated time-to-target across {vmap, 1-D shard_map,
           multi-pod (2,2,2) mesh} x {sync, async}, asserting bitwise
           cross-backend history parity and model-sharded-kernel vs
           reference drift; needs 8 devices (CI forces host devices)
  model-fwd model-zoo forward tokens/sec per kernel impl x config
           (DESIGN.md §9, ``ModelConfig.kernel_impl``): reference vs
           kernel_interpret on a sliding-window (gemma3) and a
           full-attention (granite) reduced config, with a max-abs-drift
           assertion and a window-pruned flash_gqa grid-shape check
  roofline summary table from experiments/dryrun/*.json artifacts

Output: CSV lines ``name,us_per_call,derived`` + a human table; artifacts
under experiments/bench/.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.resnet_cifar import SMALL_CNN
from repro.core import baselines as bl
from repro.core.pfedsop import PFedSOPConfig
from repro.data import (
    FederatedData,
    dirichlet_partition,
    make_class_conditional_images,
    pathological_partition,
)
from repro.fl import (
    AsyncConfig,
    AsyncFederation,
    AvailabilityConfig,
    ClientAvailability,
    Federation,
    FLRunConfig,
)
from repro.fl.runtime import masked_accuracy
from repro.models import cnn
from repro.obs import ObsConfig
from repro.utils.compile_cache import enable_compile_cache

OUT = Path(__file__).resolve().parents[1] / "experiments" / "bench"

# --trace-dir/--obs-level (main) land here; benches that support tracing
# derive a per-run subdir with _obs_for so fingerprints never collide
OBS_CFG: dict = {}


def _obs_for(tag: str):
    """Per-bench-run ObsConfig under the harness --trace-dir (or None)."""
    if not OBS_CFG.get("trace_dir"):
        return None
    return ObsConfig(
        trace_dir=str(Path(OBS_CFG["trace_dir"]) / tag.replace("/", "_")),
        level=OBS_CFG.get("level", "phase"), quiet=True)

CFG = SMALL_CNN
METHOD_LIST = ["fedavg", "fedprox", "fedavg_ft", "fedprox_ft", "ditto",
               "fedrep", "local", "pfedsop"]


def _build(name, lr=0.05, rho=1.0, lam=1.0, use_pc=True, eta1=1.0):
    # eta1 (personalization lr) tuned per the paper's protocol (Sec. V-B4:
    # grid over lr per method); probe artifacts:
    # experiments/bench/pfedsop_eta1_tuning.json / pfedsop_tuned_compare.json
    if name == "pfedsop":
        return bl.PFedSOP(cfg=PFedSOPConfig(eta1=eta1, eta2=lr, rho=rho, lam=lam,
                                            use_pc=use_pc))
    if name == "fedrep":
        return bl.FedRep(lr=lr, head_predicate=lambda p: "fc_" in p)
    return bl.METHODS[name](lr=lr)


def _data(partition, seed=0, samples=3000, classes=10, clients=10):
    images, labels = make_class_conditional_images(samples, classes,
                                                   CFG.cnn_image_size, seed=seed)
    if partition == "dirichlet":
        parts = dirichlet_partition(labels, clients, 0.07, seed=seed)
    else:
        parts = pathological_partition(labels, clients, samples // (2 * clients),
                                       seed=seed)
    return FederatedData.from_partition(images, labels, parts, seed=seed)


def _run(method, data, rounds, seed=0, clients=10, backend="vmap",
         participation=0.4, update_impl="", obs=None):
    loss = lambda p, b: cnn.loss_fn(p, CFG, b)
    acc = masked_accuracy(lambda p, t: cnn.apply(p, CFG, t["images"]))
    params = cnn.init_params(jax.random.PRNGKey(seed), CFG)
    run_cfg = FLRunConfig(n_clients=clients, participation=participation,
                          rounds=rounds, batch=25, seed=seed, backend=backend,
                          update_impl=update_impl, obs=obs)
    fed = Federation(method, loss, acc, params, data, run_cfg)
    hist = fed.run()
    if fed.obs.final_metrics is not None:
        # surfaced into the suite's BENCH_*.json via the bench return value
        hist["obs_metrics"] = fed.obs.final_metrics
    return hist


# ---------------------------------------------------------------------------


def bench_table1(rounds):
    """Per-round wall time per method (Table I analog)."""
    print("\n== table1: per-round computation cost ==")
    data = _data("dirichlet")
    rows = []
    for name in METHOD_LIST:
        h = _run(_build(name), data, max(3, rounds // 3))
        t = float(np.mean(h["round_time"][1:]))  # skip compile round
        rows.append((name, t))
        print(f"bench,table1/{name},{t*1e6:.0f},s_per_round={t:.3f}")
    base = dict(rows)["fedavg"]
    print(f"{'method':>12} {'s/round':>8} {'vs fedavg':>9}")
    for n, t in rows:
        print(f"{n:>12} {t:>8.3f} {t/base:>8.2f}x")
    return {n: t for n, t in rows}


def bench_table2(rounds):
    """Best personalized accuracy on both partitions (Table II analog)."""
    print("\n== table2: best accuracy, both heterogeneous settings ==")
    out = {}
    for partition in ["dirichlet", "pathological"]:
        data = _data(partition)
        out[partition] = {}
        for name in METHOD_LIST:
            h = _run(_build(name), data, rounds)
            out[partition][name] = h["mean_best_acc"]
            print(f"bench,table2/{partition}/{name},"
                  f"{np.mean(h['round_time'][1:])*1e6:.0f},"
                  f"best_acc={h['mean_best_acc']:.4f}")
    print(f"{'method':>12} {'dirichlet':>10} {'pathological':>13}")
    for name in METHOD_LIST:
        print(f"{name:>12} {out['dirichlet'][name]:>10.4f} "
              f"{out['pathological'][name]:>13.4f}")
    best = max(out["dirichlet"], key=out["dirichlet"].get)
    print(f"--> best (dirichlet): {best}")
    return out


def bench_table3(rounds):
    """PC ablation (Table III)."""
    print("\n== table3: personalization component ablation ==")
    data = _data("dirichlet")
    out = {}
    for tag, use_pc in [("with_pc", True), ("without_pc", False)]:
        h = _run(_build("pfedsop", use_pc=use_pc), data, rounds)
        out[tag] = h["mean_best_acc"]
        print(f"bench,table3/{tag},0,best_acc={h['mean_best_acc']:.4f}")
    print(f"with PC {out['with_pc']:.4f} vs without {out['without_pc']:.4f}")
    return out


def bench_table4(rounds):
    """rho / lambda sensitivity (Table IV)."""
    print("\n== table4: rho / lambda sensitivity ==")
    data = _data("dirichlet")
    out = {"rho": {}, "lam": {}}
    for rho in [1.0, 0.1, 0.01]:
        h = _run(_build("pfedsop", rho=rho), data, rounds)
        out["rho"][rho] = h["mean_best_acc"]
        print(f"bench,table4/rho={rho},0,best_acc={h['mean_best_acc']:.4f}")
    for lam in [5.0, 1.0, 0.5]:
        h = _run(_build("pfedsop", lam=lam), data, rounds)
        out["lam"][lam] = h["mean_best_acc"]
        print(f"bench,table4/lam={lam},0,best_acc={h['mean_best_acc']:.4f}")
    return out


def bench_figures(rounds):
    """Round-wise loss/acc histories (Figs. 2-4 analog) -> JSON artifact."""
    print("\n== figures: round-wise curves ==")
    out = {}
    for partition in ["dirichlet", "pathological"]:
        data = _data(partition)
        out[partition] = {}
        for name in ["fedavg", "fedavg_ft", "ditto", "pfedsop"]:
            h = _run(_build(name), data, rounds)
            out[partition][name] = {"loss": h["loss"], "acc": h["acc"]}
            print(f"bench,figures/{partition}/{name},0,"
                  f"final_loss={h['loss'][-1]:.4f}")
    return out


def bench_kernels():
    """Kernel microbench (interpret mode: correctness-path timing only)."""
    print("\n== kernels: microbench (interpret=True on CPU) ==")
    from repro.kernels.pfedsop_update.ops import pfedsop_update
    from repro.kernels.flash_gqa.kernel import flash_gqa_pallas
    from repro.kernels.rmsnorm.ops import rmsnorm

    out = {}

    def timeit(name, fn, *a, n=5, **kw):
        fn(*a, **kw)  # compile
        t0 = time.perf_counter()
        for _ in range(n):
            r = fn(*a, **kw)
        jax.block_until_ready(r)
        us = (time.perf_counter() - t0) / n * 1e6
        out[name] = us
        print(f"bench,kernels/{name},{us:.0f},interpret=True")
        return us

    k = jax.random.PRNGKey(0)
    n = 1 << 16
    x, di, dg = (jax.random.normal(jax.random.fold_in(k, i), (n,)) for i in range(3))
    timeit("pfedsop_update_64k", pfedsop_update, x, di, dg, interpret=True)

    q = jax.random.normal(k, (1, 4, 128, 64))
    kk = jax.random.normal(k, (1, 2, 128, 64))
    v = jax.random.normal(k, (1, 2, 128, 64))
    timeit("flash_gqa_128", flash_gqa_pallas, q, kk, v, bq=64, bk=64, interpret=True)

    xx = jax.random.normal(k, (256, 512))
    ss = jnp.zeros((512,))
    timeit("rmsnorm_256x512", rmsnorm, xx, ss, interpret=True)
    return out


def bench_engine(rounds):
    """Federation-engine throughput: rounds/sec per backend x federation size.

    The per-round client phase is the scaling axis the engine shards
    (ISSUE: second-order FL wins by cutting rounds, so each round must scale
    across devices at realistic federation sizes).  Equal-seed backends run
    the same sampled rounds, so rounds/sec is directly comparable.
    """
    print("\n== engine: rounds/sec, vmap vs shard_map ==")
    n_dev = len(jax.devices())
    out = {}
    r = max(3, rounds // 3)
    # participation 0.5 -> K' = 4, 8, 16: power-of-two shard counts, so the
    # recommended 4-device run actually splits every federation size
    for clients in [8, 16, 32]:
        data = _data("dirichlet", clients=clients, samples=200 * clients)
        out[clients] = {}
        for backend in ["vmap", "shard_map"]:
            h = _run(_build("pfedsop"), data, r, clients=clients,
                     backend=backend, participation=0.5)
            t = float(np.mean(h["round_time"][1:]))  # skip compile round
            rps = 1.0 / max(t, 1e-9)
            out[clients][backend] = {
                "rounds_per_sec": rps,
                "shards": h["engine"].get("shards", 1),
            }
            print(f"bench,engine/{backend}/k{clients},{t*1e6:.0f},"
                  f"rounds_per_sec={rps:.3f},shards={h['engine'].get('shards', 1)}")
    print(f"({n_dev} local device(s))")
    print(f"{'clients':>8} {'vmap r/s':>9} {'shard_map r/s':>14} {'shards':>7}")
    for clients, row in out.items():
        print(f"{clients:>8} {row['vmap']['rounds_per_sec']:>9.3f} "
              f"{row['shard_map']['rounds_per_sec']:>14.3f} "
              f"{row['shard_map']['shards']:>7}")
    return out


def bench_pfedsop_update(rounds, interpret=False):
    """Round-start-update impl shootout: rounds/sec, reference vs fused
    kernel (DESIGN.md §9), under both engine backends.

    On CPU (or with --interpret) the kernel impl runs the Pallas
    interpreter — a correctness-path timing that keeps the bench runnable
    in CI; the honest kernel wall-time needs a TPU, where the same flag
    resolves to the compiled Mosaic kernel.  Parity (max |loss diff| vs
    the reference history on the same seed) is checked per backend so a
    broken kernel path fails loudly here, not just in the test suite.
    """
    print("\n== pfedsop-update: rounds/sec per impl x backend ==")
    kernel_impl = ("kernel_interpret"
                   if interpret or jax.default_backend() != "tpu" else "kernel")
    data = _data("dirichlet", clients=8, samples=1600)
    r = max(3, rounds // 3)
    out = {"kernel_impl": kernel_impl, "backends": {}}
    for backend in ["vmap", "shard_map"]:
        out["backends"][backend] = {}
        ref_hist = None
        for impl in ["reference", kernel_impl]:
            h = _run(_build("pfedsop"), data, r, clients=8, backend=backend,
                     participation=0.5, update_impl=impl)
            t = float(np.mean(h["round_time"][1:]))  # skip compile round
            rps = 1.0 / max(t, 1e-9)
            if impl == "reference":
                ref_hist = h
                drift = 0.0
            else:
                drift = float(np.max(np.abs(np.asarray(h["loss"])
                                            - np.asarray(ref_hist["loss"]))))
                assert drift < 1e-4, (
                    f"kernel impl diverged from reference under {backend}: "
                    f"max |loss diff| = {drift}")
            out["backends"][backend][impl] = {
                "rounds_per_sec": rps, "max_loss_drift_vs_reference": drift,
            }
            print(f"bench,pfedsop-update/{backend}/{impl},{t*1e6:.0f},"
                  f"rounds_per_sec={rps:.3f},drift={drift:.2e}")
    print(f"{'backend':>10} {'reference r/s':>14} {kernel_impl + ' r/s':>20}")
    for backend, row in out["backends"].items():
        print(f"{backend:>10} {row['reference']['rounds_per_sec']:>14.3f} "
              f"{row[kernel_impl]['rounds_per_sec']:>20.3f}")
    return out


def bench_async_engine(rounds, interpret=False):
    """Simulated wall-clock to target accuracy, sync vs async (DESIGN.md §10).

    The scenario the async subsystem exists for: lognormal per-client
    speeds + 30% availability.  The bulk-synchronous server samples
    obliviously and waits for every straggler to come online and finish
    (its simulated clock is ``ClientAvailability.sync_round_duration``);
    the async driver dispatches only to online clients and applies a
    staleness-weighted server update every ``buffer_size`` uploads.  Both
    drivers burn the same total upload budget, so simulated
    time-to-accuracy is the honest comparison — and the async win is
    asserted, not just reported.  A second async run forces the §9
    fused-kernel dispatch (interpret off-TPU) and asserts parity with the
    reference history: the staleness-weighted path must keep dispatching
    through ``pfedsop_update``.
    """
    print("\n== async-engine: simulated wall-clock to target accuracy ==")
    kernel_impl = ("kernel_interpret"
                   if interpret or jax.default_backend() != "tpu" else "kernel")
    clients, participation = 16, 0.5  # K' = 8
    buffer_size = 4
    data = _data("dirichlet", clients=clients, samples=200 * clients)
    loss = lambda p, b: cnn.loss_fn(p, CFG, b)
    acc = masked_accuracy(lambda p, t: cnn.apply(p, CFG, t["images"]))
    params = cnn.init_params(jax.random.PRNGKey(0), CFG)
    avail = AvailabilityConfig(speed="lognormal", sigma=1.0,
                               availability=0.3, mean_on=4.0)
    r = max(6, rounds)
    kprime = int(round(participation * clients))

    def _cfg(n_rounds, update_impl=""):
        return FLRunConfig(n_clients=clients, participation=participation,
                           rounds=n_rounds, batch=25, seed=0,
                           update_impl=update_impl)

    method = _build("pfedsop")
    model = ClientAvailability(avail, clients, 0)
    h_sync = Federation(method, loss, acc, params, data, _cfg(r),
                        availability=model).run()
    # same upload budget: r sync rounds x K' uploads == async versions x B
    async_rounds = r * kprime // buffer_size
    acfg = AsyncConfig(buffer_size=buffer_size, concurrency=kprime,
                       availability=avail)
    h_async = {}
    for impl in ["reference", kernel_impl]:
        h_async[impl] = AsyncFederation(method, loss, acc, params, data,
                                        _cfg(async_rounds, impl), acfg).run()
    drift = float(np.max(np.abs(np.asarray(h_async["reference"]["loss"])
                                - np.asarray(h_async[kernel_impl]["loss"]))))
    # fp32 reduction-order tolerance, wider than the pfedsop-update bench:
    # the async run accumulates ~2x the server updates of a sync round
    # budget, so per-round 1e-5-scale reduction noise compounds further
    assert drift < 1e-3, (
        f"staleness-weighted kernel dispatch diverged from reference: {drift}")

    # time at which the running-best cohort accuracy first clears the target
    def time_to(hist, target):
        best = np.maximum.accumulate(hist["acc"])
        hit = np.nonzero(best >= target)[0]
        return float(hist["sim_time"][hit[0]]) if len(hit) else None

    target = 0.8 * max(h_sync["acc"])
    t_sync = time_to(h_sync, target)
    t_async = time_to(h_async["reference"], target)
    assert t_async is not None, (
        f"async never reached target acc {target:.4f} "
        f"(best {max(h_async['reference']['acc']):.4f})")
    assert t_sync is None or t_async < t_sync, (
        f"async must reach target acc {target:.4f} in less simulated time: "
        f"async {t_async} vs sync {t_sync}")
    mean_tau = float(np.mean(h_async["reference"]["staleness"]))
    out = {
        "kernel_impl": kernel_impl,
        "clients": clients, "kprime": kprime, "buffer_size": buffer_size,
        "availability": avail.availability, "speed_sigma": avail.sigma,
        "target_acc": target,
        "sync": {"rounds": r, "sim_time_total": h_sync["sim_time"][-1],
                 "sim_time_to_target": t_sync,
                 "best_acc": float(max(h_sync["acc"]))},
        "async": {"versions": async_rounds,
                  "sim_time_total": h_async["reference"]["sim_time"][-1],
                  "sim_time_to_target": t_async,
                  "best_acc": float(max(h_async["reference"]["acc"])),
                  "mean_staleness": mean_tau},
        "max_loss_drift_vs_reference": drift,
    }
    print(f"bench,async-engine/sync,0,sim_t_to_target="
          f"{t_sync if t_sync is not None else float('inf'):.2f}")
    print(f"bench,async-engine/async,0,sim_t_to_target={t_async:.2f},"
          f"mean_tau={mean_tau:.2f},drift={drift:.2e}")
    print(f"{'driver':>8} {'sim_t_to_target':>16} {'sim_t_total':>12} {'best_acc':>9}")
    print(f"{'sync':>8} "
          f"{t_sync if t_sync is not None else float('inf'):>16.2f} "
          f"{h_sync['sim_time'][-1]:>12.2f} {max(h_sync['acc']):>9.4f}")
    print(f"{'async':>8} {t_async:>16.2f} "
          f"{h_async['reference']['sim_time'][-1]:>12.2f} "
          f"{max(h_async['reference']['acc']):>9.4f}")
    return out


def bench_multipod_engine(rounds, interpret=False):
    """Mesh-engine shootout (DESIGN.md §11): {vmap, 1-D shard_map,
    multi-pod mesh} x {sync, async} on a reduced (2,2,2) production mesh.

    Needs 8 local devices (CI runs it under
    XLA_FLAGS=--xla_force_host_platform_device_count=8); on a smaller box
    it reports what it can and marks the multi-pod column skipped.

    Reported: rounds/sec per backend x driver x output-sharding mode,
    plus simulated time-to-target-accuracy under heterogeneous
    availability (lognormal speeds + 30% availability).  Asserted, not
    just reported: (a) same impl, different backend => loss-history
    drift < 1e-4 (not bitwise with the interpret kernel on the hot
    path — see the inline comment at the assert; bitwise under
    update_impl="reference"); (b) reference vs kernel impl on the
    multi-pod mesh => drift < 1e-4 with the model-sharded batched
    kernel; (c) sharded output mode => BITWISE identical history to the
    same backend's replicated run (the §11 sharded-at-rest contract).

    On this CPU/interpret emulation the round is dominated by the
    interpret-mode pfedsop_update client phase (~85% of the round; the
    round-boundary all-gather is milliseconds), so sharded mode shows
    only a modest rounds/sec edge here — the collective it removes is
    an O(params * K') cross-pod gather that matters on real multi-pod
    hardware, not on forced host devices sharing one memory.
    """
    print("\n== multipod-engine: backend x driver, reduced (2,2,2) mesh ==")
    kernel_impl = ("kernel_interpret"
                   if interpret or jax.default_backend() != "tpu" else "kernel")
    n_dev = len(jax.devices())
    backends = [("vmap", ""), ("shard_map", "")]
    if n_dev >= 8:
        backends.append(("mesh", "pods:2x2x2"))
    else:
        print(f"bench,multipod-engine/skip,0,devices={n_dev}_of_8 "
              "(run under XLA_FLAGS=--xla_force_host_platform_device_count=8)")

    clients, participation = 8, 0.5  # K' = 4: divides pods(2) and devices
    r = max(4, rounds // 2)
    data = _data("dirichlet", clients=clients, samples=200 * clients)
    loss = lambda p, b: cnn.loss_fn(p, CFG, b)
    acc = masked_accuracy(lambda p, t: cnn.apply(p, CFG, t["images"]))
    params = cnn.init_params(jax.random.PRNGKey(0), CFG)
    avail = AvailabilityConfig(speed="lognormal", sigma=1.0,
                               availability=0.3, mean_on=4.0)
    kprime = int(round(participation * clients))
    buffer_size = kprime  # same server-update budget across drivers

    def _cfg(backend, mesh, update_impl, driver, output_sharding="replicated"):
        return FLRunConfig(
            n_clients=clients, participation=participation,
            rounds=r, batch=25, seed=0, backend=backend,
            mesh=mesh, update_impl=update_impl,
            output_sharding=output_sharding,
            obs=_obs_for(f"multipod/{backend}/{driver}/{update_impl}"))

    def time_to(hist, target):
        best = np.maximum.accumulate(hist["acc"])
        hit = np.nonzero(best >= target)[0]
        return float(hist["sim_time"][hit[0]]) if len(hit) else None

    out = {"kernel_impl": kernel_impl, "devices": n_dev,
           "backends": {}, "skipped_multipod": n_dev < 8}
    ref_hist = {}  # driver -> reference loss history (backend-invariant)
    for backend, mesh in backends:
        row = {}
        for driver in ["sync", "async"]:
            method = _build("pfedsop")
            for impl in ([kernel_impl, "reference"]
                         if backend == "mesh" else [kernel_impl]):
                cfg = _cfg(backend, mesh, impl, driver)
                if driver == "sync":
                    fed = Federation(method, loss, acc, params, data, cfg,
                                     availability=ClientAvailability(
                                         avail, clients, 0))
                else:
                    fed = AsyncFederation(
                        method, loss, acc, params, data, cfg,
                        AsyncConfig(buffer_size=buffer_size,
                                    concurrency=kprime, availability=avail))
                h = fed.run()
                if impl == "reference":
                    # multi-pod kernel parity: model-sharded kernel vs the
                    # pytree reference (fp32 reduction-order tolerance)
                    drift = float(np.max(np.abs(
                        np.asarray(h["loss"])
                        - np.asarray(row[driver]["loss"]))))
                    assert drift < 1e-4, (
                        f"model-sharded kernel diverged from reference "
                        f"({driver}): {drift}")
                    row[driver]["kernel_vs_reference_drift"] = drift
                    continue
                t = float(np.mean(h["round_time"][1:]))
                target = 0.8 * max(h["acc"])
                row[driver] = {
                    "rounds_per_sec": 1.0 / max(t, 1e-9),
                    "sim_time_to_target": time_to(h, target),
                    "sim_time_total": h["sim_time"][-1],
                    "loss": h["loss"],
                }
                if fed.obs.final_metrics is not None:
                    row[driver]["obs_metrics"] = fed.obs.final_metrics
                # same impl, any backend: tight history parity (§11).  Not
                # bitwise: XLA:CPU fuses the interpret-mode pfedsop_update
                # HLO differently inside the vmap-batched round program vs
                # the per-shard shard_map body (the kernel itself is bitwise
                # batch-invariant in isolation), so once re-participating
                # clients personalize (round 2+) uploads drift ~1e-6.  With
                # update_impl="reference" all backends ARE bitwise equal.
                # The bitwise contract this suite enforces is sharded vs
                # replicated output mode on the SAME backend, below.
                if driver not in ref_hist:
                    ref_hist[driver] = h["loss"]
                else:
                    xdrift = float(np.max(np.abs(
                        np.asarray(ref_hist[driver]) - np.asarray(h["loss"]))))
                    assert xdrift < 1e-4, (
                        f"{backend}/{driver}: loss history diverged across "
                        f"backends beyond fp tolerance ({xdrift}; "
                        "replicated-output contract, DESIGN.md §11)")
                print(f"bench,multipod-engine/{backend}/{driver},{t*1e6:.0f},"
                      f"rounds_per_sec={1.0/max(t,1e-9):.3f},"
                      f"sim_t_total={h['sim_time'][-1]:.2f}")
        # sharded-at-rest round loop (§11 output sharding): engine outputs
        # keep the client sharding, Eq. 13 aggregation runs inside the
        # sharded program — the round-boundary all-gather disappears.
        # Histories must stay BITWISE equal to the replicated runs above.
        for driver in ([] if backend == "vmap" else ["sync", "async"]):
            method = _build("pfedsop")
            cfg = _cfg(backend, mesh, kernel_impl, f"{driver}-sharded",
                       output_sharding="sharded")
            if driver == "sync":
                fed = Federation(method, loss, acc, params, data, cfg,
                                 availability=ClientAvailability(
                                     avail, clients, 0))
            else:
                fed = AsyncFederation(
                    method, loss, acc, params, data, cfg,
                    AsyncConfig(buffer_size=buffer_size,
                                concurrency=kprime, availability=avail))
            h = fed.run()
            assert row[driver]["loss"] == h["loss"], (
                f"{backend}/{driver}: sharded-output loss history must be "
                "BITWISE identical to replicated mode (DESIGN.md §11)")
            t = float(np.mean(h["round_time"][1:]))
            row[f"{driver}_sharded"] = {
                "rounds_per_sec": 1.0 / max(t, 1e-9),
                "sim_time_total": h["sim_time"][-1],
            }
            print(f"bench,multipod-engine/{backend}/{driver}-sharded,"
                  f"{t*1e6:.0f},rounds_per_sec={1.0/max(t,1e-9):.3f},"
                  f"sim_t_total={h['sim_time'][-1]:.2f}")
        out["backends"][backend] = {
            d: {key: v for key, v in row[d].items() if key != "loss"}
            for d in row
        }
    print(f"{'backend':>10} {'sync r/s':>9} {'async r/s':>10} "
          f"{'sync-sh r/s':>12} {'async-sh r/s':>13}")
    for backend, row in out["backends"].items():
        sh = row.get("sync_sharded", {}).get("rounds_per_sec")
        ash = row.get("async_sharded", {}).get("rounds_per_sec")
        print(f"{backend:>10} {row['sync']['rounds_per_sec']:>9.3f} "
              f"{row['async']['rounds_per_sec']:>10.3f} "
              f"{sh if sh is not None else float('nan'):>12.3f} "
              f"{ash if ash is not None else float('nan'):>13.3f}")
    return out


def bench_cohort_store(rounds):
    """Fleet-scale cohort-store sweep (DESIGN.md §12): rounds/sec and
    host<->device bytes moved vs fleet size K per store kind.

    The store's claim is that K is a *throughput* knob, not a device-memory
    limit: per-client state rests on host numpy (``host``) or disk-backed
    memmap (``mmap``) and only the round's K' participants are gathered to
    device.  The sweep holds K' fixed at 64 and scales K across
    10^3..10^5 — device memory stays flat while at-rest bytes scale with
    K.  At the smallest K every kind (plus an LRU-cached host store) runs
    and the loss histories + final client states are asserted BITWISE
    identical to the all-on-device baseline; the larger sizes run only the
    kinds whose at-rest tier fits the CI budget (RAM at 10^4, disk at
    10^5 — capped below the ISSUE's 10^6 upper bound, which the mmap
    store reaches with the same command and more disk/time; the cap is
    printed, not silent).
    """
    print("\n== cohort-store: rounds/sec + bytes moved vs fleet size ==")
    from repro.fl import StoreConfig

    # tiny CNN so at-rest state is ~KB/client and the 10^5 sweep fits CI
    cfg = CFG.replace(name="fleet-cnn", cnn_channels=(4,), cnn_image_size=8,
                      n_classes=4)
    loss = lambda p, b: cnn.loss_fn(p, cfg, b)
    acc = masked_accuracy(lambda p, t: cnn.apply(p, cfg, t["images"]))
    params = cnn.init_params(jax.random.PRNGKey(0), cfg)
    kprime, r = 64, max(3, rounds // 3)

    def fleet_data(k, seed=0):
        # shared tiny sample bank, 5 overlapping samples per client: the
        # bench measures state movement, so per-client data stays O(1)
        images, labels = make_class_conditional_images(512, cfg.n_classes,
                                                       cfg.cnn_image_size,
                                                       seed=seed)
        parts = [np.arange((5 * i) % 500, (5 * i) % 500 + 5) for i in range(k)]
        return FederatedData.from_partition(images, labels, parts, seed=seed)

    def run_one(data, k, store):
        run_cfg = FLRunConfig(n_clients=k, participation=kprime / k, rounds=r,
                              batch=4, local_iters=1, seed=0, store=store)
        fed = Federation(_build("pfedsop"), loss, acc, params, data, run_cfg)
        hist = fed.run()
        return fed, hist

    plans = {
        1_000: ["device", "host", "mmap", "host+cache"],
        10_000: ["host", "host+cache"],
        100_000: ["mmap"],
    }
    print("bench,cohort-store/cap,0,max_k=100000_of_issue_1e6 "
          "(mmap reaches 1e6 with more disk/time)")
    out = {"kprime": kprime, "rounds": r, "sizes": {}}
    for k, kinds in plans.items():
        data = fleet_data(k)
        out["sizes"][k] = {}
        baseline = None  # (hist, final states) of the device store
        for tag in kinds:
            store = (StoreConfig(kind="host", cache_clients=4 * kprime)
                     if tag == "host+cache" else tag)
            fed, h = run_one(data, k, store)
            t = float(np.mean(h["round_time"][1:]))  # skip compile round
            stats = fed.store.stats()
            hits = stats["cache_hits"] + stats["cache_misses"]
            row = {
                "rounds_per_sec": 1.0 / max(t, 1e-9),
                "h2d_bytes": stats["h2d_bytes"],
                "d2h_bytes": stats["d2h_bytes"],
                "at_rest_bytes": getattr(fed.store, "at_rest_bytes", 0),
                "cache_hit_rate": stats["cache_hits"] / hits if hits else None,
            }
            out["sizes"][k][tag] = row
            print(f"bench,cohort-store/{tag}/k{k},{t*1e6:.0f},"
                  f"rounds_per_sec={row['rounds_per_sec']:.3f},"
                  f"h2d_mb={stats['h2d_bytes']/1e6:.1f},"
                  f"d2h_mb={stats['d2h_bytes']/1e6:.1f}")
            # bitwise parity vs the all-on-device baseline (the §12
            # contract), checked where the device store itself runs
            final = jax.tree.leaves(jax.tree.map(np.asarray, fed.client_states))
            if baseline is None:
                baseline = (h, final)
            else:
                assert h["loss"] == baseline[0]["loss"], (
                    f"{tag}/k{k}: loss history must be bitwise identical "
                    "to the device store")
                assert all(np.array_equal(a, b)
                           for a, b in zip(baseline[1], final)), (
                    f"{tag}/k{k}: final client states must be bitwise "
                    "identical to the device store")
    print(f"{'K':>8} {'store':>11} {'r/s':>7} {'h2d MB':>7} {'at-rest MB':>11}")
    for k, row in out["sizes"].items():
        for tag, m in row.items():
            print(f"{k:>8} {tag:>11} {m['rounds_per_sec']:>7.2f} "
                  f"{m['h2d_bytes']/1e6:>7.1f} {m['at_rest_bytes']/1e6:>11.1f}")
    return out


def bench_obs_overhead(rounds):
    """Observability overhead gate (DESIGN.md §13).

    Runs the same federation with observability off and with phase-level
    tracing + metrics on, and asserts the §13 contract in both directions:

    - **disabled is free**: the off run holds the shared NOOP facade and
      the would-be trace directory is never created — 0 bytes written;
    - **enabled changes wall-clock only**: every history series except
      ``round_time`` (and the attached ``obs_metrics``) is bitwise
      identical to the off run;
    - **enabled is cheap**: the per-round overhead fraction is recorded in
      the BENCH artifact, and ``benchmarks/check_ledger.py obs-overhead``
      gates it at <5% (the in-bench assert stays loose — CI boxes are
      noisy — the ledger gate is the enforcement point).
    """
    print("\n== obs-overhead: traced vs untraced, same seed ==")
    import shutil

    data = _data("dirichlet", clients=8, samples=1600)
    r = max(6, rounds)
    base = OUT / "obs_trace"
    off_dir, on_dir = base / "overhead_off", base / "overhead_on"
    shutil.rmtree(base, ignore_errors=True)

    h_off = _run(_build("pfedsop"), data, r, clients=8, participation=0.5)
    assert not off_dir.exists(), (
        "observability off must write 0 bytes, but the trace dir exists")
    h_on = _run(_build("pfedsop"), data, r, clients=8, participation=0.5,
                obs=ObsConfig(trace_dir=str(on_dir), level="phase",
                              quiet=True))
    for key in h_off:
        if key == "round_time":
            continue
        assert h_off[key] == h_on[key], (
            f"history[{key!r}] must be bitwise identical traced vs "
            "untraced (obs reads host numbers, never touches traced values)")

    t_off = float(np.mean(h_off["round_time"][1:]))  # skip compile round
    t_on = float(np.mean(h_on["round_time"][1:]))
    overhead = t_on / max(t_off, 1e-9) - 1.0
    trace_bytes = sum(f.stat().st_size for f in on_dir.rglob("*")
                      if f.is_file())
    out = {
        "rounds": r,
        "off": {"rounds_per_sec": 1.0 / max(t_off, 1e-9),
                "disabled_bytes": 0},
        "on": {"rounds_per_sec": 1.0 / max(t_on, 1e-9),
               "trace_bytes": trace_bytes,
               "obs_metrics": h_on.get("obs_metrics")},
        "overhead_frac": overhead,
        "disabled_bytes": 0,
    }
    print(f"bench,obs-overhead/off,{t_off*1e6:.0f},"
          f"rounds_per_sec={out['off']['rounds_per_sec']:.3f}")
    print(f"bench,obs-overhead/on,{t_on*1e6:.0f},"
          f"rounds_per_sec={out['on']['rounds_per_sec']:.3f},"
          f"overhead_frac={overhead:.4f},trace_kb={trace_bytes/1e3:.1f}")
    # loose in-bench sanity bound only (see docstring): a 2x slowdown
    # means the instrumentation landed on the traced path, not the host
    assert overhead < 1.0, (
        f"phase-level tracing more than doubled round time: {overhead:.2f}")
    return out


def bench_model_fwd():
    """Model-zoo forward throughput per kernel impl x config (DESIGN.md §9).

    The dominant per-round FLOPs of the federated LM path are the
    transformer forward/backward, so the model-level ``kernel_impl`` knob
    is benched end-to-end here: tokens/sec through ``transformer.forward``
    for the reference path vs the Pallas kernel path (interpret mode on
    CPU — correctness-path timing; honest kernel wall-times need a TPU).
    Two reduced configs, one with sliding-window layers (gemma3-1b, window
    capped so the window actually binds at bench seq-len) and one
    full-attention (granite-3-2b).  Asserts (a) max-abs hidden-state drift
    between impls and (b) that the window-pruned flash_gqa grid visits
    strictly fewer KV blocks than the unpruned grid — at the shape this
    bench runs AND at the production train_4k shape (grid-shape assertion,
    not timing).
    """
    print("\n== model-fwd: tokens/sec per kernel impl x config ==")
    from repro.configs import get_config
    from repro.kernels.flash_gqa.kernel import flash_gqa_grid
    from repro.models import transformer as tf

    b, s, iters = 2, 64, 3
    win = 16
    configs = []
    # sliding-window + qk-norm config: cap every window at `win` (the
    # long_500k machinery) and shrink attention blocks so the window is
    # smaller than the sequence at bench size
    g3 = get_config("gemma3-1b", reduced=True).replace(
        long_context_window=win, attn_q_block=win)
    configs.append(tf.apply_long_context(g3))
    configs.append(get_config("granite-3-2b", reduced=True))

    out = {}
    for cfg in configs:
        key = jax.random.PRNGKey(0)
        params = tf.init_params(key, cfg)
        batch = {"tokens": jax.random.randint(jax.random.fold_in(key, 1),
                                              (b, s), 0, cfg.vocab_size)}
        out[cfg.name] = {}
        hidden = {}
        for impl in ["reference", "kernel_interpret"]:
            c = cfg.replace(kernel_impl=impl)
            fwd = jax.jit(lambda p, bt, c=c: tf.forward(p, c, bt)[0])
            h = jax.block_until_ready(fwd(params, batch))  # compile
            t0 = time.perf_counter()
            for _ in range(iters):
                h = fwd(params, batch)
            jax.block_until_ready(h)
            dt = (time.perf_counter() - t0) / iters
            tps = b * s / max(dt, 1e-9)
            hidden[impl] = np.asarray(h, np.float32)
            out[cfg.name][impl] = {"tokens_per_sec": tps, "s_per_fwd": dt}
            print(f"bench,model-fwd/{cfg.name}/{impl},{dt*1e6:.0f},"
                  f"tokens_per_sec={tps:.0f}")
        drift = float(np.max(np.abs(hidden["reference"]
                                    - hidden["kernel_interpret"])))
        assert drift < 1e-4, (
            f"{cfg.name}: kernel impl drifted from reference: "
            f"max |hidden diff| = {drift}")
        out[cfg.name]["max_abs_drift"] = drift
        print(f"bench,model-fwd/{cfg.name}/drift,0,max_abs={drift:.2e}")

    # window-pruned grid: strictly fewer KV blocks than unpruned, at the
    # bench shape and at the production train_4k shape (gemma2 window 4096
    # at 32k prefill; gemma3 window 512 at 4k train)
    prune_cases = [
        ("bench", s, win, win, win),
        ("gemma3_train4k", 4096, 512, 512, 512),
        ("gemma2_prefill32k", 32768, 512, 512, 4096),
    ]
    out["pruned_grid"] = {}
    for tag, ss, bq, bk, w in prune_cases:
        nq_p, nk_p = flash_gqa_grid(ss, bq, bk, window=w, prune_window=True)
        nq_u, nk_u = flash_gqa_grid(ss, bq, bk, window=w, prune_window=False)
        assert nq_p == nq_u and nk_p < nk_u, (
            f"pruned grid must visit fewer KV blocks: {tag}: "
            f"pruned {(nq_p, nk_p)} vs unpruned {(nq_u, nk_u)}")
        out["pruned_grid"][tag] = {"pruned_nk": nk_p, "unpruned_nk": nk_u}
        print(f"bench,model-fwd/pruned-grid/{tag},0,"
              f"kv_blocks={nk_p}_of_{nk_u}")

    print(f"{'config':>16} {'ref tok/s':>10} {'kernel tok/s':>13} {'drift':>9}")
    for name, row in out.items():
        if name == "pruned_grid":
            continue
        print(f"{name:>16} {row['reference']['tokens_per_sec']:>10.0f} "
              f"{row['kernel_interpret']['tokens_per_sec']:>13.0f} "
              f"{row['max_abs_drift']:>9.2e}")
    return out


def bench_model_bwd():
    """Train-step (fwd+bwd) throughput per kernel impl x config, plus the
    dispatched attention backward (DESIGN.md §9, kernel ``flash_gqa_bwd``)
    benched at the ops level: fused flash backward vs the scan-of-VJPs
    reference on the same kernel forward.

    Like model-fwd this is correctness-path timing on CPU (interpret
    mode); the asymptotic claim is asserted structurally instead: at the
    production gemma3 train_4k shape the fused backward's two passes
    visit O(S·W) tiles (dq reuses the forward's pruned KV grid, dk/dv
    visits ceil((W+BK)/BQ)+1 q-blocks per k-block) while the scan VJP
    recomputes full-S attention per q-block — an O(S²) tile count.
    """
    print("\n== model-bwd: train-step tokens/sec per kernel impl x config ==")
    from repro.configs import get_config
    from repro.kernels.flash_gqa.kernel import (flash_gqa_bwd_grid,
                                                flash_gqa_grid)
    from repro.kernels.flash_gqa.ops import flash_gqa
    from repro.models import transformer as tf

    b, s, iters = 2, 64, 3
    win = 16
    g3 = get_config("gemma3-1b", reduced=True).replace(
        long_context_window=win, attn_q_block=win)
    configs = [tf.apply_long_context(g3),
               get_config("granite-3-2b", reduced=True)]

    out = {}
    for cfg in configs:
        key = jax.random.PRNGKey(0)
        params = tf.init_params(key, cfg)
        batch = {
            "tokens": jax.random.randint(jax.random.fold_in(key, 1), (b, s),
                                         0, cfg.vocab_size),
            "labels": jax.random.randint(jax.random.fold_in(key, 2), (b, s),
                                         0, cfg.vocab_size),
        }
        out[cfg.name] = {}
        results = {}
        for impl in ["reference", "kernel_interpret"]:
            c = cfg.replace(kernel_impl=impl)
            step = jax.jit(lambda p, bt, c=c: jax.value_and_grad(
                lambda pp: tf.lm_loss(pp, c, bt))(p))
            lv, g = jax.block_until_ready(step(params, batch))  # compile
            t0 = time.perf_counter()
            for _ in range(iters):
                lv, g = step(params, batch)
            jax.block_until_ready(g)
            dt = (time.perf_counter() - t0) / iters
            tps = b * s / max(dt, 1e-9)
            results[impl] = (float(lv), g)
            out[cfg.name][impl] = {"tokens_per_sec": tps, "s_per_step": dt}
            print(f"bench,model-bwd/{cfg.name}/{impl},{dt*1e6:.0f},"
                  f"tokens_per_sec={tps:.0f}")
        # kernel_interpret routes the backward through the fused flash
        # backward kernel (attention_fwd passes bwd=impl) — loss AND grads
        # must stay within fp32 reduction-order drift of the reference
        loss_drift = abs(results["kernel_interpret"][0]
                         - results["reference"][0])
        grad_drift = max(
            float(np.max(np.abs(np.asarray(a, np.float32)
                                - np.asarray(b_, np.float32))))
            for a, b_ in zip(jax.tree.leaves(results["kernel_interpret"][1]),
                             jax.tree.leaves(results["reference"][1])))
        assert loss_drift < 1e-4 and grad_drift < 5e-3, (
            f"{cfg.name}: fused backward drifted from reference: "
            f"loss {loss_drift:.2e}, grad {grad_drift:.2e}")
        out[cfg.name]["max_abs_grad_drift"] = grad_drift
        print(f"bench,model-bwd/{cfg.name}/drift,0,"
              f"loss={loss_drift:.2e},grad={grad_drift:.2e}")

    # ops-level backward shootout: same kernel forward, dispatched backward
    sb, ss, sd, sh, skv, swin = 1, 256, 32, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (sb, ss, sh, sd), jnp.float32)
    k = jax.random.normal(ks[1], (sb, ss, skv, sd), jnp.float32)
    v = jax.random.normal(ks[2], (sb, ss, skv, sd), jnp.float32)
    out["attention_bwd"] = {}
    for bwd in ["reference", "kernel_interpret"]:
        grad = jax.jit(jax.grad(
            lambda q, k, v, bwd=bwd: jnp.sum(
                flash_gqa(q, k, v, window=swin, bq=64, bk=64, interpret=True,
                          bwd=bwd).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2)))
        jax.block_until_ready(grad(q, k, v))  # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            g = grad(q, k, v)
        jax.block_until_ready(g)
        dt = (time.perf_counter() - t0) / iters
        tps = sb * ss / max(dt, 1e-9)
        out["attention_bwd"][bwd] = {"tokens_per_sec": tps, "s_per_grad": dt}
        print(f"bench,model-bwd/attention-bwd/{bwd},{dt*1e6:.0f},"
              f"tokens_per_sec={tps:.0f}")

    # structural win at the production train_4k shape: fused backward tile
    # count is O(S·W), the scan VJP's recomputation is O(S²)
    out["bwd_grid"] = {}
    for tag, ts, bq, bk, w in [("bench", s, win, win, win),
                               ("gemma3_train4k", 4096, 512, 512, 512)]:
        nq_f, nk_f = flash_gqa_grid(ts, bq, bk, window=w, prune_window=False)
        nk_dq, nq_dkv = flash_gqa_bwd_grid(ts, bq, bk, window=w)
        fused_tiles = nq_f * nk_dq + nk_f * nq_dkv  # dq pass + dk/dv pass
        scan_tiles = 2 * nq_f * nk_f  # recomputed fwd + vjp, full S keys
        assert fused_tiles < scan_tiles, (
            f"fused backward must visit fewer tiles than the scan VJP: "
            f"{tag}: {fused_tiles} vs {scan_tiles}")
        out["bwd_grid"][tag] = {"fused_tiles": fused_tiles,
                                "scan_vjp_tiles": scan_tiles}
        print(f"bench,model-bwd/bwd-grid/{tag},0,"
              f"tiles={fused_tiles}_of_{scan_tiles}")

    print(f"{'config':>16} {'ref tok/s':>10} {'kernel tok/s':>13} {'drift':>9}")
    for name, row in out.items():
        if name in ("attention_bwd", "bwd_grid"):
            continue
        print(f"{name:>16} {row['reference']['tokens_per_sec']:>10.0f} "
              f"{row['kernel_interpret']['tokens_per_sec']:>13.0f} "
              f"{row['max_abs_grad_drift']:>9.2e}")
    return out


def bench_roofline():
    """Summarise the dry-run artifacts (§Roofline table)."""
    print("\n== roofline: dry-run artifact summary ==")
    art = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"
    rows = []
    for f in sorted(art.glob("*.json")):
        r = json.loads(f.read_text())
        rl = r.get("roofline", {})
        rows.append({
            "arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
            "variant": r.get("variant", "baseline"),
            "dominant": rl.get("dominant"),
            "compute_s": rl.get("compute_s"), "memory_s": rl.get("memory_s"),
            "collective_s": rl.get("collective_s"),
        })
        print(f"bench,roofline/{r['arch']}/{r['shape']}/{r['mesh']},0,"
              f"dominant={rl.get('dominant')}")
    print(f"({len(rows)} artifacts)")
    return rows


BENCHES = {
    "table1": bench_table1,
    "table2": bench_table2,
    "table3": bench_table3,
    "table4": bench_table4,
    "figures": bench_figures,
    "engine": bench_engine,
    "kernels": bench_kernels,
    "pfedsop-update": bench_pfedsop_update,
    "async-engine": bench_async_engine,
    "multipod-engine": bench_multipod_engine,
    "cohort-store": bench_cohort_store,
    "obs-overhead": bench_obs_overhead,
    "model-fwd": bench_model_fwd,
    "model-bwd": bench_model_bwd,
    "roofline": bench_roofline,
}


def emit_bench_json(suite: str, metrics, args) -> Path:
    """Write the machine-readable per-suite trajectory file.

    ``experiments/bench/BENCH_<suite>.json``: suite name, run config, the
    suite's metrics, and the commit timestamp *passed in* by the caller
    (CI passes ``git log -1 --format=%cI``) — never sampled from the wall
    clock, so re-running a commit produces an identical artifact and the
    perf trajectory stays attributable to commits.  Uploaded as a CI
    artifact by .github/workflows/ci.yml.
    """
    payload = {
        "suite": suite,
        "commit_timestamp": args.commit_ts,
        "config": {
            "rounds": args.rounds,
            "interpret": args.interpret,
            "devices": len(jax.devices()),
            "jax_backend": jax.default_backend(),
        },
        "metrics": metrics,
    }
    path = OUT / f"BENCH_{suite}.json"
    path.write_text(json.dumps(payload, indent=1, default=float))
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="+", choices=sorted(BENCHES), default=None)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--interpret", action="store_true",
                    help="force the Pallas interpreter for kernel impls "
                         "(pfedsop-update / async-engine benches; automatic "
                         "off-TPU)")
    ap.add_argument("--commit-ts", default="",
                    help="commit timestamp (e.g. git log -1 --format=%%cI) "
                         "stamped into BENCH_<suite>.json; passed in, not "
                         "sampled, so artifacts are reproducible per commit")
    ap.add_argument("--trace-dir", default="",
                    help="trace supporting benches (multipod-engine) into "
                         "per-run subdirs here (DESIGN.md §13); summarize "
                         "with scripts/trace_report.py")
    ap.add_argument("--obs-level", choices=["round", "phase", "kernel"],
                    default="phase",
                    help="instrumentation depth for --trace-dir runs")
    args = ap.parse_args()
    enable_compile_cache()
    if args.trace_dir:
        OBS_CFG.update(trace_dir=args.trace_dir, level=args.obs_level)

    OUT.mkdir(parents=True, exist_ok=True)
    names = args.only or list(BENCHES)
    results = {}
    t0 = time.time()
    for name in names:
        fn = BENCHES[name]
        if name in ("kernels", "model-fwd", "model-bwd", "roofline"):
            results[name] = fn()
        elif name in ("pfedsop-update", "async-engine", "multipod-engine"):
            results[name] = fn(args.rounds, interpret=args.interpret)
        else:
            results[name] = fn(args.rounds)
        # one trajectory artifact per suite, written as soon as the suite
        # finishes (partial runs still land their artifacts)
        print(f"wrote {emit_bench_json(name, results[name], args)}")
    (OUT / "results.json").write_text(json.dumps(results, indent=1, default=float))
    print(f"\nwrote experiments/bench/results.json ({time.time()-t0:.0f}s total)")


if __name__ == "__main__":
    main()
