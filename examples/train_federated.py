"""End-to-end driver: the paper's experiment, miniaturised for CPU.

Heterogeneously-partitioned synthetic image classification across K
clients with partial participation, comparing pFedSOP against the
baselines (FedAvg / FedProx / FT variants / Ditto / FedRep / local-only)
under identical initialization - the setup of pFedSOP Sec. V.

Examples:
  PYTHONPATH=src python examples/train_federated.py                     # default small run
  PYTHONPATH=src python examples/train_federated.py --methods pfedsop fedavg \
      --rounds 30 --clients 20 --partition pathological
  PYTHONPATH=src python examples/train_federated.py --paper-scale       # K=100, 20%%, T=100

  # asynchronous federation (DESIGN.md §10): heterogeneous client speeds,
  # 30%% availability, FedBuff-style buffered staleness-weighted updates
  PYTHONPATH=src python examples/train_federated.py --mode async \
      --speed lognormal --availability 0.3 --buffer-size 4

  # replay a recorded device trace instead of the generative model
  PYTHONPATH=src python examples/train_federated.py --mode async \
      --availability trace:examples/traces/device_trace_8.json

  # multi-pod mesh engine (DESIGN.md §11): cohort over 2 pods, model=2
  # tensor shards per pod (8 devices; forced host devices on CPU)
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python examples/train_federated.py \
      --backend mesh --mesh pods:2x2x2

  # fleet scale (DESIGN.md §12): client state at rest on host (or disk
  # with --store mmap), gathered to device per round; LRU-cache the 50
  # hottest clients' device rows
  PYTHONPATH=src python examples/train_federated.py --clients 2000 \
      --participation 0.01 --store host --cache-clients 50

  # checkpoint every 5 server updates and resume an interrupted run
  PYTHONPATH=src python examples/train_federated.py --mode async \
      --ckpt-every 5 --ckpt-dir experiments/ckpt/demo
  PYTHONPATH=src python examples/train_federated.py --mode async \
      --ckpt-every 5 --ckpt-dir experiments/ckpt/demo --resume

Writes per-method histories to experiments/fl/<tag>.json (consumed by
benchmarks/run.py for the Table II/III/IV analogs).
"""
import argparse
import json
from dataclasses import replace
from pathlib import Path

import jax
import numpy as np

from repro.configs.resnet_cifar import RESNET9_CIFAR100, SMALL_CNN
from repro.core.baselines import METHODS, FedRep
from repro.core.pfedsop import PFedSOPConfig
from repro.core import baselines as bl
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
    Federation,
    FLRunConfig,
    StoreConfig,
    TraceAvailabilityConfig,
    make_availability,
)
from repro.fl.runtime import masked_accuracy
from repro.models import cnn
from repro.obs import ObsConfig
from repro.utils.checkpoint import latest_step, save_checkpoint
from repro.utils.compile_cache import enable_compile_cache


def build_method(name, lr, args):
    if name == "pfedsop":
        return bl.PFedSOP(cfg=PFedSOPConfig(eta1=lr, eta2=lr, rho=args.rho, lam=args.lam))
    if name == "pfedsop_nopc":
        m = bl.PFedSOP(cfg=PFedSOPConfig(eta1=lr, eta2=lr, rho=args.rho,
                                         lam=args.lam, use_pc=False))
        return type(m)(cfg=m.cfg, name="pfedsop_nopc")
    if name == "fedrep":
        return FedRep(lr=lr, head_predicate=lambda p: "fc_" in p)
    if name == "fedprox":
        return bl.FedProx(lr=lr, mu=args.mu)
    if name == "fedprox_ft":
        return bl.FedProxFT(lr=lr, mu=args.mu)
    if name == "ditto":
        return bl.Ditto(lr=lr, lam=args.ditto_lam)
    return METHODS[name](lr=lr)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--methods", nargs="+", default=["pfedsop", "fedavg"],
                    choices=sorted(METHODS) + ["pfedsop_nopc"])
    ap.add_argument("--partition", choices=["dirichlet", "pathological"],
                    default="dirichlet")
    ap.add_argument("--alpha", type=float, default=0.07)  # paper Dir(0.07)
    ap.add_argument("--shard-size", type=int, default=100)
    ap.add_argument("--clients", type=int, default=20)
    ap.add_argument("--participation", type=float, default=0.2)
    ap.add_argument("--rounds", type=int, default=15)
    ap.add_argument("--samples", type=int, default=4000)
    ap.add_argument("--classes", type=int, default=None,
                    help="label classes (default: the --model config's)")
    ap.add_argument("--image-size", type=int, default=None,
                    help="input height = width (default: the --model config's)")
    ap.add_argument("--batch", type=int, default=50)  # paper batch size
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--rho", type=float, default=1.0)
    ap.add_argument("--lam", type=float, default=1.0)
    ap.add_argument("--mu", type=float, default=0.1)
    ap.add_argument("--ditto-lam", type=float, default=0.1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", choices=["vmap", "shard_map", "mesh"],
                    default="vmap",
                    help="federation engine backend (DESIGN.md §3/§11); "
                         "shard_map splits the participating clients across "
                         "local devices on a 1-D mesh; mesh runs the "
                         "role-named mesh engine selected by --mesh")
    ap.add_argument("--shards", type=int, default=0,
                    help="shard_map only: device-shard count (0 = auto)")
    ap.add_argument("--mesh", default="",
                    help="mesh backend only: mesh spec (repro.launch.mesh."
                         "parse_mesh) — 'clients[:N]' | 'host' | 'pod:DxM' | "
                         "'pods:PxDxM'; e.g. 'pods:2x2x2' shards the cohort "
                         "over 2 pods with model=2 tensor shards each "
                         "(8 devices; run under XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8 on CPU)")
    ap.add_argument("--output-sharding", choices=["replicated", "sharded"],
                    default="replicated",
                    help="round-boundary output layout (DESIGN.md §11): "
                         "'replicated' all-gathers engine outputs at the "
                         "round boundary (the seed contract); 'sharded' "
                         "keeps them client-sharded at rest and lowers "
                         "Eq. 13 aggregation into the sharded program — "
                         "bitwise-identical histories, no all-gather span. "
                         "shard_map/mesh backends only")
    ap.add_argument("--grad-chunks", type=int, default=1,
                    help="gradient chunk count of each local SGD step "
                         "(DESIGN.md §11): the per-step gradient is the "
                         "canonical halving-tree mean over this many equal "
                         "batch chunks; on a mesh whose data-axis size "
                         "matches, chunks run one-per-device over the data "
                         "axis with bitwise-identical histories (1 = plain "
                         "value_and_grad, the seed semantics)")
    ap.add_argument("--update-impl", default="",
                    choices=["", "auto", "reference", "kernel", "kernel_interpret"],
                    help="pFedSOP round-start update impl (DESIGN.md §9): "
                         "fused Pallas kernel vs pytree reference; '' defers "
                         "to the method config (auto: kernel on TPU). "
                         "kernel_interpret runs the kernel body on CPU")
    ap.add_argument("--model", choices=["small", "resnet9"], default="small")
    ap.add_argument("--paper-scale", action="store_true",
                    help="K=100 clients, 20%% participation, 100 rounds (slow on CPU)")
    # -- async federation (DESIGN.md §10) ---------------------------------
    ap.add_argument("--mode", choices=["sync", "async"], default="sync",
                    help="sync: bulk-synchronous rounds (the paper's setup); "
                         "async: availability-aware discrete-event simulation "
                         "with FedBuff-style buffered staleness-weighted "
                         "aggregation (DESIGN.md §10). 'rounds' then counts "
                         "applied server updates")
    ap.add_argument("--buffer-size", type=int, default=0,
                    help="async: uploads per server update (0 = K', the "
                         "sync-degenerate setting)")
    ap.add_argument("--concurrency", type=int, default=0,
                    help="async: clients kept in flight (0 = K')")
    ap.add_argument("--speed", choices=["fixed", "lognormal"], default="fixed",
                    help="per-client compute-speed model (both modes: async "
                         "scheduling / sync simulated round clock)")
    ap.add_argument("--speed-sigma", type=float, default=1.0,
                    help="lognormal sigma of the per-client speed multipliers")
    ap.add_argument("--mean-duration", type=float, default=1.0,
                    help="median simulated client round duration (sim seconds)")
    ap.add_argument("--availability", default="1.0",
                    help="either a steady-state online fraction per client "
                         "(float; 1.0 = always on, exponential on/off "
                         "traces) or 'trace:<path>' to replay a recorded "
                         "device trace file (JSON on/off windows + "
                         "durations; see examples/traces/)")
    ap.add_argument("--mean-on", type=float, default=10.0,
                    help="mean online-stretch length (sim seconds)")
    # -- cohort store (DESIGN.md §12) --------------------------------------
    ap.add_argument("--store", choices=["device", "host", "mmap"],
                    default="device",
                    help="where per-client personalized state lives at rest: "
                         "'device' = one stacked device array (the seed "
                         "layout), 'host' = numpy in host RAM, 'mmap' = "
                         "disk-backed memmap; host/mmap gather only each "
                         "round's participants to device, so --clients is a "
                         "throughput knob instead of a device-memory limit — "
                         "bitwise identical results either way")
    ap.add_argument("--cache-clients", type=int, default=0,
                    help="host/mmap stores only: keep device rows of the N "
                         "most recently sampled clients in an LRU cache, "
                         "skipping their host->device copy on re-sampling "
                         "(0 = no cache)")
    # -- observability (DESIGN.md §13) -------------------------------------
    ap.add_argument("--trace-dir", default="",
                    help="write a structured event trace under this directory "
                         "(per-method subdirs, like --ckpt-dir); the drivers "
                         "export a Perfetto-loadable trace.json on completion "
                         "and scripts/trace_report.py summarizes it. "
                         "Fingerprint-stamped: re-running a --resume'd config "
                         "appends with a resume marker instead of clobbering")
    ap.add_argument("--metrics", default="",
                    help="metrics.jsonl path ('' = <trace-dir>/<method>/"
                         "metrics.jsonl when tracing); counters/gauges/"
                         "histograms snapshot once per applied server update")
    ap.add_argument("--obs-level", choices=["off", "round", "phase"],
                    default="phase",
                    help="instrumentation depth (DESIGN.md §13): round = "
                         "round spans + metrics + non-blocking sample/"
                         "dispatch/sync spans; phase = per-phase spans with "
                         "block-until-ready boundaries instead of dispatch")
    ap.add_argument("--xla-profile", type=int, default=-1,
                    help="capture a jax.profiler trace of this round/version "
                         "index under <trace-dir>/<method>/xla (-1 = off; "
                         "1 is the first post-compile round)")
    ap.add_argument("--obs-quiet", action="store_true",
                    help="suppress the drivers' stdout progress lines "
                         "(structured records still land in the trace)")
    # -- checkpointing ----------------------------------------------------
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint the full driver state every N applied "
                         "server updates (0 = off); see repro.utils.checkpoint")
    ap.add_argument("--ckpt-dir", default="",
                    help="checkpoint directory (per-method subdirs)")
    ap.add_argument("--resume", action="store_true",
                    help="resume each method from its latest checkpoint under "
                         "--ckpt-dir (bitwise-identical continuation)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="legacy: save only the final broadcast per method")
    ap.add_argument("--tag", default="run")
    args = ap.parse_args()
    enable_compile_cache()

    if args.resume and not args.ckpt_dir:
        ap.error("--resume requires --ckpt-dir")
    if args.ckpt_every and not args.ckpt_dir:
        ap.error("--ckpt-every requires --ckpt-dir (the drivers only save "
                 "when both are set, so checkpointing would be silently off)")
    if args.mode != "async" and (args.buffer_size or args.concurrency):
        ap.error("--buffer-size/--concurrency only apply to --mode async "
                 "(the sync driver has no aggregation buffer or dispatch "
                 "pipeline), so they would be silently ignored")
    if args.output_sharding == "sharded" and args.backend == "vmap":
        ap.error("--output-sharding sharded needs a client-sharding backend "
                 "(--backend shard_map or mesh); vmap outputs are born "
                 "replicated, so the flag would be a silent no-op")
    if args.mesh and args.backend != "mesh":
        ap.error("--mesh only applies to --backend mesh (the other backends "
                 "fix their own layout), so it would be silently ignored")
    if args.backend == "mesh" and not args.mesh:
        ap.error("--backend mesh requires --mesh (e.g. 'pods:2x2x2'); see "
                 "repro.launch.mesh.parse_mesh for the grammar")
    if args.xla_profile >= 0 and not args.trace_dir:
        ap.error("--xla-profile dumps under <trace-dir>/<method>/xla, so it "
                 "requires --trace-dir")
    if args.obs_level == "off" and (args.trace_dir or args.metrics):
        ap.error("--obs-level off disables every sink, so --trace-dir/"
                 "--metrics would be silently ignored")
    if args.metrics and len(args.methods) > 1:
        ap.error("--metrics names a single file; each of the "
                 f"{len(args.methods)} --methods would clobber it — use "
                 "--trace-dir (per-method metrics.jsonl subdirs) instead")
    if args.cache_clients and args.store == "device":
        ap.error("--cache-clients only applies to --store host/mmap (the "
                 "device store keeps every client resident, so a device "
                 "cache is meaningless), so it would be silently ignored")

    trace_path = None
    if args.availability.startswith("trace:"):
        trace_path = args.availability[len("trace:"):]
        if (args.speed != "fixed" or args.speed_sigma != 1.0
                or args.mean_duration != 1.0 or args.mean_on != 10.0):
            ap.error("--availability trace:<path> replays durations and "
                     "on/off windows from the file; --speed/--speed-sigma/"
                     "--mean-duration/--mean-on would be silently ignored")
    else:
        try:
            args.availability = float(args.availability)
        except ValueError:
            ap.error(f"--availability must be a float or 'trace:<path>', "
                     f"got {args.availability!r}")

    if args.update_impl and not any(m.startswith("pfedsop") for m in args.methods):
        ap.error("--update-impl targets the pFedSOP round-start update; none of "
                 f"--methods {args.methods} has a kernel dispatch path "
                 "(DESIGN.md §9), so the flag would be a silent no-op")

    if args.paper_scale:
        args.clients, args.participation, args.rounds = 100, 0.2, 100
        args.samples = 20000

    cfg = SMALL_CNN if args.model == "small" else RESNET9_CIFAR100
    if args.classes is None:
        args.classes = cfg.n_classes
    if args.image_size is None:
        args.image_size = cfg.cnn_image_size
    cfg = cfg.replace(n_classes=args.classes, cnn_image_size=args.image_size)

    print(f"dataset: {args.samples} samples, {args.classes} classes, "
          f"{args.partition} partition across {args.clients} clients")
    images, labels = make_class_conditional_images(
        args.samples, args.classes, args.image_size, seed=args.seed)
    if args.partition == "dirichlet":
        parts = dirichlet_partition(labels, args.clients, args.alpha, seed=args.seed)
    else:
        parts = pathological_partition(labels, args.clients, args.shard_size,
                                       seed=args.seed)
    data = FederatedData.from_partition(images, labels, parts, seed=args.seed)

    loss = lambda p, b: cnn.loss_fn(p, cfg, b)
    acc = masked_accuracy(lambda p, t: cnn.apply(p, cfg, t["images"]))
    params = cnn.init_params(jax.random.PRNGKey(args.seed), cfg)  # same init for all

    if trace_path is not None:
        avail_cfg = TraceAvailabilityConfig(path=trace_path)
    else:
        avail_cfg = AvailabilityConfig(
            speed=args.speed, mean_duration=args.mean_duration,
            sigma=args.speed_sigma, availability=args.availability,
            mean_on=args.mean_on,
        )
    async_cfg = AsyncConfig(
        buffer_size=args.buffer_size, concurrency=args.concurrency,
        availability=avail_cfg,
    )
    run_cfg = FLRunConfig(
        n_clients=args.clients, participation=args.participation,
        rounds=args.rounds, batch=args.batch, seed=args.seed,
        backend=args.backend, shards=args.shards, mesh=args.mesh,
        output_sharding=args.output_sharding, grad_chunks=args.grad_chunks,
        update_impl=args.update_impl,
        ckpt_every=args.ckpt_every,
        async_cfg=async_cfg,
        store=StoreConfig(kind=args.store, cache_clients=args.cache_clients),
    )

    out_dir = Path("experiments/fl")
    out_dir.mkdir(parents=True, exist_ok=True)
    results = {}
    for name in args.methods:
        # --update-impl targets the pFedSOP round-start update; baselines
        # have no kernel dispatch path, so the override stays off for them
        # (an FLRunConfig-level override on a knob-less method is an error).
        cfg_m = run_cfg if name.startswith("pfedsop") else replace(run_cfg, update_impl="")
        if args.ckpt_dir:
            cfg_m = replace(cfg_m, ckpt_dir=str(Path(args.ckpt_dir) / name))
        if args.trace_dir or args.metrics or args.obs_quiet:
            cfg_m = replace(cfg_m, obs=ObsConfig(
                trace_dir=(str(Path(args.trace_dir) / name)
                           if args.trace_dir else ""),
                metrics=args.metrics, level=args.obs_level,
                quiet=args.obs_quiet, xla_profile=args.xla_profile))
        method = build_method(name, args.lr, args)
        if args.mode == "async":
            fed = AsyncFederation(method, loss, acc, params, data, cfg_m)
        else:
            # the sync driver stays availability-oblivious (it samples and
            # waits for stragglers) but uses the same heterogeneity model
            # for its simulated clock, so sim_time is comparable
            model = make_availability(avail_cfg, args.clients, args.seed)
            fed = Federation(method, loss, acc, params, data, cfg_m,
                             availability=model)
        if args.resume and latest_step(cfg_m.ckpt_dir) is not None:
            at = fed.restore()
            fed.obs.log.info(
                f"[{name}] resumed from {cfg_m.ckpt_dir} at round {at}",
                event="resume_notice", method=name, round=int(at))
        hist = fed.run(verbose=True)
        results[name] = hist
        fed.obs.log.info(
            f"--> {name}: mean best acc {hist['mean_best_acc']:.4f}, "
            f"mean round time {np.mean(hist['round_time'][1:]):.2f}s, "
            f"sim wall-clock {hist['sim_time'][-1]:.1f}",
            event="method_summary", method=name,
            mean_best_acc=float(hist["mean_best_acc"]))
        if args.checkpoint_dir:
            save_checkpoint(Path(args.checkpoint_dir) / name, args.rounds,
                            {"broadcast": fed.broadcast},
                            extra={"mean_best_acc": hist["mean_best_acc"]})

    tag = f"{args.tag}_{args.partition}_{args.clients}c_{args.rounds}r"
    payload = {"args": vars(args), "results": results}
    (out_dir / f"{tag}.json").write_text(json.dumps(payload, indent=1))
    print(f"\nwrote experiments/fl/{tag}.json")
    print(f"{'method':>14} {'best_acc':>9} {'final_loss':>11}")
    for name, h in results.items():
        print(f"{name:>14} {h['mean_best_acc']:>9.4f} {h['loss'][-1]:>11.4f}")


if __name__ == "__main__":
    main()
