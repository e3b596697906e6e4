#!/usr/bin/env python3
"""On-chip benchmark of the pFedSOP federation: one cell per process.

  python3 benchmarks/tpu/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and traffic mix are named in BENCHMARK.json
and found as files: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``limits/<cell>.json`` and one ``metrics/<metric>.py`` reader per
per-layer metric.  Set-up builds one ``repro.fl.Federation`` from the seed
and drives it through three rounds (round 0 compiles; the later two show
that nothing compiles again); the plain reference later replays those
rounds to decide ``correct``.  The same federation then runs whole rounds
until ``--seconds`` have passed.  With ``--trace 1`` that time is split
into stretches: one under the JAX profiler recording device operations
only, a short one with the host tracer on (for the breakdown of idle
gaps), and one with the program's phase spans on; the per-layer metrics
are read from the first and the last.

The last stdout line is the result's JSON object.  A host without a TPU,
with too few chips or with a chip missing from the peak table, exits 3
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

from tpubench import check, devices as devs, profile, spec, work  # noqa: E402
from tpubench import traffic as traffic_gen  # noqa: E402

WARM_ROUNDS = 3       # rounds the reference replays; round 0 compiles
PHASES = ("gather", "client", "all_gather", "eval", "aggregate", "scatter")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts XLA compilations and persistent-cache hits/misses, from
    ``jax.monitoring`` events, between ``reset`` calls."""

    def __init__(self):
        import jax

        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def reset(self):
        self.compiles, self.compile_s, self.hits, self.misses = 0, 0.0, 0, 0

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __str__(self):
        return (f"compiles={self.compiles} ({self.compile_s:.2f}s) "
                f"cache_hits={self.hits} cache_misses={self.misses}")


def program_model(config: dict):
    """The program's ModelConfig at the sizes the configuration file
    states: the named program config with its channels, image size, input
    channels and classes replaced.  The program's ResNet has one basic
    block per stage and no option for more, so a file that states another
    count is refused."""
    import dataclasses

    mod, attr = config["program_config"].split(":")
    base = getattr(importlib.import_module(mod), attr)
    model = config["model"]
    if model.get("blocks_per_stage", 1) != 1:
        raise spec.SpecError(f"{config['name']} states blocks_per_stage "
                             f"{model['blocks_per_stage']}; the program's ResNet has "
                             "one block per stage")
    return dataclasses.replace(
        base, name=config["name"], cnn_channels=tuple(model["channels"]),
        cnn_image_size=model["image_size"], cnn_in_channels=model["in_channels"],
        n_classes=model["n_classes"])


class Cell:
    """One federation built from the seed, as the window drives it."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core import baselines as bl
        from repro.core.pfedsop import PFedSOPConfig
        from repro.data import FederatedData
        from repro.fl import FLRunConfig, Federation
        from repro.fl.runtime import masked_accuracy
        from repro.models import cnn
        from tpubench.reference import make_weights

        self.config, self.traffic, self.seed = config, traffic, seed
        model_cfg = program_model(config)
        log(f"[{time.perf_counter() - T_START:.2f}s] generating the image bank")
        self.images, self.labels, parts = traffic_gen.generate(config, traffic, seed)
        log(f"[{time.perf_counter() - T_START:.2f}s] building the federation")
        self.parts = [p.copy() for p in parts]     # the program shuffles its own
        data = FederatedData.from_partition(self.images, self.labels, parts,
                                            train_frac=config["data"]["train_frac"],
                                            seed=seed)
        self.weights = make_weights(seed, config["model"])
        m = config["method"]
        method = bl.PFedSOP(cfg=PFedSOPConfig(eta1=m["eta1"], eta2=m["eta2"],
                                              rho=m["rho"], lam=m["lam"]))
        run = traffic["run"]
        self.run_cfg = FLRunConfig(
            n_clients=traffic["clients"], participation=traffic["participation"],
            rounds=1, batch=traffic["batch"], local_iters=traffic["local_iters"],
            seed=seed, backend=run["backend"], mesh=run["mesh"], store=run["store"],
            update_impl=m["update_impl"])
        self.fed = Federation(
            method, lambda p, b: cnn.loss_fn(p, model_cfg, b),
            masked_accuracy(lambda p, t: cnn.apply(p, model_cfg, t["images"])),
            self.weights, data, self.run_cfg)
        self.test_counts = data.test_counts
        log(f"[{time.perf_counter() - T_START:.2f}s] federation built")

    def next_eval_samples(self) -> int:
        """Real test samples of the next round's cohort, read by replaying
        the driver's own draw of client ids on a copy of its RNG."""
        import numpy as np

        rng = np.random.RandomState()
        rng.set_state(self.fed.rng.get_state())
        ids = rng.choice(self.run_cfg.n_clients, self.fed.kprime, replace=False)
        return int(self.test_counts[ids].sum())

    def model_flops(self, rounds: int, eval_samples: int) -> int:
        """Model FLOPs of ``rounds`` rounds whose cohorts held
        ``eval_samples`` real test samples in all."""
        model = self.config["model"]
        return (rounds * work.round_model_flops(model, self.fed.kprime, self.fed.T,
                                                self.run_cfg.batch, 0)
                + work.forward_flops(model) * eval_samples)

    def sync(self):
        import jax

        jax.block_until_ready((self.fed.broadcast, self.fed.store.stacked()))


def warm_up(cell: Cell) -> dict:
    """Rounds 0..2 through the window's own call; returns the program's
    side of the correctness readings."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from tpubench.reference import leaf_items, leaf_norms

    fed = cell.fed
    prog = {"loss": [], "acc": []}
    for r in range(WARM_ROUNDS):
        t0 = time.perf_counter()
        m = fed.run_round()
        prog["loss"].append(m["loss"])
        prog["acc"].append(m["acc"])
        if r == 0:
            prog["update1"] = leaf_norms(fed.broadcast["delta"])
        cell.sync()
        log(f"warm-up round {r}: {time.perf_counter() - t0:.3f}s "
            f"loss={m['loss']!r} acc={m['acc']!r}")
    stack = fed.store.stacked()
    sq = lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    p_norm, d_norm = jax.jit(lambda s, w: (
        jax.tree.map(lambda a, b: sq(a - b[None]), s.params, w),
        jax.tree.map(sq, s.delta)))(stack, cell.weights)
    prog["state3"] = {
        "params": {k: float(v) for k, v in leaf_items(p_norm)},
        "delta": {k: float(v) for k, v in leaf_items(d_norm)},
        "rounds_seen": np.asarray(stack.rounds_seen),
    }
    return prog


def timed_rounds(cell: Cell, seconds: float, annotate: bool = False,
                 on_round=None, label: str = "window"):
    """Whole rounds until ``seconds`` have passed; the last round's
    aggregation and write-back are waited for.  Returns (rounds,
    elapsed s, non-finite rounds, eval samples)."""
    import jax
    import numpy as np

    n, bad, eval_samples, times = 0, 0, 0, []
    t0 = time.perf_counter()
    while True:
        eval_samples += cell.next_eval_samples()
        t_round = time.perf_counter()
        ts = time.time_ns() // 1000
        if annotate:
            with jax.profiler.TraceAnnotation("bench.round"):
                m = cell.fed.run_round()
        else:
            m = cell.fed.run_round()
        times.append(time.perf_counter() - t_round)
        if on_round is not None:
            on_round(ts, times[-1])
        n += 1
        bad += not (np.isfinite(m["loss"]) and np.isfinite(m["acc"]))
        if time.perf_counter() - t0 >= seconds:
            break
    cell.sync()
    elapsed = time.perf_counter() - t0
    times.sort()
    log(f"{label}: {n} rounds in {elapsed:.3f}s; round times: min {times[0]:.4f}s "
        f"median {times[len(times) // 2]:.4f}s max {times[-1]:.4f}s")
    return n, elapsed, bad, eval_samples


# shares of a traced run's --seconds: (P) device trace, (H) host trace, (S) spans
STRETCHES = {"P": 0.45, "H": 0.15, "S": 0.40}


def profiled(cell: Cell, seconds: float, host_level: int, label: str):
    """Whole rounds for ``seconds`` under the JAX profiler, the host tracer
    at ``host_level`` (0: device operations only); returns the trace's
    events and ``timed_rounds``' result."""
    import jax

    logdir = tempfile.mkdtemp(prefix="bench_xplane_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = host_level
    jax.profiler.start_trace(logdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(profile.WINDOW):
        out = timed_rounds(cell, seconds, annotate=host_level > 0, label=label)
    jax.profiler.stop_trace()
    events = profile.load_events(logdir)
    shutil.rmtree(logdir, ignore_errors=True)
    return events, out


def traced_stretches(cell: Cell, seconds: float, chips: int, kind: str):
    """Three stretches of whole rounds; returns the readers' context.

    (P) the profiler records device operations only, since the host
    tracer slows the host's part of every round.  Device busy time,
    kernel time and model FLOPs are read here, over the stretch's own
    length on the host clock.  (H) a short stretch with the host tracer
    on names the device's idle gaps by what the host was doing, for the
    breakdown only.  (S) the program's phase spans, no profiler."""
    from repro.obs import Obs, ObsConfig, read_events

    ctx = {"chips": chips, "peak": devs.PEAKS[kind]}
    inf = float("inf")
    events, (n, elapsed, bad, eval_samples) = profiled(
        cell, seconds * STRETCHES["P"], 0, "stretch P (device trace)")
    ctx.update(events=events, lo=-inf, hi=inf, window_s=elapsed, rounds=n,
               model_flops=cell.model_flops(n, eval_samples),
               busy_s=profile.device_busy_s(events, -inf, inf),
               update_work=work.update_work(
                   cell.fed.kprime // chips, work.param_count(cell.config["model"])))

    host_events, (n_h, _, bad_h, _) = profiled(
        cell, seconds * STRETCHES["H"], 1, "stretch H (host trace)")
    lo, hi = profile.window(host_events)
    ctx["idle_gaps"] = profile.idle_gaps(host_events, lo, hi)
    del host_events

    spandir = tempfile.mkdtemp(prefix="bench_spans_")
    obs = Obs(ObsConfig(trace_dir=spandir, level="phase", quiet=True)).open({})
    cell.fed.obs = cell.fed.programs.obs = obs
    rounds = []
    n_s, _, bad_s, _ = timed_rounds(cell, seconds * STRETCHES["S"],
                                    on_round=lambda ts, d: rounds.append((ts, d)),
                                    label="stretch S (phase spans)")
    obs.close()
    spans = [e for e in read_events(spandir) if e.get("k") == "span"]
    shutil.rmtree(spandir, ignore_errors=True)
    per_round = []
    for ts, dur in rounds:
        end = ts + dur * 1e6
        phase = {p: 0.0 for p in PHASES}
        for e in spans:
            if e["name"] in phase and ts <= e["ts"] < end:
                phase[e["name"]] += e["dur"] / 1e3
        phase["round"] = dur * 1e3
        per_round.append(phase)
    ctx["spans"] = per_round
    ctx["obs_phases"] = sorted({e["name"] for e in spans})
    return ctx, n + n_h + n_s, bad + bad_h + bad_s


def reference_readings(cell_config: dict, traffic: dict, seed: int, images, labels,
                       parts, weights, dtype="float32", precision=None) -> dict:
    """The reference's side of the readings: three rounds replayed from
    the seed, in ``dtype`` at the matmul precision the configuration
    states (``bfloat16``: the control)."""
    import jax
    import jax.numpy as jnp
    from tpubench.reference import ClientSplit, Hyper, ReferenceFederation, leaf_norms

    m = cell_config["method"]
    split = ClientSplit(parts, cell_config["data"]["train_frac"], seed)
    k = traffic["clients"]
    kprime = max(1, int(round(traffic["participation"] * k)))
    iters = traffic["local_iters"] or split.local_iters(traffic["batch"])
    with jax.default_matmul_precision(precision or cell_config["precision"]["conv_matmul"]):
        ref = ReferenceFederation(images, labels, split, weights,
                                  Hyper(m["eta1"], m["eta2"], m["rho"], m["lam"]),
                                  kprime, iters, traffic["batch"], seed,
                                  dtype=jnp.dtype(dtype))
        out = {"loss": [], "acc": []}
        for r in range(WARM_ROUNDS):
            res = ref.run_round()
            out["loss"].append(res["loss"])
            out["acc"].append(res["acc"])
            if r == 0:
                out["update1"] = leaf_norms(ref.global_delta)
        out["state3"] = ref.state_norms(k)
    return out


def run_cell(name: str, config: dict, traffic: dict, limits: dict, seed: int,
             seconds: float, trace: bool, devices, metric_specs, readers=None) -> dict:
    """One run of one cell on ``devices``; returns the result object."""
    import jax

    counter = CompileCounter()
    cell = Cell(config, traffic, seed)
    log(f"cell {name}: K={traffic['clients']} K'={cell.fed.kprime} T={cell.fed.T} "
        f"B={traffic['batch']} engine={cell.fed.engine.describe()} "
        f"store={cell.fed.store.describe()}")
    prog = warm_up(cell)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f}s: {counter}")
    counter.reset()

    metrics = {}
    breakdown = None
    kind = devices[0].device_kind
    if trace:
        ctx, attempted, failed = traced_stretches(cell, seconds, len(devices), kind)
        log(f"obs phases seen: {ctx['obs_phases']}")
        for m in metric_specs:
            value = (readers or {})[m["name"]](ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device_extra = {"busy_s": ctx["busy_s"], "window_s": ctx["window_s"]}
        breakdown = {"device_ops": profile.top_ops(ctx["events"], ctx["lo"], ctx["hi"]),
                     "idle_gaps": ctx["idle_gaps"]}
    else:
        attempted, elapsed, failed, _ = timed_rounds(cell, seconds)
        values = {"rounds_per_s": attempted / elapsed, "setup_s": setup_s}
        for m in metric_specs:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        device_extra = {}
    if counter.compiles:
        log(f"WARNING: {counter.compiles} compilation(s) inside the window: {counter}")
    else:
        log(f"window: {counter}")
    device = {**devs.describe(devices), "memory_peak_bytes": devs.memory_peak_bytes(devices),
              **device_extra}

    # free the program's state before the reference runs
    images, labels, parts, weights = cell.images, cell.labels, cell.parts, cell.weights
    weights = jax.device_get(weights)
    del cell
    gc.collect()
    t0 = time.perf_counter()
    ref = reference_readings(config, traffic, seed, images, labels, parts, weights)
    numbers = check.readings(prog, ref)
    correct, table = check.judge(numbers, limits)
    log(f"reference: {time.perf_counter() - t0:.3f}s; "
        f"program loss {prog['loss']} acc {prog['acc']}; "
        f"reference loss {ref['loss']} acc {ref['acc']}")
    log(f"worst update1 leaves: {check.leaf_gaps(prog['update1'], ref['update1'])}")
    for part in ("params", "delta"):
        log(f"worst state3 {part} leaves: "
            f"{check.leaf_gaps(prog['state3'][part], ref['state3'][part])}")
    for k in check.NUMBERS:
        if k not in table:
            log(f"not compared {k}: {numbers[k]!r}")
    for k, v in table.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    result = {"correct": correct and failed == 0, "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = table
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    bench = spec.load_benchmark()
    wl = spec.workload(bench, args.workload)
    config = spec.load_config(bench, wl["config"])
    traffic = spec.load_traffic(wl["traffic"])
    limits = spec.load_limits(wl["name"])
    metric_specs = spec.cell_metrics(bench, wl["name"], bool(args.trace))
    readers = ({m["name"]: spec.load_reader(m["name"]) for m in metric_specs}
               if args.trace else None)

    import jax

    try:
        devices = devs.cell_devices(jax.devices(), wl["chips"])
    except devs.DeviceError as e:
        log(f"run.py: {e}; not running")
        return 3
    from repro.utils.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    # every program of the cell, small ones too, comes from the cache
    # after the cell's first run in a checkout
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"device: {devs.describe(devices)}, jax {jax.__version__}")
    # numpy's RandomState, which the program and the reference seed, takes
    # 32 bits; a larger seed is folded into them
    seed = args.seed % 2**32
    result = run_cell(wl["name"], config, traffic, limits, seed, args.seconds,
                      bool(args.trace), devices, metric_specs, readers)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
