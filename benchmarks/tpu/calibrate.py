#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, at the cell's
own size, all in one process:

- the program against the reference, on each seed given;
- the control (the reference in bfloat16 in the program's place) on the
  first ``--control`` seeds;
- with ``--highest``, on those seeds, the program run at ``highest``
  matmul precision against the reference at ``highest``: where a gap
  stays, it is not the rounding of the chip's default precision;
- with ``--faults``, faults planted in the program on the first
  ``--control`` seeds: half of each local batch left out (the loss is the
  mean over the other half) and the eval's answers altered (each
  prediction moved to the next class).

  python3 benchmarks/tpu/calibrate.py --workload <cell> --seeds 1 2 3 ... \\
      [--control 3] [--faults] [--highest]

Prints one JSON line per reading; the limits in ``limits/<cell>.json``
lie between the largest program reading and the smallest control or
fault reading (PERF.md gives both).  Not run by the benchmark's runs.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time

import run
from tpubench import check, devices as devs, spec


@contextlib.contextmanager
def half_batch():
    """Local SGD's loss over the first half of each batch only."""
    from repro.models import cnn

    orig = cnn.loss_fn

    def loss(params, cfg, batch):
        half = batch["labels"].shape[0] // 2
        return orig(params, cfg, {k: v[:half] for k, v in batch.items()})

    cnn.loss_fn = loss
    try:
        yield
    finally:
        cnn.loss_fn = orig


@contextlib.contextmanager
def altered_answers():
    """The eval's predictions each moved to the next class."""
    import jax.numpy as jnp
    from repro.fl import runtime

    orig = runtime.masked_accuracy

    def masked_accuracy(apply_fn):
        def acc(params, test):
            logits = apply_fn(params, test)
            pred = (jnp.argmax(logits, -1) + 1) % logits.shape[-1]
            hit = (pred == test["labels"]).astype(jnp.float32)
            return jnp.sum(hit * test["mask"]) / jnp.maximum(jnp.sum(test["mask"]), 1.0)
        return acc

    runtime.masked_accuracy = masked_accuracy
    try:
        yield
    finally:
        runtime.masked_accuracy = orig


FAULTS = {"half_batch": half_batch, "altered_answers": altered_answers}


def leaves(side: dict) -> dict:
    """The per-leaf norms and losses of one side, for a look offline."""
    return {"loss": side["loss"], "acc": side["acc"], "update1": side["update1"],
            "params": side["state3"]["params"], "delta": side["state3"]["delta"]}


def program_side(config, traffic, seed, fault=None, precision=None):
    import jax

    with (FAULTS[fault]() if fault else contextlib.nullcontext()), \
            (jax.default_matmul_precision(precision) if precision
             else contextlib.nullcontext()):
        cell = run.Cell(config, traffic, seed)
        prog = run.warm_up(cell)
    data = (cell.images, cell.labels, cell.parts, cell.weights)
    del cell
    gc.collect()
    return prog, data


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--highest", action="store_true",
                    help="also read the program against the reference, both at highest "
                         "matmul precision")
    args = ap.parse_args()

    bench = spec.load_benchmark()
    wl = spec.workload(bench, args.workload)
    config = spec.load_config(bench, wl["config"])
    traffic = spec.load_traffic(wl["traffic"])

    import jax

    devs.cell_devices(jax.devices(), wl["chips"])
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        prog, (images, labels, parts, weights) = program_side(config, traffic, seed)
        weights = jax.device_get(weights)
        ref = run.reference_readings(config, traffic, seed, images, labels, parts, weights)
        rows = [("program", check.readings(prog, ref))]
        sides = {"program": prog}
        run.log(f"worst update1 leaves: {check.leaf_gaps(prog['update1'], ref['update1'])}")
        if i < args.control and args.highest:
            hi = run.reference_readings(config, traffic, seed, images, labels, parts,
                                        weights, precision="highest")
            prog_hi, _ = program_side(config, traffic, seed, precision="highest")
            rows.append(("highest_vs_highest", check.readings(prog_hi, hi)))
            sides["program_highest"], sides["reference_highest"] = prog_hi, hi
            run.log("worst update1 leaves, both at highest: "
                    f"{check.leaf_gaps(prog_hi['update1'], hi['update1'])}")
            for part in ("params", "delta"):
                run.log(f"worst state3 {part} leaves, both at highest: "
                        f"{check.leaf_gaps(prog_hi['state3'][part], hi['state3'][part])}")
        if i < args.control:
            ctrl = run.reference_readings(config, traffic, seed, images, labels, parts,
                                          weights, dtype="bfloat16")
            rows.append(("control_bf16", check.readings(ctrl, ref)))
            sides["control_bf16"] = ctrl
            for fault in (FAULTS if args.faults else ()):
                bad, _ = program_side(config, traffic, seed, fault)
                rows.append((fault, check.readings(bad, ref)))
                sides[fault] = bad
        for side, numbers in rows:
            print(json.dumps({"cell": wl["name"], "seed": seed, "side": side,
                              **numbers}), flush=True)
        print(json.dumps({"cell": wl["name"], "seed": seed, "side": "leaves",
                          "reference": leaves(ref),
                          **{k: leaves(v) for k, v in sides.items()}}), flush=True)
        run.log(f"seed {seed}: {time.perf_counter() - t0:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
