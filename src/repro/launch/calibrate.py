import os
# 512 placeholder CPU devices, on the CPU even where a TPU is attached (the
# chip stays free for the process that owns it); the caller's flags are kept
os.environ["XLA_FLAGS"] = " ".join(filter(None, [
    os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=512"]))
os.environ["JAX_PLATFORMS"] = "cpu"

"""Calibrated roofline: exact per-layer unit costs from the compiled
artifact, composed analytically over the loop trip counts.

WHY: XLA's ``cost_analysis()`` counts a ``while``-loop body ONCE regardless
of trip count (verified empirically - scan(n=1) and scan(n=16) report the
same FLOPs).  The production lowering uses scan over (a) the pattern
repetitions, (b) the T local-SGD iterations, (c) attention q-blocks and
(d) SSD chunks, so its raw cost numbers under-report looped work by up to
~TxN_rep (e.g. 384x for musicgen train_4k).

METHOD (two-point unit calibration):
  lower variant A: pattern unrolled ONCE  (tail=pattern, no layer scan),
                   T=1 (length-1 SGD scan), attn_q_block=seq,
                   ssm_chunk=seq  -> every loop has trip count 1, so
                   cost_analysis is exact for this shallow model;
  lower variant B: pattern unrolled TWICE -> per-pattern unit cost =
                   cost(B) - cost(A), exactly (the only difference is one
                   more pattern's worth of compute/bytes/collectives);
  compose:  total = T x [ (A - unit) + unit x n_rep + unit/|pattern| x |tail| ]
  (T multiplies everything because embed/head/grad all sit inside the
  per-iteration body; the once-per-step pFedSOP scalar work is O(3d) and
  negligible - documented overcount.)

The same A/B differencing corrects the collective-byte census.  The HBM
footprint (memory_analysis) is NOT corrected - the production scan
lowering's footprint is the real deployment footprint and is reported
from the baseline artifact.

  PYTHONPATH=src python -m repro.launch.calibrate --all
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import ARCH_NAMES, INPUT_SHAPES, get_config
from repro.launch import steps as st
from repro.launch.roofline import (
    HBM_BW, ICI_BW, PEAK_FLOPS, collective_bytes_from_hlo,
)

ART_DIR = Path(__file__).resolve().parents[3] / "experiments" / "roofline"


def _unrolled_cfg(cfg, shape, n_copies: int, ssm_chunk=None):
    seq = shape.seq_len
    pattern = tuple(cfg.pattern) * n_copies
    chunk = ssm_chunk or seq  # default: single SSD chunk (trip count 1)
    return cfg.replace(
        pattern=(), n_rep=0, tail=pattern,
        n_layers=len(pattern),
        ssm_chunk=chunk,
        # if chunked, unroll the inter-chunk scan so every trip is counted
        ssm_scan_unroll=max(1, seq // chunk),
        attn_q_block=seq,
    )


def _measure(arch, shape_name, n_copies, variant, micro_batch, ssm_chunk=None):
    """Lower one unrolled variant on the single-pod mesh; exact costs."""
    from repro.launch.dryrun import build_lowering  # shares the step builders
    import repro.launch.dryrun as dr

    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]

    # monkey-patch the config the builder sees (keeps one code path);
    # variant flags (moe_dispatch / seqshard) are applied by build_lowering
    ucfg = _unrolled_cfg(cfg, shape, n_copies, ssm_chunk=ssm_chunk)
    orig_get = dr.get_config
    dr.get_config = lambda name: ucfg
    try:
        lowered, meta, mesh = dr.build_lowering(
            arch, shape_name, multi_pod=False, micro_batch=micro_batch,
            variant=variant, t_override=1,
        )
    finally:
        dr.get_config = orig_get
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes_from_hlo(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": sum(v["bytes"] for v in coll.values()),
        "collectives": coll,
    }


def calibrate_one(arch, shape_name, variant="baseline",
                  micro_batch=st.MICRO_BATCH, save=True, verbose=True,
                  ssm_chunk=None, tag_suffix=""):
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    rcfg = st.resolve_cfg(cfg, shape)
    t0 = time.time()
    a = _measure(arch, shape_name, 1, variant, micro_batch, ssm_chunk=ssm_chunk)
    b = _measure(arch, shape_name, 2, variant, micro_batch, ssm_chunk=ssm_chunk)
    t_cal = time.time() - t0

    n_pat = len(rcfg.pattern)
    reps = rcfg.n_rep
    tail_frac = len(rcfg.tail) / max(1, n_pat)
    if shape.kind == "train":
        mb = min(micro_batch, shape.global_batch)
        t_iters = max(1, shape.global_batch // mb)
    else:
        t_iters = 1

    def compose(key):
        unit = b[key] - a[key]
        fixed = a[key] - unit
        return t_iters * (fixed + unit * (reps + tail_frac))

    flops_dev = compose("flops")
    bytes_dev = compose("bytes")
    coll_dev = compose("collective_bytes")

    n_dev = 256
    record = {
        "arch": arch, "shape": shape_name, "mesh": "16x16", "variant": variant,
        "method": "two-point unit calibration (see launch/calibrate.py)",
        "t_iters": t_iters, "n_rep": reps, "pattern_len": n_pat,
        "unit_flops_per_pattern": b["flops"] - a["flops"],
        "fixed_flops": 2 * a["flops"] - b["flops"],
        "per_device": {"flops": flops_dev, "bytes": bytes_dev,
                       "collective_bytes": coll_dev},
        "roofline": {
            "compute_s": flops_dev / PEAK_FLOPS,
            "memory_s": bytes_dev / HBM_BW,
            "collective_s": coll_dev / ICI_BW,
        },
        "total_flops": flops_dev * n_dev,
        "total_bytes": bytes_dev * n_dev,
        "calibrate_s": round(t_cal, 1),
    }
    terms = record["roofline"]
    record["roofline"]["dominant"] = max(
        ("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k]
    ).replace("_s", "")

    if verbose:
        print(f"== {arch} x {shape_name} ({variant}) calibrated in {t_cal:.0f}s ==")
        print(f"   roofline: " + " ".join(
            f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
            for k, v in record["roofline"].items()))
    if save:
        ART_DIR.mkdir(parents=True, exist_ok=True)
        tag = f"{arch}__{shape_name}__16x16"
        if variant != "baseline":
            tag += f"__{variant}"
        if tag_suffix:
            tag += f"__{tag_suffix}"
        (ART_DIR / f"{tag}.json").write_text(json.dumps(record, indent=1))
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_NAMES), default=None)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES), default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--ssm-chunk", type=int, default=None)
    ap.add_argument("--tag-suffix", default="")
    args = ap.parse_args()

    archs = list(ARCH_NAMES) if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    failures = []
    for arch in archs:
        for shape in shapes:
            try:
                calibrate_one(arch, shape, variant=args.variant,
                              ssm_chunk=args.ssm_chunk,
                              tag_suffix=args.tag_suffix)
            except Exception as e:  # noqa: BLE001
                failures.append((arch, shape, repr(e)))
                print(f"!! FAIL {arch} x {shape}: {e}")
                traceback.print_exc()
    if failures:
        print(f"{len(failures)} failures: {failures}")
        raise SystemExit(1)
    print("CALIBRATION COMPLETE")


if __name__ == "__main__":
    main()
