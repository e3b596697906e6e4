#!/usr/bin/env python3
"""Smoke test of the pFedSOP federation on a TPU.

Everything runs in this one process: a TPU belongs to one process at a
time, so nothing here starts a child that needs the device.

  default     1. kernel parity: each Pallas kernel, compiled for the chip,
                 against its reference impl on seeded inputs at real widths
                 (pfedsop_update at ResNet-9's 1,249,956 params x 20
                 clients; rmsnorm and flash_gqa forward/backward at
                 gemma3-1b widths, windowed and full attention);
              2. the main path: pFedSOP over the paper's ResNet-9 /
                 CIFAR-100 shapes (32x32x3 inputs, 100 classes) on
                 synthetic class-conditional data, Dir(0.07) over K=100
                 clients, 20% participation, batch 50, vmap engine, device
                 cohort store, update_impl="auto" (the compiled kernel),
                 then the same rounds with the reference update.
  --chips 4   only the multi-device engines, each against a one-chip vmap
              run of the same seed: the main path on the (pod, data,
              model) mesh with the model-sharded update and sharded
              outputs (pods:2x1x2), and on the 1-D clients:4 mesh; at
              default matmul precision and at "highest", each held to
              its bounds below.

The last stdout line is one JSON object,
  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}};
off a TPU, or when any check fails, the script exits non-zero and prints
no such line.  Times are printed for information only.

  python3 chip_smoke.py             # one chip
  python3 chip_smoke.py --chips 4   # a four-chip host
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# kernel-vs-reference bounds: f32 kernels differ from the reference only in
# reduction order; bf16 kernels by bf16 rounding of their outputs, so those
# bounds are relative to the largest reference magnitude
F32_ATOL = 1e-5
BF16_RTOL = 2e-2
# federation histories, kernel update vs reference update (and one chip vs
# a mesh): mean client loss within LOSS_RTOL, mean accuracy within
# ACC_ATOL (one flipped test prediction moves it by ~1/(40 * 20)), mean
# Gompertz beta within BETA_ATOL
LOSS_RTOL = 1e-3
ACC_ATOL = 1e-2
BETA_ATOL = 1e-3
# a mesh vs one chip at default matmul precision, where the chip's f32 conv
# rounds its operands to bf16 and another per-device client count flips
# some of those roundings, which local SGD amplifies: absolute bounds on
# loss, accuracy and beta, 2.5-3x the largest differences measured on a
# v5e over 3 rounds (0.0596, 0.0185, 0.0031)
MESH_DEFAULT_BOUNDS = dict(loss=0.15, acc=0.05, beta=0.01)

ROUNDS = 3

# gemma3-1b attention widths (repro.configs.gemma3_1b) at a 4k sequence
ATTN = dict(b=1, s=4096, h=4, kv=1, d=256, window=512)
RMSNORM_SHAPE = (8192, 1152)


class CheckFailed(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise CheckFailed(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def max_abs_diff(a, b) -> float:
    import numpy as np

    return float(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))))


# ---------------------------------------------------------------------------
# 1. kernel parity
# ---------------------------------------------------------------------------


def kernel_cases(seed: int, clients: int, n_params: int, attn: dict,
                 rms_shape):
    """(name, kernel fn, reference fn, args, bound kind, expected launches).

    Each fn maps the args to a tuple of arrays; the kernel fn runs the
    compiled Pallas path.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_gqa.ops import flash_gqa
    from repro.kernels.flash_gqa.ref import flash_gqa_ref
    from repro.kernels.pfedsop_update.ops import pfedsop_update_batched
    from repro.kernels.pfedsop_update.ref import pfedsop_update_batched_ref
    from repro.kernels.rmsnorm.ops import rmsnorm
    from repro.kernels.rmsnorm.ref import rmsnorm_ref

    key = jax.random.PRNGKey(seed)
    rnd = lambda i, shape, dt=jnp.float32, s=1.0: (
        jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * s
    ).astype(dt)
    hp = dict(eta1=0.05, rho=1.0, lam=1.0)

    update_args = (rnd(0, (clients, n_params), s=0.05),
                   rnd(1, (clients, n_params), s=0.01),
                   rnd(2, (n_params,), s=0.01))
    cases = [(
        "pfedsop_update_batched",
        lambda x, di, dg: pfedsop_update_batched(x, di, dg, **hp),
        lambda x, di, dg: pfedsop_update_batched_ref(
            x, di, dg, hp["eta1"], hp["rho"], hp["lam"]),
        update_args, "f32", 3,
    )]

    rms_args = (rnd(3, rms_shape, jnp.bfloat16), rnd(4, rms_shape[-1:], s=0.2))
    cases.append((
        "rmsnorm",
        lambda x, s: (rmsnorm(x, s),),
        lambda x, s: (rmsnorm_ref(x, s),),
        rms_args, "bf16", 1,
    ))

    b, s, h, kv, d = (attn[k] for k in ("b", "s", "h", "kv", "d"))
    qkv = (rnd(5, (b, s, h, d), jnp.bfloat16), rnd(6, (b, s, kv, d), jnp.bfloat16),
           rnd(7, (b, s, kv, d), jnp.bfloat16))
    cot = rnd(8, (b, s, h, d), jnp.bfloat16)

    def ref_attn(window):
        def fn(q, k, v):  # model layout (B,S,H,D) around the (B,H,S,D) oracle
            sw = lambda t: jnp.swapaxes(t, 1, 2)
            with jax.default_matmul_precision("highest"):
                return sw(flash_gqa_ref(sw(q), sw(k), sw(v), window=window))
        return fn

    def kern_attn(window):
        return lambda q, k, v: flash_gqa(q, k, v, window=window)

    def with_grads(attn_fn):
        def fn(q, k, v):
            out, vjp = jax.vjp(attn_fn, q, k, v)
            return (out,) + vjp(cot)
        return fn

    for window in (attn["window"], None):
        tag = f"window={window}" if window else "full"
        cases.append((f"flash_gqa fwd {tag}",
                      lambda q, k, v, w=window: (kern_attn(w)(q, k, v),),
                      lambda q, k, v, w=window: (ref_attn(w)(q, k, v),),
                      qkv, "bf16", 1))
        cases.append((f"flash_gqa fwd+bwd {tag}", with_grads(kern_attn(window)),
                      with_grads(ref_attn(window)), qkv, "bf16", 3))
    return cases


def run_kernel_parity(cases) -> dict:
    import jax

    diffs = {}
    for name, kern, ref, args, kind, launches in cases:
        t0 = time.perf_counter()
        kern_jit = jax.jit(kern)
        text = kern_jit.lower(*args).as_text()
        found = text.count("tpu_custom_call")
        check(found >= launches,
              f"{name}: {found} tpu_custom_call in the lowered program, "
              f"expected >= {launches} (the kernel did not run compiled)")
        got = jax.block_until_ready(kern_jit(*args))
        want = jax.block_until_ready(jax.jit(ref)(*args))
        worst = 0.0
        for g, w in zip(got, want):
            diff = max_abs_diff(g, w)
            if kind == "f32":
                bound = F32_ATOL
            else:
                bound = BF16_RTOL * max(1.0, max_abs_diff(w, 0.0))
            check(diff <= bound, f"{name}: max |kernel - reference| = "
                  f"{diff!r} > bound {bound!r}")
            worst = max(worst, diff / bound)
            diffs.setdefault(name, []).append(diff)
        log(f"kernel {name}: tpu_custom_call x{found}, max abs diff "
            f"{diffs[name]!r} (worst {worst:.3f} of bound), "
            f"{time.perf_counter() - t0:.1f}s incl. compile")
    return diffs


# ---------------------------------------------------------------------------
# 2. the federation
# ---------------------------------------------------------------------------


def make_setup(cfg, seed: int, n_samples: int, n_clients: int, alpha: float):
    """Synthetic class-conditional images at the config's shapes, Dir(alpha)
    over n_clients, and the model's loss/accuracy/init (same for every run)."""
    import jax

    from repro.data import (FederatedData, dirichlet_partition,
                            make_class_conditional_images)
    from repro.fl.runtime import masked_accuracy
    from repro.models import cnn

    images, labels = make_class_conditional_images(
        n_samples, cfg.n_classes, cfg.cnn_image_size, seed=seed)
    parts = dirichlet_partition(labels, n_clients, alpha, seed=seed)
    data = FederatedData.from_partition(images, labels, parts, seed=seed)
    loss = lambda p, b: cnn.loss_fn(p, cfg, b)
    acc = masked_accuracy(lambda p, t: cnn.apply(p, cfg, t["images"]))
    params = cnn.init_params(jax.random.PRNGKey(seed), cfg)
    return data, loss, acc, params


def run_federation(setup, run_cfg, require_kernel: bool, lr: float = 0.05,
                   note: str = ""):
    """Runs pFedSOP through ``Federation``; returns per-round history with
    the mean client beta.  ``require_kernel`` checks that the round's
    client program holds the compiled update kernel; ``note`` is added to
    the printed tag."""
    import jax
    import numpy as np

    from repro.core import baselines as bl
    from repro.core.pfedsop import PFedSOPConfig
    from repro.fl import Federation

    class RecordingFederation(Federation):
        """The stock Federation, also keeping each round's mean client beta."""

        def _observe_client_metrics(self, metrics):
            self.betas.append(float(np.mean(np.asarray(metrics["beta"]))))
            super()._observe_client_metrics(metrics)

    data, loss, acc, params = setup
    method = bl.PFedSOP(cfg=PFedSOPConfig(eta1=lr, eta2=lr))
    fed = RecordingFederation(method, loss, acc, params, data, run_cfg)
    fed.betas = []
    if require_kernel:
        ids = np.arange(fed.kprime)
        gathered = fed.store.gather(
            ids, fed.programs.gather_shardings(fed.kprime, fed._store_struct))
        batches = data.sample_round_batches(np.random.RandomState(0), ids,
                                            fed.T, run_cfg.batch)
        text = fed.programs.client_fn(fed.kprime).lower(
            gathered, fed.broadcast, batches).as_text()
        check("tpu_custom_call" in text,
              "the client program holds no tpu_custom_call: the pFedSOP "
              "update did not resolve to the compiled kernel")
    hist = fed.run()
    hist["beta"] = fed.betas
    tag = " ".join(filter(None, [
        f"{run_cfg.backend}{'/' + run_cfg.mesh if run_cfg.mesh else ''}", note]))
    for t in range(run_cfg.rounds):
        log(f"  [{tag} update_impl={run_cfg.update_impl}] round {t}: "
            f"loss={hist['loss'][t]!r} acc={hist['acc'][t]!r} "
            f"beta={hist['beta'][t]!r} wall={hist['round_time'][t]:.2f}s")
    for key in ("loss", "acc", "beta"):
        check(bool(np.all(np.isfinite(hist[key]))), f"{tag}: non-finite {key}")
    return hist


def tight_bounds(want: dict) -> dict:
    """Absolute bounds on loss, accuracy and beta for histories that may
    differ only by f32 reduction order."""
    return dict(loss=LOSS_RTOL * max(abs(v) for v in want["loss"]),
                acc=ACC_ATOL, beta=BETA_ATOL)


def compare_histories(name: str, got: dict, want: dict, bounds: dict) -> dict:
    """Holds two federation histories to absolute ``bounds`` on loss,
    accuracy and beta; returns the max differences and whether they were
    bitwise equal."""
    out = {key: max_abs_diff(got[key], want[key]) for key in bounds}
    out["bitwise"] = all(got[k] == want[k] for k in bounds)
    log(f"{name}: max |diff| loss={out['loss']!r} acc={out['acc']!r} "
        f"beta={out['beta']!r} bitwise={out['bitwise']} (bounds {bounds!r})")
    for key, bound in bounds.items():
        check(out[key] <= bound, f"{name}: {key} differs by {out[key]!r} > {bound!r}")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4: run only the multi-device engine phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              f"({len(devices)} device(s)); not running", file=sys.stderr)
        return 2

    from repro.utils.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}, jax {jax.__version__}")
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    from repro.configs.resnet_cifar import RESNET9_CIFAR100 as CFG
    from repro.fl import FLRunConfig
    from repro.kernels.dispatch import registered_kernels, resolve_impl

    for kernel in registered_kernels():
        impl = resolve_impl("auto", kernel)
        check(impl == "kernel", f"{kernel}: auto resolved to {impl!r} on the TPU")
    log(f"dispatch: auto -> 'kernel' for {', '.join(registered_kernels())}")

    t0 = time.perf_counter()
    setup = make_setup(CFG, args.seed, n_samples=20_000, n_clients=100, alpha=0.07)
    log(f"data: 20000 synthetic {CFG.cnn_image_size}x{CFG.cnn_image_size}x3 "
        f"images, {CFG.n_classes} classes, Dir(0.07) over 100 clients "
        f"({time.perf_counter() - t0:.1f}s)")
    base = FLRunConfig(n_clients=100, participation=0.2, rounds=ROUNDS,
                       batch=50, seed=args.seed, update_impl="auto")

    if args.chips == 1:
        cases = kernel_cases(args.seed, clients=20, n_params=1_249_956,
                             attn=ATTN, rms_shape=RMSNORM_SHAPE)
        run_kernel_parity(cases)
        log("phase kernels: ok")
        hist_k = run_federation(setup, base, require_kernel=True)
        hist_r = run_federation(setup, replace(base, update_impl="reference"),
                                require_kernel=False)
        compare_histories("federation kernel vs reference update", hist_k, hist_r,
                          tight_bounds(hist_r))
        log("phase federation: ok")
    else:
        # At "highest" the conv is f32 throughout and the meshes differ
        # from one chip only by reduction order; at default precision
        # they are held to the looser MESH_DEFAULT_BOUNDS.
        for precision in ("default", "highest"):
            with jax.default_matmul_precision(precision):
                hist_v = run_federation(setup, base, require_kernel=True,
                                        note=f"precision={precision}")
                for mesh in ("pods:2x1x2", "clients:4"):
                    cfg = replace(base, backend="mesh", mesh=mesh,
                                  output_sharding="sharded")
                    hist_m = run_federation(setup, cfg, require_kernel=True,
                                            note=f"precision={precision}")
                    check(len(hist_m["engine"]["devices"]) == 4,
                          f"mesh {mesh} ran on {hist_m['engine']['devices']}")
                    compare_histories(
                        f"mesh {mesh} vs one-chip vmap, precision={precision}",
                        hist_m, hist_v, tight_bounds(hist_v)
                        if precision == "highest" else MESH_DEFAULT_BOUNDS)
        log("phase four-chip engines: ok")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
