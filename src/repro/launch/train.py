"""Production training driver.

Assembles mesh + sharding rules + the pFedSOP round step for an assigned
architecture and runs real rounds on whatever devices exist.  On the CPU
container this runs reduced configs on a 1x1 mesh (functional smoke of the
exact production codepath); on a TPU pod slice the same entrypoint builds
the (data, model) mesh and full config.

  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b --rounds 3 \
      --reduced --seq-len 64 --micro-batch 2 --local-iters 2
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_NAMES, INPUT_SHAPES, get_config
from repro.configs.base import InputShape
from repro.data import lm_batch_iterator, synthetic_lm_stream
from repro.launch import sharding as sh
from repro.launch import steps as st
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import transformer as tf
from repro.obs import ObsConfig, make_obs
from repro.utils.checkpoint import save_checkpoint
from repro.utils.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_NAMES), default="granite-3-2b")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--local-iters", type=int, default=2)
    ap.add_argument("--micro-batch", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 mesh (requires 256 devices)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--output-sharding", choices=["replicated", "sharded"],
                    default="replicated",
                    help="round-step lowering (DESIGN.md §11): 'sharded' "
                         "routes the client phase + Eq. 13 aggregation "
                         "through the federation MeshBackend engine, so "
                         "client-state outputs stay sharded at rest on a "
                         "client-axis (pods) mesh; 'replicated' keeps the "
                         "plain vmap lowering.  Identical numerics — the "
                         "two share the canonical cohort_mean reduction")
    ap.add_argument("--kernel-impl", default="auto",
                    choices=["auto", "reference", "kernel", "kernel_interpret"],
                    help="model-zoo kernel policy (rmsnorm/flash_gqa, "
                         "DESIGN.md §9); auto = kernel on TPU")
    ap.add_argument("--trace-dir", default="",
                    help="structured round trace + Perfetto trace.json export "
                         "(DESIGN.md §13)")
    ap.add_argument("--metrics", default="",
                    help="metrics.jsonl path ('' = <trace-dir>/metrics.jsonl)")
    ap.add_argument("--obs-level", choices=["off", "round", "phase"],
                    default="phase")
    ap.add_argument("--xla-profile", type=int, default=-1,
                    help="round index to wrap in a jax.profiler capture "
                         "under <trace-dir>/xla (-1 = off)")
    ap.add_argument("--obs-quiet", action="store_true",
                    help="suppress stdout progress lines (records still trace)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.xla_profile >= 0 and not args.trace_dir:
        ap.error("--xla-profile requires --trace-dir")

    cfg = get_config(args.arch, reduced=args.reduced)
    cfg = cfg.replace(kernel_impl=args.kernel_impl)
    if cfg.frontend != "none":
        raise SystemExit("text archs only in this driver")
    mesh = make_production_mesh() if args.production_mesh else make_host_mesh()
    dsize, msize = mesh.shape["data"], mesh.shape["model"]
    obs = make_obs(ObsConfig(
        trace_dir=args.trace_dir, metrics=args.metrics, level=args.obs_level,
        quiet=args.obs_quiet, xla_profile=args.xla_profile,
    ) if (args.trace_dir or args.metrics or args.obs_quiet) else None)
    obs.open(fingerprint={
        "driver": "launch", "arch": cfg.name, "mesh": dict(mesh.shape),
        "seed": args.seed, "kernel_impl": args.kernel_impl,
        "seq_len": args.seq_len, "micro_batch": args.micro_batch,
        "local_iters": args.local_iters,
    })
    obs.log.info(f"mesh {dict(mesh.shape)}, arch {cfg.name}",
                 event="run_start", mesh=dict(mesh.shape), arch=cfg.name)

    shape = InputShape("custom", args.seq_len, args.micro_batch * args.local_iters, "train")
    if args.output_sharding == "sharded":
        from repro.fl.engine import MeshBackend
        from repro.launch.mesh import MeshSpec

        spec = (MeshSpec.single_pod(16, 16) if args.production_mesh
                else MeshSpec.host())
        engine = MeshBackend(1, spec, strict=False, data_chunks=dsize)
        step = st.make_train_step(cfg, shape, engine=engine)
    else:
        step = st.make_train_step(cfg, shape)

    params = tf.init_params(jax.random.PRNGKey(args.seed), cfg)
    zeros = jax.tree.map(jnp.zeros_like, params)
    state = jax.tree.map(lambda x: x[None], {"params": params, "delta": zeros})
    global_delta = zeros

    pspec = sh.param_pspecs(state["params"], msize, client=True)
    in_sh = (
        {"params": pspec, "delta": pspec},
        sh.param_pspecs(global_delta, msize),
        None,
    )
    named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                   is_leaf=lambda x: isinstance(x, P))
    jit_step = jax.jit(step, in_shardings=(named(in_sh[0]), named(in_sh[1]), None))

    stream = synthetic_lm_stream(50_000, cfg.vocab_size, seed=args.seed)
    it = lm_batch_iterator(stream, args.micro_batch, args.seq_len, seed=args.seed)

    with mesh:
        for r in range(args.rounds):
            t0 = time.perf_counter()
            obs.xla_round_start(r)
            with obs.span("round", round=r):
                bs = [next(it) for _ in range(args.local_iters)]
                batches = jax.tree.map(lambda *xs: jnp.stack(xs)[None], *bs)  # (1,T,b,S)
                state, global_delta, loss = obs.timed(
                    "train_step", jit_step, state, global_delta, batches,
                    round=r)
            obs.xla_round_end(r)
            dt = time.perf_counter() - t0
            obs.log.info(f"round {r} loss={float(loss):.4f} ({dt:.1f}s)",
                         event="round", round=r, loss=float(loss),
                         round_time=dt)
            if obs.metrics is not None:
                obs.metrics.gauge("train.loss").set(float(loss))
                obs.metrics.gauge("train.round_time").set(dt)
                obs.flush_metrics(step=r)
            obs.flush()
            if args.checkpoint_dir:
                save_checkpoint(args.checkpoint_dir, r, state)
    obs.close()
    assert np.isfinite(float(loss))
    print("OK")


if __name__ == "__main__":
    main()
