import os
# 512 placeholder CPU devices, on the CPU even where a TPU is attached (the
# chip stays free for the process that owns it); the caller's flags are kept
os.environ["XLA_FLAGS"] = " ".join(filter(None, [
    os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=512"]))
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) combination against the production mesh, with NO device allocation
(ShapeDtypeStruct stand-ins).

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all            # 40 pairs, both meshes
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single

Per combination it records to experiments/dryrun/<arch>__<shape>__<mesh>.json:
  - compiled.memory_analysis()  (bytes/device: proves the config fits HBM)
  - compiled.cost_analysis()    (HLO FLOPs / bytes for §Roofline)
  - the collective-op byte census parsed from the post-SPMD HLO text
  - input/output sharding specs (audit trail)

The environment lines above MUST run before any jax import: jax reads
the platform at import and locks the device count at first
initialisation.  Do not set them globally - smoke tests and benches see
the real single CPU device.
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_NAMES, INPUT_SHAPES, get_config
from repro.fl.engine import MeshBackend
from repro.launch import sharding as sh
from repro.launch import steps as st
from repro.launch.mesh import MeshSpec
from repro.launch.roofline import collective_bytes_from_hlo, roofline_terms

ART_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _strip_model_axis(spec_tree):
    """seqshard variant: layer weights replicated (sequence parallelism
    shards the residual stream instead); embedding keeps its vocab shard."""

    def strip(path, s):
        names = [str(getattr(k, "key", "")) for k in path]
        if "embed" in names or "heads" in names:
            return s
        return P(*[None if ax == "model" else ax for ax in s])

    return jax.tree_util.tree_map_with_path(
        strip, spec_tree, is_leaf=lambda x: isinstance(x, P)
    )


def build_lowering(arch: str, shape_name: str, multi_pod: bool,
                   micro_batch: int = st.MICRO_BATCH, variant: str = "baseline",
                   t_override=None):
    """Lower one (arch, shape, mesh) combination; returns (lowered, meta)."""
    cfg = get_config(arch)
    if variant == "moe_dispatch":
        cfg = cfg.replace(moe_impl="dispatch")
    elif variant == "moe_grouped":
        cfg = cfg.replace(moe_impl="dispatch_grouped")
    elif variant == "seqshard":
        cfg = cfg.replace(seq_shard=True)
    shape = INPUT_SHAPES[shape_name]
    # one mesh code path with the federation engine (DESIGN.md §11): the
    # production MeshSpec resolves through MeshBackend, and the train
    # lowering below routes its client phase + Eq. 13 aggregation through
    # the same engine the FL drivers run
    spec = (MeshSpec.multi_pod(2, 16, 16) if multi_pod
            else MeshSpec.single_pod(16, 16))
    n_clients = spec.client_size if multi_pod else 1
    engine = MeshBackend(n_clients, spec, strict=False,
                         data_chunks=spec.data_size)
    mesh = engine.mesh
    dsize = mesh.shape["data"]
    msize = mesh.shape["model"]
    client_axis = "pod" if multi_pod else None

    if micro_batch == st.MICRO_BATCH:  # CLI default -> per-arch override
        micro_batch = min(micro_batch, cfg.train_micro_batch)
    specs = st.input_specs(cfg, shape, n_clients=n_clients,
                           micro_batch=micro_batch, t_override=t_override)
    rcfg = st.resolve_cfg(cfg, shape)

    donate = ()
    if shape.kind == "train":
        step = st.make_train_step(cfg, shape, engine=engine)
        donate = (0,)  # client state updated in place (params + delta)
        pp = lambda t: sh.param_pspecs(t, msize, client=True, client_axis=client_axis)
        gp = lambda t: sh.param_pspecs(t, msize)
        if variant == "seqshard":
            _pp, _gp = pp, gp
            pp = lambda t: _strip_model_axis(_pp(t))
            gp = lambda t: _strip_model_axis(_gp(t))
        in_shardings = (
            {
                "params": pp(specs["state"]["params"]),
                "delta": pp(specs["state"]["delta"]),
            },
            gp(specs["global_delta"]),
            sh.batch_pspecs(specs["batches"], dsize, batch_axis_index=1,
                            client=True, client_axis=client_axis),
        )
        out_shardings = (in_shardings[0], in_shardings[1], P())
        args = (specs["state"], specs["global_delta"], specs["batches"])
    elif shape.kind == "prefill":
        step = st.make_prefill_step(cfg, shape)
        ppre = sh.param_pspecs(specs["params"], msize, client=True, client_axis=client_axis)
        if variant == "seqshard":
            ppre = _strip_model_axis(ppre)
        in_shardings = (
            ppre,
            sh.batch_pspecs(specs["batch"], dsize, batch_axis_index=0,
                            client=True, client_axis=client_axis),
        )
        out_shardings = P(client_axis)  # last-token logits
        args = (specs["params"], specs["batch"])
    else:  # decode
        step = st.make_serve_step(cfg, shape)
        donate = (3,)  # KV caches / SSM state updated in place
        cache_sh = sh.cache_pspecs(specs["caches"], dsize, msize,
                                   client=True, client_axis=client_axis)
        in_shardings = (
            sh.param_pspecs(specs["params"], msize, client=True, client_axis=client_axis),
            sh.batch_pspecs(specs["batch"], dsize, batch_axis_index=0,
                            client=True, client_axis=client_axis),
            P(),
            cache_sh,
        )
        out_shardings = (P(client_axis), cache_sh)
        args = (specs["params"], specs["batch"], specs["pos"], specs["caches"])

    jitted = jax.jit(
        step,
        in_shardings=_named(mesh, in_shardings),
        out_shardings=_named(mesh, out_shardings),
        # donation = in-place state/cache update on the pod (the deployment
        # semantics; also removes the output-buffer double count from
        # memory_analysis - §Perf iteration 2)
        donate_argnums=donate,
    )
    with mesh:
        lowered = jitted.lower(*args)
    meta = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "variant": variant,
        "n_devices": int(len(mesh.devices.flat)),
        "kind": shape.kind,
        "micro_batch": micro_batch if shape.kind == "train" else None,
        "long_context_mode": rcfg.long_context_mode if shape_name == "long_500k" else None,
        "cfg_name": rcfg.name,
    }
    return lowered, meta, mesh


def run_one(arch: str, shape_name: str, multi_pod: bool, save: bool = True,
            verbose: bool = True, variant: str = "baseline",
            micro_batch: int = st.MICRO_BATCH):
    t0 = time.time()
    lowered, meta, mesh = build_lowering(arch, shape_name, multi_pod,
                                         micro_batch=micro_batch, variant=variant)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # jax < 0.5 returns [per-module dict]
        cost = cost[0] if cost else {}
    coll = collective_bytes_from_hlo(compiled.as_text())

    record = dict(meta)
    record["lower_s"] = round(t_lower, 2)
    record["compile_s"] = round(t_compile, 2)
    record["memory_analysis"] = {
        k: int(getattr(mem, k))
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes")
        if hasattr(mem, k)
    }
    record["cost_analysis"] = {
        k: float(v) for k, v in (cost or {}).items()
        if isinstance(v, (int, float)) and (k in ("flops", "bytes accessed") or k.startswith("bytes accessed"))
    }
    record["collectives"] = coll
    record["roofline"] = roofline_terms(record, n_devices=meta["n_devices"])

    if verbose:
        print(f"== {arch} x {shape_name} [{record['mesh']}] ({variant}) ==")
        print(f"   lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"   memory_analysis: {record['memory_analysis']}")
        print(f"   cost: flops={record['cost_analysis'].get('flops')} "
              f"bytes={record['cost_analysis'].get('bytes accessed')}")
        print(f"   collectives: " + ", ".join(
            f"{k}={v['bytes']:.3e}B x{v['count']}" for k, v in coll.items()) or "none")
        print(f"   roofline: {record['roofline']}")

    if save:
        ART_DIR.mkdir(parents=True, exist_ok=True)
        tag = f"{arch}__{shape_name}__{record['mesh']}"
        if variant != "baseline":
            tag += f"__{variant}"
        (ART_DIR / f"{tag}.json").write_text(json.dumps(record, indent=1))
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_NAMES), default=None)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES), default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--micro-batch", type=int, default=st.MICRO_BATCH)
    args = ap.parse_args()

    archs = list(ARCH_NAMES) if (args.all or args.arch is None) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    run_one(arch, shape, mp, variant=args.variant,
                            micro_batch=args.micro_batch)
                except Exception as e:  # noqa: BLE001 - report, keep sweeping
                    failures.append((arch, shape, mp, repr(e)))
                    print(f"!! FAIL {arch} x {shape} multi_pod={mp}: {e}")
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nALL DRY-RUNS PASSED")


if __name__ == "__main__":
    main()
