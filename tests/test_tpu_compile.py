"""Ahead-of-time TPU v5e compiles of the Pallas kernels at real widths.

Interpret mode (the rest of the suite) cannot see what the chip's compiler
refuses: block shapes off the (8, 128) tiling, scalars stored to VMEM, more
fast memory than a kernel may use.  These tests compile each kernel of the
main path for a described ``v5e:2x2`` topology — nothing runs, so they say
nothing about results or times — and check that the compiled program holds
the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler library, and under pytest-xdist every
worker imports this file while only the worker that runs it loads the
library.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.fl.cohort_store import store_gather, store_scatter
from repro.kernels.flash_gqa.ops import flash_gqa
from repro.kernels.pfedsop_update.ops import (
    pfedsop_update_batched,
    pfedsop_update_batched_sharded,
)
from repro.kernels.rmsnorm.ops import rmsnorm

RESNET9_PARAMS = 1_249_956  # RESNET9_CIFAR100
RESNET18_PARAMS = 4_901_450  # RESNET18_CIFAR10
COHORT = 20  # K' = 20% of K = 100 (paper Sec. V-B4)
# the cross-device cell's client stack: K=125, K'=10, its largest leaf
STORE_K, STORE_COHORT, STORE_LEAF = 125, 10, (3, 3, 512, 512)
# gemma3-1b attention (repro.configs.gemma3_1b) at a 4k sequence
B, S, H, KV, D, WINDOW = 1, 4096, 4, 1, 256, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n", [RESNET9_PARAMS, RESNET18_PARAMS])
def test_pfedsop_update_batched_compiles(one_chip, n):
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    text = _compiled_text(pfedsop_update_batched, f32(COHORT, n), f32(COHORT, n), f32(n))
    assert text.count("tpu_custom_call") >= 3  # partials, tile sums, update


def test_pfedsop_update_model_sharded_compiles(topo):
    """The (pod, data, model) mesh's model-sharded update on a 2-chip
    sub-mesh: per-shard tile sweep, all_gather of partials and slices."""
    mesh = Mesh(np.array(topo.devices[:2]), ("model",),
                axis_types=(jax.sharding.AxisType.Auto,))
    rep = NamedSharding(mesh, P())
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=rep)

    def fn(x, di, dg):
        return jax.shard_map(
            lambda x, di, dg: pfedsop_update_batched_sharded(x, di, dg, "model", 2),
            mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
            check_vma=False)(x, di, dg)

    n = RESNET9_PARAMS
    text = _compiled_text(fn, f32(COHORT // 2, n), f32(COHORT // 2, n), f32(n))
    assert text.count("tpu_custom_call") >= 3
    assert "all-gather" in text


def test_rmsnorm_compiles(one_chip):
    x = jax.ShapeDtypeStruct((8192, 1152), jnp.bfloat16, sharding=one_chip)
    s = jax.ShapeDtypeStruct((1152,), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(rmsnorm, x, s)


@pytest.mark.parametrize("window", [WINDOW, None], ids=["windowed", "full"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_gqa_compiles(one_chip, window, grad):
    bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    attn = lambda q, k, v: flash_gqa(q, k, v, window=window, bwd="kernel")
    if grad:
        fn = jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32)),
                      argnums=(0, 1, 2))
    else:
        fn = attn
    text = _compiled_text(fn, bf16(B, S, H, D), bf16(B, S, KV, D), bf16(B, S, KV, D))
    # forward kernel; with grad the residual forward + dq and dk/dv passes
    assert text.count("tpu_custom_call") >= (3 if grad else 1)


def _instructions(text, computation="ENTRY"):
    """(result type, opcode) of every instruction of one computation of a
    compiled module's text."""
    out, inside = [], False
    for line in text.splitlines():
        if not inside:
            inside = line.startswith(computation)
            continue
        if line.startswith("}"):
            break
        m = re.match(r"\s*(?:ROOT\s+)?%[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(", line)
        if m:
            out.append(m.groups())
    return out


def _store_shapes(sharding):
    """A stack (K, ...) and cohort (K', ...) of one big f32 leaf and a
    scalar-per-client int32 leaf, with the cohort's ids."""
    def tree(n):
        return {"w": jax.ShapeDtypeStruct((n,) + STORE_LEAF, jnp.float32, sharding=sharding),
                "seen": jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sharding)}
    ids = jax.ShapeDtypeStruct((STORE_COHORT,), jnp.int32, sharding=sharding)
    return tree(STORE_K), tree(STORE_COHORT), ids


def test_store_scatter_writes_in_place(one_chip):
    """The device store's write-back aliases the donated stack and copies
    none of it."""
    full, new, ids = _store_shapes(one_chip)
    text = store_scatter.lower(full, ids, new).compile().as_text()
    header = text.splitlines()[0]
    assert "input_output_alias" in header
    assert header.count("-alias)") == len(jax.tree.leaves(full))
    stack = f"f32[{STORE_K},"
    copies = [line for line in text.splitlines()
              if re.search(r" copy(-start)?\(", line) and stack in line.split(" copy")[0]]
    assert not copies, copies


def test_store_gather_reads_only_the_cohort_rows(one_chip):
    """The device store's gather holds no stack-sized operation but its
    parameter and the loop that reads rows from it (an XLA gather first
    rewrites its whole operand)."""
    full, _, ids = _store_shapes(one_chip)
    text = store_gather.lower(full, ids).compile().as_text()
    stack = f"f32[{STORE_K},"
    ops = [op for shape, op in _instructions(text) if stack in shape]
    assert "parameter" in ops
    assert set(ops) <= {"parameter", "tuple", "while", "get-tuple-element"}, ops
