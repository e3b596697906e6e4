"""Public wrapper: fused pFedSOP round-start update.

``pfedsop_update(x, delta_i, delta_g, ...)`` takes flat parameter vectors
(any float dtype) and returns (x_new, beta); it is
``pfedsop_update_batched`` at C=1, the same update with a leading
participating-client axis — (C, N) operands zero-padded to (rows, 128)
tiles, (C,) betas — backed by the (clients, tiles) grid kernels.
``pfedsop_update_batched_sharded`` is the multi-pod layout (DESIGN.md
§11): called inside a mesh-engine
shard_map body, it sweeps only the local model-axis slice of the tile rows
and gathers the per-tile partials of the three Gompertz scalars across
shards — bit-identical to the unsharded batched kernel.  ``pfedsop_update_tree`` is
the pytree convenience for one client.

Call sites: the production path is ``repro.core.pfedsop.personalize``,
which dispatches here when ``PFedSOPConfig.update_impl`` resolves to the
kernel (DESIGN.md §9) — its vmap rule routes the federation engines'
per-client vmap onto ``pfedsop_update_batched``.  Validation lives in
tests/test_kernels.py + tests/test_kernel_dispatch.py (interpret mode) and
``benchmarks/run.py --only pfedsop-update`` times reference vs. kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.pfedsop_update.kernel import (
    LANES,
    reduce3_partials_pallas,
    sum_partials_pallas,
    tile_rows,
    update_batched_pallas,
)
from repro.kernels.pfedsop_update.ref import gompertz_beta
from repro.utils.pytree import tree_flatten_to_vector, tree_unflatten_from_vector


def _tiling(n: int):
    """(rows per tile, tile count) for a flat length-N operand."""
    m = -(-n // LANES)
    rows = tile_rows(m)
    return rows, -(-m // rows)


def _to_tiles(v, rows: int, tiles: int):
    """(C, N) -> (C, tiles*rows, 128) lane-aligned tiles (zero padding)."""
    c, n = v.shape
    return jnp.pad(v, ((0, 0), (0, tiles * rows * LANES - n))).reshape(
        c, tiles * rows, LANES)


def _coeff_from_sums(dot, nl2, ng2, beta, rho):
    """eta-free Sherman-Morrison coefficient from the three reductions.

    ||dp||^2 expands as a quadratic form of (dot, nl2, ng2) — the fusion
    observation of DESIGN.md §4 — so no fourth sweep is needed."""
    sq = (1.0 - beta) ** 2 * nl2 + 2.0 * beta * (1.0 - beta) * dot + beta**2 * ng2
    return 1.0 / rho - sq / (rho**2 + rho * sq)


def _scalars(sums, eta1, rho, lam, eps):
    """(C, 3) sums -> per-client (beta, eta1 * Sherman-Morrison coeff)."""
    dot, nl2, ng2 = sums[:, 0], sums[:, 1], sums[:, 2]
    beta = gompertz_beta(dot, nl2, ng2, lam, eps)  # elementwise -> (C,)
    return beta, eta1 * _coeff_from_sums(dot, nl2, ng2, beta, rho)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pfedsop_update(x, delta_i, delta_g, eta1=0.01, rho=1.0, lam=1.0,
                   eps=1e-12, interpret: bool = False):
    """Flat-vector fused update for one client: the batched kernel at C=1.
    Returns (x_new (N,), beta scalar f32)."""
    out, beta = pfedsop_update_batched(x[None], delta_i[None], delta_g,
                                       eta1=eta1, rho=rho, lam=lam, eps=eps,
                                       interpret=interpret)
    return out[0], beta[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def pfedsop_update_batched(x, delta_i, delta_g, eta1=0.01, rho=1.0, lam=1.0,
                           eps=1e-12, interpret: bool = False):
    """Fused update over a leading participating-client axis.

    x/delta_i: (C, N).  delta_g: (C, N), or (N,) for the usual FL case where
    every client sees the same server broadcast — then the kernel reads one
    shared (1, M, 128) buffer instead of materializing C copies.
    Returns (x_new (C, N), beta (C,) f32).
    """
    if delta_g.ndim == 1:
        delta_g = delta_g[None]
    c, n = x.shape
    rows, tiles = _tiling(n)
    di3d, dg3d, x3d = (_to_tiles(v, rows, tiles) for v in (delta_i, delta_g, x))

    partials = reduce3_partials_pallas(di3d, dg3d, rows, interpret=interpret)
    beta, ec = _scalars(sum_partials_pallas(partials, interpret=interpret),
                        eta1, rho, lam, eps)
    out3d = update_batched_pallas(x3d, di3d, dg3d, beta, ec, rows,
                                  interpret=interpret)
    return out3d.reshape(c, -1)[:, :n], beta


def pfedsop_update_batched_sharded(x, delta_i, delta_g, axis_name: str,
                                   n_shards: int, eta1=0.01, rho=1.0, lam=1.0,
                                   eps=1e-12, interpret: bool = False):
    """Model-sharded batched update: the flattened-N axis over a mesh axis.

    Runs INSIDE a shard_map body whose mesh carries a model-role axis
    ``axis_name`` of size ``n_shards`` (DESIGN.md §11); operands are the
    same replicated (C, N) buffers as ``pfedsop_update_batched``.  Each
    shard sweeps only its contiguous run of tiles:

      1. slice   — tiles are assigned to shards at the UNSHARDED kernel's
                   tile granularity (``_tiling(N)``), zero-padding the
                   tile count up to a multiple of ``n_shards``; shard s
                   takes tiles [s*Tl, (s+1)*Tl).
      2. reduce  — the (clients, local tiles) grid kernel emits per-tile
                   partials for the three Gompertz scalars (<d_i,d_g>,
                   ||d_i||^2, ||d_g||^2 — Eqs. 10-13); an all_gather over
                   ``axis_name`` (tiled, shard order) reconstructs the
                   full per-tile partial array exactly, and the same
                   tile-order sum kernel as the unsharded path reduces it.
      3. scalars — beta (Gompertz, Eq. 14) and the Sherman-Morrison
                   coefficient from the sums, identically on every shard
                   (replicated scalars).
      4. update  — each shard updates its own tile slice and an all_gather
                   over ``axis_name`` reassembles (C, N).

    Bitwise contract: because the tile decomposition, the per-tile partial
    values and the tile-order summation all match the unsharded batched
    kernel, the result is bit-identical to ``pfedsop_update_batched`` on
    the same operands — the anchor of the §11 degenerate-parity guarantee
    (vmap == 1-D shard_map == multi-pod, tests/test_multipod.py).  A psum
    of per-SHARD sums would be cheaper by a few bytes but would
    re-associate the float reduction and break that contract.
    """
    lax = jax.lax

    if delta_g.ndim == 1:
        delta_g = delta_g[None]
    c, n = x.shape
    rows, tiles = _tiling(n)  # the UNSHARDED kernel's tiles (bitwise ref)
    t_loc = -(-tiles // n_shards)  # tiles per shard (ceil)
    di3d, dg3d, x3d = (_to_tiles(v, rows, t_loc * n_shards)
                       for v in (delta_i, delta_g, x))
    idx = lax.axis_index(axis_name)
    sl = lambda a: lax.dynamic_slice_in_dim(a, idx * t_loc * rows,
                                            t_loc * rows, axis=1)
    di_l, dg_l, x_l = sl(di3d), sl(dg3d), sl(x3d)

    part_l = reduce3_partials_pallas(di_l, dg_l, rows, interpret=interpret)
    partials = lax.all_gather(part_l, axis_name, axis=1, tiled=True)[:, :tiles]
    beta, ec = _scalars(sum_partials_pallas(partials, interpret=interpret),
                        eta1, rho, lam, eps)  # (C,) — replicated

    out_l = update_batched_pallas(x_l, di_l, dg_l, beta, ec, rows,
                                  interpret=interpret)
    out = lax.all_gather(out_l, axis_name, axis=1, tiled=True)
    return out.reshape(c, -1)[:, :n], beta


def pfedsop_update_tree(params, delta_i, delta_g, eta1=0.01, rho=1.0, lam=1.0,
                        interpret: bool = False):
    """Pytree convenience wrapper for ONE client (flatten -> kernel ->
    unflatten).  The engine-facing batched path lives in
    ``repro.core.pfedsop`` (flatten-once adapter + vmap dispatch)."""
    xv = tree_flatten_to_vector(params)
    div = tree_flatten_to_vector(delta_i)
    dgv = tree_flatten_to_vector(delta_g)
    new_v, beta = pfedsop_update(xv, div, dgv, eta1=eta1, rho=rho, lam=lam,
                                 interpret=interpret)
    return tree_unflatten_from_vector(new_v, params), beta
