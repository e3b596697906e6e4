"""Fused pFedSOP round-start update - Pallas TPU kernels.

The paper's per-round client step (Algorithm 1) is five elementwise/
reduction sweeps over the d-parameter vectors if done naively:

  3 reductions (dot, ||d_i||^2, ||d_g||^2)  ->  beta (Gompertz)
  1 reduction  (||dp||^2)                   ->  Sherman-Morrison coeff
  2 elementwise (dp = lerp, x -= eta*coeff*dp)

Observation (DESIGN.md §4): ||dp||^2 = (1-b)^2||d_i||^2 + 2b(1-b)<d_i,d_g>
+ b^2||d_g||^2 - a quadratic form of the SAME three scalars, so no fourth
sweep is needed.  The kernels do:

  phase 1 (reduce):  one pass over (d_i, d_g) tiles reducing each tile's
                     three products to three f32 scalars (one 128-lane row
                     per tile); a second, tiny kernel sums those rows in
                     tile order.
  phase 2 (update):  one pass computing x - eta*coeff*((1-b) d_i + b d_g)
                     with (beta, eta*coeff) read from SMEM.

=> 2 HBM sweeps instead of 5.  At d ~ 9B params (gemma2-9b) this is the
difference between ~108 GB and ~270 GB of HBM traffic per round start.

Layout: operands are (C, M, 128) — a leading participating-client axis,
lane-aligned rows — and every grid is (clients, tiles) with (1, rows, 128)
blocks.  ``tile_rows`` picks ``rows`` (a multiple of ``ROW_ALIGN``, so the
block satisfies the TPU's (8, 128) tiling for f32 and bf16's packed
(16, 128) one); callers zero-pad M to a whole number of tiles (ops.py).
The server broadcast delta may be shared — shape (1, M, 128) with a
client-invariant index map — so the global update is read once, not
materialized per client.

Every reduction a client's beta depends on happens inside a kernel body
whose block shapes do not depend on C, so a client's result is the same
whether it shares the launch with 0 or 99 others — the property behind the
bitwise backend parity of DESIGN.md §11 (vmap over K' clients vs. a
shard_map with one client per device).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
ROW_ALIGN = 32
BLOCK_ROWS = 512


def tile_rows(m: int) -> int:
    """Row block for M lane rows: ``BLOCK_ROWS``, or M rounded up to
    ``ROW_ALIGN`` when M is smaller.  Callers pad M to a multiple of it."""
    return min(BLOCK_ROWS, -(-m // ROW_ALIGN) * ROW_ALIGN)


def _dg_index_map(c_global: int):
    """Client index map for the broadcast delta: shared (C_g=1) operands are
    read from the same block for every client; per-client operands follow
    the grid's client index."""
    if c_global == 1:
        return lambda c, i: (0, i, 0)
    return lambda c, i: (c, i, 0)


def _grid(x3d, rows: int):
    """(clients, tiles) for a (C, M, 128) operand of whole row tiles."""
    return x3d.shape[0], x3d.shape[1] // rows


def _tile_sum(v):
    """(rows, 128) -> (1, 1): the row groups folded to (8, 128) with vector
    adds, then the sublanes and lanes reduced."""
    f = v.reshape(-1, SUBLANES, LANES).sum(axis=0)
    return jnp.sum(jnp.sum(f, axis=0, keepdims=True), axis=1, keepdims=True)


def _partials_kernel(di_ref, dg_ref, out_ref):
    di = di_ref[0].astype(jnp.float32)
    dg = dg_ref[0].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    row = jnp.zeros((1, LANES), jnp.float32)
    for k, prod in enumerate((di * dg, di * di, dg * dg)):
        row = jnp.where(lane == k, _tile_sum(prod), row)
    out_ref[0, 0] = row


def reduce3_partials_pallas(di3d, dg3d, rows: int, interpret: bool = False):
    """di3d: (C, M, 128); dg3d: (C, M, 128) or (1, M, 128) shared.

    Returns per-(client, tile) partials (C, T, 1, 128) f32 whose lanes 0-2
    hold the tile's (<d_i,d_g>, ||d_i||^2, ||d_g||^2) and the rest zero;
    ``sum_partials_pallas`` finishes the reduction."""
    c, t = _grid(di3d, rows)
    return pl.pallas_call(
        _partials_kernel,
        grid=(c, t),
        in_specs=[
            pl.BlockSpec((1, rows, LANES), lambda ci, i: (ci, i, 0)),
            pl.BlockSpec((1, rows, LANES), _dg_index_map(dg3d.shape[0])),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, LANES), lambda ci, i: (ci, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((c, t, 1, LANES), jnp.float32),
        interpret=interpret,
    )(di3d, dg3d)


def _sum_partials_kernel(p_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[0] += p_ref[0, 0]


def sum_partials_pallas(partials, interpret: bool = False):
    """(C, T, 1, 128) per-tile partials -> (C, 3) sums, the tiles added in
    tile order inside the kernel."""
    c, t = partials.shape[:2]
    out = pl.pallas_call(
        _sum_partials_kernel,
        grid=(c, t),
        in_specs=[pl.BlockSpec((1, 1, 1, LANES), lambda ci, i: (ci, i, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, LANES), lambda ci, i: (ci, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((c, 1, LANES), jnp.float32),
        interpret=interpret,
    )(partials)
    return out[:, 0, :3]


def _update_kernel(beta_ref, etacoeff_ref, x_ref, di_ref, dg_ref, o_ref):
    c = pl.program_id(0)
    beta = beta_ref[c]
    ec = etacoeff_ref[c]
    di = di_ref[0].astype(jnp.float32)
    dg = dg_ref[0].astype(jnp.float32)
    dp = (1.0 - beta) * di + beta * dg
    o_ref[0] = (x_ref[0].astype(jnp.float32) - ec * dp).astype(o_ref.dtype)


def update_batched_pallas(x3d, di3d, dg3d, beta, eta_coeff, rows: int,
                          interpret: bool = False):
    """x_new[c] = x[c] - eta_coeff[c] * ((1-beta[c]) d_i[c] + beta[c] d_g[c]).

    x3d/di3d: (C, M, 128); dg3d: (C, M, 128) or (1, M, 128) shared;
    beta/eta_coeff: (C,) f32 per-client scalars, passed in SMEM."""
    c, t = _grid(x3d, rows)
    tile = lambda f: pl.BlockSpec((1, rows, LANES), f)
    per_client = lambda ci, i: (ci, i, 0)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _update_kernel,
        grid=(c, t),
        in_specs=[smem, smem, tile(per_client), tile(per_client),
                  tile(_dg_index_map(dg3d.shape[0]))],
        out_specs=tile(per_client),
        out_shape=jax.ShapeDtypeStruct(x3d.shape, x3d.dtype),
        interpret=interpret,
    )(jnp.asarray(beta, jnp.float32).reshape(c),
      jnp.asarray(eta_coeff, jnp.float32).reshape(c), x3d, di3d, dg3d)
