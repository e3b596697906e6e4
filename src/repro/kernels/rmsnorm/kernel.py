"""Fused RMSNorm Pallas TPU kernel.

Tiling: grid over row blocks; each program normalises a (ROWS, D) VMEM tile
(rows = tokens, D = model dim).  The mean-square reduction and the scale
multiply happen in one VMEM pass - one HBM read + one HBM write per
element, vs read(reduce) + read(scale) for the unfused pair.

ROWS is sized so the tile fits comfortably in VMEM: ROWS*D*4B (f32 compute
copy) <= ~4 MiB leaves headroom for the bf16 input/output tiles.  D is the
lane-aligned model dim (all assigned archs have D % 128 == 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (ROWS, D)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * (1.0 + scale_ref[...].astype(jnp.float32))).astype(o_ref.dtype)


def rmsnorm_pallas(x, scale, eps: float = 1e-6, block_rows: int = 256,
                   interpret: bool = False):
    """x: (N, D) (callers flatten leading dims); scale: (D,).

    Row blocks are ``block_rows`` (a multiple of 8), or N rounded up to a
    multiple of 8 when N is smaller; N is zero-padded to whole blocks (a
    zero row normalizes to zero) and the padding sliced off the output.
    """
    n, d = x.shape
    rows = min(block_rows, -(-n // 8) * 8)
    n_pad = -(-n // rows) * rows
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    grid = (n_pad // rows,)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), x.dtype),
        interpret=interpret,
    )(x, scale)
    return out if n_pad == n else out[:n]
