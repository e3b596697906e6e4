"""Blockwise online-softmax GQA attention - Pallas TPU kernel.

The canonical flash-attention tiling adapted for the assigned archs:

  grid = (B * H, S/BQ, nk)     - the KV block index is the INNERMOST grid
  dimension; TPU executes the grid sequentially per core, so the running
  (m, l, acc) online-softmax state lives in VMEM scratch and persists
  across the KV iterations of one (batch-head, q-block) pair.

  q tile   (BQ, D)  VMEM     k/v tiles (BK, D) VMEM
  scratch: m (BQ,1) l (BQ,1) acc (BQ, D) - all f32.

GQA: query head h reads KV head h // (H/KV) via the k/v BlockSpec index
maps - no KV replication in HBM.  Sliding window + causality are enforced
element-wise inside each tile via broadcasted iota; fully-masked tiles
contribute exp(-inf) = 0.

Window-pruned grid (``prune_window``, default on): with a sliding window
W << S most (q_block, k_block) steps are fully masked, so for windowed
layers the KV grid axis shrinks from nk = S/BK to

  nkp = min(nk, ceil((W + BQ) / BK) + 1)

blocks per q row and the k/v index maps shift to the window: for q block
qi the visited k blocks are max(0, last - nkp + 1) .. last with
last = (qi*BQ + BQ - 1) // BK.  Coverage is exact: every k block holding
a key inside the union of the rows' windows (k in (qi*BQ - W, qi*BQ +
BQ - 1]) lands in that range, earlier blocks are fully outside the
window, and any visited block beyond a row's window is element-masked as
before.  ``flash_gqa_grid`` exposes the resulting (nq, nk_visited) pair -
it is the same computation ``flash_gqa_pallas`` builds its grid from, so
benchmarks/tests assert block-count wins against it directly.

Softcap (gemma2) is applied to the scaled scores before masking, matching
repro/models/attention.py.

D (head_dim) is 64..256 for all assigned archs - lane-aligned; BQ/BK are
multiples of 8 (sublane).  VMEM budget at BQ=BK=512, D=256, f32:
q 512x256x4 = 512 KiB, k+v 1 MiB, acc 512 KiB - comfortably inside 16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _block_sizes(s: int, bq: int, bk: int):
    """Clamp/halve the requested block sizes until they divide S."""
    bq = min(bq, s)
    while s % bq:
        bq //= 2
    bk = min(bk, s)
    while s % bk:
        bk //= 2
    return bq, bk, s // bq, s // bk


def _first_kv_block(qi, bq: int, bk: int, nkp: int):
    """First visited k-block for q block ``qi`` under the pruned grid.

    The single source for the window shift: both the kernel body's mask
    positions and the k/v BlockSpec index maps derive the true k-block
    index as ``_first_kv_block(qi, ...) + j`` — they MUST agree, or the
    element mask would be computed for a different tile than the one the
    BlockSpec loaded.
    """
    last = (qi * bq + bq - 1) // bk
    return jnp.maximum(last - (nkp - 1), 0)


def flash_gqa_grid(s: int, bq: int = 512, bk: int = 512, window=None,
                   prune_window: bool = True):
    """(nq, nk_visited) for the given sequence/window/tiling.

    ``nk_visited`` is the number of KV grid steps each q row actually
    executes — pruned to ceil((W+BQ)/BK)+1 for sliding-window layers when
    ``prune_window`` (the asymptotic win: O(S·W) instead of O(S²) tiles).
    This is the exact grid ``flash_gqa_pallas`` launches.
    """
    bq, bk, nq, nk = _block_sizes(s, bq, bk)
    if window is None or not prune_window:
        return nq, nk
    return nq, min(nk, pl.cdiv(window + bq, bk) + 1)


def flash_gqa_bwd_grid(s: int, bq: int = 512, bk: int = 512, window=None,
                       prune_window: bool = True):
    """Visited block counts of the two backward passes: (nk_dq, nq_dkv).

    The dq pass reuses the forward's (possibly window-pruned) KV grid, so
    ``nk_dq`` equals the forward's ``nk_visited``.  The dk/dv pass sweeps,
    per k-block, the q-blocks that can see it: under a sliding window
    that is min(nq, ceil((W + BK)/BQ) + 1) — the forward's pruning with
    the roles of BQ/BK swapped — and nq otherwise.  These are the exact
    extents ``flash_gqa_bwd_pallas`` launches, so benches/tests assert
    the backward's O(S·W) tile count against it directly.
    """
    bq, bk, nq, nk = _block_sizes(s, bq, bk)
    _, nkp = flash_gqa_grid(s, bq, bk, window, prune_window)
    if window is not None and prune_window:
        nqv = min(nq, pl.cdiv(window + bk, bq) + 1)
    else:
        nqv = nq
    return nkp, nqv


def _mask_block(qi, ki, bq: int, bk: int, window):
    """The (BQ, BK) causal/window element mask for tile (qi, ki) — the one
    mask shared by the forward kernel and both backward passes (positions
    are the canonical arange(S) every model entry point passes)."""
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos <= q_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    return mask


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale: float, window,
                  softcap, bq: int, bk: int, nkp: int, pruned: bool,
                  residual: bool = False):
    if residual:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    j = pl.program_id(2)  # pruned: offset into the visited window blocks

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ki = _first_kv_block(qi, bq, bk, nkp) + j if pruned else j  # true k-block

    q = q_ref[0].astype(jnp.float32) * scale  # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (BK, D)
    s = q @ k.T  # (BQ, BK)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    s = jnp.where(_mask_block(qi, ki, bq, bk, window), s, NEG_INF)

    m_prev = m_scr[...]  # (BQ, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # rows with no valid key yet keep m=NEG_INF; clamp so alpha stays finite
    alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
    p = jnp.exp(s - m_new)  # masked entries: exp(NEG_INF - m) = 0
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = alpha * acc_scr[...] + p @ v_ref[0, 0].astype(jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(j == nkp - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)
        if residual:
            # log-sum-exp per row: the backward passes recompute the
            # normalized probabilities as exp(s - L) in one shot, no
            # second online-softmax sweep.  Causal masking guarantees at
            # least one valid key per row (k = q), so l > 0 always; the
            # where() mirrors the output guard for safety.
            lse_ref[0] = m_scr[...] + jnp.log(jnp.where(l == 0.0, 1.0, l))


def flash_gqa_pallas(q, k, v, window=None, softcap=None, scale=None,
                     bq: int = 512, bk: int = 512, interpret: bool = False,
                     prune_window: bool = True, return_residual: bool = False):
    """q: (B,H,S,D), k/v: (B,KV,S,D) -> (B,H,S,D).  Causal GQA.

    With ``return_residual`` also emits the per-row log-sum-exp
    (B,H,S) f32 — the forward residual the fused backward kernels need
    to recompute probabilities without a second online-softmax sweep.
    Inside the kernels such row vectors carry a trailing unit dim, (B*H,
    S, 1), so each (1, BQ, 1) block is legal TPU tiling (BQ a multiple of
    8, 1 the full minor dim) and is already the (BQ, 1) column the
    kernels broadcast against.
    """
    b, h, s, d = q.shape
    kv = k.shape[1]
    assert h % kv == 0
    g = h // kv
    sc = scale if scale is not None else d**-0.5

    bq, bk, nq, nk = _block_sizes(s, bq, bk)
    _, nkp = flash_gqa_grid(s, bq, bk, window, prune_window)
    pruned = nkp < nk

    qf = q.reshape(b * h, s, d)
    grid = (b * h, nq, nkp)

    if pruned:
        # shift the KV grid axis to the window: blocks last-nkp+1 .. last
        def kv_index(bh, qi, j):
            return (bh // h, (bh % h) // g, _first_kv_block(qi, bq, bk, nkp) + j, 0)
    else:
        def kv_index(bh, qi, j):
            return (bh // h, (bh % h) // g, j, 0)

    kernel = functools.partial(
        _flash_kernel, scale=sc, window=window, softcap=softcap,
        bq=bq, bk=bk, nkp=nkp, pruned=pruned, residual=return_residual,
    )
    out_specs = pl.BlockSpec((1, bq, d), lambda bh, qi, j: (bh, qi, 0))
    out_shape = jax.ShapeDtypeStruct((b * h, s, d), q.dtype)
    if return_residual:
        out_specs = (out_specs,
                     pl.BlockSpec((1, bq, 1), lambda bh, qi, j: (bh, qi, 0)))
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((b * h, s, 1), jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, j: (bh, qi, 0)),
            # GQA: map the flattened batch-head index to (batch, kv head)
            pl.BlockSpec((1, 1, bk, d), kv_index),
            pl.BlockSpec((1, 1, bk, d), kv_index),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, k, v)
    if return_residual:
        out, lse = out
        return out.reshape(b, h, s, d), lse.reshape(b, h, s)
    return out.reshape(b, h, s, d)


# ---------------------------------------------------------------------------
# Fused backward: recompute-p flash backward in two window-pruned passes.
#
# Standard flash-attention backward with the LSE residual: each tile
# recomputes p = exp(s_masked - L) in one shot (no online-softmax sweep),
# then with delta = rowsum(dO * O) the softmax backward collapses to
#
#   dp = dO @ v.T          ds = p * (dp - delta)
#   dq += (ds @ k) * scale dk += ds.T @ (q * scale)   dv += p.T @ dO
#
# (softcap inserts ds *= 1 - tanh^2(s_raw / cap) between ds and the
# dq/dk products, mirroring the forward's tanh).
#
# Two kernels because dq and dk/dv reduce over opposite grid axes:
#   dq pass : grid (B*H,  nq, nkp)     - the forward's own pruned grid,
#             dq accumulates across the visited KV blocks in scratch.
#   dkv pass: grid (B*KV, nk, G*nqv)   - dk/dv accumulate across the G
#             query heads of the group and the nqv q-blocks that can see
#             this k-block.  nqv mirrors the forward's pruning with the
#             roles of BQ/BK swapped: ceil((W + BK) / BQ) + 1 visited
#             q-blocks under a sliding window, nq otherwise.  The first
#             visited q-block (ki*BK)//BQ also prunes the causal lower
#             triangle for free in the full-attention case; the tail
#             past nq-1 is clamped in the index maps and its
#             accumulation guarded out (clamping alone would double
#             count block nq-1).
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, *, scale: float, window, softcap,
                         bq: int, bk: int, nkp: int, pruned: bool):
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    ki = _first_kv_block(qi, bq, bk, nkp) + j if pruned else j

    qs = q_ref[0].astype(jnp.float32) * scale  # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (BK, D)
    s_raw = jnp.dot(qs, k.T, preferred_element_type=jnp.float32)
    if softcap is not None:
        t = jnp.tanh(s_raw / softcap)
        s = softcap * t
    else:
        s = s_raw
    s = jnp.where(_mask_block(qi, ki, bq, bk, window), s, NEG_INF)

    p = jnp.exp(s - lse_ref[0])  # masked entries -> exp(-inf) = 0
    do = do_ref[0].astype(jnp.float32)  # (BQ, D)
    dp = jnp.dot(do, v_ref[0, 0].astype(jnp.float32).T,
                 preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0])
    if softcap is not None:
        ds = ds * (1.0 - t * t)
    dq_scr[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(j == nkp - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                          window, softcap, bq: int, bk: int, nq: int,
                          nqv: int, g: int):
    ki = pl.program_id(1)
    t = pl.program_id(2)  # decomposes to (group head, visited q-block)

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # True q-block for this step; the index maps clamp it to nq-1, the
    # accumulation guard below skips the clamped duplicates.
    qi = (ki * bk) // bq + t % nqv

    qs = q_ref[0].astype(jnp.float32) * scale  # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (BK, D)
    s_raw = jnp.dot(qs, k.T, preferred_element_type=jnp.float32)
    if softcap is not None:
        tc = jnp.tanh(s_raw / softcap)
        s = softcap * tc
    else:
        s = s_raw
    s = jnp.where(_mask_block(qi, ki, bq, bk, window), s, NEG_INF)

    p = jnp.exp(s - lse_ref[0])  # (BQ, BK)
    do = do_ref[0].astype(jnp.float32)  # (BQ, D)
    dp = jnp.dot(do, v_ref[0, 0].astype(jnp.float32).T,
                 preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0])
    if softcap is not None:
        ds = ds * (1.0 - tc * tc)

    @pl.when(qi < nq)
    def _accumulate():
        dk_scr[...] += jnp.dot(ds.T, qs, preferred_element_type=jnp.float32)
        dv_scr[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)

    @pl.when(t == g * nqv - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_gqa_bwd_pallas(q, k, v, out, lse, do, window=None, softcap=None,
                         scale=None, bq: int = 512, bk: int = 512,
                         interpret: bool = False, prune_window: bool = True):
    """Fused flash backward.  Residuals: forward output + per-row LSE.

    q/do/out: (B,H,S,D), k/v: (B,KV,S,D), lse: (B,H,S) f32.
    Returns (dq, dk, dv) in the input dtypes.
    """
    b, h, s, d = q.shape
    kv = k.shape[1]
    assert h % kv == 0
    g = h // kv
    sc = scale if scale is not None else d**-0.5

    bq, bk, nq, nk = _block_sizes(s, bq, bk)
    _, nkp = flash_gqa_grid(s, bq, bk, window, prune_window)
    pruned = nkp < nk

    # delta = rowsum(dO * O): O(S*D) elementwise work, done once outside
    # the kernels so both passes read a precomputed (B*H, S, 1) column.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)

    qf = q.reshape(b * h, s, d)
    dof = do.reshape(b * h, s, d)
    lsef = lse.reshape(b * h, s, 1)
    deltaf = delta.reshape(b * h, s, 1)

    # --- dq pass: the forward's grid, accumulating over KV blocks -------
    if pruned:
        def kv_index(bh, qi, j):
            return (bh // h, (bh % h) // g,
                    _first_kv_block(qi, bq, bk, nkp) + j, 0)
    else:
        def kv_index(bh, qi, j):
            return (bh // h, (bh % h) // g, j, 0)

    def q_index(bh, qi, j):
        return (bh, qi, 0)

    def row_index(bh, qi, j):
        return (bh, qi, 0)

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, scale=sc, window=window, softcap=softcap,
            bq=bq, bk=bk, nkp=nkp, pruned=pruned,
        ),
        grid=(b * h, nq, nkp),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_index),
            pl.BlockSpec((1, 1, bk, d), kv_index),
            pl.BlockSpec((1, 1, bk, d), kv_index),
            pl.BlockSpec((1, bq, d), q_index),
            pl.BlockSpec((1, bq, 1), row_index),
            pl.BlockSpec((1, bq, 1), row_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), q_index),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(qf, k, v, dof, lsef, deltaf)

    # --- dk/dv pass: one pass over K blocks, innermost axis sweeps the ---
    # --- group's query heads x the q-blocks that can see this k-block ----
    _, nqv = flash_gqa_bwd_grid(s, bq, bk, window, prune_window)

    def bwd_q_block(ki, t):
        # clamp: steps past the last q-block load block nq-1; their
        # accumulation is guarded out inside the kernel.
        return jnp.minimum((ki * bk) // bq + t % nqv, nq - 1)

    def bh_index(bkv, t):
        # flattened batch-head for (batch, kv-head, group-member t//nqv)
        return (bkv // kv) * h + (bkv % kv) * g + t // nqv

    def qd_index(bkv, ki, t):
        return (bh_index(bkv, t), bwd_q_block(ki, t), 0)

    def rowd_index(bkv, ki, t):
        return (bh_index(bkv, t), bwd_q_block(ki, t), 0)

    def kvd_index(bkv, ki, t):
        return (bkv // kv, bkv % kv, ki, 0)

    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, scale=sc, window=window, softcap=softcap,
            bq=bq, bk=bk, nq=nq, nqv=nqv, g=g,
        ),
        grid=(b * kv, nk, g * nqv),
        in_specs=[
            pl.BlockSpec((1, bq, d), qd_index),
            pl.BlockSpec((1, 1, bk, d), kvd_index),
            pl.BlockSpec((1, 1, bk, d), kvd_index),
            pl.BlockSpec((1, bq, d), qd_index),
            pl.BlockSpec((1, bq, 1), rowd_index),
            pl.BlockSpec((1, bq, 1), rowd_index),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, bk, d), kvd_index),
            pl.BlockSpec((1, 1, bk, d), kvd_index),
        ),
        out_shape=(
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ),
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, k, v, dof, lsef, deltaf)

    return dq.reshape(b, h, s, d), dk, dv
