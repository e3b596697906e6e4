"""pFedSOP: personalized federated learning with second-order optimization.

The paper's contribution, as pure-JAX pytree math (Sen & Mohan, 2025;
abstract and equation numbering in PAPER.md):

per client i at round t
  1. beta   = gompertz(angle(delta_i(t-1), delta(t-1)))          (Eq. 14)
  2. dp     = (1-beta) * delta_i + beta * delta                  (Eq. 15)
  3. step   = [dp dp^T + rho I]^{-1} dp   via Sherman-Morrison   (Eq. 18)
  4. x_it   = x_i(t-1) - eta1 * step                             (Eq. 19)
  5. T-step local SGD from x_it; delta_it = (x0 - xT)/eta2       (Eq. 11)
server
  6. delta_t = mean_i delta_it                                   (Eq. 13)

This module is pure math for ONE client; the federation-facing adapter
(``repro.core.baselines.PFedSOP``) wraps it in the ``FLMethod`` interface
documented on ``repro.core.baselines.FLMethod``, and the engine backends
in ``repro.fl.engine`` run it across clients (DESIGN.md §2/§3).

Everything operates on *pytrees* of parameters so the same code serves the
paper-faithful CNN reproduction, the 10 assigned transformer-family
architectures, and the sharded multi-pod deployment (the scalar reductions
become cross-`model`-shard psums under pjit; see launch/steps.py).

The rank-1 + identity structure of the regularized FIM collapses the
Sherman-Morrison step to a scalar rescale:

  F^{-1} dp = dp/rho - dp ||dp||^2 / (rho^2 + rho ||dp||^2)
            = dp / (rho + ||dp||^2)

We implement the explicit Sherman-Morrison expression (left) — faithful to
the paper's Algorithm 1 line 5 — and verify the algebraic collapse (right)
and the dense matrix-inverse oracle agreement in tests/test_pfedsop_math.py.

The round-start update (steps 1-4 above) has two interchangeable
implementations selected by ``PFedSOPConfig.update_impl`` (DESIGN.md §9):
the per-leaf pytree math in this module (the reference), and the fused
Pallas kernel (``repro.kernels.pfedsop_update``) reached through a
flatten-once adapter whose ``jax.custom_batching.custom_vmap`` rule turns
the engines' per-client vmap into ONE batched (clients, N) kernel launch
per round.  Both impls agree within fp32 reduction-order tolerance
(tests/test_kernel_dispatch.py).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import resolve_update_impl
from repro.utils.pytree import (
    tree_dot,
    tree_flatten_to_vector,
    tree_lerp,
    tree_scale,
    tree_sqnorm,
    tree_sub,
    tree_unflatten_from_vector,
    tree_where,
    tree_zeros_like,
)

Pytree = Any


@dataclass(frozen=True)
class PFedSOPConfig:
    """Hyperparameters (paper Sec. V-B4: rho=1, lambda=1, batch 50, 1 epoch)."""

    eta1: float = 0.01  # personalization learning rate (Eq. 19)
    eta2: float = 0.01  # local-SGD learning rate (Eq. 10)
    rho: float = 1.0  # FIM regularization (Eq. 17)
    lam: float = 1.0  # Gompertz steepness (Eq. 14)
    local_iters: int = 0  # T; 0 = derive from data (one epoch)
    use_pc: bool = True  # personalization component (ablation Table III)
    eps: float = 1e-12  # cosine-similarity guard
    # async aggregation only (DESIGN.md §10): exponent of the polynomial
    # staleness discount composed with the Gompertz weight in stale_blend;
    # irrelevant to the synchronous driver (staleness is identically zero).
    staleness_exp: float = 0.5
    # round-start update implementation (repro.kernels.dispatch, DESIGN.md
    # §9): "auto" = fused Pallas kernel on TPU, pytree reference elsewhere;
    # "reference" / "kernel" / "kernel_interpret" force one path.
    update_impl: str = "auto"


class ClientState(NamedTuple):
    """Per-client persistent state.

    A pytree, so a K-client federation is one ClientState with a leading
    client axis on every leaf (vmap-able simulation backend) or one
    ClientState per pod (distributed backend).
    """

    params: Pytree  # personalized model x_i
    delta: Pytree  # latest local gradient update Delta_i
    has_delta: jnp.ndarray  # bool scalar: False for new clients
    rounds_seen: jnp.ndarray  # int32 scalar (diagnostics)


def init_client_state(params: Pytree) -> ClientState:
    return ClientState(
        params=params,
        delta=tree_zeros_like(params),
        has_delta=jnp.asarray(False),
        rounds_seen=jnp.asarray(0, jnp.int32),
    )


# ---------------------------------------------------------------------------
# Personalized aggregation (Algorithm 1 lines 1-4)
# ---------------------------------------------------------------------------


def gompertz_weight(local_delta: Pytree, global_delta: Pytree, lam, eps=1e-12):
    """Aggregation weight beta from the Gompertz-normalized angle.

    Returns (beta, aux) where aux carries the intermediate scalars for
    diagnostics.  All reductions are f32.  Zero-norm guard: if either update
    is (numerically) zero the angle is undefined; we fall back to theta=pi/2
    ("no information"), matching the paper's neutral-trust reading.
    """
    dot = tree_dot(local_delta, global_delta)
    nl2 = tree_sqnorm(local_delta)
    ng2 = tree_sqnorm(global_delta)
    denom = jnp.sqrt(nl2) * jnp.sqrt(ng2)
    ok = denom > eps
    sim = jnp.where(ok, dot / jnp.where(ok, denom, 1.0), 0.0)
    sim = jnp.clip(sim, -1.0, 1.0)
    theta = jnp.arccos(sim)  # [0, pi]
    beta = 1.0 - jnp.exp(-jnp.exp(-lam * (theta - 1.0)))  # Eq. 14
    return beta, {"sim": sim, "theta": theta, "beta": beta, "dot": dot,
                  "local_sqnorm": nl2, "global_sqnorm": ng2}


def personalized_delta(local_delta, global_delta, lam, eps=1e-12):
    """Eq. 15: dp = (1-beta) * delta_i + beta * delta."""
    beta, aux = gompertz_weight(local_delta, global_delta, lam, eps)
    return tree_lerp(beta, local_delta, global_delta), aux


def theta_from_beta(beta, lam):
    """Invert Eq. 14 to recover the angle theta from a recorded beta.

    Host-side numpy, for diagnostics only (the observability layer's
    per-round theta histograms, DESIGN.md §13): the client programs
    materialize beta in their metrics, and

        theta = 1 - ln(-ln(1 - beta)) / lam

    maps it back.  beta is clipped away from {0, 1} (where the double
    exponential saturates) and the result to Eq. 14's domain [0, pi].
    """
    import numpy as np

    b = np.clip(np.asarray(beta, np.float64), 1e-9, 1.0 - 1e-9)
    theta = 1.0 - np.log(-np.log1p(-b)) / float(lam)
    return np.clip(theta, 0.0, np.pi)


# ---------------------------------------------------------------------------
# Sherman-Morrison second-order step (Algorithm 1 line 5, Eq. 18)
# ---------------------------------------------------------------------------


def sherman_morrison_step(delta_p: Pytree, rho):
    """F^{-1} dp for F = dp dp^T + rho I, via Sherman-Morrison (Eq. 18).

    step = dp/rho - dp * ||dp||^2 / (rho^2 + rho ||dp||^2)

    Equivalent to dp / (rho + ||dp||^2); the explicit two-term form is kept
    to mirror the paper (tests assert the identity).
    """
    sq = tree_sqnorm(delta_p)  # dp^T dp, f32
    coeff = 1.0 / rho - sq / (rho**2 + rho * sq)
    return tree_scale(coeff, delta_p)


@functools.lru_cache(maxsize=None)
def _fused_flat_update(eta1, rho, lam, eps, interpret, shard=None):
    """Flat-vector fused update with a custom vmap rule (cached per-config).

    The primal runs the single-client kernel; the vmap rule — fired by the
    engines' per-client ``jax.vmap`` (also inside the mesh engines'
    shard_map body, where it sees each shard's local client slice) —
    dispatches the whole batch to the (clients, N) grid kernel in one
    launch.  An unbatched global delta (the usual replicated server
    broadcast) is passed through as (N,) so the kernel reads one shared
    buffer instead of materializing C copies.

    ``shard`` is the ``(model_axis_name, n_shards)`` announced by a mesh
    engine whose mesh carries a model-role axis
    (``repro.kernels.dispatch.model_shard_axis``, DESIGN.md §11): both the
    primal and the batched rule then take the model-sharded kernel layout,
    which splits the flattened-N tile rows over the mesh axis and combines
    the three Gompertz scalars' per-tile partials with a cross-shard
    all-gather — bit-identical to the unsharded kernel.
    """
    from repro.kernels.pfedsop_update.ops import (
        pfedsop_update,
        pfedsop_update_batched,
        pfedsop_update_batched_sharded,
    )

    if shard:
        axis_name, n_shards = shard

        def _batched(x, di, dg):
            return pfedsop_update_batched_sharded(
                x, di, dg, axis_name, n_shards, eta1=eta1, rho=rho, lam=lam,
                eps=eps, interpret=interpret)
    else:

        def _batched(x, di, dg):
            return pfedsop_update_batched(x, di, dg, eta1=eta1, rho=rho,
                                          lam=lam, eps=eps,
                                          interpret=interpret)

    @jax.custom_batching.custom_vmap
    def fused(x, di, dg):
        if shard:  # unvmapped single client: the batched layout with C=1
            out, beta = _batched(x[None], di[None], dg)
            return out[0], beta[0]
        return pfedsop_update(x, di, dg, eta1=eta1, rho=rho, lam=lam,
                              eps=eps, interpret=interpret)

    @fused.def_vmap
    def _batched_rule(axis_size, in_batched, x, di, dg):
        x_b, di_b, _ = in_batched
        if not x_b:
            x = jnp.broadcast_to(x, (axis_size,) + x.shape)
        if not di_b:
            di = jnp.broadcast_to(di, (axis_size,) + di.shape)
        out, beta = _batched(x, di, dg)
        return (out, beta), (True, True)

    return fused


def _personalize_fused(params, local_delta, global_delta, cfg, interpret):
    """Kernel-impl personalize: flatten once, one fused call, unflatten once.

    The f32 flat vectors concatenate all leaves, so the three reductions
    run over the whole model in one tiled pass (vs. per-leaf partial sums
    in the reference) — numerically equal up to fp32 reduction order.
    ``aux`` carries only beta; the reference path's extra diagnostics
    (sim/theta/...) would need a third sweep the fusion exists to avoid.
    The model-shard context (set by a §11 mesh engine around body tracing)
    is read host-side here, so the sharded layout is baked into the trace.
    """
    from repro.kernels.dispatch import current_model_shard, kernel_scope

    xv = tree_flatten_to_vector(params)
    div = tree_flatten_to_vector(local_delta)
    dgv = tree_flatten_to_vector(global_delta)
    fused = _fused_flat_update(cfg.eta1, cfg.rho, cfg.lam, cfg.eps, interpret,
                               shard=current_model_shard())
    with kernel_scope("pfedsop_update",
                      "kernel_interpret" if interpret else "kernel"):
        new_v, beta = fused(xv, div, dgv)
    return tree_unflatten_from_vector(new_v, params), {"beta": beta}


def personalize(
    params: Pytree,
    local_delta: Pytree,
    global_delta: Pytree,
    cfg: PFedSOPConfig,
):
    """Algorithm 1: returns (x_it, aux) from (x_i(t-1), Delta_i, Delta).

    Dispatches on ``cfg.update_impl`` (resolved host-side, so the choice is
    baked into the trace): the fused Pallas kernel covers the personalized
    blend + Sherman-Morrison step; the no-PC ablation removes the blend the
    kernel fuses, so it always runs the reference pytree path.
    """
    impl = resolve_update_impl(cfg.update_impl)
    if cfg.use_pc and impl != "reference":
        return _personalize_fused(params, local_delta, global_delta, cfg,
                                  interpret=impl == "kernel_interpret")
    if cfg.use_pc:
        dp, aux = personalized_delta(local_delta, global_delta, cfg.lam, cfg.eps)
    else:
        # ablation: no personalization component -> use the global update
        dp, aux = global_delta, {"beta": jnp.float32(1.0)}
    step = sherman_morrison_step(dp, cfg.rho)
    new_params = jax.tree.map(
        lambda x, s: (x.astype(jnp.float32) - cfg.eta1 * s.astype(jnp.float32)).astype(x.dtype),
        params,
        step,
    )
    return new_params, aux


# ---------------------------------------------------------------------------
# Local training (Algorithm 2)
# ---------------------------------------------------------------------------


def local_sgd_delta(
    loss_fn: Callable[[Pytree, Any], jnp.ndarray],
    params: Pytree,
    batches: Any,  # pytree with leading axis T (local iterations)
    eta2: float,
):
    """T iterations of SGD; returns (delta_i, final_params, mean_loss).

    delta_i = (x0 - xT)/eta2 = sum of the per-iteration stochastic gradients
    (Eq. 11/12 — verified by test against an explicit gradient sum).

    The per-step gradient dispatches through ``chunked_value_and_grad``
    (DESIGN.md §11): plain ``jax.value_and_grad`` at the default
    ``grad_chunks = 1``, the canonical chunk-tree reduction otherwise —
    including the data-axis-sharded layout inside a mesh engine body.
    """
    from repro.optim.sgd import chunked_value_and_grad

    grad_fn = chunked_value_and_grad(loss_fn)

    def step(p, batch):
        loss, g = grad_fn(p, batch)
        p = jax.tree.map(
            lambda x, gi: (x.astype(jnp.float32) - eta2 * gi.astype(jnp.float32)).astype(x.dtype),
            p,
            g,
        )
        return p, loss

    final, losses = jax.lax.scan(step, params, batches)
    delta = tree_scale(1.0 / eta2, tree_sub(params, final))
    return delta, final, jnp.mean(losses)


# ---------------------------------------------------------------------------
# Full client round (Algorithm 3 lines 4-11) and server aggregation
# ---------------------------------------------------------------------------


def client_round(
    loss_fn: Callable[[Pytree, Any], jnp.ndarray],
    state: ClientState,
    global_delta: Pytree,
    global_has_delta: jnp.ndarray,
    batches: Any,
    cfg: PFedSOPConfig,
    init_params: Pytree | None = None,
):
    """One pFedSOP round for one client.  Fully traceable (vmap/shard_map).

    New clients (has_delta=False) skip personalization and start local
    training from their stored params (which the runtime seeds with the
    shared random init, Algorithm 3 line 6).  Round 1 has no global update
    yet (global_has_delta=False) -> also skip personalization.
    """
    del init_params  # runtime seeds state.params; kept for API clarity
    can_personalize = jnp.logical_and(state.has_delta, global_has_delta)
    personalized, aux = personalize(state.params, state.delta, global_delta, cfg)
    params = tree_where(can_personalize, personalized, state.params)

    delta, final_params, loss = local_sgd_delta(loss_fn, params, batches, cfg.eta2)

    new_state = ClientState(
        params=final_params,
        delta=delta,
        has_delta=jnp.asarray(True),
        rounds_seen=state.rounds_seen + 1,
    )
    metrics = {"loss": loss, "beta": aux.get("beta", jnp.float32(1.0)),
               "personalized": can_personalize}
    return new_state, delta, metrics


def server_aggregate(deltas: Pytree) -> Pytree:
    """Eq. 13: mean over the client axis (leading axis of every leaf).

    Routed through the canonically associated ``cohort_mean`` (DESIGN.md
    §11) so the replicated aggregation program, the sharded-at-rest
    program (where this traces inside a ``client_shard_axis`` context and
    the leading axis is the shard-local cohort slice) and the async
    driver's host-stacked flush all produce bit-identical means.
    """
    from repro.optim.reduce import cohort_mean

    return cohort_mean(deltas)


# ---------------------------------------------------------------------------
# Staleness-weighted aggregation (async federation, DESIGN.md §10)
# ---------------------------------------------------------------------------


def staleness_discount(staleness, exponent):
    """FedBuff-style polynomial discount s(tau) = (1 + tau)^(-exponent), f32.

    ``staleness`` counts server versions elapsed since the upload's client
    was dispatched.  tau = 0 yields exactly 1.0 (1^x == 1 in IEEE), which is
    what lets a buffer of fresh uploads aggregate bit-identically to the
    synchronous path -- the degenerate-sync anchor of the async subsystem.
    """
    tau = jnp.asarray(staleness, jnp.float32)
    return (1.0 + tau) ** jnp.float32(-exponent)


def stale_blend(upload, global_delta, discount, lam, eps=1e-12):
    """Down-blend ONE stale local delta toward the current global delta.

    Composes the staleness discount s(tau) with the Gompertz-normalized
    angle weight (Eq. 14):

        c       = (1 - s) * (1 - beta)
        blended = (1 - c) * upload + c * global_delta

    beta is Eq. 14's trust-toward-global weight -- large when the upload
    agrees with the current global direction -- so (1 - beta) measures
    disagreement.  A stale AND conflicting delta is pulled hardest toward
    the global consensus; a fresh upload (s = 1 -> c = 0) passes through
    bit-exactly.  Feeding the blended deltas to the Eq. 13 mean
    down-*blends* staleness into the aggregate instead of merely
    down-averaging it (the generic FedAvg-family default in
    ``repro.core.baselines``).
    """
    beta, _ = gompertz_weight(upload, global_delta, lam, eps)
    c = (1.0 - discount) * (1.0 - beta)
    return tree_lerp(c, upload, global_delta)
