"""Kernel-dispatch layer: impl selection for fused Pallas hot paths.

A compute primitive with both a pure-pytree reference implementation and a
fused Pallas kernel is selected by an impl knob (DESIGN.md §9).  Every
dispatched kernel — ``pfedsop_update`` (knob: ``PFedSOPConfig.update_impl``),
``rmsnorm`` and ``flash_gqa`` (knob: ``ModelConfig.kernel_impl``) — resolves
through the same vocabulary and the same ``resolve_impl`` code path:

  "auto"              resolve at trace time from the host platform: the
                      Pallas kernel on TPU, the reference path elsewhere.
  "reference"         always the pure-JAX math (the oracle).
  "kernel"            always the Pallas kernel, compiled for the
                      accelerator (Mosaic on TPU).
  "kernel_interpret"  the Pallas kernel body run through the interpreter —
                      same code path and tiling as "kernel" but executable
                      on CPU; used by CI, the parity tests, and the
                      interpret-mode benches (``benchmarks/run.py --only
                      pfedsop-update --interpret`` / ``--only model-fwd``).

Resolution happens host-side (python, not traced), so the selected impl is
baked into the jitted round/forward function — there is no runtime branch
on the hot path.  The parity guarantee: a kernel impl must match the
reference impl within fp32 reduction-order tolerance on identical inputs
(asserted in tests/test_kernel_dispatch.py and tests/test_model_dispatch.py).

The per-kernel registry maps each dispatched kernel to the config-knob
name its callers use; registering here is what makes a kernel's "auto"
resolution attributable in logs and its error messages name the right
knob.  New kernel integrations call ``register_kernel`` (or add an entry
below) rather than growing a parallel resolve function.
"""
from __future__ import annotations

import contextlib
import functools
import logging
from typing import Optional, Tuple

import jax

logger = logging.getLogger(__name__)

IMPLS = ("auto", "reference", "kernel", "kernel_interpret")

# Backwards-compatible alias from the first (pfedsop_update-only) dispatch.
UPDATE_IMPLS = IMPLS

# kernel name -> the config-knob name callers select it with (used in error
# messages and the one-shot "auto resolved to ..." log line).
_REGISTRY: dict[str, str] = {}

# kernels whose "auto" resolution has been logged already (log once per
# kernel per process, so long federations don't spam but every run's log
# still says which impl it actually executed).
_AUTO_LOGGED: set[str] = set()


def register_kernel(name: str, knob: str = "kernel_impl") -> None:
    """Register a dispatched kernel under the config knob that selects it."""
    _REGISTRY[name] = knob


def registered_kernels() -> tuple[str, ...]:
    return tuple(_REGISTRY)


@functools.lru_cache(maxsize=1)
def _default_backend() -> str:
    """The host platform, looked up once per process.

    ``jax.default_backend()`` initializes the backend on first call; hoisting
    it behind a cache keeps repeated resolution (every norm/attention call
    site of every layer trace) off that path.
    """
    return jax.default_backend()


def resolve_impl(impl: str, kernel: str) -> str:
    """Resolve an impl knob for a registered kernel to a concrete impl name.

    Returns one of ("reference", "kernel", "kernel_interpret"); raises
    ValueError on an unregistered kernel or anything outside ``IMPLS``.
    """
    knob = _REGISTRY.get(kernel)
    if knob is None:
        raise ValueError(
            f"unregistered kernel {kernel!r}; registered: {registered_kernels()}"
        )
    if impl not in IMPLS:
        raise ValueError(f"unknown {knob} {impl!r}; choose from {IMPLS}")
    if impl != "auto":
        return impl
    backend = _default_backend()
    resolved = "kernel" if backend == "tpu" else "reference"
    if kernel not in _AUTO_LOGGED:
        _AUTO_LOGGED.add(kernel)
        # routed through the obs structured logger: the stdlib record keeps
        # its historical logger name + format (pinned by the dispatch tests),
        # and an open trace additionally gets a structured mirror record
        from repro.obs import get_obs

        get_obs().log.info(
            f"kernel-dispatch: {knob}=auto resolved to {resolved!r} for "
            f"{kernel} (backend={backend})",
            logger=logger, event="kernel_dispatch",
            kernel=kernel, knob=knob, impl=resolved, backend=backend,
        )
    return resolved


# ---------------------------------------------------------------------------
# Mesh-axis contexts (DESIGN.md §11)
#
# When a mesh-aware federation engine traces a phase inside a shard_map,
# code that supports a sharded layout should split its work over the
# announced mesh axis instead of running replicated on every shard.  The
# engine announces the axis with a context manager around body tracing;
# consumers read the ``current_*`` getter host-side, so the choice is
# baked into the trace like every other dispatch decision.  Three roles:
#
#   model_shard_axis   kernels with a model-sharded layout (pfedsop_
#                      update's flattened-N axis) split their sweep —
#                      per-tile partials + cross-shard all-gather.
#   client_shard_axis  the sharded aggregation program (§11 output-
#                      sharding): cohort reductions (``repro.optim.
#                      reduce.cohort_mean``/``cohort_sum``) combine
#                      shard-local halving-tree partials in shard order.
#   data_shard_axis    the per-client batch is sharded over the data
#                      axis: ``optim.sgd.chunked_value_and_grad`` treats
#                      the local slice as its gradient chunk and gathers
#                      the chunk partials across the axis.
# ---------------------------------------------------------------------------


def _axis_context(stack: list):
    @contextlib.contextmanager
    def ctx(axis_name: str, n_shards: int):
        stack.append((axis_name, int(n_shards)))
        try:
            yield
        finally:
            stack.pop()

    def current() -> Optional[Tuple[str, int]]:
        return stack[-1] if stack else None

    return ctx, current


_MODEL_SHARD_STACK: list = []
_CLIENT_SHARD_STACK: list = []
_DATA_SHARD_STACK: list = []

model_shard_axis, current_model_shard = _axis_context(_MODEL_SHARD_STACK)
client_shard_axis, current_client_shard = _axis_context(_CLIENT_SHARD_STACK)
data_shard_axis, current_data_shard = _axis_context(_DATA_SHARD_STACK)

model_shard_axis.__name__ = "model_shard_axis"
client_shard_axis.__name__ = "client_shard_axis"
data_shard_axis.__name__ = "data_shard_axis"


# ---------------------------------------------------------------------------
# Gradient-chunk context (DESIGN.md §11)
#
# ``FLRunConfig.grad_chunks`` fixes the *numeric semantics* of each local
# SGD step: the gradient is the canonical chunk-tree reduction over n
# equal batch chunks (``repro.optim.reduce``), whether those chunks are
# computed in-body (data axis inactive) or one-per-device over the data
# axis.  The run driver enters this context around every call of the
# jitted client program — jit defers tracing to the first call, so the
# count is read at trace time, like the mesh-axis contexts above.
# ---------------------------------------------------------------------------

_GRAD_CHUNK_STACK: list = []


@contextlib.contextmanager
def grad_chunk_count(n: int):
    """Declare the run-level gradient chunk count around client tracing."""
    _GRAD_CHUNK_STACK.append(int(n))
    try:
        yield
    finally:
        _GRAD_CHUNK_STACK.pop()


def current_grad_chunks() -> int:
    """The active gradient chunk count (1 outside any context)."""
    return _GRAD_CHUNK_STACK[-1] if _GRAD_CHUNK_STACK else 1


@contextlib.contextmanager
def kernel_scope(kernel: str, impl: str):
    """Name a dispatched-kernel launch in HLO (DESIGN.md §13).

    Wraps tracing in ``jax.named_scope("{kernel}[{impl}]")`` so the
    resolved impl shows up in the HLO op metadata.  Host-side only — the
    traced computation is unchanged (names, not values).
    """
    with jax.named_scope(f"{kernel}[{impl}]"):
        yield


def resolve_update_impl(impl: str) -> str:
    """Resolve the pFedSOP round-start-update knob (back-compat wrapper).

    Returns one of ("reference", "kernel", "kernel_interpret");
    raises ValueError on anything outside ``UPDATE_IMPLS``.
    """
    return resolve_impl(impl, "pfedsop_update")


register_kernel("pfedsop_update", knob="update_impl")
register_kernel("rmsnorm")
register_kernel("flash_gqa")
# The attention backward dispatches independently of the forward: "reference"
# is the blockwise scan-of-VJPs (oracle math), the kernel impls run the
# fused two-pass flash backward (kernel.flash_gqa_bwd_pallas).
register_kernel("flash_gqa_bwd")
