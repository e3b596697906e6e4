"""Backend-pluggable federation engine (DESIGN.md §3/§11).

The round logic in ``repro.fl.runtime`` is backend-agnostic: a
``FederationEngine`` decides *where* the per-client work of one round runs.
Three interchangeable backends ship today:

  VmapBackend      single host, single device: the K' participating clients
                   are one ``jax.vmap`` over the stacked client axis (the
                   seed behaviour, and the reference semantics).
  MeshBackend      the general mesh engine (DESIGN.md §11): shard_maps the
                   participating-client axis over the mesh's *client-role*
                   axis (``pod`` on the production `(pod, data, model)`
                   mesh, ``clients`` on the 1-D engine mesh) and each
                   device vmaps its local client slice.  Within a pod the
                   per-client phase replicates over `(data, model)` —
                   except the §9 round-start update, whose flattened-N
                   axis shards over ``model`` (per-tile partial
                   reductions + cross-shard all-gather for the three
                   Gompertz scalars; `repro.kernels.pfedsop_update`).  In specs
                   come from the composed pspec helpers
                   (`launch/sharding.py::client_stacked_pspecs`), so
                   Megatron-eligible leaves of transformer-family state
                   additionally live model-sharded at rest and are
                   gathered transiently inside the body.
  ShardMapBackend  the 1-D special case of MeshBackend kept under its own
                   name: the client axis over a ``"clients"`` mesh — the
                   §3 layout.

All backends run the *same* traced client function on the *same* stacked
operands and return their outputs **fully replicated** (an explicit
round-boundary all-gather inside the program), so downstream server
aggregation (Eq. 13) compiles to the same mesh-shape-invariant program
everywhere.  That replication is what upgrades backend parity from
"equal up to cross-shard reduction order" to **bitwise** — asserted on a
1-device mesh, a 4-way client mesh and a forced 8-device `(2,2,2)`
multi-pod mesh (tests/test_engine.py, tests/test_multipod.py).

The client function contract is the ``FLMethod`` interface documented in
``repro.core.baselines``; the engine only requires that it is traceable
(vmap/shard_map-safe: no python control flow on traced values).
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, Optional, Protocol, Union, runtime_checkable

import jax

from jax import shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.dispatch import (
    client_shard_axis,
    data_shard_axis,
    model_shard_axis,
)
from repro.launch.mesh import MeshSpec, is_auto_clients, parse_mesh, resolve_mesh
from repro.launch.sharding import client_stacked_pspecs

Pytree = Any
CLIENT_AXIS = "clients"


@runtime_checkable
class FederationEngine(Protocol):
    """Executes the data-parallel (per-client) phases of one FL round.

    ``one_client``/``one_eval`` are traced functions of ONE client's slice
    (no leading client axis); every other argument carries a leading
    stacked-client axis except ``broadcast``, which is replicated.
    """

    name: str

    def client_phase(
        self,
        one_client: Callable[[Pytree, Pytree, Pytree], Any],
        gathered_states: Pytree,
        broadcast: Pytree,
        batches: Pytree,
    ) -> Any:
        """(states, broadcast, batches) -> (new_states, uploads, metrics)."""
        ...

    def eval_phase(
        self,
        one_eval: Callable[[Pytree, Pytree, Pytree], Any],
        states: Pytree,
        broadcast: Pytree,
        test_sets: Pytree,
    ) -> Any:
        """(states, broadcast, test_sets) -> per-client accuracies (K',)."""
        ...

    def describe(self) -> dict:
        """Static metadata for logs/benchmarks (backend, shards, ...)."""
        ...


class VmapBackend:
    """Single-host reference backend: one jax.vmap over the client axis."""

    name = "vmap"
    n_pods = 1

    def signature(self) -> str:
        """Engine layout id (RoundPrograms cache key, DESIGN.md §11)."""
        return "vmap"

    def client_phase(self, one_client, gathered_states, broadcast, batches):
        return jax.vmap(one_client, in_axes=(0, None, 0))(
            gathered_states, broadcast, batches
        )

    # single-device outputs are trivially "replicated": the sharded/
    # replicate factoring (see MeshBackend) collapses to the fused phase
    client_phase_sharded = client_phase
    replicate = None

    def eval_phase(self, one_eval, states, broadcast, test_sets):
        return jax.vmap(one_eval, in_axes=(0, None, 0))(
            states, broadcast, test_sets
        )

    def input_shardings(self, tree):
        """No mesh placement: the cohort store's default single-device
        ``device_put`` is already this backend's layout (DESIGN.md §12)."""
        return None

    def describe(self):
        return {"backend": self.name, "shards": 1}


def resolve_shards(kprime: int, n_devices: int, requested: int = 0) -> int:
    """Shard count for a K'-client round on ``n_devices`` local devices.

    The stacked-client axis is split evenly (no padding — padded dummy
    clients would change the server mean, breaking backend equivalence), so
    the shard count must divide K'.  ``requested=0`` picks the largest
    divisor of K' that fits the device count; an explicit request is
    validated strictly.
    """
    if requested < 0:
        raise ValueError(f"shards must be >= 0 (0 = auto), got {requested}")
    if requested:
        if requested > n_devices:
            raise ValueError(
                f"requested {requested} shards but only {n_devices} devices"
            )
        if kprime % requested:
            raise ValueError(
                f"shards={requested} must divide the {kprime} participating "
                "clients per round (no padding; see DESIGN.md §3)"
            )
        return requested
    for n in range(min(kprime, n_devices), 0, -1):
        if kprime % n == 0:
            return n
    return 1


def resolve_client_split(kprime: int, spec: MeshSpec, strict: bool = True) -> bool:
    """Whether a K'-cohort can shard over ``spec``'s client-role axis.

    Unlike the 1-D ``resolve_shards`` (which picks a dividing shard count),
    a mesh's client-axis size is fixed by the spec, so a non-divisor K' has
    no partial split: ``strict=True`` raises (a requested layout must never
    be silently changed, §3); ``strict=False`` — the async driver's
    micro-cohorts — falls back to an unsharded client axis (the cohort
    replicates across pods; the §9 model-sharded update still applies).
    Returns True when the client axis is used, False for the fallback.
    """
    size = spec.client_size
    if spec.client_axis is None or size == 1:
        return False
    if kprime % size == 0:
        return True
    if strict:
        raise ValueError(
            f"mesh {spec.signature()}: client axis {spec.client_axis!r} of "
            f"size {size} must divide the {kprime} participating clients per "
            "round (no padding; see DESIGN.md §3/§11) — pick a dividing pod "
            "count or adjust participation"
        )
    return False


class MeshBackend:
    """Mesh engine: client axis over the client-role axis of a MeshSpec.

    Each device holding a client-axis coordinate runs ``jax.vmap`` over its
    local clients inside ``shard_map``; the remaining mesh axes (``data``,
    ``model``) replicate the per-client phase except where a kernel opts
    into the model axis via the §9 dispatch context
    (``repro.kernels.dispatch.model_shard_axis`` — the model-sharded
    ``pfedsop_update`` layout, DESIGN.md §11).

    Inputs may arrive model-sharded at rest: in-specs come from the
    composed ``client_stacked_pspecs`` (client axis x Megatron param
    rules), and the body transiently all-gathers any model-sharded leaf
    before the per-client compute.  Outputs are returned fully replicated
    (see module docstring — the bitwise-parity contract).
    """

    name = "mesh"

    def __init__(self, kprime: int, spec: MeshSpec, strict: bool = True,
                 data_chunks: int = 0):
        self.kprime = kprime
        self.spec = spec
        self.client_sharded = resolve_client_split(kprime, spec, strict)
        self.mesh = resolve_mesh(spec)
        # FLRunConfig.grad_chunks, threaded through make_engine: when it
        # equals the mesh's data-axis size, the client phase shards the
        # per-client batch over the data axis and each device computes its
        # gradient *chunk* (optim.sgd.chunked_value_and_grad) — same
        # chunk-tree semantics as the in-body path, so histories stay
        # bitwise vs data=1 (DESIGN.md §11).
        self.data_chunks = int(data_chunks)

    @property
    def client_shards(self) -> int:
        return self.spec.client_size if self.client_sharded else 1

    @property
    def n_pods(self) -> int:
        """Pods the async scheduler maps micro-cohorts onto (DESIGN.md
        §11): the client-axis size of an explicit multi-pod mesh; 1
        otherwise (the 1-D client mesh keeps global scheduling)."""
        return (self.spec.client_size
                if self.spec.client_axis == "pod" and self.client_sharded
                else 1)

    def signature(self) -> str:
        """Engine layout id (RoundPrograms cache key, DESIGN.md §11)."""
        sig = self.spec.signature()
        if not self.client_sharded:
            sig += "|cohort-replicated"
        if self.data_chunks > 1:
            sig += f"|data-chunks={self.data_chunks}"
        return sig

    def _in_specs(self, tree):
        caxis = self.spec.client_axis if self.client_sharded else None
        return client_stacked_pspecs(
            tree, caxis, model_axis=self.spec.model_axis,
            msize=self.spec.model_size,
        )

    def _data_split(self, batches) -> bool:
        """Whether this call's batch tree shards over the data axis.

        Engages only when the run-level chunk count equals the data-axis
        size (the local slice must BE one semantic chunk) and every leaf
        carries a stacked (client, step, batch, ...) layout whose batch
        dim (index 2) splits evenly.  Decided per trace from static
        shapes, so a non-dividing batch (e.g. the multipod bench's 25)
        falls back to the in-body chunk path with identical numbers.
        """
        dsize = self.spec.data_size
        if (self.spec.data_axis is None or dsize <= 1
                or self.data_chunks != dsize):
            return False
        leaves = jax.tree.leaves(batches)
        return bool(leaves) and all(
            x.ndim >= 3 and x.shape[2] % dsize == 0 for x in leaves
        )

    def _batch_specs(self, tree):
        """In-specs for a data-sharded batch tree: client axis on the
        stacked dim, data axis on the per-step batch dim (index 2)."""
        caxis = self.spec.client_axis if self.client_sharded else None
        daxis = self.spec.data_axis
        return jax.tree.map(lambda _: P(caxis, None, daxis), tree)

    def _gather_model(self, tree, specs):
        """All-gather any model-sharded dims so the per-client compute sees
        full leaves (transient: storage stays sharded, compute replicates
        across the model axis — the §11 v1 semantics; the model axis does
        real parallel work inside the §9 model-sharded update kernel)."""
        maxis = self.spec.model_axis
        if maxis is None or self.spec.model_size <= 1:
            return tree

        def gather(x, spec):
            # spec dims after the leading client axis map to x's dims 1:
            # inside the body the client axis is local (dim 0 retained)
            for d, ax in enumerate(spec):
                if d == 0:
                    continue  # client axis handled by shard_map itself
                if ax == maxis:
                    x = jax.lax.all_gather(x, maxis, axis=d, tiled=True)
            return x

        return jax.tree.map(gather, tree, specs)

    def _sharded(self, fn, *in_trees, broadcast, replicated: bool = True,
                 data_tree: bool = False):
        # data_tree: the LAST in_tree is a stacked batch tree eligible for
        # data-axis sharding (the client phase; never eval/test sets)
        data_split = data_tree and self._data_split(in_trees[-1])
        specs = [self._in_specs(t) for t in in_trees]
        if data_split:
            specs[-1] = self._batch_specs(in_trees[-1])
        specs = tuple(specs)
        caxis = self.spec.client_axis if self.client_sharded else None
        out_spec = P(caxis) if caxis else P()

        def local(broadcast_, *local_trees):
            local_trees = tuple(
                self._gather_model(t, s) for t, s in zip(local_trees, specs)
            )
            return jax.vmap(fn, in_axes=(0, None) + (0,) * (len(local_trees) - 1))(
                local_trees[0], broadcast_, *local_trees[1:]
            )

        # check_vma=False: jax has no varying-manual-axes rule for
        # pallas_call, so the checker rejects the kernel update impl
        # (DESIGN.md §9).  Safe here — outputs are re-constrained to
        # replicated at the round boundary (``replicate``), so the check
        # would not tighten anything.
        msize = self.spec.model_size
        with contextlib.ExitStack() as ctx:
            if self.spec.model_axis is not None and msize > 1:
                ctx.enter_context(
                    model_shard_axis(self.spec.model_axis, msize))
            if data_split:
                ctx.enter_context(
                    data_shard_axis(self.spec.data_axis, self.spec.data_size))
            out = shard_map(
                local,
                mesh=self.mesh,
                in_specs=(P(),) + specs,
                out_specs=out_spec,
                check_vma=False,
            )(broadcast, *in_trees)
        return self.replicate(out) if replicated else out

    def replicate(self, out):
        """The round-boundary all-gather: outputs leave the engine fully
        replicated, so server aggregation compiles to the same
        mesh-shape-invariant program everywhere (the bitwise parity
        contract; DESIGN.md §11).  Pure data movement — values are bitwise
        identical whether this runs fused with the client phase or as its
        own program, which is how the observability layer times it as a
        separate span without forking the math (§13)."""
        return jax.lax.with_sharding_constraint(
            out, NamedSharding(self.mesh, P())
        )

    def input_shardings(self, tree):
        """Per-leaf ``NamedSharding`` for a gathered client-stacked cohort
        at this engine's at-rest layout (client axis x Megatron param
        rules — the same ``_in_specs`` the phase programs consume).  The
        host cohort store ``device_put``s each gathered leaf against
        these, so the participants' rows land as per-pod (and per
        model-shard) slices directly instead of a replicated cohort that
        shard_map re-lays out (DESIGN.md §12).  ``tree`` only needs the
        leaf names/ranks (a ShapeDtypeStruct probe works)."""
        specs = self._in_specs(tree)
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs)

    def client_phase(self, one_client, gathered_states, broadcast, batches):
        return self._sharded(one_client, gathered_states, batches,
                             broadcast=broadcast, data_tree=True)

    def client_phase_sharded(self, one_client, gathered_states, broadcast, batches):
        """Client phase WITHOUT the round-boundary all-gather: outputs stay
        client-sharded (P(caxis)); callers compose ``replicate`` before
        aggregation.  The drivers use this factored pair so the all-gather
        is attributable as its own trace span (DESIGN.md §13) — and so the
        §11 sharded-at-rest round loop can drop it entirely, feeding the
        pod-sharded outputs straight into ``aggregate_phase``."""
        return self._sharded(one_client, gathered_states, batches,
                             broadcast=broadcast, replicated=False,
                             data_tree=True)

    def aggregate_phase(self, fn, broadcast, *upload_trees):
        """Server aggregation lowered into the sharded program (§11).

        ``fn(broadcast, *uploads) -> new_broadcast`` is the method's
        ``server_update``, traced inside a shard_map whose upload in-specs
        match ``client_phase_sharded``'s out-specs exactly (client axis on
        dim 0 of every leaf) — no resharding between the phases.  The body
        announces ``client_shard_axis``, so the cohort reductions inside
        ``fn`` (``repro.optim.reduce.cohort_mean``/``cohort_sum``) combine
        shard-local halving-tree partials in shard order: bitwise equal to
        the replicated program by the ordered-decomposition argument in
        ``repro.optim.reduce``.  Output replicates (every device computes
        the identical new broadcast from the gathered partials).
        """
        caxis = self.spec.client_axis
        csize = self.spec.client_size
        specs = tuple(
            jax.tree.map(lambda _: P(caxis), t) for t in upload_trees
        )

        def local(broadcast_, *local_trees):
            with client_shard_axis(caxis, csize):
                return fn(broadcast_, *local_trees)

        return shard_map(
            local,
            mesh=self.mesh,
            in_specs=(P(),) + specs,
            out_specs=P(),
            check_vma=False,
        )(broadcast, *upload_trees)

    def eval_phase(self, one_eval, states, broadcast, test_sets):
        return self._sharded(one_eval, states, test_sets, broadcast=broadcast)

    def describe(self):
        out = {
            "backend": self.name,
            "mesh": self.spec.signature(),
            "shards": self.client_shards,
            "n_pods": self.n_pods,
            "model_shards": self.spec.model_size,
            "devices": [str(d) for d in self.mesh.devices.flat],
        }
        if self.data_chunks > 1:
            out["data_chunks"] = self.data_chunks
        return out


class ShardMapBackend(MeshBackend):
    """1-D special case of ``MeshBackend``: the participating-client axis
    over a ``"clients"`` mesh (DESIGN.md §3), shard count resolved from
    (K', local devices) by ``resolve_shards``."""

    name = "shard_map"

    def __init__(self, kprime: int, shards: int = 0, data_chunks: int = 0):
        self.shards = resolve_shards(kprime, len(jax.devices()), shards)
        super().__init__(kprime, MeshSpec.clients(self.shards, CLIENT_AXIS),
                         data_chunks=data_chunks)

    def describe(self):
        return {
            "backend": self.name,
            "shards": self.shards,
            "devices": [str(d) for d in self.mesh.devices.flat],
        }


BACKENDS = ("vmap", "shard_map", "mesh")


def make_engine(backend: str, kprime: int, shards: int = 0,
                mesh: Union[str, MeshSpec, None] = None,
                strict: bool = True,
                data_chunks: int = 0) -> FederationEngine:
    """Engine factory used by ``Federation`` (selected via FLRunConfig).

    ``mesh`` (a spec string for ``repro.launch.mesh.parse_mesh``, or a
    ``MeshSpec``) selects the layout for ``backend="mesh"`` and is rejected
    elsewhere — like ``shards``, a layout request must never be silently
    ignored.  ``strict=False`` (the async driver's micro-cohorts) lets a
    non-divisor cohort fall back instead of erroring (§3/§11).
    ``data_chunks`` threads ``FLRunConfig.grad_chunks`` to the mesh engines
    (the data-axis local-SGD layout, §11); the vmap backend computes its
    chunks in-body via the dispatch context, so it takes no engine knob.
    """
    if backend == "vmap":
        if shards or mesh:
            raise ValueError(
                "shards/mesh are only meaningful with backend='shard_map'/"
                f"'mesh' (got shards={shards}, mesh={mesh!r} with "
                "backend='vmap')"
            )
        return VmapBackend()
    if backend == "shard_map":
        if mesh:
            raise ValueError(
                "backend='shard_map' is the 1-D client mesh; pass the mesh "
                f"spec (got {mesh!r}) with backend='mesh' instead"
            )
        # async micro-cohorts (strict=False): an explicitly requested split
        # that does not divide the cohort falls back to auto (largest
        # divisor) instead of erroring
        if not strict and shards and kprime % shards:
            shards = 0
        return ShardMapBackend(kprime, shards, data_chunks=data_chunks)
    if backend == "mesh":
        if shards:
            raise ValueError(
                "backend='mesh' takes its client split from the mesh spec's "
                f"client-role axis; shards={shards} is only meaningful with "
                "backend='shard_map'"
            )
        if not mesh:
            raise ValueError(
                "backend='mesh' requires a mesh spec (FLRunConfig.mesh / "
                "--mesh), e.g. 'pods:2x2x2'; see repro.launch.mesh.parse_mesh"
            )
        spec = parse_mesh(mesh) if isinstance(mesh, str) else mesh
        if is_auto_clients(spec):
            spec = MeshSpec.clients(
                resolve_shards(kprime, len(jax.devices())), CLIENT_AXIS)
        return MeshBackend(kprime, spec, strict=strict,
                           data_chunks=data_chunks)
    raise ValueError(f"unknown FL backend {backend!r}; choose from {BACKENDS}")
