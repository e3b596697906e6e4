"""Mesh layer: role-named mesh specs + resolution (DESIGN.md §11).

Every driver — the §3/§11 federation engines, the launch/serve paths and
the §6 dry-run — builds its device mesh from one abstraction:

  ``MeshSpec``      a frozen description of axis names/sizes plus the *roles*
                    they play: the client axis (the participating-client /
                    FL-cohort axis the engines shard_map over), the data
                    axis (batch parallelism) and the model axis (Megatron
                    tensor parallelism + the §9 model-sharded update kernel).
  ``resolve_mesh``  MeshSpec -> jax.sharding.Mesh, with device-count
                    validation and the XLA_FLAGS hint in the error.
  ``parse_mesh``    CLI grammar ("clients[:N]" | "host" | "pod:DxM" |
                    "pods:PxDxM") -> MeshSpec, for ``--mesh`` flags.

Shipped layouts (TPU v5e target, all shapes parameterizable so reduced
meshes run on forced host devices — e.g. ``pods:2x2x2`` on 8):

  client mesh      1-D (clients,): the §3 engine layout; embarrassingly
                   parallel client phase, cross-device traffic confined to
                   the round-boundary collective.
  single pod       256 chips as (data=16, model=16).
  multi-pod        2 pods x 256 chips as (pod=2, data=16, model=16); the
                   ``pod`` axis is the FL-cohort axis — each pod runs an
                   equal contiguous slice of the round's participating-client
                   cohort (the cohort-sharded layout of DESIGN.md §11; the
                   per-client local phase replicates over (data, model)
                   inside a pod except the §9 model-sharded round-start
                   update), and the cross-pod collective is the
                   round-boundary aggregation of the local updates.
  host mesh        degenerate 1x1 (data, model) for CPU smoke runs.

Defined as FUNCTIONS (and a pure-data spec) so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any jax
initialisation).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np


@dataclass(frozen=True)
class MeshSpec:
    """Axis names/sizes plus role annotations; pure data, no jax state.

    ``client_axis``/``data_axis``/``model_axis`` name which mesh axis plays
    each role (or None when the role is absent — e.g. the 1-D client mesh
    has no model axis, the single-pod mesh no client axis).  Roles are what
    the consumers key on: ``repro.fl.engine.MeshBackend`` shard_maps the
    participating-client axis over ``client_axis``, ``launch/sharding.py``
    rules shard params over ``model_axis`` and batches over ``data_axis``.
    """

    shape: Tuple[int, ...]
    axes: Tuple[str, ...]
    client_axis: Optional[str] = None
    data_axis: Optional[str] = None
    model_axis: Optional[str] = None

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} / axes {self.axes} length mismatch")
        if len(set(self.axes)) != len(self.axes):
            raise ValueError(f"duplicate axis names in {self.axes}")
        for s, a in zip(self.shape, self.axes):
            if s < 1:
                raise ValueError(f"axis {a!r} has non-positive size {s}")
        for role, name in [("client_axis", self.client_axis),
                           ("data_axis", self.data_axis),
                           ("model_axis", self.model_axis)]:
            if name is not None and name not in self.axes:
                raise ValueError(
                    f"{role}={name!r} is not a mesh axis (axes: {self.axes})")

    # -- role-keyed sizes --------------------------------------------------

    def size(self, axis: Optional[str]) -> int:
        """Size of a named axis; 1 for None (an absent role is a size-1
        degenerate axis as far as divisibility/sharding math goes)."""
        if axis is None:
            return 1
        return self.shape[self.axes.index(axis)]

    @property
    def n_devices(self) -> int:
        return int(np.prod(self.shape))

    @property
    def client_size(self) -> int:
        return self.size(self.client_axis)

    @property
    def data_size(self) -> int:
        return self.size(self.data_axis)

    @property
    def model_size(self) -> int:
        return self.size(self.model_axis)

    def signature(self) -> str:
        """Stable id for program-cache keys and logs (RoundPrograms caches
        phase programs per (cohort size, mesh signature) — DESIGN.md §11)."""
        dims = ",".join(f"{a}={s}" for a, s in zip(self.axes, self.shape))
        roles = ",".join(
            f"{r}:{n}" for r, n in [("client", self.client_axis),
                                    ("data", self.data_axis),
                                    ("model", self.model_axis)] if n)
        return f"{dims}[{roles}]" if roles else f"{dims}[]"

    # -- shipped layouts ---------------------------------------------------

    @staticmethod
    def clients(n_shards: int, axis_name: str = "clients") -> "MeshSpec":
        """1-D mesh over the FL participating-client axis (DESIGN.md §3)."""
        return MeshSpec((n_shards,), (axis_name,), client_axis=axis_name)

    @staticmethod
    def host() -> "MeshSpec":
        """Degenerate 1x1 (data, model) mesh for CPU smoke runs."""
        return MeshSpec((1, 1), ("data", "model"),
                        data_axis="data", model_axis="model")

    @staticmethod
    def single_pod(data: int = 16, model: int = 16) -> "MeshSpec":
        """One pod: (data, model) tensor/batch parallelism, no client axis."""
        return MeshSpec((data, model), ("data", "model"),
                        data_axis="data", model_axis="model")

    @staticmethod
    def multi_pod(pods: int = 2, data: int = 16, model: int = 16) -> "MeshSpec":
        """(pod, data, model): ``pod`` is the FL-cohort (client-role) axis."""
        return MeshSpec((pods, data, model), ("pod", "data", "model"),
                        client_axis="pod", data_axis="data",
                        model_axis="model")


_MESH_GRAMMAR = (
    "mesh spec grammar: 'clients' | 'clients:N' (1-D client mesh, N shards, "
    "0/omitted = auto) | 'host' (1x1 data,model) | 'pod:DxM' (single pod) | "
    "'pods:PxDxM' (multi-pod; pod = client-role axis)"
)


def parse_mesh(spec: str) -> MeshSpec:
    """Parse a ``--mesh`` CLI string into a MeshSpec (see _MESH_GRAMMAR).

    ``clients:0``/``clients`` returns a client spec with shape ``(0,)``
    sentinel meaning "auto shard count" — callers (the engine factory)
    replace it with ``resolve_shards`` before touching devices.
    """
    s = spec.strip().lower()
    head, _, tail = s.partition(":")
    try:
        if head == "clients":
            n = int(tail) if tail else 0
            if n < 0:
                raise ValueError
            # size-0 sentinel bypasses validation via direct construction
            return MeshSpec.clients(max(n, 1)) if n else _auto_clients_spec()
        if head == "host" and not tail:
            return MeshSpec.host()
        if head == "pod":
            d, m = (int(x) for x in tail.split("x"))
            return MeshSpec.single_pod(d, m)
        if head == "pods":
            p, d, m = (int(x) for x in tail.split("x"))
            return MeshSpec.multi_pod(p, d, m)
    except (ValueError, TypeError) as e:
        raise ValueError(f"bad mesh spec {spec!r}; {_MESH_GRAMMAR}") from e
    raise ValueError(f"unknown mesh spec {spec!r}; {_MESH_GRAMMAR}")


class _AutoClients(MeshSpec):
    """Marker subclass: 1-D client mesh whose shard count is resolved from
    (K', local devices) by the engine factory (``clients``/``clients:0``)."""


def _auto_clients_spec() -> MeshSpec:
    return _AutoClients((1,), ("clients",), client_axis="clients")


def is_auto_clients(spec: MeshSpec) -> bool:
    return isinstance(spec, _AutoClients)


def resolve_mesh(spec: MeshSpec):
    """MeshSpec -> jax.sharding.Mesh over the first n_devices local devices.

    The only function here that touches jax device state; raises with the
    forced-host-device hint when the host is short on devices.
    """
    import jax  # deferred: importing this module must not init jax

    devices = jax.devices()
    n = spec.n_devices
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {spec.signature()} needs {n} devices, found {len(devices)}"
            f" - run under XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} for CPU simulation (see dryrun.py), or pick a smaller "
            f"spec ({_MESH_GRAMMAR})"
        )
    # Auto axes: the engines place data with ``with_sharding_constraint``
    # and shard_map specs, not with sharding-in-types (jax's default for
    # ``make_mesh`` is Explicit axes, which rejects that style)
    auto = (jax.sharding.AxisType.Auto,) * len(spec.axes)
    return jax.make_mesh(spec.shape, spec.axes, axis_types=auto,
                         devices=devices[:n])


# ---------------------------------------------------------------------------
# Back-compat constructors (now routed through MeshSpec/resolve_mesh)
# ---------------------------------------------------------------------------


def make_production_mesh(*, multi_pod: bool = False,
                         shape: Optional[Tuple[int, ...]] = None):
    """Production mesh; ``shape`` overrides the v5e default so CI-sized
    smokes run (e.g. ``shape=(2, 2, 2)`` with ``multi_pod=True`` on 8
    forced host devices).  ``shape`` is (pods, data, model) when
    ``multi_pod`` else (data, model)."""
    if multi_pod:
        spec = MeshSpec.multi_pod(*(shape or (2, 16, 16)))
    else:
        spec = MeshSpec.single_pod(*(shape or (16, 16)))
    return resolve_mesh(spec)


def make_host_mesh():
    """Degenerate 1x1 mesh for CPU smoke runs of the sharded step code."""
    return resolve_mesh(MeshSpec.host())


def make_client_mesh(n_shards: int, axis_name: str = "clients"):
    """1-D mesh over the FL participating-client axis (DESIGN.md §3).

    Used by ``repro.fl.engine`` to split a round's K' clients across local
    devices; the single-axis layout keeps the client phase embarrassingly
    parallel and confines cross-device traffic to the round-boundary
    aggregation collective.
    """
    return resolve_mesh(MeshSpec.clients(n_shards, axis_name))
