"""Federation runtime: round loop + history, backend-agnostic.

The federation is one SPMD program: per-client states live as stacked
pytrees (leading K axis); each round the K' participating clients are
gathered, a ``FederationEngine`` backend (``repro.fl.engine``) runs the
method's ``client_round`` across them — ``jax.vmap`` on one device, or
``shard_map`` over a client-axis device mesh — uploads are aggregated by
the method's ``server_update``, and the states are scattered back.

The round is executed as jitted *phase programs* (client, eval,
aggregate) built by ``RoundPrograms`` — the cohort gather/scatter around
them belongs to the ``repro.fl.cohort_store`` store (DESIGN.md §12), so
the same programs run whether the K-stack rests on device or on host —
and shared between the synchronous driver here and the asynchronous driver
(``repro.fl.async_``): because both drivers run literally the same
compiled programs on the same operands, the async subsystem's
sync-degenerate guarantee (DESIGN.md §10) is structural — bitwise, not
"up to XLA fusion".  Each phase program compiles once per cohort size, so
recompilation under the async scheduler's micro-cohorts stays bounded.

This is numerically identical to the paper's sequential-client loop (same
initialization, same per-client sampling; verified in
tests/test_fl_runtime.py) but runs K' clients as one vectorized program -
the JAX-idiomatic replacement for a parameter-server process pool
(DESIGN.md §3/§8).  The method object must satisfy the ``FLMethod``
interface documented in ``repro.core.baselines``.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.baselines import FLMethod
from repro.core.pfedsop import theta_from_beta
from repro.data.federated import FederatedData
from repro.fl.cohort_store import make_store
from repro.fl.engine import make_engine
from repro.kernels.dispatch import grad_chunk_count, resolve_update_impl
from repro.optim.reduce import is_pow2
from repro.obs import NOOP, make_obs
from repro.utils.checkpoint import (
    load_checkpoint,
    read_manifest,
    restore_rng_state,
    rng_state_tree,
    save_checkpoint,
)

Pytree = Any

# derived from the Protocol so the contract stays single-sourced
_METHOD_INTERFACE = tuple(
    a for a, v in vars(FLMethod).items() if callable(v) and not a.startswith("_")
)
# the staleness hook is exercised by the async driver alone — a sync-only
# custom method may omit it (AsyncFederation re-validates with the hook)
_SYNC_METHOD_INTERFACE = tuple(
    a for a in _METHOD_INTERFACE if a != "server_update_stale"
)


def validate_method(method, require_stale_hook: bool = False) -> None:
    """Fail fast (with the contract spelled out) on a malformed method.

    The full interface is documented once on ``repro.core.baselines.FLMethod``.
    ``server_update_stale`` is only required when ``require_stale_hook`` is
    set (the async driver is its sole caller, DESIGN.md §10).
    """
    interface = _METHOD_INTERFACE if require_stale_hook else _SYNC_METHOD_INTERFACE
    missing = [a for a in interface if not callable(getattr(method, a, None))]
    if missing or not isinstance(getattr(method, "name", None), str):
        raise TypeError(
            f"{type(method).__name__} does not implement the FLMethod interface "
            f"(missing/uncallable: {missing or ['name']}); see "
            "repro.core.baselines.FLMethod and DESIGN.md §2"
        )


def override_update_impl(method, impl: str):
    """Push a run-level update-impl choice into the method's config.

    Methods expose the knob as an ``update_impl`` field on their frozen
    ``cfg`` dataclass (``PFedSOPConfig`` today); anything else is an error
    because silently running the reference path after an explicit kernel
    request would invalidate impl benchmarks.
    """
    resolve_update_impl(impl)  # validate the name before touching the method
    cfg = getattr(method, "cfg", None)
    if cfg is None or not dataclasses.is_dataclass(cfg) or not hasattr(cfg, "update_impl"):
        raise ValueError(
            f"method {getattr(method, 'name', type(method).__name__)!r} has no "
            "update_impl knob (expected a dataclass `cfg` with an `update_impl` "
            "field, cf. PFedSOPConfig); unset FLRunConfig.update_impl or pick a "
            "method with a kernel dispatch path (DESIGN.md §9)"
        )
    return dataclasses.replace(method, cfg=dataclasses.replace(cfg, update_impl=impl))


@dataclass(frozen=True)
class FLRunConfig:
    """Federation-level run parameters (method hyperparameters live on the
    method object itself, e.g. ``PFedSOPConfig``)."""

    n_clients: int = 100
    participation: float = 0.2  # 20% per round (paper Sec. V-B4)
    rounds: int = 100
    batch: int = 50
    local_iters: int = 0  # 0 = one-local-epoch equivalent (mean client size)
    seed: int = 0
    eval_every: int = 1
    backend: str = "vmap"  # one of repro.fl.engine.BACKENDS
    shards: int = 0  # shard_map only; 0 = auto (largest divisor of K')
    # backend="mesh" only (DESIGN.md §11): mesh spec string for
    # repro.launch.mesh.parse_mesh — "clients[:N]" | "host" | "pod:DxM" |
    # "pods:PxDxM".  The client-role axis of the spec ("pod" on the
    # production mesh) shards the participating-client cohort; rejected
    # for other backends so a layout request is never silently ignored.
    mesh: str = ""
    # Round-boundary output layout (DESIGN.md §11): "replicated" keeps the
    # seed contract — engine outputs leave the client phase fully
    # replicated (an explicit all-gather span) and server aggregation runs
    # over the replicated cohort.  "sharded" opts out of that all-gather
    # on the mesh engines: outputs stay client-sharded at rest (P over the
    # client-role axis), the store scatter/offload consumes the sharded
    # rows, and Eq. 13's mean lowers into a sharded aggregation program
    # whose cohort reductions combine per-shard halving-tree partials in
    # shard order (repro.optim.reduce) — bitwise identical histories to
    # "replicated", asserted in tests/test_output_sharding.py.  Engages
    # per cohort when the client split is active with a power-of-two shard
    # count (the tree-decomposition condition); other cohorts (e.g. async
    # micro-cohorts that fell back to cohort-replicated) keep the
    # replicated path.  Rejected for backend="vmap", whose outputs are
    # born replicated.  Deliberately NOT in the checkpoint fingerprint:
    # it is a layout knob, not a semantics knob.
    output_sharding: str = "replicated"
    # Gradient chunk count of each local SGD step (DESIGN.md §11): the
    # step's gradient is DEFINED as the canonical halving-tree mean over
    # ``grad_chunks`` equal batch chunks (optim.sgd.chunked_value_and_grad).
    # 1 = plain value_and_grad (the seed semantics).  On a mesh whose
    # data-axis size equals this count, the engine shards the per-client
    # batch over the data axis and each device computes one chunk — same
    # numbers, bitwise, by construction.  Changing it CHANGES THE
    # SEMANTICS of training (a different, equally valid gradient), so it
    # IS part of the checkpoint fingerprint.
    grad_chunks: int = 1
    # Round-start update impl override (repro.kernels.dispatch.UPDATE_IMPLS;
    # DESIGN.md §9).  "" = defer to the method's own config (e.g.
    # PFedSOPConfig.update_impl); a non-empty value is pushed into the
    # method at federation construction and errors on methods without the
    # knob — a run-level impl request must never be silently ignored.
    update_impl: str = ""
    # Checkpointing (repro.utils.checkpoint): save the full driver state
    # (stacked client states, broadcast, host RNG state, history, and — for
    # the async driver — scheduler/buffer state) every ``ckpt_every``
    # applied server updates into ``ckpt_dir``.  0/"" disables.  Restart
    # with Federation.restore / AsyncFederation.restore (CLI: --resume on
    # examples/train_federated.py); a restored run reproduces the
    # uninterrupted history bitwise (tests/test_checkpoint_resume.py).
    ckpt_every: int = 0
    ckpt_dir: str = ""
    # Async subsystem (DESIGN.md §10): nested repro.fl.async_.AsyncConfig
    # consumed by AsyncFederation (ignored by the synchronous driver).
    # Typed Any to keep runtime free of an async_ import cycle.
    async_cfg: Any = None
    # Cohort store (DESIGN.md §12): where the (K, ...)-stacked client
    # states live at rest — None/"device" (resident jnp stack, the seed
    # behaviour), "host" (numpy at rest, participants gathered to device
    # per round), "mmap" (disk-backed memmaps), or a full
    # repro.fl.cohort_store.StoreConfig for the cache/threshold knobs.
    # Streamed execution is bitwise identical to the device store
    # (tests/test_cohort_store.py), so this is purely a capacity knob.
    store: Any = None
    # Observability (DESIGN.md §13): None (off — the driver holds the
    # shared NOOP facade and histories are bitwise-identical to an
    # uninstrumented build), a repro.obs.ObsConfig, or a kwargs dict for
    # one.  Deliberately excluded from the checkpoint fingerprint: tracing
    # may be enabled/disabled across a resume (the trace dir itself is
    # fingerprint-stamped and append-only, with a `resume` marker).
    obs: Any = None


class RoundPrograms:
    """Jitted per-phase round programs, cached per cohort size.

    One FL round factors into (1) the client phase over the gathered
    cohort, (2) per-client eval, (3) server aggregation — the cohort
    gather before (1) and the scatter-back after (3) live in the
    ``CohortStore`` (DESIGN.md §12) — and both federation drivers
    (synchronous ``Federation`` here, buffered-asynchronous
    ``AsyncFederation`` in ``repro.fl.async_``) execute the SAME compiled
    programs from this cache.  That sharing is the
    correctness anchor of the async subsystem: in its degenerate
    configuration the async driver feeds identical operands to identical
    programs, so its history matches the synchronous one bitwise
    (DESIGN.md §10, tests/test_async_federation.py).

    Engines (and therefore the client/eval programs, whose mesh is baked
    in at trace time) are cached per ``(cohort size, mesh signature)``
    (DESIGN.md §11) — the signature is the engine's resolved layout id
    (``engine.signature()``), so a micro-cohort whose client split falls
    back to a different layout gets its own program entry instead of
    colliding with the full-cohort one.  The aggregate programs are
    single ``jax.jit`` objects that retrace per operand shape.  The
    async scheduler dispatches in grouped cohorts, so the cache stays
    bounded by the distinct (cohort, layout) pairs actually seen.

    ``strict_shards=False`` (the async driver) falls back when an
    explicitly requested split does not divide a micro-cohort — to the
    largest dividing shard count on the 1-D client mesh, and to an
    unsharded (cohort-replicated) client axis on a multi-pod mesh; the
    synchronous driver keeps the strict §3 validation (a requested split
    must never be silently changed).
    """

    def __init__(self, method, loss_fn, acc_fn, backend: str, shards: int = 0,
                 mesh: str = "", strict_shards: bool = True,
                 output_sharding: str = "replicated", grad_chunks: int = 1):
        self.method = method
        self.loss_fn = loss_fn
        self.acc_fn = acc_fn
        self.backend = backend
        self.shards = shards
        self.mesh = mesh
        self.strict_shards = strict_shards
        self.output_sharding = output_sharding
        self.grad_chunks = grad_chunks
        self._engines: Dict[int, Any] = {}
        self._client: Dict[Any, Any] = {}
        self._eval: Dict[Any, Any] = {}
        self._replicate: Dict[Any, Any] = {}
        self._shardings: Dict[Any, Any] = {}
        self._aggregate_sharded: Dict[Any, Any] = {}
        # the owning driver swaps in its facade; cache-miss events make
        # recompilation visible on the timeline (DESIGN.md §13) and are
        # the ONLY thing obs touches here — programs are identical either way
        self.obs = NOOP
        method_ = method

        # every program's function name is its XLA module's name
        # (``jit_<name>``), which is how a device trace attributes its
        # operations to a phase (DESIGN.md §13)
        def aggregate(broadcast, uploads):
            return method_.server_update(broadcast, uploads)

        def aggregate_stale(broadcast, uploads, staleness):
            return method_.server_update_stale(broadcast, uploads, staleness)

        self.aggregate = jax.jit(aggregate)
        self.aggregate_stale = jax.jit(aggregate_stale)

    def seen_cohorts(self):
        """Cohort sizes an engine was actually instantiated for (sorted)."""
        return sorted(self._engines)

    def engine(self, cohort: int):
        eng = self._engines.get(cohort)
        if eng is None:
            # micro-cohort split fallbacks live in make_engine(strict=False)
            eng = make_engine(self.backend, cohort, self.shards,
                              mesh=self.mesh, strict=self.strict_shards,
                              data_chunks=self.grad_chunks)
            self._engines[cohort] = eng
            self.obs.event("engine_create", cat="compile", cohort=cohort,
                           signature=eng.signature(), backend=self.backend)
        return eng

    def _key(self, cohort: int):
        """(cohort size, mesh signature) program-cache key (DESIGN.md §11)."""
        return (cohort, self.engine(cohort).signature())

    def client_fn(self, cohort: int):
        """(gathered_states (c-stacked), broadcast, batches) ->
        (new_states, uploads, metrics).  The cohort gather happens in the
        CohortStore before this program runs (DESIGN.md §12) — a pure
        data movement, so the program sees bitwise the same operands the
        previous fused ``x[client_ids]`` gather produced.

        Mesh-backend outputs leave this program still client-sharded: the
        round-boundary all-gather is the separate ``replicate_fn`` program
        (pure data movement — same values, see
        ``MeshBackend.replicate``), so the drivers can time it as its own
        span; compose ``replicate_fn`` before server aggregation."""
        key = self._key(cohort)
        fn = self._client.get(key)
        if fn is None:
            engine = self.engine(cohort)
            method, loss_fn = self.method, self.loss_fn

            def one_client(state, broadcast, batch_seq):
                return method.client_round(loss_fn, state, broadcast, batch_seq)

            def client_round(gathered_states, broadcast, batches):
                return engine.client_phase_sharded(one_client, gathered_states,
                                                   broadcast, batches)

            fn = jax.jit(client_round)
            if self.grad_chunks > 1:
                # jit defers tracing to the first call, so the run-level
                # chunk count is announced around every call — the traced
                # body reads it via the dispatch context (DESIGN.md §11)
                jitted, n = fn, self.grad_chunks

                def fn(gathered_states, broadcast, batches):
                    with grad_chunk_count(n):
                        return jitted(gathered_states, broadcast, batches)

            self._client[key] = fn
            self.obs.event("program_cache_miss", cat="compile",
                           program="client", cohort=cohort, signature=key[1])
        return fn

    def sharded_outputs(self, cohort: int) -> bool:
        """Whether this cohort's round runs the §11 sharded-at-rest loop:
        the run opted in, the engine's client split is active, and the
        shard count is a power of two (the halving-tree boundary-alignment
        condition — see repro.optim.reduce).  Cohorts that fail the gate
        (vmap, fallback micro-cohorts, non-pow2 splits) keep the
        replicated path; both paths are bitwise identical."""
        if self.output_sharding != "sharded":
            return False
        eng = self.engine(cohort)
        return bool(getattr(eng, "client_sharded", False)) and is_pow2(
            eng.client_shards)

    def aggregate_fn(self, cohort: int):
        """Server aggregation program for this cohort: the shared host-path
        ``aggregate`` jit, or — under the §11 sharded round loop — the
        engine's ``aggregate_phase`` lowering of ``server_update``, which
        consumes the client-sharded uploads in place (no round-boundary
        all-gather) and reduces over the client-role axis in shard order."""
        if not self.sharded_outputs(cohort):
            return self.aggregate
        key = self._key(cohort)
        fn = self._aggregate_sharded.get(key)
        if fn is None:
            engine = self.engine(cohort)
            method_ = self.method

            def aggregate_sharded(broadcast, uploads):
                return engine.aggregate_phase(
                    method_.server_update, broadcast, uploads)

            fn = jax.jit(aggregate_sharded)
            self._aggregate_sharded[key] = fn
            self.obs.event("program_cache_miss", cat="compile",
                           program="aggregate_sharded", cohort=cohort,
                           signature=key[1])
        return fn

    def replicate_fn(self, cohort: int):
        """The round-boundary all-gather as its own program (None for
        engines whose outputs are born replicated, i.e. vmap — and None
        under the §11 sharded round loop, which is exactly the point:
        outputs stay client-sharded at rest and the all_gather span
        disappears from the trace)."""
        if self.sharded_outputs(cohort):
            return None
        key = self._key(cohort)
        fn = self._replicate.get(key, False)
        if fn is False:
            # the engine method's own name gives module jit_replicate
            rep = getattr(self.engine(cohort), "replicate", None)
            fn = None if rep is None else jax.jit(rep)
            self._replicate[key] = fn
        return fn

    def gather_shardings(self, cohort: int, stacked_struct):
        """Engine input shardings for a gathered cohort tree (cached per
        program key): ``NamedSharding`` per leaf for the mesh backends —
        the host store device_puts against them so a multi-pod mesh
        receives per-pod slices directly (DESIGN.md §12) — or None for
        engines without a mesh placement (vmap)."""
        key = self._key(cohort)
        if key not in self._shardings:
            eng = self.engine(cohort)
            fn = getattr(eng, "input_shardings", None)
            self._shardings[key] = None if fn is None else fn(stacked_struct)
        return self._shardings[key]

    def eval_fn(self, cohort: int):
        """(states (c-stacked), broadcast, test_sets) -> accuracies (c,)."""
        key = self._key(cohort)
        fn = self._eval.get(key)
        if fn is None:
            engine = self.engine(cohort)
            method, acc_fn = self.method, self.acc_fn

            def one_eval(state, broadcast, test):
                params = method.eval_params(state, broadcast)
                return acc_fn(params, test)

            def eval_round(states, broadcast, test_sets):
                return engine.eval_phase(one_eval, states, broadcast, test_sets)

            fn = jax.jit(eval_round)
            self._eval[key] = fn
            self.obs.event("program_cache_miss", cat="compile",
                           program="eval", cohort=cohort, signature=key[1])
        return fn


_HISTORY_KEYS = ("loss", "acc", "round_time", "sim_time")

# metric-histogram bucket edges (DESIGN.md §13): theta spans Eq. 14's
# domain [0, pi] in pi/8 steps; beta/loss use fixed decades so histograms
# from different runs/backends are directly comparable
_THETA_EDGES = tuple(i * np.pi / 8 for i in range(1, 8))
_BETA_EDGES = tuple(i / 10 for i in range(1, 10))
_LOSS_EDGES = (0.01, 0.03, 0.1, 0.3, 1.0, 2.0, 3.0, 5.0, 10.0)


class Federation:
    """Drives ``rounds`` FL rounds of ``method`` over ``data``.

    Sampling (client participation + local SGD batches) is host-side numpy
    seeded by ``run_cfg.seed`` and therefore identical across backends;
    backend choice only changes where the traced client phase executes.

    ``AsyncFederation`` (``repro.fl.async_``) subclasses this driver,
    reusing the construction, the shared phase programs, and the
    checkpoint core; ``_strict_shards`` is the only knob it flips (its
    micro-cohorts may not divide an explicitly requested shard count).

    ``availability`` (optional, ``repro.fl.availability``) attaches the
    client-heterogeneity model to the *simulated clock* only: the
    bulk-synchronous server samples obliviously and then waits for every
    sampled client to come online and finish, so each round advances
    ``sim_time`` by max_i(wait_i + duration_i).  Without a model every
    round costs one simulated unit.  The model never touches numerics or
    the participation RNG (it draws from its own seeded streams), so
    attaching it changes nothing but the ``sim_time`` history column.
    """

    def __init__(
        self,
        method,
        loss_fn: Callable[[Pytree, Dict], jnp.ndarray],
        acc_fn: Callable[[Pytree, Dict], jnp.ndarray],
        init_params: Pytree,
        data: FederatedData,
        run_cfg: FLRunConfig,
        availability=None,
    ):
        self._init_core(method, loss_fn, acc_fn, init_params, data, run_cfg)
        self.availability = availability
        self._obs_open()

    _strict_shards = True

    def _init_core(self, method, loss_fn, acc_fn, init_params, data, run_cfg):
        validate_method(method)
        if run_cfg.output_sharding not in ("replicated", "sharded"):
            raise ValueError(
                f"unknown output_sharding {run_cfg.output_sharding!r}; "
                "choose 'replicated' or 'sharded' (DESIGN.md §11)"
            )
        if run_cfg.output_sharding == "sharded" and run_cfg.backend == "vmap":
            raise ValueError(
                "output_sharding='sharded' is the mesh engines' layout "
                "opt-out (backend='shard_map'/'mesh'); vmap outputs are "
                "born replicated, so the request would be silently ignored"
            )
        if run_cfg.grad_chunks < 1:
            raise ValueError(
                f"grad_chunks must be >= 1, got {run_cfg.grad_chunks}"
            )
        if run_cfg.update_impl:
            method = override_update_impl(method, run_cfg.update_impl)
        self.method = method
        self.loss_fn = loss_fn
        self.acc_fn = acc_fn
        self.data = data
        self.cfg = run_cfg
        self.obs = make_obs(run_cfg.obs)
        self.rng = np.random.RandomState(run_cfg.seed)

        k = run_cfg.n_clients
        assert data.n_clients == k, (data.n_clients, k)
        self.kprime = max(1, int(round(run_cfg.participation * k)))
        self.T = run_cfg.local_iters or data.local_iters(run_cfg.batch)
        self.programs = RoundPrograms(method, loss_fn, acc_fn,
                                      run_cfg.backend, run_cfg.shards,
                                      mesh=run_cfg.mesh,
                                      strict_shards=self._strict_shards,
                                      output_sharding=run_cfg.output_sharding,
                                      grad_chunks=run_cfg.grad_chunks)
        self.programs.obs = self.obs
        # built eagerly: validates backend/shards at construction (§3)
        self.engine = self.programs.engine(self.kprime)

        # same init for every client (paper: "same initialization for all
        # methods"); states stacked on a leading K axis, living at rest in
        # the cohort store (device-resident by default; host/mmap for
        # fleet-scale K — DESIGN.md §12)
        proto = method.init_client(init_params)
        self.store = make_store(run_cfg.store, proto, k)
        # structure/rank probe for the engines' input shardings (the
        # stacked layout never changes, so compute it once)
        self._store_struct = self.store.stacked_struct()
        self.broadcast = method.init_server(init_params)
        self.best_acc = np.zeros(k, np.float64)  # per-client best (Table II)
        # explicit participation mask: ``best_acc > 0`` is NOT a
        # participation proxy — a participating client's best accuracy can
        # legitimately be 0.0 and must still count in mean_best_acc
        self.participated = np.zeros(k, bool)
        self.sim_time = 0.0
        self._round = 0
        self._history = {key: [] for key in _HISTORY_KEYS}

    @property
    def client_states(self):
        """The (K, ...)-stacked client states in the store's at-rest
        representation (jnp for the device store, numpy for host/mmap)."""
        return self.store.stacked()

    @client_states.setter
    def client_states(self, tree):
        self.store.load_stacked(tree)

    # -- observability (DESIGN.md §13) ------------------------------------

    def _obs_fingerprint(self) -> dict:
        """Facets stamped into the trace directory's meta.json.  The
        checkpoint fingerprint plus the method name: two methods (or two
        configs) must never append into one timeline."""
        return {"driver": "sync", "method": self.method.name,
                **self._run_fingerprint()}

    def _obs_open(self) -> None:
        if not self.obs.enabled:
            return
        self.obs.open(self._obs_fingerprint())
        self.obs.event("run_start", engine=self.engine.describe(),
                       rounds=self.cfg.rounds)
        if getattr(self.store, "promoted", False):
            # the host store silently spilled to disk-backed memmaps
            # (capacity threshold, §12) — surface it on the timeline
            self.obs.event("mmap_promote", store=self.store.describe())

    def attach_obs(self, obs):
        """Switch a live federation's observability to the facade ``obs``
        (an ``Obs``; ``repro.obs.NOOP`` switches it off) and open it
        under this run's fingerprint.  The driver and its round programs
        report to it from the next round on; programs and values are
        unchanged (tests/test_obs_invariance.py).  Closing it is the
        caller's.  Returns ``obs``."""
        self.obs = self.programs.obs = obs
        return obs.open(self._obs_fingerprint())

    def _observe_client_metrics(self, metrics) -> None:
        """Per-client method diagnostics -> histograms: the Gompertz
        weight beta and its angle theta (recovered host-side from Eq. 14's
        inverse), and the per-round fraction of personalized clients.
        Reads already-materialized host values only."""
        reg = self.obs.metrics
        if reg is None:
            return
        reg.histogram("client.loss", _LOSS_EDGES).observe(
            np.asarray(metrics["loss"], np.float64))
        beta = metrics.get("beta") if hasattr(metrics, "get") else None
        if beta is not None:
            b = np.asarray(beta, np.float64)
            reg.histogram("pfedsop.beta", _BETA_EDGES).observe(b)
            lam = getattr(getattr(self.method, "cfg", None), "lam", None)
            if lam is not None:
                reg.histogram("pfedsop.theta", _THETA_EDGES).observe(
                    theta_from_beta(b, lam))
        if hasattr(metrics, "get") and metrics.get("personalized") is not None:
            reg.gauge("pfedsop.personalized_frac").set(
                float(np.mean(np.asarray(metrics["personalized"], np.float64))))

    def _observe_round(self, t: int, m: dict, dt: float) -> None:
        reg = self.obs.metrics
        if reg is not None:
            reg.counter("rounds").inc()
            reg.gauge("loss").set(m["loss"])
            reg.gauge("acc").set(m["acc"])
            reg.gauge("round_time").set(dt)
            reg.set_gauges("store", self.store.stats())
            self.obs.flush_metrics(step=t, sim_time=self.sim_time)
        self.obs.flush()

    # -- round loop -------------------------------------------------------

    def run_round(self):
        """One round.  Returns the cohort's mean ``loss`` and ``acc``, its
        client ids (``clients``) and the real (unpadded) test rows its
        eval covered (``eval_samples``).

        Traced at ``round`` level the round records ``sample`` (the host's
        draws and gathers), one ``dispatch.<phase>`` span per program call
        and ``sync`` (the wait for the accuracies, where the round meets
        the device), and never blocks elsewhere; at ``phase`` level the
        program calls block under their phase names (``Obs.timed``)."""
        obs = self.obs
        with obs.span("sample"):
            ids = self.rng.choice(self.cfg.n_clients, self.kprime, replace=False)
            batches = self.data.sample_round_batches(self.rng, ids, self.T,
                                                     self.cfg.batch)
            tests = self.data.client_test_set(ids)
        gathered = obs.timed(
            "gather", self.store.gather,
            ids, self.programs.gather_shardings(self.kprime, self._store_struct)
        )
        out = obs.timed("client", self.programs.client_fn(self.kprime),
                        gathered, self.broadcast, batches)
        # round-boundary all-gather: its own program AND its own span —
        # the phase the mesh-gap analysis needs attributed (§11/§13)
        rep = self.programs.replicate_fn(self.kprime)
        if rep is not None:
            out = obs.timed("all_gather", rep, out)
        new_states, uploads, metrics = out
        # personalized eval against the pre-update broadcast (the model a
        # client would deploy this round)
        accs = obs.timed("eval", self.programs.eval_fn(self.kprime),
                         new_states, self.broadcast, tests)
        self.broadcast = obs.timed("aggregate",
                                   self.programs.aggregate_fn(self.kprime),
                                   self.broadcast, uploads)
        # write-back after upload (§12): the host store starts the d2h
        # copies here and overlaps them with the next round's host-side
        # sampling; the device store writes the K' rows in place into its
        # donated stack.
        # sync=False: blocking would serialize that overlap, so the span
        # measures submit time only.
        obs.timed("scatter", self.store.scatter, ids, new_states, sync=False)

        with obs.span("sync"):
            accs = np.asarray(accs, np.float64)
        self.best_acc[ids] = np.maximum(self.best_acc[ids], accs)
        self.participated[ids] = True
        if self.availability is not None:
            self.sim_time += self.availability.sync_round_duration(ids, self.sim_time)
        else:
            self.sim_time += 1.0
        self._observe_client_metrics(metrics)
        return {
            "loss": float(np.mean(np.asarray(metrics["loss"]))),
            "acc": float(np.mean(accs)),
            "clients": ids,
            "eval_samples": int(self.data.test_counts[ids].sum()),
        }

    def run(self, verbose: bool = False):
        obs = self.obs
        while self._round < self.cfg.rounds:
            t = self._round
            obs.xla_round_start(t)
            t0 = time.perf_counter()
            with obs.span("round", round=t, sim=self.sim_time):
                m = self.run_round()
            dt = time.perf_counter() - t0
            obs.xla_round_end(t)
            self._history["loss"].append(m["loss"])
            self._history["acc"].append(m["acc"])
            self._history["round_time"].append(dt)
            self._history["sim_time"].append(self.sim_time)
            self._round += 1
            if verbose and (t % 10 == 0 or t == self.cfg.rounds - 1):
                obs.log.info(
                    f"[{self.method.name}/{self.engine.name}] round {t:4d} "
                    f"loss={m['loss']:.4f} acc={m['acc']:.4f} ({dt:.2f}s)",
                    event="round", round=t, loss=m["loss"], acc=m["acc"],
                    dt=dt,
                )
            self._observe_round(t, m, dt)
            if (self.cfg.ckpt_every and self.cfg.ckpt_dir
                    and self._round % self.cfg.ckpt_every == 0):
                self.save(self.cfg.ckpt_dir)
        history = self._finalize_history()
        history["engine"] = self.engine.describe()
        obs.close()
        return history

    def _finalize_history(self):
        """History lists + mean_best_acc over the explicit participation
        mask (shared by both drivers — the ``best_acc > 0`` proxy it
        replaces dropped clients whose best accuracy is legitimately 0.0)."""
        history = {key: list(v) for key, v in self._history.items()}
        history["mean_best_acc"] = (
            float(np.mean(self.best_acc[self.participated]))
            if self.participated.any() else 0.0
        )
        return history

    # -- checkpoint / resume ----------------------------------------------

    def _ckpt_tree(self):
        # client_states are NOT in this tree: the store streams them in
        # client-range shards beside arrays.npz (CohortStore.save_shards,
        # DESIGN.md §12), bounding checkpoint working memory at one shard
        return {
            "broadcast": self.broadcast,
            "best_acc": self.best_acc,
            "participated": self.participated,
            "rng": rng_state_tree(self.rng),
            "history": {key: np.asarray(v, np.float64)
                        for key, v in self._history.items()},
        }

    def _run_fingerprint(self) -> dict:
        """Config facets a resumed run must share with the checkpoint
        writer for the restored RNG/clock streams to continue bitwise:
        the sampling/data-shape knobs plus the availability model.
        ``rounds`` is excluded on purpose (extending the budget keeps the
        common prefix bitwise), as are backend/shards/mesh, whose
        histories are parity-tested bit-exact across settings
        (tests/test_engine.py, tests/test_multipod.py; the async driver
        separately fingerprints its resolved ``n_pods``, which changes
        delivery granularity).  The store facets (kind/cache) are stamped
        too: store kinds are parity-tested bitwise as well, but the
        at-rest layout governs how the step directory's shard files are
        restored, so a resume silently changing it is surfaced rather
        than absorbed (DESIGN.md §12).
        """
        av = getattr(self, "availability", None)
        return {
            "seed": self.cfg.seed,
            "n_clients": self.cfg.n_clients,
            "participation": self.cfg.participation,
            "batch": self.cfg.batch,
            "local_iters": self.cfg.local_iters,
            "grad_chunks": self.cfg.grad_chunks,
            "update_impl": self.cfg.update_impl,
            "availability": None if av is None else dataclasses.asdict(av.cfg),
            "store": self.store.describe(),
        }

    def _check_run_fingerprint(self, extra: dict, ckpt_dir) -> None:
        want = self._run_fingerprint()
        if extra.get("run_cfg") != want:
            raise ValueError(
                f"checkpoint at {ckpt_dir} was written with run config "
                f"{extra.get('run_cfg')}, but this driver is configured "
                f"with {want}; resuming across a config change is not a "
                "bitwise continuation"
            )

    def _ckpt_extra(self) -> dict:
        return {"round": self._round, "sim_time": self.sim_time,
                "driver": "sync", "run_cfg": self._run_fingerprint()}

    def save(self, ckpt_dir) -> str:
        """Checkpoint the full driver state after ``self._round`` rounds:
        the driver tree into arrays.npz, the client-states stack streamed
        beside it in store shards (DESIGN.md §12)."""
        path = save_checkpoint(ckpt_dir, self._round, self._ckpt_tree(),
                               extra=self._ckpt_extra())
        self.store.save_shards(path)
        self.obs.event("checkpoint_save", cat="checkpoint", round=self._round)
        return path

    def _load_store_shards(self, ckpt_dir, step: int) -> None:
        self.store.load_shards(Path(ckpt_dir) / f"step_{step:08d}")

    def restore(self, ckpt_dir=None, step=None) -> int:
        """Restore state saved by ``save``; returns the round to resume at.

        Must be called on a freshly constructed, identically configured
        federation (the manifest's stamped config fingerprint rejects a
        mismatch); the resumed run reproduces the uninterrupted loss/acc
        history bitwise (tests/test_checkpoint_resume.py).
        """
        ckpt_dir = ckpt_dir or self.cfg.ckpt_dir
        manifest = read_manifest(ckpt_dir, step)
        ex = manifest["extra"]
        driver = ex.get("driver")
        if driver != "sync":
            raise ValueError(
                f"checkpoint at {ckpt_dir} was written by the {driver!r} "
                "driver, not 'sync'; resume it with the matching driver "
                "(e.g. train_federated.py --mode async)"
            )
        self._check_run_fingerprint(ex, ckpt_dir)
        # pin the validated manifest's step: with step=None a concurrent
        # writer could land a new latest between the two reads, loading
        # arrays the driver/fingerprint checks never saw
        tree, extra = load_checkpoint(ckpt_dir, self._ckpt_template(),
                                      step=manifest["step"])
        self._restore_core(tree, extra)
        self._load_store_shards(ckpt_dir, manifest["step"])
        self.obs.event("checkpoint_restore", cat="checkpoint",
                       round=self._round, step=manifest["step"])
        return self._round

    def _restore_core(self, tree, extra):
        self.broadcast = tree["broadcast"]
        self.best_acc = np.asarray(tree["best_acc"], np.float64)
        self.participated = np.asarray(tree["participated"], bool)
        restore_rng_state(self.rng, tree["rng"])
        self._history = {key: [float(x) for x in np.asarray(v)]
                         for key, v in tree["history"].items()}
        self._round = int(extra["round"])
        self.sim_time = float(extra["sim_time"])

    def _ckpt_template(self):
        tmpl = self._ckpt_tree()
        # history arrays vary in length across checkpoints; only the key
        # names matter for restore (repro.utils.checkpoint matches names)
        tmpl["history"] = {key: np.zeros(0, np.float64) for key in self._history}
        return tmpl


def masked_accuracy(apply_fn):
    """acc_fn factory for padded test sets ({"images","labels","mask"})."""

    def acc(params, test):
        logits = apply_fn(params, test)
        hit = (jnp.argmax(logits, -1) == test["labels"]).astype(jnp.float32)
        return jnp.sum(hit * test["mask"]) / jnp.maximum(jnp.sum(test["mask"]), 1.0)

    return acc
