"""Fleet-scale cohort store: per-client state at rest on host (DESIGN.md §12).

The federation keeps every client's personalized state as one stacked
pytree with a leading K axis (DESIGN.md §3).  Resident on device that
layout caps K at accelerator memory — but pFedSOP's partial participation
means each round touches only K' << K clients, so the store moves the
stack *at rest* to host numpy (optionally memory-mapped to disk past a
size threshold) and materializes only the round's participants on device:

    gather(ids)  host rows -> device (K', ...) cohort   [h2d]
    scatter(ids) device (K', ...) cohort -> host rows   [d2h, async]

K becomes a throughput knob instead of a memory limit.  Three stores
behind one interface, selected by ``StoreConfig.kind``:

  DeviceStore  the seed behaviour: stacked jnp tree resident on device,
               gather/scatter are the jitted take/at[ids].set programs the
               runtime previously owned.  kind="device".
  HostStore    stacked numpy at rest (kind="host"), or numpy memmaps under
               ``mmap_dir`` (kind="mmap"; a "host" store auto-promotes to
               mmap when its at-rest bytes exceed ``mmap_threshold_bytes``).
               Gather batches the participants' rows through ONE
               ``jax.device_put`` per leaf — against the engine's input
               shardings when provided, so a multi-pod mesh receives
               per-pod slices directly (DESIGN.md §11) instead of a full
               replicated cohort.  Scatter starts ``copy_to_host_async``
               on every leaf and *defers* the numpy write-back until the
               next host access (gather/stacked/save), overlapping the
               d2h copies with the host-side sampling + dispatch of the
               next round — the §12 overlap timeline.

An optional LRU device cache (``cache_clients > 0``) keeps the most
recently touched clients' device rows resident, skipping the h2d copy for
frequently-sampled clients (hit/miss/eviction counts in ``stats()``).
The cache serves the default single-device placement only: a sharded
gather (mesh/shard_map input shardings) bypasses it, because per-pod
placement of individual cached rows would re-shard what the bypass path
lays out directly.

Bitwise contract (asserted in tests/test_cohort_store.py across
{vmap, shard_map, mesh} x {sync, async}): gather and scatter are pure
data movement — np<->jnp round-trips are bit-exact and the jitted phase
programs receive identical operand *values* regardless of store kind —
so a streamed federation reproduces the all-on-device history bitwise.

Checkpointing streams the store beside the driver's arrays.npz in
client-range shards (``store_00000.npz`` + ``store_manifest.json`` under
the same ``step_<N>/`` directory), bounding checkpoint working memory at
``ckpt_shard_clients`` rows regardless of K.
"""
from __future__ import annotations

import json
import tempfile
from collections import OrderedDict
from dataclasses import asdict, dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.checkpoint import _flatten_with_names

Pytree = Any

STORE_KINDS = ("device", "host", "mmap")


@dataclass(frozen=True)
class StoreConfig:
    """Where the K-stacked client states live at rest (DESIGN.md §12).

    ``kind``: "device" (seed behaviour, resident jnp stack), "host"
    (numpy at rest, auto-promoting to memmap past ``mmap_threshold_bytes``)
    or "mmap" (always disk-backed memmaps under ``mmap_dir``).

    ``cache_clients``: LRU device cache capacity in clients (0 = off);
    host/mmap stores only — the device store is its own cache.

    ``mmap_dir``: backing directory for memmapped leaves ("" = a fresh
    ``tempfile.mkdtemp``; checkpoints never depend on it — shards are
    written under the checkpoint step directory).

    ``mmap_threshold_bytes``: a "host" store spills to memmaps when the
    at-rest stack exceeds this many bytes (0 = never spill).

    ``ckpt_shard_clients``: clients per checkpoint shard file — the
    checkpoint path's working-memory bound.
    """

    kind: str = "device"
    cache_clients: int = 0
    mmap_dir: str = ""
    mmap_threshold_bytes: int = 4 << 30  # 4 GiB
    ckpt_shard_clients: int = 65536

    def __post_init__(self):
        if self.kind not in STORE_KINDS:
            raise ValueError(
                f"store kind must be one of {STORE_KINDS}, got {self.kind!r}"
            )
        if self.cache_clients < 0:
            raise ValueError(
                f"cache_clients must be >= 0, got {self.cache_clients}"
            )
        if self.cache_clients and self.kind == "device":
            raise ValueError(
                "cache_clients only applies to host/mmap stores (the device "
                "store is already resident); drop the flag or pick "
                "store='host'"
            )
        if self.ckpt_shard_clients < 1:
            raise ValueError(
                f"ckpt_shard_clients must be >= 1, got {self.ckpt_shard_clients}"
            )


def as_store_config(store) -> StoreConfig:
    """Resolve ``FLRunConfig.store``: None -> device, str -> kind, or a
    full ``StoreConfig`` passed through."""
    if store is None:
        return StoreConfig()
    if isinstance(store, str):
        return StoreConfig(kind=store)
    if isinstance(store, StoreConfig):
        return store
    raise TypeError(
        f"store must be None, a kind string {STORE_KINDS}, or a StoreConfig; "
        f"got {type(store).__name__}"
    )


def _tree_bytes(tree) -> int:
    return sum(leaf.nbytes for leaf in jax.tree.leaves(tree))


class CohortStore:
    """Interface + shared bookkeeping of the two store implementations.

    ``proto`` is ONE client's state pytree; the store broadcasts it to the
    (K,)-stacked at-rest layout (every client starts from the same
    initialization — paper Sec. V-B4).  Stats keys are the §12 bench
    columns: gathers/scatters, h2d/d2h bytes actually moved, and the LRU
    cache's hit/miss/eviction counters.
    """

    def __init__(self, cfg: StoreConfig, k: int):
        self.cfg = cfg
        self.k = k
        self._stats = {
            "gathers": 0, "scatters": 0, "h2d_bytes": 0, "d2h_bytes": 0,
            "cache_hits": 0, "cache_misses": 0, "cache_evictions": 0,
            # batched-cache counters (DESIGN.md §13): cohorts assembled by
            # the slot buffer's single gather-by-index, and rows written
            # into it by batched inserts (gather misses + scatter
            # write-through) — one device op each where the pre-batched
            # cache issued one per row
            "cache_assembles": 0, "cache_insert_rows": 0,
        }

    # -- the gather/scatter contract (DESIGN.md §12) ----------------------

    def gather(self, ids: np.ndarray, shardings=None) -> Pytree:
        """Stacked (K', ...) device cohort for ``ids`` (row order = ids
        order).  ``shardings``: optional tree of ``NamedSharding`` (one
        per leaf, from ``FederationEngine.input_shardings``) the cohort is
        placed against — the mesh backends' per-pod gather."""
        raise NotImplementedError

    def scatter(self, ids: np.ndarray, new_states: Pytree) -> None:
        """Write the (K', ...) cohort back to rows ``ids``."""
        raise NotImplementedError

    def offload(self, tree: Pytree, force_host: bool = False) -> Pytree:
        """Representation for results buffered OUTSIDE the store (the
        async driver's in-flight dispatches): host copies whenever the
        store itself is host-resident — buffered uploads must never pin
        device memory — or when the caller forces it (the sharded-backend
        mesh-lifetime rule in ``AsyncFederation._dispatch``)."""
        raise NotImplementedError

    # -- whole-stack access (checkpoints, tests, property access) ---------

    def stacked(self) -> Pytree:
        """The full (K, ...) stacked tree in the at-rest representation."""
        raise NotImplementedError

    def load_stacked(self, tree: Pytree) -> None:
        """Replace the full stack (values copied into the at-rest layout)."""
        raise NotImplementedError

    def stacked_struct(self) -> Pytree:
        """ShapeDtypeStruct tree of the stacked layout (pspec probes)."""
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype),
            self.stacked(),
        )

    # -- stats / fingerprint ----------------------------------------------

    def stats(self) -> dict:
        return dict(self._stats)

    def describe(self) -> dict:
        """Store facets stamped into the checkpoint fingerprint
        (repro.fl.runtime._run_fingerprint): the at-rest layout a resumed
        driver must share to restore the step directory's shard files."""
        return {"kind": self.cfg.kind, "cache_clients": self.cfg.cache_clients}

    # -- checkpoint shard streaming (DESIGN.md §12) -----------------------

    def _shard_ranges(self):
        s = self.cfg.ckpt_shard_clients
        return [(lo, min(lo + s, self.k)) for lo in range(0, max(self.k, 1), s)]

    def save_shards(self, step_dir) -> None:
        """Stream the stack into ``<step_dir>/store_<i>.npz`` client-range
        shards + a ``store_manifest.json`` naming the flattened leaves —
        working memory is bounded by one shard, not K."""
        d = Path(step_dir)
        d.mkdir(parents=True, exist_ok=True)
        ranges = self._shard_ranges()
        names = None
        for i, (lo, hi) in enumerate(ranges):
            named = _flatten_with_names(self._host_block(lo, hi))
            if names is None:
                names = [n for n, _ in named]
            np.savez(d / f"store_{i:05d}.npz",
                     **{f"a{j}": leaf for j, (_, leaf) in enumerate(named)})
        manifest = {
            "k": self.k,
            "shard_clients": self.cfg.ckpt_shard_clients,
            "n_shards": len(ranges),
            "names": names or [],
            "store": self.describe(),
        }
        (d / "store_manifest.json").write_text(json.dumps(manifest, indent=1))

    def load_shards(self, step_dir) -> None:
        """Inverse of ``save_shards`` (validates K + leaf names)."""
        d = Path(step_dir)
        manifest = json.loads((d / "store_manifest.json").read_text())
        if manifest["k"] != self.k:
            raise ValueError(
                f"store shards at {d} hold {manifest['k']} clients, but this "
                f"federation has {self.k}"
            )
        want = [n for n, _ in _flatten_with_names(self._host_block(0, 0))]
        if manifest["names"] != want:
            raise ValueError(
                f"store shards at {d} hold leaves {manifest['names']}, but "
                f"this method's client state flattens to {want}"
            )
        ranges = self._shard_ranges()
        if manifest["n_shards"] != len(ranges) or (
                manifest["shard_clients"] != self.cfg.ckpt_shard_clients):
            # shard granularity is part of the on-disk layout; recompute
            # ranges from the writer's granularity so a reader with a
            # different ckpt_shard_clients still restores exactly
            s = int(manifest["shard_clients"])
            ranges = [(lo, min(lo + s, self.k))
                      for lo in range(0, max(self.k, 1), s)]
        for i, (lo, hi) in enumerate(ranges):
            data = np.load(d / f"store_{i:05d}.npz")
            block = [data[f"a{j}"] for j in range(len(want))]
            self._load_host_block(lo, hi, block)

    # subclass hooks: (lo, hi) client range as a host (numpy) pytree, and
    # its inverse taking flat leaves in _flatten_with_names order
    def _host_block(self, lo: int, hi: int) -> Pytree:
        raise NotImplementedError

    def _load_host_block(self, lo: int, hi: int, flat_leaves) -> None:
        raise NotImplementedError


class DeviceStore(CohortStore):
    """The seed layout: the (K, ...) stack resident on device.

    Gather/scatter are the jitted take / ``at[ids].set`` programs the
    runtime owned before §12 — byte-for-byte the same device values, so
    this store IS the baseline the streamed stores are parity-tested
    against."""

    def __init__(self, cfg: StoreConfig, proto: Pytree, k: int):
        super().__init__(cfg, k)
        self._stack = jax.tree.map(
            lambda x: jnp.broadcast_to(jnp.asarray(x), (k,) + jnp.shape(x)),
            proto,
        )

        # named functions: the XLA modules jit_store_gather /
        # jit_store_scatter, which a device trace attributes to the store
        def store_gather(full, ids):
            return jax.tree.map(lambda x: x[ids], full)

        def store_scatter(full, ids, new):
            return jax.tree.map(lambda f, n: f.at[ids].set(n), full, new)

        self._gather = jax.jit(store_gather)
        self._scatter = jax.jit(store_scatter)

    def gather(self, ids, shardings=None):
        # shardings are an h2d placement hint; the resident stack already
        # lives where jit wants it, and the engine's in_specs re-lay it out
        self._stats["gathers"] += 1
        return self._gather(self._stack, jnp.asarray(ids))

    def scatter(self, ids, new_states):
        self._stats["scatters"] += 1
        self._stack = self._scatter(
            self._stack, jnp.asarray(ids),
            jax.tree.map(jnp.asarray, new_states),
        )

    def offload(self, tree, force_host=False):
        return jax.device_get(tree) if force_host else tree

    def stacked(self):
        return self._stack

    def load_stacked(self, tree):
        self._stack = jax.tree.map(jnp.asarray, tree)

    def _host_block(self, lo, hi):
        return jax.tree.map(lambda x: np.asarray(x[lo:hi]), self._stack)

    def _load_host_block(self, lo, hi, flat_leaves):
        flat, treedef = jax.tree_util.tree_flatten(self._stack)
        flat = [f.at[lo:hi].set(jnp.asarray(b)) for f, b in zip(flat, flat_leaves)]
        self._stack = jax.tree_util.tree_unflatten(treedef, flat)


class HostStore(CohortStore):
    """Host-at-rest store: numpy (or memmap) stack + LRU device cache.

    See the module docstring for the gather/scatter/overlap semantics.
    The at-rest tree is plain numpy; ``kind="mmap"`` (or a "host" store
    crossing ``mmap_threshold_bytes``) backs each leaf with an
    ``np.memmap`` under ``mmap_dir`` so K is bounded by disk, not RAM.
    """

    def __init__(self, cfg: StoreConfig, proto: Pytree, k: int):
        super().__init__(cfg, k)
        proto_np = jax.tree.map(np.asarray, proto)
        total = k * _tree_bytes(proto_np)
        self.mmapped = cfg.kind == "mmap" or (
            cfg.mmap_threshold_bytes > 0 and total > cfg.mmap_threshold_bytes
        )
        self._mmap_dir = None
        if self.mmapped:
            self._mmap_dir = Path(
                cfg.mmap_dir or tempfile.mkdtemp(prefix="cohort_store_")
            )
            self._mmap_dir.mkdir(parents=True, exist_ok=True)

        def alloc(path_leaf):
            name, leaf = path_leaf
            shape = (k,) + leaf.shape
            if self.mmapped:
                f = self._mmap_dir / (name.replace("/", ".") + ".mmap")
                arr = np.memmap(f, dtype=leaf.dtype, mode="w+", shape=shape)
            else:
                arr = np.empty(shape, leaf.dtype)
            arr[...] = leaf  # broadcast the shared init row-wise
            return arr

        named = _flatten_with_names(proto_np)
        leaves = [alloc(nl) for nl in named]
        self._names = [n for n, _ in named]
        _, self._treedef = jax.tree_util.tree_flatten(proto_np)
        self._data = jax.tree_util.tree_unflatten(self._treedef, leaves)
        self.at_rest_bytes = k * _tree_bytes(proto_np)
        # a "host" store that crossed mmap_threshold_bytes silently spilled
        # to disk — surfaced as a timeline event by the drivers (§13)
        self.promoted = cfg.kind == "host" and self.mmapped
        # deferred write-backs: (ids, device tree) with d2h copies started
        self._writeback: List[Tuple[np.ndarray, Pytree]] = []
        # LRU device cache as a slot buffer (see _slots_* above): one
        # (cache_clients, ...)-stacked device tree (lazily allocated),
        # client id -> slot index in LRU order, and the free slot pool
        self._slots: Optional[Pytree] = None
        self._lru: "OrderedDict[int, int]" = OrderedDict()
        self._free: List[int] = []

    # -- deferred write-back ----------------------------------------------

    def _flush(self):
        """Materialize pending scatters into the numpy stack (FIFO: last
        write wins, matching the scatter order)."""
        for ids, tree in self._writeback:
            host = jax.tree.map(np.asarray, tree)  # copies already in flight
            jax.tree.map(lambda a, h: a.__setitem__(ids, h), self._data, host)
        self._writeback.clear()

    # -- gather / scatter --------------------------------------------------

    def gather(self, ids, shardings=None):
        self._flush()
        self._stats["gathers"] += 1
        ids = np.asarray(ids)
        if shardings is not None or not self.cfg.cache_clients:
            # bypass path: one batched fancy-index + device_put per leaf,
            # placed against the engine's input shardings when given (the
            # mesh backends' per-pod slices land on their pods directly)
            block = jax.tree.map(lambda a: a[ids], self._data)
            self._stats["h2d_bytes"] += _tree_bytes(block)
            if shardings is None:
                return jax.tree.map(jax.device_put, block)
            return jax.tree.map(jax.device_put, block, shardings)
        return self._gather_cached(ids)

    def _ensure_slots(self):
        if self._slots is None:
            cap = self.cfg.cache_clients
            self._slots = jax.tree.map(
                lambda a: jnp.zeros((cap,) + a.shape[1:], a.dtype), self._data
            )
            self._free = list(range(cap - 1, -1, -1))  # pop() fills 0, 1, ...

    def _gather_cached(self, ids):
        """Cohort assembly through the LRU slot buffer: ONE batched
        gather-by-index over [slot buffer ‖ fetched miss block] instead of
        a per-row stack (DESIGN.md §12) — row values bit-identical.

        The output index map is computed BEFORE any cache bookkeeping:
        filling a miss can evict a slot this same cohort still needs (a
        hit older in LRU order, or an earlier miss when K' exceeds the
        capacity), so assembly must see the pre-insertion slot layout.
        """
        id_list = ids.tolist()
        cap = self.cfg.cache_clients
        lru = self._lru
        # duplicate occurrences count per-occurrence, and a duplicated miss
        # fetches (and later writes) its row once per occurrence with the
        # last one winning — the per-row cache's exact semantics
        miss = [i for i in id_list if i not in lru]
        self._stats["cache_hits"] += len(id_list) - len(miss)
        self._stats["cache_misses"] += len(miss)
        self._stats["cache_assembles"] += 1
        block = None
        if miss:
            self._ensure_slots()
            marr = np.asarray(miss, np.int64)
            host_block = jax.tree.map(lambda a: a[marr], self._data)
            self._stats["h2d_bytes"] += _tree_bytes(host_block)
            block = jax.tree.map(jax.device_put, host_block)
        mpos = {i: j for j, i in enumerate(miss)}  # last occurrence wins
        idx = np.asarray(
            [lru[i] if i in lru else cap + mpos[i] for i in id_list],
            np.int64,
        )
        if block is None:
            cohort = _slots_take(self._slots, idx)
        else:
            cohort = _slots_assemble(self._slots, block, idx)
        # LRU bookkeeping, in the per-row cache's exact order: hits touch
        # in cohort order, then misses insert (evicting from the front) in
        # miss order
        for i in id_list:
            if i in lru:
                lru.move_to_end(i)
        pend: Dict[int, int] = {}
        for j, i in enumerate(miss):
            if i in lru:  # duplicated miss: already placed this cohort
                lru.move_to_end(i)
            else:
                if len(lru) >= cap:
                    _, slot = lru.popitem(last=False)
                    self._free.append(slot)
                    self._stats["cache_evictions"] += 1
                lru[i] = self._free.pop()
            pend[i] = j
        # one batched fill for the misses that survived their own cohort's
        # evictions (an id evicted above never reaches the slot buffer,
        # exactly as its row never stayed in the per-row cache)
        live = [(lru[i], j) for i, j in pend.items() if i in lru]
        if live:
            sarr = np.asarray([s for s, _ in live], np.int64)
            jarr = np.asarray([j for _, j in live], np.int64)
            self._slots = _slots_insert(self._slots, block, jarr, sarr)
            self._stats["cache_insert_rows"] += len(live)
        return cohort

    def scatter(self, ids, new_states):
        self._stats["scatters"] += 1
        ids = np.asarray(ids)
        leaves = jax.tree.leaves(new_states)
        on_device = leaves and isinstance(leaves[0], jax.Array)
        if not on_device:
            # host-resident cohort (async deliveries of offloaded rows):
            # write through directly, no d2h copy to wait on
            host = jax.tree.map(np.asarray, new_states)
            jax.tree.map(lambda a, h: a.__setitem__(ids, h), self._data, host)
            for i in ids.tolist():  # cached device rows are now stale
                slot = self._lru.pop(i, None)
                if slot is not None:
                    self._free.append(slot)
            return
        # start the d2h copies now, materialize at the next host access:
        # the copy overlaps the host-side sampling/dispatch of the next
        # round (the §12 overlap timeline)
        jax.tree.map(lambda x: x.copy_to_host_async(), new_states)
        self._stats["d2h_bytes"] += _tree_bytes(new_states)
        self._writeback.append((ids, new_states))
        if self.cfg.cache_clients:
            # write-through into the slot buffer, one batched fill: rows
            # already resident refresh in place; new rows only while free
            # capacity remains (the per-row cache's sequential admission —
            # scatter never evicts)
            self._ensure_slots()
            lru, pend = self._lru, {}
            for j, i in enumerate(ids.tolist()):
                if i in lru:
                    lru.move_to_end(i)
                    pend[i] = j
                elif len(lru) < self.cfg.cache_clients:
                    lru[i] = self._free.pop()
                    pend[i] = j
            if pend:
                sarr = np.asarray([lru[i] for i in pend], np.int64)
                jarr = np.asarray(list(pend.values()), np.int64)
                self._slots = _slots_insert(self._slots, new_states, jarr, sarr)
                self._stats["cache_insert_rows"] += len(pend)

    def offload(self, tree, force_host=False):
        del force_host  # host store: buffered results NEVER pin device memory
        jax.tree.map(
            lambda x: x.copy_to_host_async() if isinstance(x, jax.Array) else None,
            tree,
        )
        return jax.device_get(tree)

    # -- whole-stack access -----------------------------------------------

    def stacked(self):
        self._flush()
        return self._data

    def _drop_cache(self):
        self._slots = None  # reallocated lazily on the next cached access
        self._lru.clear()
        self._free = []

    def load_stacked(self, tree):
        self._writeback.clear()
        self._drop_cache()
        jax.tree.map(
            lambda a, src: a.__setitem__(slice(None), np.asarray(src)),
            self._data, tree,
        )

    def _host_block(self, lo, hi):
        self._flush()
        return jax.tree.map(lambda a: np.asarray(a[lo:hi]), self._data)

    def _load_host_block(self, lo, hi, flat_leaves):
        self._writeback.clear()
        self._drop_cache()
        flat, _ = jax.tree_util.tree_flatten(self._data)
        for a, b in zip(flat, flat_leaves):
            a[lo:hi] = b


# -- batched LRU slot-buffer programs (DESIGN.md §12) -----------------------
#
# The LRU device cache keeps its resident rows in ONE (C, ...)-stacked
# device tree (the "slot buffer") instead of C per-row arrays, so cohort
# assembly and cache fill are single jitted programs over the whole cohort
# rather than per-row stacks/slices.  Pure data movement — row values are
# bit-identical to the per-row representation they replace (asserted in
# tests/test_cohort_store.py).  Module-level jits: shared across stores,
# cached per (capacity, cohort, leaf) shapes.

@jax.jit
def _slots_take(slots, idx):
    """Assemble an all-hit cohort: one gather-by-index per leaf."""
    return jax.tree.map(lambda s: s[idx], slots)


@jax.jit
def _slots_assemble(slots, block, idx):
    """Assemble a mixed cohort from the slot buffer (C rows) and the
    freshly fetched miss block (M rows): index into their concatenation —
    position j < C selects slot j, position C + m selects miss row m."""
    return jax.tree.map(lambda s, b: jnp.concatenate([s, b], 0)[idx],
                        slots, block)


@jax.jit
def _slots_insert(slots, src, jarr, sarr):
    """Batched cache fill: slot[sarr[r]] = src[jarr[r]] for every row r."""
    return jax.tree.map(lambda s, x: s.at[sarr].set(x[jarr]), slots, src)


def make_store(store, proto: Pytree, k: int) -> CohortStore:
    """Store factory (``FLRunConfig.store`` -> a ``CohortStore``)."""
    cfg = as_store_config(store)
    if cfg.kind == "device":
        return DeviceStore(cfg, proto, k)
    return HostStore(cfg, proto, k)
