"""Cohort-store unit + parity tests (DESIGN.md §12, ISSUE 7).

Units: config validation, LRU eviction order, deferred write-back after
upload, mmap round-trip, cache-hit accounting, checkpoint shard
streaming.  Integration: 3-way backend parity (vmap == shard_map == mesh)
with store=host vs store=device on a forced 8-device mesh, sync AND
async — the §12 bitwise contract — in a subprocess (XLA device count must
be set before jax initialises; the rest of the suite needs the single
real CPU device).
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fl.cohort_store import (
    DeviceStore,
    HostStore,
    StoreConfig,
    as_store_config,
    make_store,
)

REPO = Path(__file__).resolve().parents[1]

PROTO = {
    "w": np.arange(6, dtype=np.float32).reshape(2, 3),
    "nest": {"b": np.float32(0.5)},
}


def _host(k=8, **kw):
    return make_store(StoreConfig(kind="host", **kw), PROTO, k)


def _rows(store, ids):
    """np view of the at-rest rows for ``ids`` (flushes deferred writes)."""
    return jax.tree.map(lambda a: np.asarray(a[np.asarray(ids)]),
                        store.stacked())


class TestConfig:
    def test_as_store_config_resolution(self):
        assert as_store_config(None).kind == "device"
        assert as_store_config("mmap").kind == "mmap"
        cfg = StoreConfig(kind="host", cache_clients=3)
        assert as_store_config(cfg) is cfg
        with pytest.raises(TypeError):
            as_store_config(42)

    def test_invalid_kind_and_cache_rejected(self):
        with pytest.raises(ValueError, match="store kind"):
            StoreConfig(kind="gpu")
        with pytest.raises(ValueError, match="cache_clients"):
            StoreConfig(cache_clients=-1)
        with pytest.raises(ValueError, match="host/mmap"):
            StoreConfig(kind="device", cache_clients=4)
        with pytest.raises(ValueError, match="ckpt_shard_clients"):
            StoreConfig(ckpt_shard_clients=0)

    def test_make_store_kinds(self):
        assert isinstance(make_store(None, PROTO, 4), DeviceStore)
        assert isinstance(make_store("host", PROTO, 4), HostStore)
        assert not make_store("host", PROTO, 4).mmapped
        assert make_store("mmap", PROTO, 4).mmapped

    def test_host_auto_promotes_to_mmap_past_threshold(self, tmp_path):
        cfg = StoreConfig(kind="host", mmap_threshold_bytes=64,
                          mmap_dir=str(tmp_path))
        assert make_store(cfg, PROTO, 1024).mmapped


class TestGatherScatter:
    @pytest.mark.parametrize("kind", ["device", "host"])
    def test_gather_matches_rows_in_ids_order(self, kind):
        s = make_store(kind, PROTO, 8)
        got = s.gather(np.asarray([5, 1, 1]))
        for name in ["w"]:
            row = np.asarray(got[name])
            assert row.shape == (3, 2, 3)
            np.testing.assert_array_equal(row[0], PROTO["w"])
            np.testing.assert_array_equal(row[1], row[2])

    @pytest.mark.parametrize("kind", ["device", "host"])
    def test_scatter_roundtrips_bitwise(self, kind):
        s = make_store(kind, PROTO, 8)
        ids = np.asarray([2, 6])
        new = {
            "w": jnp.stack([jnp.full((2, 3), 7.25), jnp.full((2, 3), -1.5)]),
            "nest": {"b": jnp.asarray([3.0, 4.0], jnp.float32)},
        }
        s.scatter(ids, new)
        got = _rows(s, ids)
        np.testing.assert_array_equal(got["w"], np.asarray(new["w"]))
        np.testing.assert_array_equal(got["nest"]["b"], [3.0, 4.0])
        # untouched rows keep the broadcast init
        np.testing.assert_array_equal(_rows(s, [0])["w"][0], PROTO["w"])

    def test_host_write_back_is_deferred_until_host_access(self):
        """scatter starts the d2h copy but defers the numpy write until the
        next gather/stacked — the §12 overlap window."""
        s = _host()
        ids = np.asarray([1])
        new = {"w": jnp.ones((1, 2, 3)) * 9.0,
               "nest": {"b": jnp.asarray([8.0], jnp.float32)}}
        s.scatter(ids, new)
        assert len(s._writeback) == 1
        # the raw at-rest array still holds the old value (write deferred)
        np.testing.assert_array_equal(s._data["w"][1], PROTO["w"])
        # any host access flushes
        np.testing.assert_array_equal(_rows(s, [1])["w"][0], 9.0 * np.ones((2, 3)))
        assert not s._writeback

    def test_host_scatter_of_np_rows_writes_through(self):
        """Async deliveries arrive as host numpy rows: direct write, and any
        cached device row for those ids is dropped as stale."""
        s = _host(cache_clients=4)
        s.gather(np.asarray([0, 1]))  # warm the cache
        new = {"w": np.full((1, 2, 3), 5.0, np.float32),
               "nest": {"b": np.asarray([2.0], np.float32)}}
        s.scatter(np.asarray([0]), new)
        assert 0 not in s._lru and 1 in s._lru
        np.testing.assert_array_equal(_rows(s, [0])["w"][0], 5.0)
        # next gather re-fetches the written value through the cache path
        np.testing.assert_array_equal(
            np.asarray(s.gather(np.asarray([0]))["w"][0]), 5.0)


# one client's state with every leaf kind the methods stack: f32 weights,
# an int32 counter and a bool flag, the last two scalar per client
MIXED = {
    "w": np.arange(24, dtype=np.float32).reshape(2, 3, 4) / 7.0,
    "rounds_seen": np.int32(0),
    "has_delta": np.bool_(False),
}


def _mixed_rows(n, seed):
    """(n, ...) cohort of random MIXED rows."""
    r = np.random.RandomState(seed)
    return {
        "w": jnp.asarray(r.standard_normal((n, 2, 3, 4)).astype(np.float32)),
        "rounds_seen": jnp.asarray(r.randint(0, 100, n).astype(np.int32)),
        "has_delta": jnp.asarray(r.rand(n) > 0.5),
    }


def _bitwise_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(x.view(np.uint8), y.view(np.uint8))


class TestDeviceStoreRows:
    """The device store moves K' rows: a row-wise gather and an in-place
    scatter into the donated stack, bitwise those of x[ids] / at[ids].set."""

    @pytest.mark.parametrize("ids", [[3, 0, 7], [6], [1, 2, 3, 4, 5, 6, 7, 0]],
                             ids=["three", "one", "all"])
    def test_gather_and_scatter_bitwise_vs_reference(self, ids):
        s = make_store("device", MIXED, 8)
        s.scatter(np.arange(8), _mixed_rows(8, seed=0))  # distinct rows
        full = jax.tree.map(np.asarray, s.stacked())
        ids = np.asarray(ids)
        _bitwise_equal(s.gather(ids), jax.tree.map(lambda x: x[ids], full))
        new = _mixed_rows(len(ids), seed=1)
        s.scatter(ids, new)
        want = jax.tree.map(lambda f, n: jnp.asarray(f).at[ids].set(n), full, new)
        _bitwise_equal(s.stacked(), want)

    def test_scatter_consumes_the_previous_stack(self):
        s = make_store("device", MIXED, 8)
        for r in range(3):
            before = jax.tree.leaves(s.stacked())
            s.scatter(np.asarray([r, 7 - r]), _mixed_rows(2, seed=r))
            assert all(x.is_deleted() for x in before)
        st = s.stats()
        assert st["scatters"] == 3
        assert st["scatters_in_place"] == st["scatters"]

    def test_load_stacked_copies_the_callers_tree(self):
        tree = _mixed_rows(8, seed=2)
        keep = jax.tree.map(np.asarray, tree)
        s = make_store("device", MIXED, 8)
        s.load_stacked(tree)
        for r in range(2):
            s.scatter(np.asarray([r]), _mixed_rows(1, seed=10 + r))
        assert not any(x.is_deleted() for x in jax.tree.leaves(tree))
        _bitwise_equal(tree, keep)
        assert s.stats()["scatters_in_place"] == 2

    def test_repeated_ids_write_in_cohort_order(self):
        """The async driver delivers a client at most once per scatter (the
        scheduler never dispatches a client that is in flight), but a
        repeated id keeps at[ids].set's order: the last row wins."""
        ids = np.asarray([5, 2, 5, 5])
        s = make_store("device", MIXED, 8)
        full = jax.tree.map(np.asarray, s.stacked())
        new = _mixed_rows(len(ids), seed=3)
        s.scatter(ids, new)
        want = jax.tree.map(lambda f, n: jnp.asarray(f).at[ids].set(n), full, new)
        _bitwise_equal(s.stacked(), want)
        _bitwise_equal(_rows(s, [5]), jax.tree.map(lambda n: n[3:4], new))

    def test_host_store_reports_no_in_place_scatters(self):
        s = _host()
        s.scatter(np.asarray([1]), {"w": jnp.ones((1, 2, 3)),
                                    "nest": {"b": jnp.asarray([2.0])}})
        assert s.stats()["scatters"] == 1
        assert s.stats()["scatters_in_place"] == 0

    def test_in_place_count_reaches_the_obs_registry(self, tmp_path):
        from repro.configs.resnet_cifar import SMALL_CNN as CFG
        from repro.core.baselines import METHODS
        from repro.data import (FederatedData, dirichlet_partition,
                                make_class_conditional_images)
        from repro.fl import Federation, FLRunConfig
        from repro.fl.runtime import masked_accuracy
        from repro.models import cnn
        from repro.obs import ObsConfig, read_metrics

        images, labels = make_class_conditional_images(
            200, CFG.n_classes, CFG.cnn_image_size, seed=0)
        parts = dirichlet_partition(labels, 4, alpha=0.3, seed=0)
        data = FederatedData.from_partition(images, labels, parts, seed=0)
        params = cnn.init_params(jax.random.PRNGKey(0), CFG)
        tdir = tmp_path / "t"
        cfg = FLRunConfig(n_clients=4, participation=0.5, rounds=2, batch=8,
                          local_iters=1, seed=0,
                          obs=ObsConfig(trace_dir=str(tdir), level="round",
                                        quiet=True))
        fed = Federation(METHODS["pfedsop"](),
                         lambda p, b: cnn.loss_fn(p, CFG, b),
                         masked_accuracy(lambda p, t: cnn.apply(p, CFG, t["images"])),
                         params, data, cfg)
        fed.run()
        gauges = read_metrics(tdir / "metrics.jsonl")[-1]["gauges"]
        assert gauges["store.scatters"] == 2
        assert gauges["store.scatters_in_place"] == 2


class TestLRUCache:
    def test_eviction_order_is_least_recently_used(self):
        s = _host(cache_clients=2)
        s.gather(np.asarray([0]))
        s.gather(np.asarray([1]))
        s.gather(np.asarray([0]))  # touch 0: now 1 is the LRU entry
        s.gather(np.asarray([2]))  # evicts 1, not 0
        assert list(s._lru) == [0, 2]
        assert s.stats()["cache_evictions"] == 1
        s.gather(np.asarray([1]))  # miss: evicts 0 (front of [0, 2])
        assert list(s._lru) == [2, 1]

    def test_hit_accounting_and_h2d_savings(self):
        s = _host(cache_clients=4)
        s.gather(np.asarray([0, 1, 2, 3]))
        st = s.stats()
        assert (st["cache_hits"], st["cache_misses"]) == (0, 4)
        moved = st["h2d_bytes"]
        s.gather(np.asarray([3, 0]))  # pure hits: no new h2d traffic
        st = s.stats()
        assert (st["cache_hits"], st["cache_misses"]) == (2, 4)
        assert st["h2d_bytes"] == moved

    def test_cohort_larger_than_cache_is_still_correct(self):
        """K' > cache_clients: every id resolves even though insertion
        evicts earlier rows of the same cohort (regression test)."""
        s = _host(k=8, cache_clients=2)
        ids = np.asarray([0, 1, 2, 3, 0])
        got = s.gather(ids)
        assert np.asarray(got["w"]).shape == (5, 2, 3)
        s2 = _host(k=8)
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.asarray(s2.gather(ids)["w"]))

    def test_device_scatter_write_allocates_cache(self):
        s = _host(cache_clients=2)
        new = {"w": jnp.zeros((1, 2, 3)), "nest": {"b": jnp.asarray([1.0])}}
        s.scatter(np.asarray([5]), new)
        assert 5 in s._lru
        s.gather(np.asarray([5]))
        assert s.stats()["cache_hits"] == 1

    def test_sharded_gather_bypasses_cache(self):
        """A non-None shardings tree takes the bypass path: no cache fills."""
        dev = jax.devices()[0]
        shardings = jax.tree.map(
            lambda _: jax.sharding.SingleDeviceSharding(dev), PROTO)
        s = _host(cache_clients=4)
        s.gather(np.asarray([0, 1]), shardings)
        assert not s._lru
        assert s.stats()["cache_misses"] == 0


class TestMmap:
    def test_mmap_roundtrip_on_disk(self, tmp_path):
        cfg = StoreConfig(kind="mmap", mmap_dir=str(tmp_path))
        s = make_store(cfg, PROTO, 6)
        files = sorted(p.name for p in tmp_path.glob("*.mmap"))
        assert files, "mmap store must back its leaves with files"
        new = {"w": jnp.full((2, 2, 3), 4.5),
               "nest": {"b": jnp.asarray([1.0, 2.0], jnp.float32)}}
        s.scatter(np.asarray([0, 5]), new)
        got = s.gather(np.asarray([5, 0, 3]))
        np.testing.assert_array_equal(np.asarray(got["nest"]["b"]),
                                      [2.0, 1.0, 0.5])
        # the bytes really live in the backing file
        s.stacked()  # flush
        disk = np.memmap(tmp_path / "w.mmap", dtype=np.float32,
                         mode="r", shape=(6, 2, 3))
        np.testing.assert_array_equal(disk[0], 4.5 * np.ones((2, 3)))

    def test_shard_save_load_roundtrip(self, tmp_path):
        s = _host(k=10, ckpt_shard_clients=3)  # 4 shards: 3+3+3+1
        rng = np.random.RandomState(0)
        full = {"w": rng.randn(10, 2, 3).astype(np.float32),
                "nest": {"b": rng.randn(10).astype(np.float32)}}
        s.load_stacked(full)
        s.save_shards(tmp_path)
        assert len(list(tmp_path.glob("store_*.npz"))) == 4
        # a reader with DIFFERENT shard granularity restores exactly
        r = _host(k=10, ckpt_shard_clients=7)
        r.load_shards(tmp_path)
        for a, b in zip(jax.tree.leaves(r.stacked()), jax.tree.leaves(full)):
            np.testing.assert_array_equal(np.asarray(a), b)

    def test_shard_load_rejects_wrong_k_and_leaves(self, tmp_path):
        s = _host(k=4)
        s.save_shards(tmp_path)
        with pytest.raises(ValueError, match="clients"):
            _host(k=5).load_shards(tmp_path)
        other = make_store("host", {"z": np.zeros(3, np.float32)}, 4)
        with pytest.raises(ValueError, match="leaves"):
            other.load_shards(tmp_path)


class TestOffload:
    def test_host_store_offload_always_host(self):
        s = _host()
        out = s.offload({"x": jnp.ones(3)})
        assert isinstance(out["x"], np.ndarray)

    def test_device_store_offload_respects_force(self):
        s = make_store(None, PROTO, 4)
        dev = s.offload({"x": jnp.ones(3)})
        assert isinstance(dev["x"], jax.Array)
        host = s.offload({"x": jnp.ones(3)}, force_host=True)
        assert isinstance(host["x"], np.ndarray)


_PARITY_SCRIPT = textwrap.dedent(
    """
    import jax, numpy as np
    assert len(jax.devices()) == 8, jax.devices()
    from repro.configs.resnet_cifar import SMALL_CNN as CFG
    from repro.core.baselines import METHODS
    from repro.data import (FederatedData, dirichlet_partition,
                            make_class_conditional_images)
    from repro.fl import (AsyncConfig, AsyncFederation, AvailabilityConfig,
                          Federation, FLRunConfig)
    from repro.fl.runtime import masked_accuracy
    from repro.models import cnn

    images, labels = make_class_conditional_images(400, CFG.n_classes,
                                                   CFG.cnn_image_size, seed=0)
    parts = dirichlet_partition(labels, 8, alpha=0.3, seed=0)
    data = FederatedData.from_partition(images, labels, parts, seed=0)
    params = cnn.init_params(jax.random.PRNGKey(0), CFG)
    loss = lambda p, b: cnn.loss_fn(p, CFG, b)
    acc = masked_accuracy(lambda p, t: cnn.apply(p, CFG, t["images"]))

    def run(backend, mesh, store, mode):
        cfg = FLRunConfig(n_clients=8, participation=0.5, rounds=2, batch=8,
                          local_iters=2, seed=1, backend=backend, mesh=mesh,
                          store=store)
        if mode == "async":
            fed = AsyncFederation(METHODS["pfedsop"](), loss, acc, params,
                                  data, cfg,
                                  AsyncConfig(buffer_size=4, concurrency=4,
                                              availability=AvailabilityConfig()))
        else:
            fed = Federation(METHODS["pfedsop"](), loss, acc, params, data, cfg)
        h = fed.run()
        states = jax.tree.leaves(jax.tree.map(np.asarray, fed.client_states))
        return h, states

    ref = None
    for backend, mesh in [("vmap", ""), ("shard_map", ""),
                          ("mesh", "pods:2x2x2")]:
        for store in ["device", "host"]:
            mode_grid = ["sync", "async"] if store == "host" else ["sync"]
            for mode in mode_grid:
                h, states = run(backend, mesh, store, mode)
                if ref is None:
                    ref = (h, states)
                else:
                    assert h["loss"] == ref[0]["loss"], (backend, store, mode)
                    assert h["acc"] == ref[0]["acc"], (backend, store, mode)
                    assert all(np.array_equal(a, b)
                               for a, b in zip(ref[1], states)), (
                        backend, store, mode)
    print("COHORT_STORE_PARITY_OK")
    """
)


def test_three_way_backend_parity_host_vs_device_8dev():
    """vmap == shard_map == mesh, store=host vs store=device, sync + async:
    loss/acc histories AND final client states bitwise identical on a
    forced 8-device mesh (ISSUE 7 acceptance)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "-c", _PARITY_SCRIPT],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    assert "COHORT_STORE_PARITY_OK" in res.stdout
