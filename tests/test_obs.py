"""Unit tests for repro.obs (DESIGN.md §13): tracer span semantics,
Chrome-trace export schema, metrics histograms, the structured logger,
fingerprint-stamped resume-append, and the zero-bytes-disabled contract.
The cross-backend bitwise invariance contract lives in
tests/test_obs_invariance.py (forced 8-device subprocess)."""
import json
import logging

import jax
import numpy as np
import pytest

from repro.obs import (
    NOOP,
    Histogram,
    MetricsRegistry,
    Obs,
    ObsConfig,
    Tracer,
    as_obs_config,
    export_chrome,
    make_obs,
    read_events,
    read_metrics,
)
from repro.obs.log import ObsLog


class TestObsConfig:
    def test_bad_level_raises(self):
        with pytest.raises(ValueError, match="obs level"):
            ObsConfig(trace_dir="x", level="verbose")

    def test_as_obs_config_accepts_none_config_dict(self):
        assert as_obs_config(None) is None
        cfg = ObsConfig(trace_dir="x")
        assert as_obs_config(cfg) is cfg
        assert as_obs_config({"trace_dir": "y"}).trace_dir == "y"
        with pytest.raises(TypeError, match="obs must be"):
            as_obs_config(42)

    def test_enabled_requires_level_and_sink(self):
        assert not make_obs(None).enabled
        assert make_obs(None) is NOOP
        assert not Obs(ObsConfig(level="off", trace_dir="x")).enabled
        assert not Obs(ObsConfig(level="phase")).enabled  # no sink
        assert Obs(ObsConfig(level="phase", trace_dir="x")).enabled

    def test_disabled_facade_writes_nothing(self, tmp_path):
        target = tmp_path / "never"
        obs = Obs(ObsConfig(level="off", trace_dir=str(target)))
        obs.open(fingerprint={"a": 1})
        with obs.span("round"):
            obs.event("x")
            obs.flush_metrics(step=0)
        obs.close()
        assert not target.exists()
        assert list(tmp_path.iterdir()) == []


class TestTracer:
    def test_span_nesting_depth(self, tmp_path):
        tr = Tracer(tmp_path / "t", fingerprint={"s": 1})
        with tr.span("outer"):
            with tr.span("inner", track="srv"):
                with tr.span("leaf"):
                    pass
        tr.event("done")
        tr.close()
        evs = read_events(tmp_path / "t")
        spans = {e["name"]: e for e in evs if e["k"] == "span"}
        # spans are written at exit (innermost first) with entry-time depth
        assert [e["name"] for e in evs if e["k"] == "span"] == [
            "leaf", "inner", "outer"]
        assert spans["outer"]["depth"] == 0
        assert spans["inner"]["depth"] == 1
        assert spans["leaf"]["depth"] == 2
        assert all("dur" in s and "ts" in s for s in spans.values())

    def test_resume_appends_with_marker(self, tmp_path):
        fp = {"seed": 3, "driver": "sync"}
        tr = Tracer(tmp_path / "t", fingerprint=fp)
        tr.event("first")
        tr.close()
        tr2 = Tracer(tmp_path / "t", fingerprint=fp)
        tr2.event("second")
        tr2.close()
        names = [e["name"] for e in read_events(tmp_path / "t")
                 if e["k"] == "ev"]
        assert names == ["first", "resume", "second"]
        marker = [e for e in read_events(tmp_path / "t")
                  if e["name"] == "resume"][0]
        assert marker["cat"] == "marker"

    def test_fingerprint_mismatch_raises(self, tmp_path):
        Tracer(tmp_path / "t", fingerprint={"seed": 3}).close()
        with pytest.raises(ValueError, match="incomparable timelines"):
            Tracer(tmp_path / "t", fingerprint={"seed": 4})

    def test_chrome_export_schema(self, tmp_path):
        tr = Tracer(tmp_path / "t", fingerprint=None)
        with tr.span("round", sim=2.5):
            pass
        tr.event("dispatch", track="async", sim=1.0, cohort=3)
        tr.client_span(7, "inflight", 1.0, 4.0, pod=1)
        tr.sink({"k": "log", "event": "round", "msg": "hi"})
        tr.close()
        path = export_chrome(tmp_path / "t")
        doc = json.loads(path.read_text())
        assert path.name == "trace.json"
        assert doc["displayTimeUnit"] == "ms"
        evs = doc["traceEvents"]
        assert isinstance(evs, list)
        for e in evs:
            assert e["ph"] in ("M", "X", "i")
            assert isinstance(e["pid"], int) and "name" in e
            if e["ph"] == "X":
                assert isinstance(e["ts"], int) and isinstance(e["dur"], int)
        # client span: sim pid, tid = client+1, sim seconds -> trace µs
        cspan = [e for e in evs if e["ph"] == "X" and e["pid"] == 2][0]
        assert cspan["tid"] == 8
        assert cspan["ts"] == 1_000_000 and cspan["dur"] == 3_000_000
        # sim-annotated server records mirror as instants on the sim track
        mirrors = [e for e in evs if e["ph"] == "i" and e["pid"] == 2]
        assert {m["name"] for m in mirrors} == {"round", "dispatch"}
        # log records never become timeline entries
        assert not any(e.get("cat") == "log" for e in evs)

    def test_zero_duration_cspan_renders_visible(self, tmp_path):
        tr = Tracer(tmp_path / "t", fingerprint=None)
        tr.client_span(0, "buffered", 2.0, 2.0)
        tr.close()
        doc = json.loads(export_chrome(tmp_path / "t").read_text())
        cspan = [e for e in doc["traceEvents"] if e["ph"] == "X"][0]
        assert cspan["dur"] == 1


class TestHistogram:
    def test_right_open_buckets(self):
        h = Histogram(edges=[1.0, 2.0, 4.0])
        h.observe([0.5, 1.0, 1.5, 2.0, 3.9, 4.0, 100.0])
        assert h.counts == [1, 2, 2, 2]  # <1, [1,2), [2,4), >=4
        assert h.count == 7
        assert h.min == 0.5 and h.max == 100.0
        assert h.sum == pytest.approx(112.9)

    def test_accepts_scalars_and_arrays(self):
        h = Histogram(edges=[0.5])
        h.observe(0.1)
        h.observe(np.asarray([[0.6, 0.7], [0.1, 0.9]]))
        assert h.counts == [2, 3]
        h.observe(np.asarray([]))  # empty observation is a no-op
        assert h.count == 5

    def test_non_ascending_edges_raise(self):
        with pytest.raises(ValueError, match="ascending"):
            Histogram(edges=[2.0, 1.0])
        with pytest.raises(ValueError, match="ascending"):
            Histogram(edges=[])

    def test_snapshot_roundtrips_through_json(self):
        h = Histogram(edges=[1.0])
        h.observe([0.5, 2.0])
        snap = json.loads(json.dumps(h.snapshot()))
        assert snap["counts"] == [1, 1] and snap["count"] == 2


class TestMetricsRegistry:
    def test_counters_gauges_flush(self, tmp_path):
        reg = MetricsRegistry(tmp_path / "m.jsonl")
        reg.counter("rounds").inc()
        reg.counter("rounds").inc(2)
        reg.gauge("loss").set(0.5)
        reg.histogram("tau", edges=[1.0]).observe([0.0, 3.0])
        reg.flush(step=0, sim_time=1.5)
        reg.gauge("loss").set(0.25)
        reg.flush(step=1)
        reg.close()
        snaps = read_metrics(tmp_path / "m.jsonl")
        assert len(snaps) == 2
        assert snaps[0]["step"] == 0 and snaps[0]["sim_time"] == 1.5
        assert snaps[0]["counters"]["rounds"] == 3
        assert snaps[1]["gauges"]["loss"] == 0.25
        assert snaps[1]["histograms"]["tau"]["counts"] == [1, 1]

    def test_set_gauges_skips_non_numeric(self, tmp_path):
        reg = MetricsRegistry(None)
        reg.set_gauges("store", {"h2d_bytes": 10, "kind": "host",
                                 "promoted": True, "rate": 0.5})
        snap = reg.snapshot()
        assert snap["gauges"] == {"store.h2d_bytes": 10.0, "store.rate": 0.5}

    def test_pathless_registry_never_writes(self):
        reg = MetricsRegistry(None)
        reg.counter("x").inc()
        reg.flush(step=0)  # no sink: a no-op, not an error
        reg.close()


class TestObsLog:
    def test_quiet_suppresses_stdout_not_sink(self, capsys):
        recs = []
        log = ObsLog(quiet=True, sink=recs.append)
        log.info("hello", event="greet", n=1)
        assert capsys.readouterr().out == ""
        assert recs[0]["k"] == "log" and recs[0]["event"] == "greet"
        assert recs[0]["msg"] == "hello" and recs[0]["fields"] == {"n": 1}

    def test_loud_prints(self, capsys):
        ObsLog(quiet=False).info("to stdout")
        assert capsys.readouterr().out == "to stdout\n"

    def test_stdlib_logger_routing(self, caplog, capsys):
        lg = logging.getLogger("repro.test.obslog")
        with caplog.at_level(logging.INFO, logger="repro.test.obslog"):
            ObsLog(quiet=False).info("via stdlib", logger=lg)
        assert [r.getMessage() for r in caplog.records] == ["via stdlib"]
        # logger routing replaces the print (no double emission)
        assert capsys.readouterr().out == ""

    def test_debug_is_sink_only(self, capsys):
        recs = []
        ObsLog(quiet=False, sink=recs.append).debug("quiet detail")
        assert capsys.readouterr().out == ""
        assert recs[0]["msg"] == "quiet detail"

    def test_non_jsonable_fields_coerced(self, tmp_path):
        recs = []
        ObsLog(quiet=True, sink=recs.append).info(
            "x", arr=np.float32(1.5), path=tmp_path)
        json.dumps(recs[0])  # must be serializable as written


class TestObsFacade:
    def test_timed_returns_value_and_records(self, tmp_path):
        obs = Obs(ObsConfig(trace_dir=str(tmp_path / "t"), level="phase"))
        obs.open(fingerprint={"x": 1})
        out = obs.timed("work", lambda a, b: a + b, 2, 3, round=0)
        obs.close()
        assert out == 5
        spans = [e for e in read_events(tmp_path / "t") if e["k"] == "span"]
        assert spans[0]["name"] == "work"
        assert spans[0]["args"]["round"] == 0

    def test_round_level_skips_phase_spans(self, tmp_path, monkeypatch):
        """At ``round`` level a timed call records its host time as
        ``dispatch.<name>`` and never blocks on its outputs; no span goes
        under the phase's own name."""
        waits = []
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: waits.append(x) or x)
        obs = Obs(ObsConfig(trace_dir=str(tmp_path / "t"), level="round"))
        obs.open()
        assert obs.timed("work", lambda: 1, round=3) == 1
        obs.event("marker")
        obs.close()
        evs = read_events(tmp_path / "t")
        assert [e["name"] for e in evs] == ["dispatch.work", "marker"]
        assert evs[0]["args"] == {"round": 3} and evs[0]["dur"] >= 0
        assert waits == []

    def test_failed_xla_profile_start_raises(self, tmp_path, monkeypatch):
        """A requested --xla-profile capture that cannot start must fail
        the run, not leave one that exits 0 without the profile."""

        def refuse(*_a, **_k):
            raise RuntimeError("profiler unavailable")

        monkeypatch.setattr(jax.profiler, "start_trace", refuse)
        obs = Obs(ObsConfig(trace_dir=str(tmp_path / "t"), level="phase",
                            xla_profile=1))
        obs.open()
        obs.xla_round_start(0)  # not the requested round: no capture
        with pytest.raises(RuntimeError, match="profiler unavailable"):
            obs.xla_round_start(1)
        obs.close()

    def test_default_metrics_path_lands_in_trace_dir(self, tmp_path):
        obs = Obs(ObsConfig(trace_dir=str(tmp_path / "t"), level="phase"))
        obs.open()
        obs.metrics.counter("n").inc()
        obs.flush_metrics(step=0)
        obs.close()
        assert obs.final_metrics["counters"]["n"] == 1
        assert read_metrics(tmp_path / "t" / "metrics.jsonl")
        assert (tmp_path / "t" / "trace.json").exists()

    def test_close_is_idempotent(self, tmp_path):
        obs = Obs(ObsConfig(trace_dir=str(tmp_path / "t"), level="phase"))
        obs.open()
        obs.close()
        obs.close()


def test_theta_from_beta_matches_reference_aux():
    """The metrics-side inversion must reproduce the angle the reference
    update path computed (the fused kernel carries only beta)."""
    from repro.core.pfedsop import gompertz_weight, theta_from_beta

    k = jax.random.PRNGKey(0)
    for lam in (0.5, 1.0, 5.0):
        di = jax.random.normal(k, (64,))
        dg = jax.random.normal(jax.random.fold_in(k, 1), (64,))
        _, aux = gompertz_weight(di, dg, lam=lam)
        theta = theta_from_beta(float(aux["beta"]), lam)
        np.testing.assert_allclose(theta, float(aux["theta"]),
                                   rtol=1e-5, atol=1e-6)
    # clipping keeps degenerate betas finite and in [0, pi]
    for b in (0.0, 1.0, -1.0, 2.0):
        assert 0.0 <= theta_from_beta(b, 1.0) <= np.pi


class TestFederationObs:
    """Driver-level integration on a tiny sync federation."""

    def _fed(self, tmp_path, obs=None, seed=0):
        from repro.configs.resnet_cifar import SMALL_CNN as CFG
        from repro.core.baselines import METHODS
        from repro.data import (FederatedData, dirichlet_partition,
                                make_class_conditional_images)
        from repro.fl import Federation, FLRunConfig
        from repro.fl.runtime import masked_accuracy
        from repro.models import cnn

        images, labels = make_class_conditional_images(
            200, CFG.n_classes, CFG.cnn_image_size, seed=0)
        parts = dirichlet_partition(labels, 4, alpha=0.3, seed=0)
        data = FederatedData.from_partition(images, labels, parts, seed=0)
        params = cnn.init_params(jax.random.PRNGKey(0), CFG)
        loss = lambda p, b: cnn.loss_fn(p, CFG, b)
        acc = masked_accuracy(lambda p, t: cnn.apply(p, CFG, t["images"]))
        cfg = FLRunConfig(n_clients=4, participation=0.5, rounds=2, batch=8,
                          local_iters=1, seed=seed, obs=obs)
        return Federation(METHODS["pfedsop"](), loss, acc, params, data, cfg)

    def test_traced_run_emits_phases_and_metrics(self, tmp_path):
        tdir = tmp_path / "t"
        obs = ObsConfig(trace_dir=str(tdir), level="phase", quiet=True)
        fed = self._fed(tmp_path, obs=obs)
        hist = fed.run(verbose=True)
        assert len(hist["loss"]) == 2
        evs = read_events(tdir)
        spans = {e["name"] for e in evs if e["k"] == "span"}
        assert {"round", "gather", "client", "eval", "aggregate",
                "scatter"} <= spans
        rounds = [e for e in evs if e["k"] == "span" and e["name"] == "round"]
        assert len(rounds) == 2
        snaps = read_metrics(tdir / "metrics.jsonl")
        assert snaps[-1]["counters"]["rounds"] == 2
        assert {"client.loss", "pfedsop.beta",
                "pfedsop.theta"} <= set(snaps[-1]["histograms"])
        assert (tdir / "trace.json").exists()
        # quiet mode: round prints were recorded, not printed
        logs = [e for e in evs if e.get("k") == "log" and e["event"] == "round"]
        assert len(logs) == 2

    @staticmethod
    def _round_spans(evs):
        """Span names of each traced round, in the order they closed (the
        driver's own ``round`` span closes last)."""
        rounds, cur = [], []
        for e in evs:
            if e.get("k") == "span":
                cur.append(e["name"])
                if e["name"] == "round":
                    rounds.append(cur)
                    cur = []
        return rounds

    def test_round_level_spans_in_order_without_blocking(self, tmp_path,
                                                         monkeypatch):
        """Level ``round``: each round records sample, one dispatch span
        per program call and sync, and never calls block_until_ready."""
        waits = []
        real = jax.block_until_ready
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: waits.append(1) or real(x))
        tdir = tmp_path / "t"
        fed = self._fed(tmp_path, obs=ObsConfig(trace_dir=str(tdir),
                                                level="round", quiet=True))
        fed.run()
        assert waits == []
        want = ["sample", "dispatch.gather", "dispatch.client", "dispatch.eval",
                "dispatch.aggregate", "dispatch.scatter", "sync", "round"]
        assert self._round_spans(read_events(tdir)) == [want, want]

    def test_phase_level_keeps_phase_names_and_adds_sample_sync(self, tmp_path):
        tdir = tmp_path / "t"
        self._fed(tmp_path, obs=ObsConfig(trace_dir=str(tdir), level="phase",
                                          quiet=True)).run()
        want = ["sample", "gather", "client", "eval", "aggregate", "scatter",
                "sync", "round"]
        assert self._round_spans(read_events(tdir)) == [want, want]

    def test_round_spans_share_the_epoch_clock(self, tmp_path):
        """Span ``ts`` is epoch microseconds: a device trace's events,
        counted from its epoch-ns ``profile_start_time``, line up with
        one shift."""
        import time

        t0 = time.time_ns() // 1000
        tdir = tmp_path / "t"
        self._fed(tmp_path, obs=ObsConfig(trace_dir=str(tdir), level="round",
                                          quiet=True)).run()
        t1 = time.time_ns() // 1000
        spans = [e for e in read_events(tdir) if e.get("k") == "span"]
        assert all(t0 <= e["ts"] and e["ts"] + e["dur"] <= t1 for e in spans)
        # the round's inner spans follow one another (1 us: the two
        # clocks' roundings)
        inner = [e for e in spans if e["name"] != "round"]
        assert all(a["ts"] + a["dur"] <= b["ts"] + 1
                   for a, b in zip(inner, inner[1:]))

    def test_run_round_reports_its_cohort(self, tmp_path):
        fed = self._fed(tmp_path)
        for _ in range(3):
            m = fed.run_round()
            ids = np.asarray(m["clients"])
            assert len(set(ids.tolist())) == len(ids) == fed.kprime
            assert m["eval_samples"] == int(fed.data.test_counts[ids].sum()) > 0
            assert fed.participated[ids].all()

    def test_same_config_reopen_appends(self, tmp_path):
        obs = ObsConfig(trace_dir=str(tmp_path / "t"), level="round",
                        quiet=True)
        self._fed(tmp_path, obs=obs).run()
        self._fed(tmp_path, obs=obs).run()
        evs = read_events(tmp_path / "t")
        assert sum(1 for e in evs if e.get("name") == "resume") == 1

    def test_config_change_rejected(self, tmp_path):
        obs = ObsConfig(trace_dir=str(tmp_path / "t"), level="round",
                        quiet=True)
        self._fed(tmp_path, obs=obs, seed=0).run()
        with pytest.raises(ValueError, match="incomparable timelines"):
            self._fed(tmp_path, obs=obs, seed=1)
