"""repro.obs — zero-overhead-when-disabled observability (DESIGN.md §13).

Three instruments behind one ``Obs`` facade:

- ``Tracer`` (``repro.obs.trace``): nested wall-clock spans + discrete
  sim-time client tracks, JSONL event stream, Chrome-trace/Perfetto
  export, checkpoint-style fingerprint stamping with resume-append.
- ``MetricsRegistry`` (``repro.obs.metrics``): counters/gauges/
  histograms with a per-round JSONL snapshot sink.
- ``ObsLog`` (``repro.obs.log``): the structured logger every ad-hoc
  driver print routes through (quiet mode suppresses stdout only).

The hard contract (tests/test_obs_invariance.py): observability NEVER
touches traced values.  Every instrument reads host-side numbers the run
already produced; the only on-path effect of enabling it at ``phase``
level is wall-clock (``timed`` blocks between phases so span durations
are honest).  With it off (``FLRunConfig.obs = None``, the default) the
drivers hold the shared ``NOOP`` facade: no files, no objects, no extra
synchronization — training histories are bitwise identical to an
uninstrumented build.

Levels: ``off`` < ``round`` (round spans, metrics, and non-blocking
spans inside the round: ``sample``, ``dispatch.<phase>`` — the host time
of each program call — and ``sync``; no ``block_until_ready``, so the
device pipeline runs as it does untraced) < ``phase`` (per-phase spans
under the phase's own name, blocking between phases, plus ``sample`` and
``sync``).  Span ``ts`` is epoch microseconds, so a ``jax.profiler``
trace (whose events count from its ``profile_start_time``, epoch ns)
lines up with one shift.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Optional

from repro.obs.log import ObsLog
from repro.obs.metrics import Histogram, MetricsRegistry, read_metrics
from repro.obs.trace import Tracer, export_chrome, read_events

__all__ = [
    "OBS_LEVELS", "ObsConfig", "Obs", "NOOP", "make_obs", "as_obs_config",
    "get_obs", "ObsLog", "MetricsRegistry", "Histogram", "Tracer",
    "export_chrome", "read_events", "read_metrics",
    "LEVEL_OFF", "LEVEL_ROUND", "LEVEL_PHASE",
]

OBS_LEVELS = ("off", "round", "phase")
LEVEL_OFF, LEVEL_ROUND, LEVEL_PHASE = range(3)


@dataclass(frozen=True)
class ObsConfig:
    """Observability knobs, nested under ``FLRunConfig.obs``.

    ``trace_dir``: event-stream directory ("" = no tracing).  The drivers
    stamp it with the run's config fingerprint (``meta.json``); reopening
    with a matching fingerprint appends (a ``resume`` marker event marks
    the cut), a mismatch raises — mirroring checkpoint-restore rejection.
    Deliberately NOT part of the checkpoint fingerprint itself: resuming
    a run with tracing newly enabled (or disabled) is always allowed.

    ``metrics``: metrics.jsonl path; "" defaults to
    ``<trace_dir>/metrics.jsonl`` when tracing (and to off otherwise).

    ``level``: one of ``OBS_LEVELS`` — see the module docstring.

    ``quiet``: suppress the drivers' stdout progress lines (structured
    records still land in the trace).

    ``xla_profile``: 0-based round/version index to wrap in a
    ``jax.profiler`` trace window (dumped under ``<trace_dir>/xla``);
    -1 = off.  Round 1 is the first post-compile round.
    """

    trace_dir: str = ""
    metrics: str = ""
    level: str = "phase"
    quiet: bool = False
    xla_profile: int = -1

    def __post_init__(self):
        if self.level not in OBS_LEVELS:
            raise ValueError(
                f"obs level must be one of {OBS_LEVELS}, got {self.level!r}"
            )


def as_obs_config(obs) -> Optional[ObsConfig]:
    """Resolve ``FLRunConfig.obs``: None passes through (disabled)."""
    if obs is None or isinstance(obs, ObsConfig):
        return obs
    if isinstance(obs, dict):
        return ObsConfig(**obs)
    raise TypeError(
        f"obs must be None, an ObsConfig, or a kwargs dict; got "
        f"{type(obs).__name__}"
    )


_NULL_CTX = contextlib.nullcontext()


class Obs:
    """The facade the drivers thread through every layer.

    Constructed eagerly (``make_obs``) so the level/quiet knobs resolve
    at federation construction; file handles open in ``open()``, which
    the drivers call once the run fingerprint is known.  The shared
    ``NOOP`` instance (``Obs(None)``) is what a federation without an
    ``ObsConfig`` holds: every method is a cheap guard-and-return.
    """

    def __init__(self, cfg: Optional[ObsConfig]):
        self.cfg = cfg
        self.level = LEVEL_OFF
        self.enabled = False
        if cfg is not None and cfg.level != "off" and (
                cfg.trace_dir or cfg.metrics):
            self.level = OBS_LEVELS.index(cfg.level)
            self.enabled = True
        self.log = ObsLog(quiet=bool(cfg and cfg.quiet))
        self.tracer: Optional[Tracer] = None
        self.metrics: Optional[MetricsRegistry] = None
        # last registry snapshot, stashed by close() so callers that want
        # the final numbers (the bench harness embedding them in
        # BENCH_*.json) don't have to re-read metrics.jsonl
        self.final_metrics: Optional[dict] = None
        self._xla_active = False

    # -- lifecycle ---------------------------------------------------------

    def open(self, fingerprint: Optional[dict] = None) -> "Obs":
        """Open the sinks (idempotent).  ``fingerprint`` is stamped into
        (and checked against) the trace's ``meta.json``."""
        if not self.enabled:
            return self
        if self.cfg.trace_dir and self.tracer is None:
            self.tracer = Tracer(self.cfg.trace_dir, fingerprint=fingerprint)
            self.log.attach_sink(self.tracer.sink)
            _set_global(self)
        metrics_path = self.cfg.metrics or (
            str(Path(self.cfg.trace_dir) / "metrics.jsonl")
            if self.cfg.trace_dir else "")
        if metrics_path and self.metrics is None:
            self.metrics = MetricsRegistry(metrics_path)
        return self

    def close(self) -> None:
        """Flush + close sinks and export the Chrome trace (idempotent;
        the exported ``trace.json`` is regenerated from the FULL event
        stream, so a resumed run exports one combined timeline)."""
        if self.metrics is not None:
            self.final_metrics = self.metrics.snapshot()
            self.metrics.close()
            self.metrics = None
        if self.tracer is not None:
            self.log.attach_sink(None)
            self.tracer.close()
            export_chrome(self.tracer.dir)
            self.tracer = None

    # -- recording ---------------------------------------------------------

    def span(self, name: str, **kw):
        """Nested wall-clock span at ``round`` level and above."""
        if self.tracer is None or self.level < LEVEL_ROUND:
            return _NULL_CTX
        return self.tracer.span(name, **kw)

    def timed(self, name: str, fn, *args, sync: bool = True, **meta):
        """Run ``fn(*args)`` under a span of its host or device time.

        At ``phase`` level the span is ``name`` and ``sync`` blocks on
        the outputs, so it measures the phase's actual device time, not
        its dispatch time — the documented wall-clock-only cost of phase
        tracing.  ``sync=False`` is for phases whose deferral IS the
        design (the store's overlapped d2h scatter).  At ``round`` level
        the span is ``dispatch.<name>``, the host time of the call alone
        (argument transfers and the launch), and nothing blocks.  Below
        that this is exactly ``fn(*args)``.
        """
        if self.tracer is None or self.level < LEVEL_ROUND:
            return fn(*args)
        ts = time.time_ns() // 1000
        t0 = time.perf_counter_ns()
        out = fn(*args)
        if self.level < LEVEL_PHASE:
            name = "dispatch." + name
        elif sync:
            import jax
            out = jax.block_until_ready(out)
        self.tracer.complete(name, ts, (time.perf_counter_ns() - t0) // 1000,
                             **meta)
        return out

    def event(self, name: str, **kw) -> None:
        if self.tracer is not None and self.level >= LEVEL_ROUND:
            self.tracer.event(name, **kw)

    def client_span(self, client: int, name: str, sim0: float, sim1: float,
                    **args) -> None:
        if self.tracer is not None and self.level >= LEVEL_ROUND:
            self.tracer.client_span(client, name, sim0, sim1, **args)

    def flush_metrics(self, step=None, **extra) -> None:
        if self.metrics is not None:
            self.metrics.flush(step=step, **extra)

    def flush(self) -> None:
        """Push buffered trace events to disk (the drivers call this per
        round so a crashed run still leaves a readable timeline)."""
        if self.tracer is not None:
            self.tracer.flush()

    # -- jax.profiler window (--xla-profile) -------------------------------

    def xla_round_start(self, t: int) -> None:
        if (self._xla_active or self.tracer is None
                or self.cfg.xla_profile < 0 or t != self.cfg.xla_profile):
            return
        import jax
        # a requested capture that cannot start fails the run: a run that
        # exits 0 without the profile it was asked for hides the fault
        jax.profiler.start_trace(str(self.tracer.dir / "xla"))
        self._xla_active = True
        self.event("xla_profile_start", round=t)

    def xla_round_end(self, t: int) -> None:
        if not self._xla_active:
            return
        import jax
        self._xla_active = False
        jax.profiler.stop_trace()
        self.event("xla_profile_stop", round=t)


NOOP = Obs(None)

_GLOBAL: Obs = NOOP


def _set_global(obs: Obs) -> None:
    global _GLOBAL
    _GLOBAL = obs


def get_obs() -> Obs:
    """The most recently opened tracing facade (NOOP otherwise) — the
    hook layers without a driver handle (kernel dispatch) report to."""
    return _GLOBAL


def make_obs(obs) -> Obs:
    """``FLRunConfig.obs`` -> an ``Obs`` facade (shared NOOP when None)."""
    cfg = as_obs_config(obs)
    if cfg is None:
        return NOOP
    return Obs(cfg)
