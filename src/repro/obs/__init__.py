"""Fleet flight recorder: spans + metrics + exporters on one substrate.

This package is the reproduction's answer to MemProf's "always-on profiler
+ tracing tool" pairing (paper §3, §6.2): PR 3-5 built the virtual-time
scheduler, the device counter plane, and the dispatch/sync budget books,
but their telemetry was ad-hoc ``stats()`` dicts — totals with no time
dimension, no per-request story, no export format. The flight recorder
threads one instrumentation substrate through admission, routing,
scheduling, elasticity, the serving engine, and the tiered-KV drain path:

* ``spans``   — request-lifecycle spans (admit/queue/dispatch/prefill/
  decode/migrate/shed/complete, plus per-chunk ``prefill_chunk`` spans
  under chunked prefill — the ``prefill`` span then covers admission to
  the prompt-completing chunk, labeled with its chunk count) stamped with
  scheduler virtual time, in a ring buffer with a drop counter (bounded
  under million-request runs);
* ``metrics`` — typed counters/gauges/exponential histograms with tenant +
  replica label dimensions and an exact fleet ``merge``; device-side series
  enter ONLY from ``drain_counters()`` deltas, so the decode hot path stays
  at one dispatch and zero mandatory host syncs per step and the PR-5
  drain-cadence invariant extends to every metric. Engines record a
  per-tenant ``ttft`` histogram (submit -> first generated token, virtual
  time; the prompt-completing chunk step under chunked prefill), merged
  into ``tenant_report``'s ``ttft_p50``/``ttft_p99``;
* ``export``  — Perfetto/Chrome trace_event JSON for the span timeline and
  JSON-lines metric snapshots per profiler window;
* ``phase``   — wall-clock phases of the host path (off unless the recorder
  is built with ``phases=True``): each opens a ``jax.profiler``
  annotation, so on a profiled run it lands on the host plane on the device
  trace's clock, and adds its ``perf_counter_ns`` time to the
  ``phase_ns`` / ``phase_calls`` counters, which cover the whole run.

:class:`FlightRecorder` is the facade the fleet attaches
(``FleetRouter.attach_recorder`` / ``build_fleet(recorder=...)``); a
process-global default recorder can be installed explicitly
(:func:`set_default_recorder`, what ``benchmarks/run.py --trace`` does) or
via the strict boolean env ``REPRO_FLIGHT_RECORDER=1`` (what CI uses to run
the dispatch-budget suite with tracing on).
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from repro.env import env_flag
from repro.obs import export as export_mod
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricSnapshot,
    MetricsRegistry,
    merge_snapshots,
    merged_histogram,
    prefetch_report,
    sum_counters,
)
from repro.obs.spans import Span, SpanRecorder

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricSnapshot",
    "MetricsRegistry",
    "merge_snapshots",
    "merged_histogram",
    "prefetch_report",
    "sum_counters",
    "Span",
    "SpanRecorder",
    "FlightRecorder",
    "PHASES_OFF",
    "default_recorder",
    "set_default_recorder",
]

_ENV_FLAG = "REPRO_FLIGHT_RECORDER"


class Phase:
    """One timed host phase: a profiler annotation plus two counters.

    ``kind`` (given at entry, or by :meth:`set_kind` once known) labels the
    counters next to ``phase`` and rides the annotation as an argument;
    every other argument only annotates."""

    __slots__ = ("_counters", "_name", "_kind", "_ann", "_t0")

    def __init__(self, counters, name: str, args: dict):
        self._counters = counters
        self._name = name
        self._kind = args.get("kind")
        self._ann = TraceAnnotation(name, **args)

    def set_kind(self, kind: str):
        self._kind = kind
        self._ann.set_metadata(kind=kind)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        ns, calls = self._counters(self._name, self._kind)
        ns.inc(time.perf_counter_ns() - self._t0)
        calls.inc()
        self._ann.__exit__(*exc)


class _NoPhase:
    """What a phase is while phases are off: nothing to label."""

    def set_kind(self, kind: str):
        pass


# the one context every phase hook returns while phases are off: no clock
# read, no annotation built
PHASES_OFF = contextlib.nullcontext(_NoPhase())


class FlightRecorder:
    """Spans + a fleet-level registry + every attached engine registry.

    ``now_fn`` is set by whatever owns the clock (the FleetRouter points it
    at fleet virtual time; a standalone engine at its step counter), so all
    emission points share one causal timeline. ``metrics_window`` sets the
    vtime cadence of metric snapshots (the JSONL export rows).
    """

    def __init__(
        self,
        capacity: int = 65536,
        metrics_window: float = 16.0,
        step_spans: bool = True,
        phases: bool = False,
    ):
        self.spans = SpanRecorder(capacity)
        self.metrics = MetricsRegistry()
        self.extra_registries: List[MetricsRegistry] = []
        self.metrics_window = float(metrics_window)
        self.metric_rows: List[dict] = []
        self.step_spans = bool(step_spans)  # per-replica step spans on host tracks
        self.phases = bool(phases)  # wall-clock host phases (``phase``)
        self._phase_counters: Dict[Tuple[str, Optional[str]], tuple] = {}
        self.now_fn = lambda: 0.0
        self._last_window: Optional[float] = None

    # ------------------------------------------------------------------
    def now(self) -> float:
        return float(self.now_fn())

    def register(self, registry: MetricsRegistry):
        """Include an engine/replica registry in snapshots and exports."""
        if registry is not self.metrics and registry not in self.extra_registries:
            self.extra_registries.append(registry)

    # span API (t defaults to the shared virtual clock) ----------------
    def begin(self, name, trace, t=None, **kw):
        self.spans.begin(name, trace, self.now() if t is None else t, **kw)

    def end(self, name, trace, t=None, **kw):
        return self.spans.end(name, trace, self.now() if t is None else t, **kw)

    def instant(self, name, trace, t=None, **kw):
        self.spans.instant(name, trace, self.now() if t is None else t, **kw)

    def span(self, name, trace, t0, t1, **kw):
        self.spans.span(name, trace, t0, t1, **kw)

    # wall-clock phases ------------------------------------------------
    def phase(self, name: str, **args):
        """Time one host phase on the wall clock: a ``jax.profiler``
        annotation named ``name`` (``args`` as its arguments) and, on exit,
        ``phase_ns`` and ``phase_calls`` labeled ``phase=name`` (and
        ``kind=`` when the phase has one). Callers check ``phases`` first;
        with it off they use :data:`PHASES_OFF` instead."""
        return Phase(self._counters_for, name, args)

    def _counters_for(self, name: str, kind: Optional[str]) -> tuple:
        key = (name, kind)
        pair = self._phase_counters.get(key)
        if pair is None:
            labels = {"phase": name} if kind is None else {"phase": name, "kind": kind}
            pair = self._phase_counters[key] = (
                self.metrics.counter("phase_ns", **labels),
                self.metrics.counter("phase_calls", **labels),
            )
        return pair

    # metrics snapshots -------------------------------------------------
    def on_step(self, now: float):
        """FleetRouter hook: snapshot the registries once per window."""
        if self._last_window is None:
            self._last_window = now
            return
        if now - self._last_window >= self.metrics_window:
            self._last_window = now
            self.snapshot_metrics(now)

    def merged_snapshot(self) -> MetricSnapshot:
        self.metrics.gauge("spans_dropped").set(self.spans.dropped)
        self.metrics.gauge("spans_emitted").set(self.spans.emitted)
        self.metrics.gauge("spans_double_end").set(self.spans.double_end)
        return merge_snapshots(
            [self.metrics.snapshot()] + [r.snapshot() for r in self.extra_registries]
        )

    def snapshot_metrics(self, now: float) -> dict:
        row = {"vtime": float(now), **self.merged_snapshot().flat()}
        self.metric_rows.append(row)
        return row

    # export ------------------------------------------------------------
    def trace_events(self, drain_open: bool = True) -> List[dict]:
        if drain_open:
            self.spans.drain_open(self.now())
        return export_mod.to_trace_events(self.spans.finished())

    def validate(self) -> dict:
        return export_mod.validate_trace_events(self.trace_events())

    def write(
        self,
        trace_path: str,
        metrics_path: Optional[str] = None,
        validate: bool = True,
    ) -> dict:
        """Export the span timeline (and final metrics row) to disk.

        ``metrics_path`` defaults to ``<trace_path>.metrics.jsonl``. Returns
        the validator's summary so callers can assert on it.
        ``validate=False`` skips the schema gate — for traces that span
        several independent scenarios (benchmarks/run.py over the whole
        suite), where unrelated fleets reuse rids on one timeline.
        """
        events = self.trace_events()
        if validate:
            summary = export_mod.validate_trace_events(events)
        else:
            summary = {"events": len(events)}
        export_mod.write_trace(trace_path, events)
        self.snapshot_metrics(self.now())
        export_mod.write_metrics(
            metrics_path or f"{trace_path}.metrics.jsonl", self.metric_rows
        )
        return summary


_DEFAULT: Optional[FlightRecorder] = None


def set_default_recorder(rec: Optional[FlightRecorder]):
    """Install (or clear, with None) the process-global recorder that
    engines and routers attach when not given one explicitly."""
    global _DEFAULT
    _DEFAULT = rec


def default_recorder() -> Optional[FlightRecorder]:
    """The global recorder, if any: one installed via
    :func:`set_default_recorder` (``benchmarks/run.py --trace``), else a
    lazily created singleton when ``REPRO_FLIGHT_RECORDER=1``."""
    global _DEFAULT
    if _DEFAULT is None and env_flag(_ENV_FLAG, default=False):
        _DEFAULT = FlightRecorder()
    return _DEFAULT
