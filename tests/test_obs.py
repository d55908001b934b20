"""Fleet flight recorder: spans, metrics plane, Perfetto export.

The contracts this file pins (ISSUE 6 acceptance):

1. Span ring buffer is bounded: overflow drops the OLDEST spans and counts
   them; the drop count is itself a metric (``spans_dropped``).
2. The metrics plane is exact: counters/histograms merge bit-identically
   (quantiles are deterministic bucket upper bounds, identical before and
   after merge), and fleet metric totals merged from per-replica registries
   equal the legacy ``fleet_stats`` sums bit-for-bit.
3. Observability is free: recorder on/off and drain-every-step vs
   once-per-window produce identical tokens, live_counters, and registry
   totals — the PR-5 drain-cadence invariant extends to every metric — and
   the segmented decode still pays exactly 1 dispatch/step with tracing on.
4. A seeded multi-tenant straggler+autoscale scenario exports a
   Perfetto-loadable trace_event JSON with causally-ordered spans
   (monotone virtual time, balanced B/E pairs, tenant+replica labels on
   every event).
5. ``tenant_report`` queue-wait p50/p99 now come from the mergeable
   histogram and pin against the legacy np.percentile values on a seeded
   run (within one exponential bucket).
6. Wall-clock phases: with phases on, a chunk step and a decode step each
   open ``engine.step`` around their child phases, in order, none
   overlapping, each counted once by phase and kind; without a recorder,
   or with its phases off, a step reads no clock and annotates nothing.
"""
import dataclasses
import json
import math
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.workloads import get_profile
from repro.data.requests import Request, RequestGenerator, interleave
from repro.fleet import (
    AdmissionController,
    SLOModel,
    aggregate_metrics,
    build_fleet,
    fleet_vocab,
)
from repro.models.api import get_model
from repro.obs import (
    FlightRecorder,
    Histogram,
    MetricsRegistry,
    SpanRecorder,
    default_recorder,
    merge_snapshots,
    merged_histogram,
    set_default_recorder,
    sum_counters,
)
from repro.obs.export import read_trace, to_trace_events, validate_trace_events
from repro.obs.spans import Span
from repro.runtime.serving import EngineConfig, ServingEngine


# ---------------------------------------------------------------------------
# 1. span recorder: ring cap + drop counter


def test_ring_buffer_caps_and_counts_drops():
    rec = SpanRecorder(capacity=4)
    for i in range(10):
        rec.instant("tick", i, float(i))
    assert len(rec.finished()) == 4
    assert rec.dropped == 6
    assert rec.emitted == 10
    # oldest fell off the ring; newest survived
    assert [s.trace for s in rec.finished()] == [6, 7, 8, 9]


def test_drop_count_is_a_metric():
    fr = FlightRecorder(capacity=2)
    for i in range(5):
        fr.instant("tick", i, t=float(i), tenant="t")
    snap = fr.merged_snapshot()
    assert snap.gauges[("spans_dropped", ())] == 3
    assert snap.gauges[("spans_emitted", ())] == 5


def test_span_lifecycle_and_drain_open():
    rec = SpanRecorder()
    rec.begin("queue", 7, 1.0, tenant="web")
    assert rec.open_count == 1
    s = rec.end("queue", 7, 3.5, wait=2.5)
    assert (s.t0, s.t1, s.dur) == (1.0, 3.5, 2.5)
    assert s.args["wait"] == 2.5
    # unmatched end degrades to a tagged instant, not a crash
    u = rec.end("queue", 99, 4.0)
    assert u.kind == "instant" and u.args["unmatched"] is True
    # open spans flush as truncated at export time (B/E stay balanced)
    rec.begin("decode", 8, 5.0)
    rec.drain_open(9.0)
    assert rec.open_count == 0
    last = rec.finished()[-1]
    assert last.name == "decode" and last.t1 == 9.0 and last.args["truncated"]


def test_double_end_records_one_span_and_is_counted():
    """Failover races can end the same span twice (e.g. a queue span closed
    by dispatch, then again by a stale path): exactly ONE span reaches the
    ring, the duplicate is counted in the ``double_end`` book instead of
    producing a bogus unmatched-instant."""
    rec = SpanRecorder()
    rec.begin("queue", 7, 1.0)
    rec.end("queue", 7, 3.0)
    assert rec.end("queue", 7, 4.0) is None  # duplicate: swallowed
    assert rec.double_end == 1
    spans = [s for s in rec.finished() if s.name == "queue"]
    assert len(spans) == 1 and spans[0].t1 == 3.0
    # a NEVER-begun end still degrades to the tagged instant (distinct case)
    u = rec.end("queue", 99, 5.0)
    assert u.args["unmatched"] is True and rec.double_end == 1
    # re-begin after a close re-arms the pair: next end is legitimate
    rec.begin("queue", 7, 6.0)
    s = rec.end("queue", 7, 8.0)
    assert s.t1 == 8.0 and rec.double_end == 1
    # drain_open flushes re-opened spans; a later duplicate end of a
    # drained key is still just a count, not a span
    rec.begin("decode", 7, 9.0)
    rec.drain_open(10.0)
    assert rec.end("decode", 7, 11.0) is None
    assert rec.double_end == 2
    # the book rides the merged metric snapshot like the drop counter
    fr = FlightRecorder(capacity=8)
    fr.spans.begin("x", 1, 0.0)
    fr.spans.end("x", 1, 1.0)
    fr.spans.end("x", 1, 2.0)
    assert fr.merged_snapshot().gauges[("spans_double_end", ())] == 1


# ---------------------------------------------------------------------------
# 2. metrics plane: exact merge, deterministic quantiles


def test_counter_merge_is_exact():
    regs = [MetricsRegistry(const_labels={"replica": str(i)}) for i in range(3)]
    for i, r in enumerate(regs):
        r.counter("tokens", tenant="web").inc(10 + i)
        r.counter("tokens", tenant="cache").inc(2)
    merged = merge_snapshots([r.snapshot() for r in regs])
    assert sum_counters(merged, "tokens") == (10 + 11 + 12) + 3 * 2
    # replica labels keep the per-host series distinct in the merge
    assert len([k for k in merged.counters if k[0] == "tokens"]) == 6


def test_histogram_quantile_deterministic_and_merge_invariant():
    rng = np.random.default_rng(0)
    values = np.abs(rng.standard_normal(500)) * 10.0
    whole = Histogram()
    parts = [Histogram(), Histogram()]
    for i, v in enumerate(values):
        whole.record(v)
        parts[i % 2].record(v)
    merged = Histogram()
    for p in parts:
        merged.merge(p)
    for q in (0.5, 0.9, 0.99):
        assert whole.quantile(q) == merged.quantile(q)
    assert whole.count == merged.count == 500
    assert whole.sum == pytest.approx(merged.sum)
    # quantile is the bucket upper bound of the rank sample: within one
    # growth factor of the exact rank statistic
    sv = np.sort(values)
    for q in (0.5, 0.99):
        exact = sv[math.ceil(q * len(sv)) - 1]
        assert exact <= whole.quantile(q) <= exact * whole.growth * (1 + 1e-9)


def test_histogram_zero_and_state_roundtrip():
    h = Histogram()
    h.record(0.0, n=5)
    h.record(1.0)
    assert h.quantile(0.5) == 0.0  # rank 3 of 6 sits in the zero bucket
    assert h.quantile(0.99) == 1.0  # exact power lands on its own boundary
    st = h.state()
    assert st["count"] == 6 and st["zero"] == 5
    json.dumps(st)  # JSONL-exportable


def test_registry_snapshot_is_frozen():
    r = MetricsRegistry()
    c = r.counter("x")
    h = r.histogram("h")
    c.inc(3)
    h.record(1.0)
    snap = r.snapshot()
    c.inc(100)
    h.record(50.0)
    assert snap.counters[("x", ())] == 3
    assert snap.histograms[("h", ())].count == 1


# ---------------------------------------------------------------------------
# 3. export schema


def _span(name, trace, t0, t1, **kw):
    return Span(name, trace, t0, t1, **kw)


def test_trace_events_balanced_and_monotone():
    spans = [
        _span("queue", 1, 0.0, 2.0, tenant="web"),
        _span("decode", 1, 2.0, 7.0, tenant="web", replica=0),
        _span("step", -1, 0.0, 1.0, replica=0),
        _span("migrate", -1, 1.0, 1.0, replica=0),
        _span("shed", 2, 0.5, 0.5, tenant="cache", kind="instant"),
    ]
    events = to_trace_events(spans)
    summary = validate_trace_events(events)
    assert summary["spans"] == 4 and summary["instants"] == 1
    ts = [e["ts"] for e in events if e["ph"] != "M"]
    assert ts == sorted(ts)
    for e in events:
        if e["ph"] != "M":
            assert "tenant" in e["args"] and "replica" in e["args"]
    # request tracks live in tenant processes; host spans in host processes
    pids = {e["pid"] for e in events}
    assert 1_000_000 in pids  # host:0


def test_validator_rejects_broken_traces():
    ok = to_trace_events([_span("a", 1, 0.0, 1.0, tenant="t")])
    bad_order = [e.copy() for e in ok]
    bad_order[-1]["ts"] = -5.0
    with pytest.raises(ValueError, match="monotone"):
        validate_trace_events(bad_order)
    unbalanced = [e for e in ok if e["ph"] != "E"]
    with pytest.raises(ValueError, match="unbalanced"):
        validate_trace_events(unbalanced)
    unlabeled = [dict(e, args={}) if e["ph"] != "M" else e for e in ok]
    with pytest.raises(ValueError, match="labels"):
        validate_trace_events(unlabeled)


# ---------------------------------------------------------------------------
# 4. engine-level: observability is free (tokens, books, budget)


def _mk_engine(recorder=None, **ekw):
    cfg = get_config("smollm-360m").reduced()
    if not hasattr(_mk_engine, "_cached"):
        api = get_model(cfg)
        _mk_engine._cached = (api, api.init(jax.random.PRNGKey(0)))
    api, params = _mk_engine._cached
    kw = dict(
        max_batch=4, max_len=64, n_pages=256, near_frac=0.02, placement_window=4,
        device_tiering=True, tiered_identity_scales=True,
    )
    kw.update(ekw)
    return cfg, ServingEngine(api, params, EngineConfig(**kw), seed=0, recorder=recorder)


def _gen(cfg, seed=0):
    prof = dataclasses.replace(
        get_profile("Web1"), prompt_mean=24, decode_mean=8,
        prefix_share=0.5, n_prefixes=2,
    )
    return RequestGenerator(prof, vocab_size=cfg.vocab_size, seed=seed)


# meta-counters that meter the drain operations themselves — they scale
# WITH cadence by design (more drains = more host syncs) and are excluded
# from the cadence-invariance equality below
_SYNC_METERS = ("kv_drains", "kv_host_syncs")


def _counter_totals(engine):
    snap = engine.metrics.snapshot()
    return {k: v for k, v in snap.counters.items() if k[0] not in _SYNC_METERS}


@pytest.mark.slow
def test_recorder_and_drain_cadence_leave_books_identical():
    """Recorder ON + drain every step vs recorder OFF + window drains:
    identical tokens, live_counters, and registry totals — tracing adds no
    dispatches, no syncs, and no accounting drift at any cadence."""
    rec = FlightRecorder()
    cfg, traced = _mk_engine(recorder=rec)
    cfg, plain = _mk_engine(recorder=None)
    assert plain.recorder is None  # no env default leaking in
    g1, g2 = _gen(cfg, seed=5), _gen(cfg, seed=5)
    for _ in range(6):
        traced.submit(next(g1))
        plain.submit(next(g2))
    while (traced.queue or any(s.active for s in traced.slots)) and traced.engine_steps < 200:
        traced.step()
        traced.drain_tier_counters()  # extra per-step drains on the traced one
        plain.step()
    st, sp = traced.stats(), plain.stats()
    assert st["tokens_decoded"] == sp["tokens_decoded"]
    assert st["tenants"] == sp["tenants"]
    assert traced.live_counters() == plain.live_counters()
    assert _counter_totals(traced) == _counter_totals(plain)
    # the sync meters DO see the cadence: per-step drains cost more syncs,
    # and the registry counts them exactly
    assert sum_counters(traced.metrics.snapshot(), "kv_drains") == traced.tiered.drains
    assert traced.tiered.drains > plain.tiered.drains
    # the budget held with tracing on: 1 dispatch/step, syncs only at drains
    assert traced.tiered.dispatches == traced.engine_steps
    # and the recorder actually saw the run
    assert rec.spans.emitted > 0
    assert any(s.name == "decode" for s in rec.spans.finished())


def test_registry_mirrors_legacy_books_exactly():
    cfg, eng = _mk_engine()
    gen = _gen(cfg)
    eng.run(gen, n_requests=6, max_steps=200)
    snap = eng.metrics.snapshot()
    assert sum_counters(snap, "tokens_decoded") == eng.tokens_decoded
    assert sum_counters(snap, "requests_finished") == len(eng.finished)
    assert sum_counters(snap, "prefill_tokens") == eng.prefill_tokens
    assert sum_counters(snap, "near_hits") == eng.placement.stats.near_hits
    assert sum_counters(snap, "far_hits") == eng.placement.stats.far_hits
    assert sum_counters(snap, "kv_dispatches") == eng.tiered.dispatches
    # tenant label dimension partitions the same totals
    assert sum_counters(snap, "tenant_tokens_decoded") == eng.tokens_decoded


# ---------------------------------------------------------------------------
# 5. fleet acceptance: traced straggler+autoscale scenario


@pytest.fixture(scope="module")
def traced_scenario(tmp_path_factory):
    """Seeded multi-tenant straggler+autoscale run with the recorder on."""
    set_default_recorder(None)
    rec = FlightRecorder(metrics_window=8.0)
    fleet = build_fleet(
        2,
        policy="least-loaded",
        n_pages=128,
        trace_window=16,
        trace_period=32,
        speeds=(1.0, 4.0),  # host 1 is a 4x straggler
        admission=AdmissionController(SLOModel(max_delay_steps=16.0)),
        autotier=dict(near_frac=0.3, epoch_steps=4),
        elastic=dict(min_replicas=2, max_replicas=4, cooldown=3.0,
                     up_shed_rate=0.05, up_backlog_frac=0.6,
                     down_backlog_frac=0.15),
        tenant_weights={"web": 2.0, "cache": 1.0},
        recorder=rec,
    )
    prof = dataclasses.replace(
        get_profile("Web1"), prompt_mean=16, decode_mean=6,
        prefix_share=0.8, n_prefixes=3,
    )
    web = RequestGenerator(prof, vocab_size=fleet_vocab(), seed=0, rate=8.0, tenant="web")
    cache = RequestGenerator(
        dataclasses.replace(prof, prefix_share=0.0, prompt_mean=8, decode_mean=4),
        vocab_size=fleet_vocab(), seed=1, rate=24.0, tenant="cache",
    )
    reqs = interleave([cache, web], 48)
    stats = fleet.run(iter(reqs), n_requests=48, max_steps=400, submit_per_step=6)
    out = tmp_path_factory.mktemp("obs") / "fleet_trace.json"
    summary = rec.write(str(out))
    return fleet, rec, stats, summary, out


@pytest.mark.slow
def test_scenario_scaled_and_served(traced_scenario):
    fleet, rec, stats, summary, out = traced_scenario
    assert stats["requests_finished"] > 0
    assert any(e[1] == "up" for e in stats["scale_events"]), stats["scale_events"]


@pytest.mark.slow
def test_scenario_trace_is_perfetto_loadable(traced_scenario):
    fleet, rec, stats, summary, out = traced_scenario
    # write() already ran the schema gate; re-validate the on-disk file
    events = read_trace(str(out))
    s2 = validate_trace_events(events)
    assert s2 == summary
    assert summary["spans"] > 0 and summary["instants"] > 0
    doc = json.loads(out.read_text())
    assert doc["displayTimeUnit"] == "ms"
    names = {e["name"] for e in events}
    # the full request lifecycle + host/fleet story is on the timeline
    for expected in ("admit", "queue", "dispatch", "prefill", "decode",
                     "complete", "step", "scale_up"):
        assert expected in names, f"missing {expected!r} spans"
    # metrics JSONL rode along, one flat row per window + the final row
    rows = [json.loads(l) for l in
            (out.parent / (out.name + ".metrics.jsonl")).read_text().splitlines()]
    assert rows and all("vtime" in r for r in rows)
    assert any(k.startswith("tokens_decoded") for k in rows[-1])


@pytest.mark.slow
def test_scenario_fleet_merge_matches_fleet_stats_bit_exactly(traced_scenario):
    fleet, rec, stats, summary, out = traced_scenario
    merged = fleet.fleet_metrics()
    for key in ("tokens_decoded", "requests_finished", "prefill_tokens",
                "prefill_tokens_saved"):
        assert sum_counters(merged, key) == stats[key], key
    assert sum_counters(merged, "shed") == stats["shed"]
    assert sum_counters(merged, "routed") == stats["routed"]
    near = sum_counters(merged, "near_hits")
    far = sum_counters(merged, "far_hits")
    assert near / max(near + far, 1) == stats["near_hit_rate"]
    # per-tenant partition sums to the fleet totals
    assert sum_counters(merged, "tenant_tokens_decoded") == stats["tokens_decoded"]
    # the aggregator path over exported profiles gives the same engine books
    prof_merge = aggregate_metrics(fleet.export_profiles())
    assert sum_counters(prof_merge, "tokens_decoded") == stats["tokens_decoded"]
    assert sum_counters(prof_merge, "near_hits") == near


@pytest.mark.slow
def test_scenario_wait_percentiles_pin_legacy(traced_scenario):
    """New histogram p50/p99 vs legacy np.percentile over the raw samples:
    within one exponential bucket (and bit-equal on zero waits)."""
    fleet, rec, stats, summary, out = traced_scenario
    rep = fleet.tenant_report()
    growth = 2.0 ** 0.125
    saw_nonzero = False
    for t, waits in fleet.wait_samples.items():
        assert waits, t
        for q, key in ((50, "wait_p50"), (99, "wait_p99")):
            legacy = float(np.percentile(waits, q))
            new = rep[t][key]
            if legacy <= 0.0:
                assert new == 0.0, (t, key)
            else:
                saw_nonzero = True
                # rank statistic the histogram actually answers for
                sv = sorted(waits)
                exact = sv[max(1, math.ceil(q / 100 * len(sv))) - 1]
                if exact <= 0.0:
                    assert new == 0.0, (t, key)
                else:
                    assert exact <= new <= exact * growth * (1 + 1e-9), (t, key, exact, new)
                # and stays within one bucket of the interpolated legacy value
                assert new <= max(legacy, exact) * growth * (1 + 1e-9), (t, key)
    assert saw_nonzero, "scenario produced no queueing — pin is vacuous"


@pytest.mark.slow
def test_scenario_histograms_merge_fleet_wide(traced_scenario):
    fleet, rec, stats, summary, out = traced_scenario
    merged = fleet.fleet_metrics()
    h = merged_histogram(merged, "queue_wait")
    assert h is not None
    assert h.count == sum(len(w) for w in fleet.wait_samples.values())


def test_default_recorder_env_flag(monkeypatch):
    set_default_recorder(None)
    monkeypatch.delenv("REPRO_FLIGHT_RECORDER", raising=False)
    assert default_recorder() is None
    monkeypatch.setenv("REPRO_FLIGHT_RECORDER", "1")
    rec = default_recorder()
    assert rec is not None and default_recorder() is rec
    set_default_recorder(None)


# ---------------------------------------------------------------------------
# 6. wall-clock phases of the engine step


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps every span as
    ``[name, enter_ns, exit_ns, args]`` in the order they open."""

    def __init__(self):
        self.spans = []

    def __call__(self, name, **args):
        spans = self.spans

        class Annotation:
            def __enter__(self):
                self.rec = [name, time.perf_counter_ns(), None, dict(args)]
                spans.append(self.rec)

            def __exit__(self, *exc):
                self.rec[2] = time.perf_counter_ns()

            def set_metadata(self, **kw):
                self.rec[3].update(kw)

        return Annotation()


def test_phase_counts_calls_and_time_by_phase_and_kind(monkeypatch):
    import repro.obs as obs

    ann = _Annotations()
    monkeypatch.setattr(obs, "TraceAnnotation", ann)
    assert FlightRecorder().phases is False
    rec = FlightRecorder(phases=True)
    with rec.phase("engine.step") as p:
        with rec.phase("engine.dispatch", kind="decode", n=3):
            pass
        p.set_kind("decode")
    with rec.phase("engine.step"):
        pass
    c = rec.metrics.counter
    assert c("phase_calls", phase="engine.step", kind="decode").value == 1
    assert c("phase_calls", phase="engine.step").value == 1
    assert c("phase_calls", phase="engine.dispatch", kind="decode").value == 1
    step_ns = c("phase_ns", phase="engine.step", kind="decode").value
    assert step_ns >= c("phase_ns", phase="engine.dispatch", kind="decode").value > 0
    names = [(s[0], s[3]) for s in ann.spans]
    assert names == [("engine.step", {"kind": "decode"}),
                     ("engine.dispatch", {"kind": "decode", "n": 3}),
                     ("engine.step", {})]


CHUNK_STEP = ["engine.admit", "engine.plan", "engine.dispatch", "tier.lookup",
              "engine.account", "engine.retire", "tier.write"]
DECODE_STEP = ["engine.admit", "engine.dispatch", "tier.lookup", "engine.account",
               "engine.retire", "tier.write", "tier.drain", "tier.placement"]


def _prompted_engine(recorder):
    """A chunked engine holding one request whose 5-token prompt fits one
    chunk: its first step is a chunk step, its second a decode step that
    closes a placement window."""
    cfg, eng = _mk_engine(recorder=recorder, max_batch=2, prefill_chunk=8,
                          placement_window=2)
    prompt = np.arange(1, 6, dtype=np.int32)
    eng.submit(Request(0, prompt, 3, -1, 0.0))
    return eng


def test_step_phases_nest_in_order_and_are_counted(monkeypatch):
    import repro.obs as obs

    ann = _Annotations()
    monkeypatch.setattr(obs, "TraceAnnotation", ann)
    rec = FlightRecorder(phases=True)
    eng = _prompted_engine(rec)
    for kind, children in (("chunk", CHUNK_STEP), ("decode", DECODE_STEP)):
        ann.spans.clear()
        eng.step()
        (step, *kids) = ann.spans
        assert step[0] == "engine.step" and step[3] == {"kind": kind}
        assert [k[0] for k in kids] == children
        assert all(k[3] == {"kind": kind} for k in kids if k[0] != "engine.admit")
        # the children do not overlap and fit inside the step
        bounds = [step[1]] + [t for k in kids for t in k[1:3]] + [step[2]]
        assert bounds == sorted(bounds)
    c = rec.metrics.counter
    for kind, children in (("chunk", CHUNK_STEP), ("decode", DECODE_STEP)):
        assert c("phase_calls", phase="engine.step", kind=kind).value == 1
        assert eng.metrics.counter("engine_steps", kind=kind).value == 1
        for name in children[1:]:
            assert c("phase_calls", phase=name, kind=kind).value == 1, (name, kind)
    assert c("phase_calls", phase="engine.admit").value == 2


@pytest.mark.parametrize("phases_off", ["no recorder", "recorder, phases off"])
def test_step_without_phases_reads_no_clock(monkeypatch, phases_off):
    import repro.obs as obs

    eng = _prompted_engine(None if phases_off == "no recorder" else FlightRecorder())

    def refuse(*a, **k):
        raise AssertionError("a step without phases read the clock or annotated")

    monkeypatch.setattr(time, "perf_counter_ns", refuse)
    monkeypatch.setattr(obs, "TraceAnnotation", refuse)
    for _ in range(3):  # a chunk step, then decode steps over a window boundary
        eng.step()
    assert eng.tokens_decoded == 2
    steps = {k: eng.metrics.counter("engine_steps", kind=k).value for k in ("chunk", "decode")}
    assert steps == {"chunk": 1, "decode": 2}
