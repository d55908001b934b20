"""Tests of ``bench/phases.py``, the traced run with the engine's phases on.

They run on the CPU: the readings of the phase counters, the naming of idle
gaps by the innermost program span, the exposed host time per decode step
on a synthetic trace, the program spans of a real profiler trace of engine
steps, and a reduced rehearsal of a whole traced run. None of it says
anything about the chip's speed.
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, phases, spec, trace  # noqa: E402

MS = 1_000_000


def _synthetic():
    ops = [[("%fusion.1 = f32[8] fusion()", 0, 3 * MS),
            ("%tiered_gather_segmented.1 = f32[8] custom-call()", 5 * MS, 6 * MS),
            ("%fusion.2 = f32[8] fusion()", 8 * MS, 9 * MS)]]
    bench_spans = [("bench.window", 0, 10 * MS), ("bench.step", 0, 7 * MS),
                   ("bench.readback", 7 * MS, 8 * MS), ("bench.submit", 9 * MS, 10 * MS)]
    tr = trace.from_events(ops, [[]], bench_spans)
    spans = sorted([
        ("engine.step", 0, 6 * MS, "decode"),
        ("engine.dispatch", 0, 1 * MS, "decode"),
        ("engine.retire", 1 * MS, 3 * MS, "decode"),
        ("tier.write", 3 * MS, 4.5 * MS, "decode"),
        ("engine.step", 6.5 * MS, 7 * MS, "chunk"),
    ], key=lambda s: (s[1], -s[2]))
    return tr, spans


def test_gaps_are_named_by_the_innermost_program_span():
    tr, spans = _synthetic()
    gaps = phases.named_gaps(tr, spans)
    # (3, 5) ms falls in tier.write inside engine.step; (6, 8) ms in no
    # program span, so the loop's readback names it; (9, 10) ms the submit
    assert gaps == [["tier.write", pytest.approx(0.002)], ["readback", pytest.approx(0.002)],
                    ["submit", pytest.approx(0.001)]]
    assert trace.breakdown(tr)["idle_gaps"][0][0] == "step"  # the loop's own naming
    assert phases.named_gaps(tr, []) == trace.breakdown(tr)["idle_gaps"]


def test_exposed_host_time_and_coverage_on_a_synthetic_trace():
    tr, spans = _synthetic()
    # the decode step runs from 0 to the next step's start at 6.5 ms; the
    # device is busy 0-3 and 5-6 ms of it
    assert phases.exposed_host_ms_per_decode_step(tr, spans) == pytest.approx(2.5)
    cov = phases.coverage(spans)
    assert cov["decode"] == {"steps": 1, "covered_pct": pytest.approx(75.0)}
    assert cov["chunk"] == {"steps": 1, "covered_pct": 0.0}
    no_device = trace.from_events([], [], [("bench.window", 0, 10 * MS)])
    assert phases.exposed_host_ms_per_decode_step(no_device, spans) is None
    assert phases.exposed_host_ms_per_decode_step(tr, spans[-1:]) is None


def _registry(ns: dict, steps: dict):
    from repro.obs import MetricsRegistry

    reg = MetricsRegistry()
    for (name, kind), (calls, t) in ns.items():
        labels = {"phase": name} if kind is None else {"phase": name, "kind": kind}
        reg.counter("phase_ns", **labels).inc(t)
        reg.counter("phase_calls", **labels).inc(calls)
    for kind, n in steps.items():
        reg.counter("engine_steps", kind=kind).inc(n)
    return reg.snapshot()


def test_readings_of_the_phase_counters():
    before = phases.phase_table(_registry({("engine.step", "decode"): (2, 10 * MS)},
                                          {"decode": 2}))
    after = phases.phase_table(_registry({
        ("engine.step", "decode"): (6, 130 * MS),
        ("engine.step", "chunk"): (2, 5000 * MS),
        ("engine.admit", None): (8, 8 * MS),
        ("engine.account", "decode"): (4, 12 * MS),
        ("engine.account", "chunk"): (2, 2 * MS),
        ("engine.retire", "decode"): (4, 8 * MS),
        ("tier.lookup", "decode"): (4, 4 * MS),
        ("tier.write", "chunk"): (2, 4900 * MS),
        ("tier.drain", "decode"): (1, 6 * MS),
    }, {"decode": 6, "chunk": 2}))
    got, steps = phases.window_delta(before, after)
    assert steps == {"decode": 4, "chunk": 2}
    assert got["engine.step{kind=decode}"] == [4, pytest.approx(0.12)]
    r = phases.readings(got, steps)
    assert r["engine.decode_step_ms"] == pytest.approx(30.0)
    assert r["engine.bookkeeping_ms_per_step"] == pytest.approx(22.0 / 6)
    assert r["tier.host_ms_per_step"] == pytest.approx(4910.0 / 6)
    decode = phases.split(got, "decode")
    assert decode["engine.account"] == pytest.approx(3.0)
    assert decode["unspanned"] == pytest.approx(30.0 - 3.0 - 2.0 - 1.0 - 1.5)
    assert phases.split(got, "chunk")["tier.write"] == pytest.approx(2450.0)
    assert phases.readings({}, {}) == dict.fromkeys(
        ["engine.decode_step_ms", "engine.bookkeeping_ms_per_step", "tier.host_ms_per_step"])


def _engine(recorder):
    import jax

    from repro.configs import get_config
    from repro.models.api import get_model
    from repro.runtime.serving import EngineConfig, ServingEngine

    cfg = get_config("smollm-360m").reduced()
    api = get_model(cfg)
    ecfg = EngineConfig(max_batch=2, max_len=64, n_pages=64, placement_window=2,
                        device_tiering=True, prefill_chunk=8)
    return ServingEngine(api, api.init(jax.random.PRNGKey(0)), ecfg, recorder=recorder)


def test_program_spans_of_a_cpu_trace_nest_inside_the_loop_step(tmp_path):
    import jax
    import numpy as np

    from repro.data.requests import Request
    from repro.obs import FlightRecorder

    eng = _engine(FlightRecorder(phases=True))
    eng.submit(Request(0, np.arange(1, 6, dtype=np.int32), 3, -1, 0.0))
    eng.step()  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.step"):
                    eng.step()
    finally:
        jax.profiler.stop_trace()
    tr = trace.load(tmp_path)
    spans = phases.program_spans(tmp_path, tr.window)
    steps = [s for s in spans if s[0] == "engine.step"]
    loop_steps = [s for s in tr.spans if s[0] == "bench.step"]
    assert [s[3] for s in steps] == ["decode", "decode"] and len(loop_steps) == 2
    for (_, a, b, _), (_, x, y) in zip(steps, loop_steps):
        assert x <= a < b <= y
    kids = [s for s in spans if s[0] != "engine.step"]
    assert {s[0] for s in kids} >= {"engine.dispatch", "tier.lookup", "engine.account",
                                    "engine.retire", "tier.write", "tier.drain"}
    assert all(any(a <= x and y <= b for _, a, b, _ in steps) for _, x, y, _ in kids)
    assert all(c["covered_pct"] <= 100.0 for c in phases.coverage(spans).values())


# the rehearsal's reduced cell: the configuration cut as
# tests/bench/test_bench_harness.py cuts it
REDUCED_LIMITS = {"logit_gap": 0.004, "kv_row_err": 0.015}


def test_reduced_traced_run_reads_the_phase_counters(tmp_path):
    bench = spec.benchmark()
    prog = harness.program()
    cell = {"name": "smollm-360m.decode_long", "config": "smollm-360m",
            "traffic": "decode_long", "chips": 1, "end_to_end": bench["end_to_end"],
            "per_layer": bench["per_layer"]}
    conf = spec.config("smollm-360m")
    cfg = prog.get_config(conf["registry_name"]).reduced()
    conf.update(hidden_size=cfg.d_model, num_hidden_layers=cfg.n_layers,
                num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
                intermediate_size=cfg.d_ff, vocab_size=cfg.vocab_size)
    conf["engine"] = dict(conf["engine"], max_batch=4, max_len=128, n_pages=32,
                          prefill_chunk=16)
    mix = spec.traffic("decode_long")
    mix["prompt"].update(median=24, lo=4, hi=64)
    mix["output"].update(median=10, lo=2, hi=60)
    window = harness.window
    r = phases.measure(
        prog, cell, conf, mix, REDUCED_LIMITS, 11, 2.0, tmp_path, model_cfg=cfg,
        log=lambda m: None,
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10})
    assert harness.window is window  # the harness is left as it was
    assert r["correct"], r["checks"]
    for name in ("engine.decode_step_ms", "engine.bookkeeping_ms_per_step",
                 "tier.host_ms_per_step"):
        assert r["phase_readings"][name] > 0, name
    # no device plane on the CPU: nothing to read there
    assert r["phase_readings"]["device.exposed_host_ms_per_decode_step"] is None
    steps = r["steps_by_kind"]
    assert steps["decode"] > 0 and steps["chunk"] > 0
    assert r["phases"]["engine.step{kind=decode}"][0] == steps["decode"]
    assert r["program_spans"] > 0 and set(r["coverage"]) <= {"decode", "chunk", None}
    assert r["split_ms"]["decode"]["engine.dispatch"] > 0
    # the run's own per-layer metrics read as the benchmark reads them
    assert r["metrics"]["engine.chunk_step_share"]["value"] > 0
