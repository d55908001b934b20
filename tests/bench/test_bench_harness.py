"""Tests of the benchmark harness under ``bench/``.

They run on the CPU: the traffic generators, the end-to-end arithmetic, each
per-layer reader on a synthetic trace, the cost functions against
hand-worked shapes, the name lookups, the refusal of a backend that is not
a TPU, and a rehearsal of whole runs at a reduced model size with
interpret-mode kernels, sound and with the served path broken underneath.
None of it says anything about the chip's speed.
"""
from __future__ import annotations

import copy
import functools
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, spec, stats, trace  # noqa: E402
from bench.costs import dense_lm, tiered_gather  # noqa: E402
from bench.traffic import _draws  # noqa: E402

BENCH = spec.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]

# An open-loop mix of short requests, for the open-loop generator; no cell
# runs it yet (its rate waits for a knee measured on the chip).
SHORT_MIX = {
    "kind": "open_loop", "rate_per_s": 0.5,
    "prompt": {"median": 96, "sigma": 0.9, "lo": 8, "hi": 1024},
    "output": {"median": 16, "sigma": 0.8, "lo": 1, "hi": 256},
    "block": 64,
}
# The sizes of a model with an untied output head (internlm2-1.8b).
UNTIED = {"hidden_size": 2048, "num_hidden_layers": 24, "num_attention_heads": 16,
          "num_key_value_heads": 8, "intermediate_size": 8192, "vocab_size": 92544}


def _mix(name):
    return copy.deepcopy(SHORT_MIX) if name == "short" else spec.traffic(name)


# ---------------------------------------------------------------------------
# traffic


def _source(mix_name, seed, **kw):
    mix = _mix(mix_name)
    return spec.generator(mix["kind"]).make(
        mix, seed=seed, vocab=1000, max_len=2048, max_batch=kw.get("max_batch", 16))


@pytest.mark.parametrize("mix", ["decode_long", "short"])
def test_same_seed_same_traffic(mix):
    a, b, c = _source(mix, 2**33 + 7), _source(mix, 2**33 + 7), _source(mix, 7)
    assert np.array_equal(a.prompts, b.prompts) and np.array_equal(a.outputs, b.outputs)
    assert not np.array_equal(a.prompts, c.prompts)  # the seed's high bits count
    if mix == "decode_long":
        assert np.array_equal(a._req(0.0).prompt, b._req(0.0).prompt)
    else:
        assert np.array_equal(a.offsets, b.offsets)


@pytest.mark.parametrize("mix", ["decode_long", "short"])
def test_seeds_permute_one_set_of_sizes(mix):
    a, b = _source(mix, 1), _source(mix, 2)
    if mix == "decode_long":
        # the first requests, prompt and residual life together, are dealt
        # to the slots in the seed's order; every later size is the mix's own
        n = a.clients
        first = lambda s: sorted(zip(s.prompts[:n], s.outputs[:n]))  # noqa: E731
        assert first(a) == first(b)
        assert not np.array_equal(a.prompts[:n], b.prompts[:n])
        assert np.array_equal(a.prompts[n:], b.prompts[n:])
        assert np.array_equal(a.outputs[n:], b.outputs[n:])
        return
    sl = slice(0, _mix(mix)["block"])
    assert sorted(a.prompts[sl]) == sorted(b.prompts[sl])
    assert not np.array_equal(a.prompts[sl], b.prompts[sl])


def test_clipped_lognormal_quantiles():
    v = _draws.lognormal_set(median=256, sigma=0.6, lo=32, hi=1024, block=1001)
    assert np.median(v) == 256
    assert v.min() >= 32 and v.max() <= 1024
    # the 84th percentile of a lognormal is median * e^sigma
    assert abs(np.percentile(v, 84.13) / (256 * np.exp(0.6)) - 1) < 0.02
    clipped = _draws.lognormal_set(median=96, sigma=0.9, lo=8, hi=100, block=64)
    assert clipped.max() == 100


def test_residual_first_lengths():
    src = _source("decode_long", 5, max_batch=64)
    first, later = src.outputs[:64], src.outputs[64:]
    assert first.min() >= 1 and (first <= 2047 - src.prompts[:64]).all()
    # an in-flight request has on average half of a length-biased draw left
    # (here about 0.59 of the mean length), and some are near their end
    assert 0.4 < first.mean() / later.mean() < 0.8
    assert first.min() < 64 <= later.min()


def test_open_loop_timing():
    src = _source("short", 3)
    rate = SHORT_MIX["rate_per_s"]
    gaps = np.diff(np.concatenate([[0.0], src.offsets]))
    assert abs(gaps.mean() * rate - 1) < 0.02
    # exponential gaps: the median is ln 2 of the mean
    assert abs(np.median(gaps) * rate - np.log(2)) < 0.05


def test_open_loop_thread_pushes_at_due_times():
    mix = dict(SHORT_MIX, rate_per_s=200.0)
    src = spec.generator("open_loop").make(mix, seed=1, vocab=100, max_len=256, max_batch=4)
    t0 = time.perf_counter()
    src.start(t0)
    time.sleep(0.2)
    src.stop()
    got = src.poll(time.perf_counter(), 0)
    assert len(got) > 10
    assert [r.rid for r in got] == list(range(len(got)))
    assert all(r.due <= time.perf_counter() for r in got)
    assert len(src.lateness_ms) == len(got) and min(src.lateness_ms) >= 0


# ---------------------------------------------------------------------------
# end-to-end arithmetic


def test_itl_ttft_tokens_on_synthetic_stamps():
    stamps = [[1.0, 1.1, 1.3, 2.5], [0.5, 2.0], [3.5]]
    gaps = stats.itl_gaps(stamps, 1.05, 3.0)
    assert sorted(np.round(gaps, 6)) == [0.1, 0.2, 1.2, 1.5]
    assert stats.tokens_in(stamps, 1.05, 3.0) == 4
    ttft = stats.ttfts([(1.0, 0.2), (0.5, 0.1), (3.5, 3.0)], 0.6, 4.0)
    assert np.allclose(ttft, [0.8, 0.5])
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(101)), 95) == 95


def test_granted_tokens():
    assert harness.granted_tokens(100, 16, 2048) == 17
    assert harness.granted_tokens(2000, 500, 2048) == 1 + 47


# ---------------------------------------------------------------------------
# per-layer readers on a synthetic trace


def _synthetic_readings():
    ms = 1_000_000
    ops = [[("%while.3 = (s32[]) while()", 0 * ms, 4 * ms),
            ("%fusion.1 = f32[8] fusion()", 0 * ms, 3 * ms),
            ("%fusion.1 = f32[8] fusion()", 3 * ms, 4 * ms),
            ("%custom-call.7 = f32[8] custom-call()", 5 * ms, 6 * ms),
            ("%custom-call.9 = f32[8] custom-call()", 8 * ms, 9 * ms)]]
    modules = [[("jit__decode_step(1)", 0, 4 * ms), ("jit__chunk_step(2)", 8 * ms, 9 * ms),
                ("jit__tiered_lookup_segments(3)", 5 * ms, 6 * ms)]]
    spans = [("bench.window", 0, 10 * ms), ("bench.step", 0, 4 * ms),
             ("bench.readback", 6 * ms, 8 * ms), ("bench.submit", 9 * ms, 10 * ms)]
    tr = trace.from_events(ops, modules, spans)
    model = spec.config("smollm-360m")
    peaks = spec.peaks("TPU v5 lite")
    return {
        "trace": tr, "steps": 4, "chunk_steps": 1, "step_host_s": 0.02,
        "near_hits": 30, "far_hits": 10, "lateness_ms": [0.1] * 19 + [5.0],
        "peak_bytes": peaks["hbm_bytes"] // 4, "peaks": peaks, "model": model,
        "step_log": [(16, 16 * 300, False)], "lookups": [(2000, 1000)],
    }


def test_trace_busy_idle_and_breakdown():
    r = _synthetic_readings()
    tr = r["trace"]
    assert tr.window_s == pytest.approx(0.01)
    assert trace.busy_s(tr) == pytest.approx(0.006)
    assert trace.idle_gaps(tr) == [(4_000_000, 5_000_000), (6_000_000, 8_000_000),
                                   (9_000_000, 10_000_000)]
    b = trace.breakdown(tr)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.004)]
    assert [n for n, _ in b["device_ops"]] == ["fusion.1", "custom-call.7", "custom-call.9"]
    assert b["idle_gaps"][0] == ["readback", pytest.approx(0.002)]
    assert b["idle_gaps"][1][0] in ("other", "submit")


EXPECTED = {
    "engine.host_ms_per_step": 5.0,
    "engine.chunk_step_share": 25.0,
    "model.decode_dispatch_ms": 4.0,
    "model.chunk_dispatch_ms": 1.0,
    "tier.far_read_share": 25.0,
    "tiered_gather_roofline": 100.0 * 430_144_000 / 819e9 / 0.001,
    "serve.mfu": 100.0 * dense_lm.flops(spec.config("smollm-360m"), 16, 4800) / (0.01 * 197e12),
    "device.idle_share": 40.0,
    "device.peak_hbm_share": 25.0,
}


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_each_reader_on_a_synthetic_trace(name):
    r = _synthetic_readings()
    assert spec.metric_reader(name)(r) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", ["model.decode_dispatch_ms", "tiered_gather_roofline",
                                  "serve.mfu", "device.idle_share", "model.chunk_dispatch_ms"])
def test_reader_with_nothing_to_read_returns_none(name):
    r = _synthetic_readings()
    r.update(trace=None, lateness_ms=[], lookups=None, step_log=None)
    assert spec.metric_reader(name)(r) is None


# ---------------------------------------------------------------------------
# costs, peaks, names


def test_dense_costs_hand_worked():
    m = spec.config("smollm-360m")
    assert dense_lm.matmul_flops_per_token(m) == 723_517_440
    assert dense_lm.span_context_sum(0, 512) == 512 * 513 // 2
    assert dense_lm.flops(m, 512, dense_lm.span_context_sum(0, 512)) == 386_578_513_920
    il = UNTIED
    # untied head: 24 * (2048*(2048+2*1024) + 2048*2048 + 3*2048*8192) + 2048*92544
    per_layer = 2048 * 4096 + 2048 * 2048 + 3 * 2048 * 8192
    assert dense_lm.matmul_flops_per_token(il) == 2 * (24 * per_layer + 2048 * 92544)


def test_tiered_gather_costs_hand_worked():
    m = spec.config("smollm-360m")
    assert tiered_gather.row_dim(m) == 20480
    assert tiered_gather.bytes_needed(2000, 1000, 20480) == 430_144_000
    assert tiered_gather.row_dim(UNTIED) == 2 * 24 * 8 * 128


def test_peaks_refuse_an_unknown_device_kind():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.UnknownName):
        spec.peaks("TPU v9 imaginary")


def test_every_name_resolves():
    for c in BENCH["configs"]:
        conf = spec.config(c["name"])
        assert (ROOT / c["file"]).is_file() and conf["name"] == c["name"]
        assert conf["source"] == c["source"] and conf["reduced"] == c["reduced"]
        assert spec.reference(conf["reference"]).make
    for w in BENCH["workloads"]:
        cell = spec.cell(w["name"], BENCH)
        mix = spec.traffic(w["traffic"])
        assert spec.generator(mix["kind"]).make
        assert set(spec.limits(w["name"])) >= {"logit_gap", "kv_row_err"}
        e2e = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2 and cell["per_layer"]
        for m in cell["per_layer"]:
            assert m["moves"] in e2e, (w["name"], m["name"])
    for m in BENCH["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


@pytest.mark.parametrize("lookup", [
    lambda: spec.config("no-such-model"),
    lambda: spec.traffic("no_such_mix"),
    lambda: spec.generator("no_such_kind"),
    lambda: spec.metric_reader("no.such.metric"),
    lambda: spec.cell("no-such.cell", BENCH),
    lambda: spec.config("../BENCHMARK"),
])
def test_an_unknown_name_fails_loudly(lookup):
    with pytest.raises(spec.UnknownName):
        lookup()


def test_benchmark_file_keeps_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert all((ROOT / p).is_dir() for p in BENCH["paths"])
    assert (ROOT / BENCH["command"][1]).is_file()
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert all(w["chips"] == 1 for w in BENCH["workloads"])


def test_a_new_cell_is_new_files_and_entries(tmp_path):
    """A configuration, a traffic mix, a per-layer metric and a cell are
    each a new file plus a new entry; no existing file changes."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    conf = dict(spec.config("smollm-360m"), name="smollm-360m-b8")
    conf["engine"] = dict(conf["engine"], max_batch=8, n_pages=1024)
    (bench / "configs" / "smollm-360m-b8.json").write_text(json.dumps(conf))
    (bench / "traffic" / "burst_new.json").write_text(json.dumps(
        dict(SHORT_MIX, rate_per_s=3.0)))
    (bench / "metrics" / "engine.steps_new.py").write_text("def read(r):\n    return r['steps']\n")
    (bench / "limits" / "smollm-360m-b8.burst_new.json").write_text(
        json.dumps(spec.limits(CELLS[0])))
    doc = copy.deepcopy(BENCH)
    doc["configs"].append(dict(doc["configs"][0], name="smollm-360m-b8",
                               file="bench/configs/smollm-360m-b8.json"))
    doc["workloads"].append({"name": "smollm-360m-b8.burst_new", "config": "smollm-360m-b8",
                             "traffic": "burst_new", "chips": 1, "why": "test"})
    doc["per_layer"].append({"name": "engine.steps_new", "unit": "steps", "better": "higher",
                             "source": "program_counter", "layer": "engine",
                             "moves": "output_tokens_per_s",
                             "workloads": ["smollm-360m-b8.burst_new"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    for p, data in before.items():
        assert p.read_bytes() == data
    found = spec.benchmark(tmp_path)
    cell = spec.cell("smollm-360m-b8.burst_new", found)
    assert spec.config(cell["config"], bench)["engine"]["max_batch"] == 8
    mix = spec.traffic(cell["traffic"], bench)
    src = spec.generator(mix["kind"], bench).make(mix, seed=1, vocab=10, max_len=64, max_batch=8)
    assert len(src.offsets) > 0
    assert "engine.steps_new" in [m["name"] for m in cell["per_layer"]]
    assert spec.metric_reader("engine.steps_new", bench)({"steps": 7}) == 7
    assert spec.limits(cell["name"], bench)["logit_gap"] > 0


# ---------------------------------------------------------------------------
# whole runs on the CPU, at a reduced model size


def test_run_refuses_a_cpu_backend():
    from bench import run

    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELLS[0], "--seed", "3", "--seconds", "1"])
    assert "tpu" in str(e.value).lower()


def test_run_fails_without_the_program(tmp_path):
    """In a directory that holds only the benchmark's files, a run exits
    non-zero and prints no result."""
    import subprocess

    for rel in BENCH["paths"]:
        shutil.copytree(ROOT / rel, tmp_path / rel, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    done = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path)})
    assert done.returncode != 0
    assert "correct" not in done.stdout


@pytest.fixture(scope="module")
def prog():
    """The program, with one model object per reduced configuration, so the
    runs of this module compile each engine step once."""
    p = harness.program()
    return type(p)(**dict(vars(p), get_model=functools.lru_cache(None)(p.get_model)))


# Limits for the reduced model (2 layers, d_model 64, float32 weights, a
# bfloat16 KV cache). Its readings on the CPU: sound runs read logit gaps of
# 0 to 7e-4 and KV rows within 6.6e-3; the int8 control reads rows off by
# 0.03-0.04, fp8 by 0.12-0.15; a token altered where it is produced reads a
# gap of 0.94, rows altered read 2.0.
REDUCED_LIMITS = {"logit_gap": 0.004, "kv_row_err": 0.015}


def _reduced_run(prog, mix_name, *, seconds=2.0, seed=11, limits=REDUCED_LIMITS, fault=None,
                 controls=()):
    """One whole run of the smollm-360m configuration under ``mix_name``,
    reporting every metric the benchmark defines."""
    cell = {"name": f"smollm-360m.{mix_name}", "config": "smollm-360m", "traffic": mix_name,
            "chips": 1, "end_to_end": BENCH["end_to_end"], "per_layer": BENCH["per_layer"]}
    conf = spec.config(cell["config"])
    cfg = prog.get_config(conf["registry_name"]).reduced()
    conf.update(hidden_size=cfg.d_model, num_hidden_layers=cfg.n_layers,
                num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
                intermediate_size=cfg.d_ff, vocab_size=cfg.vocab_size)
    conf["engine"] = dict(conf["engine"], max_batch=4, max_len=128, n_pages=32,
                          prefill_chunk=16)
    mix = _mix(mix_name)
    mix["prompt"].update(median=24, lo=4, hi=64)
    mix["output"].update(median=10, lo=2, hi=60)
    if mix["kind"] == "open_loop":
        mix["rate_per_s"] = 4.0
    build = harness.build
    if fault is not None:
        harness.build = lambda *a, **k: fault(build(*a, **k))
    try:
        return harness.run(
            prog, cell, conf, mix, limits, seed, seconds, trace=False, out_dir=None,
            t_start=time.perf_counter(), model_cfg=cfg, log=lambda m: None,
            peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10},
            controls=controls)
    finally:
        harness.build = build


@pytest.mark.parametrize("mix_name", ["decode_long", "short"])
def test_reduced_run_delivers_every_granted_token(prog, mix_name):
    r = _reduced_run(prog, mix_name)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["checks"]["delivery_misses"]["value"] == 0
    assert r["checks"]["hits_minus_ids"]["value"] == 0
    assert r["window_compiles"] == 0  # set-up built every executable the window used
    if mix_name == "decode_long":  # more live pages than near rows
        assert r["compared"]["far_rows"] > 0, r["compared"]
    m = r["metrics"]
    assert m["setup_s"]["value"] > 0 and m["output_tokens_per_s"]["value"] > 0
    assert m["itl_p99_ms"]["value"] >= m["itl_p50_ms"]["value"] > 0
    assert list(r)[-1] == "checks"


def _alter_tokens(eng):
    """A token altered where it is produced: the decode step's argmax + 1."""
    inner = eng._decode

    def bad(params, cache, tokens):
        nxt, cache = inner(params, cache, tokens)
        return (nxt + 1) % eng.cfg.vocab_size, cache

    eng._decode = bad
    return eng


def _alter_rows(eng):
    """Tier-plane rows altered where they are gathered."""
    store = eng.tiered
    inner = store.lookup_segments
    store.lookup_segments = lambda *a, **k: -inner(*a, **k)
    return eng


def _drop_counts(eng):
    """A lookup that leaves one of the ids it was handed uncounted."""
    store = eng.tiered
    inner = store.lookup_segments

    def dropped(ids, seg_of, n, **k):
        return inner(np.asarray(ids)[:-1], np.asarray(seg_of)[:-1], n, **k)

    store.lookup_segments = dropped
    return eng


@pytest.mark.parametrize("fault", [_alter_tokens, _alter_rows, _drop_counts],
                         ids=["token", "rows", "counts"])
def test_a_broken_served_path_is_not_correct(prog, fault):
    r = _reduced_run(prog, "decode_long", fault=fault)
    assert not r["correct"], r["checks"]


def test_the_control_is_not_correct(prog):
    """The control, the reference in a lower precision put in the program's
    place, is read on the same served tokens and KV positions as the
    program, and fails a limit that the program meets."""
    r = _reduced_run(prog, "decode_long", controls=("int8", "fp8"))
    assert r["correct"], r["checks"]
    for q in ("int8", "fp8"):
        assert not r["control"][q]["correct"], (q, r["control"][q]["checks"])
