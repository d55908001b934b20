"""Benchmark suite driver: one benchmark per paper table/figure.

PYTHONPATH=src python -m benchmarks.run            # all
PYTHONPATH=src python -m benchmarks.run table5     # one

``--trace out.json`` attaches the fleet flight recorder (repro.obs) for the
whole run: every engine/fleet the selected benchmarks build emits
request-lifecycle spans and registry metrics through one process-global
recorder, exported on exit as Perfetto/Chrome trace-event JSON (open at
https://ui.perfetto.dev) plus ``out.json.metrics.jsonl``.
"""
import importlib
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(__file__))  # allow intra-package helpers

MODULES = [
    "fig9_code_bw",
    "table2_correlation",
    "fig13_pooling",
    "fig17_pagetable",
    "fig18_membw_dist",
    "table5_tiering",
    "fig21_prefetch_bw",
    "fig22_prefetch_acc",
    "table6_trace",
    "fleet_bench",
    "chaos_bench",
    "straggler_bench",
    "tenant_interference",
    "tiered_decode_bench",
    "decode_dispatch_bench",
    "kernels_bench",
]


def parse_trace_flag(argv):
    """Split ``--trace PATH`` out of argv; returns (path_or_None, rest)."""
    argv = list(argv)
    if "--trace" not in argv:
        return None, argv
    i = argv.index("--trace")
    if i + 1 >= len(argv):
        raise SystemExit("--trace requires an output path")
    path = argv[i + 1]
    return path, argv[:i] + argv[i + 2 :]


def main(argv):
    trace_path, argv = parse_trace_flag(argv)
    recorder = None
    if trace_path is not None:
        from repro.obs import FlightRecorder, set_default_recorder

        recorder = FlightRecorder()
        set_default_recorder(recorder)
    sel = [m for m in MODULES if not argv or any(a in m for a in argv)]
    if argv and not sel:
        print(f"no benchmark matches {argv}; available: {MODULES}")
        return 2
    failures = []
    for name in sel:
        print("\n" + "=" * 78)
        t0 = time.time()
        try:
            mod = importlib.import_module(name)
            rc = mod.main()
            # benchmarks return result dicts on success; an int is a
            # process-style return code (fleet_bench's self-check)
            if isinstance(rc, int) and rc != 0:
                failures.append(name)
                print(f"[{name}] FAILED: main() returned {rc}")
            else:
                print(f"[{name}] ok in {time.time()-t0:.1f}s")
        except Exception:  # noqa: BLE001
            failures.append(name)
            print(f"[{name}] FAILED:\n{traceback.format_exc(limit=6)}")
    print("\n" + "=" * 78)
    print(f"benchmarks: {len(sel) - len(failures)}/{len(sel)} ok" + (f"; failed: {failures}" if failures else ""))
    if recorder is not None:
        # one timeline over everything that ran; the schema gate only holds
        # within a single scenario (benchmarks rebuild fleets, reusing rids
        # on one timeline), so the suite export skips validation — the CI
        # smoke job validates a single-scenario trace instead
        summary = recorder.write(trace_path, validate=False)
        print(f"flight recorder: {summary['events']} trace events -> {trace_path} "
              f"(+ {trace_path}.metrics.jsonl)")
    return 1 if failures else 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main(sys.argv[1:]))
