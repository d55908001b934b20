"""Run one benchmark cell once.

    python bench/run.py --workload smollm-360m.decode_long --seed 7 --seconds 40 --trace 0

The cell, its configuration, traffic mix and metrics are found by name from
``BENCHMARK.json`` (see ``bench/spec.py``). The run refuses any backend that
is not a TPU, and interpret-mode kernels. It prints progress and the numbers
it compared, each beside its limit, on standard error (the compared numbers
last), and as the last line of standard output one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (with
``--trace 1`` also ``breakdown``), and ``checks`` last. With ``--trace 0``
the metrics are the cell's end-to-end metrics; with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window's first
seconds.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
OUT = ROOT / ".bench_out"


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    try:
        from bench import harness
    except ImportError as e:
        log(f"cannot import the benchmark or the program ({e})")
        return 2
    out = OUT / args.workload
    if args.trace:
        shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True, exist_ok=True)
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  trace=bool(args.trace), out_dir=out, log=log)
    finally:
        shutil.rmtree(out / "trace", ignore_errors=True)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
