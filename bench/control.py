"""Read the numbers ``correct`` compares, for the program and its control,
over many seeds in one process. The benchmark's own runs never run this.

    python bench/control.py --workload smollm-360m.decode_long --seconds 15 \\
        --seeds 101,102,103 --controls int8,fp8 --out .bench_out/control.jsonl

For each seed it runs the cell as ``bench/run.py`` does (set-up, a window of
``--seconds``, the post-window row capture) and then the reference twice
over: in float32, against what the program served (the lower readings), and
in each lower precision put in the program's place (the control's upper
readings). One JSON line per seed goes to ``--out``. The limits in
``bench/limits/<cell>.json`` are set from these lines.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="int8,fp8")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import harness, spec

    prog = harness.program()
    # one model object for every seed, so each engine step compiles once
    prog.get_model = functools.lru_cache(None)(prog.get_model)
    cell = spec.cell(args.workload, spec.benchmark())
    harness.require_tpu(prog, cell["chips"])
    harness.compile_cache()
    conf = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    limits = spec.limits(args.workload)
    controls = tuple(q for q in args.controls.split(",") if q)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with open(out, "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            t = time.perf_counter()
            r = harness.run(prog, cell, conf, mix, limits, seed, args.seconds, trace=False,
                            out_dir=None, t_start=t, controls=controls,
                            log=lambda m: print(f"[control] {m}", file=sys.stderr, flush=True))
            line = {"workload": args.workload, "seed": seed, "correct": r["correct"],
                    "checks": r["checks"], "control": r["control"],
                    "metrics": r["metrics"], "device": r["device"],
                    "seconds": time.perf_counter() - t}
            f.write(json.dumps(line) + "\n")
            f.flush()
            print(json.dumps(line), flush=True)
            gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
