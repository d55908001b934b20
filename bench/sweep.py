"""Find the knee of an open-loop cell: the highest arrival rate at which
the backlog does not grow over a window. The benchmark's runs never run
this; a benchmark change runs it once, on the chip, and writes the rate it
finds into the traffic mix (``knee_per_s``).

    python bench/sweep.py --workload <open-loop cell> --rates 0.5,1,2 --seconds 45

Each rate gets a fresh engine (the compiled steps are shared) and a window
of ``--seconds``; one JSON line per rate reports the requests that arrived
and finished, the queue left at the close, and the delivered tokens.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import harness, spec, stats

    prog = harness.program()
    prog.get_model = functools.lru_cache(None)(prog.get_model)
    cell = spec.cell(args.workload, spec.benchmark())
    harness.require_tpu(prog, cell["chips"])
    harness.compile_cache()
    conf = spec.config(cell["config"])
    ref = spec.reference(conf["reference"])
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(spec.traffic(cell["traffic"]), rate_per_s=rate)
        eng = harness.build(prog, conf, ref, args.seed)
        e = eng.ecfg
        harness.warm(eng, min(e.n_pages, e.max_batch * -(-e.max_len // e.page_size)),
                     lambda m: print(f"[sweep] {m}", file=sys.stderr, flush=True))
        source = spec.generator(mix["kind"]).make(
            mix, seed=args.seed, vocab=conf["vocab_size"], max_len=e.max_len,
            max_batch=e.max_batch)
        loop = harness.Loop(prog, eng, source, vocab=conf["vocab_size"])
        w = harness.window(loop, args.seconds)
        t0, t1 = w["t0"], w["t1"]
        served = loop.served.values()
        ttft = stats.ttfts([(s.stamps[0], s.due) for s in served if s.stamps], t0, t1)
        line = {
            "rate_per_s": rate, "window_s": t1 - t0, "arrived": len(loop.served),
            "finished": sum(s.done for s in served), "queued_at_close": len(eng.queue),
            "busy_slots_at_close": sum(s.active for s in eng.slots),
            "steps": w["steps"], "chunk_steps": w["chunk_steps"],
            "tokens_per_s": stats.tokens_in([s.stamps for s in served], t0, t1) / (t1 - t0),
            "ttft_p50_s": stats.percentile(ttft, 50) if len(ttft) else None,
            "ttft_max_s": float(ttft.max()) if len(ttft) else None,
        }
        print(json.dumps(line), flush=True)
        del eng, loop, source
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
