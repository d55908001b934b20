"""Where a step's host time goes: one traced run of a cell with the engine's
wall-clock phases on. The benchmark's own runs never run this.

    python bench/phases.py --workload smollm-360m.decode_long --seed 7 --seconds 51 \\
        --out .bench_out/phases.json

It runs the cell as ``bench/run.py --trace 1`` does, with the program's flight
recorder built with ``phases=True`` (``repro.obs.FlightRecorder``), so that
``ServingEngine.step`` times its phases (``engine.*`` and ``tier.*``) into
counters over the whole window and marks them on the profiler's host plane,
on the device trace's clock. It prints, as its last line, one JSON object:
the cell's per-layer metrics as ``bench/run.py`` reads them, the readings
below, the phase counters over the window (``phases``, ``{label: [calls,
seconds]}``, ``label`` being ``phase`` or ``phase{kind=...}``), the steps of
each kind (``steps_by_kind``), the share of each step kind's time that its
child phases cover in the trace, and the breakdown with every idle gap named
by the innermost phase that covers its middle.

- ``engine.decode_step_ms``: ``engine.step`` time on decode steps over decode
  steps, whole window;
- ``engine.bookkeeping_ms_per_step``: ``engine.account`` + ``engine.retire``
  over steps;
- ``tier.host_ms_per_step``: ``tier.lookup`` + ``tier.write`` + ``tier.drain``
  + ``tier.placement`` over steps;
- ``device.exposed_host_ms_per_decode_step``: device idle time from the start
  of each decode ``engine.step`` to the start of the next ``engine.step``,
  summed over the traced window, over the number of such intervals.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

PREFIXES = ("engine.", "tier.")
STEP = "engine.step"
BOOKKEEPING = ("engine.account", "engine.retire")
TIER = ("tier.lookup", "tier.write", "tier.drain", "tier.placement")


# ---------------------------------------------------------------------------
# counters


def phase_table(snapshot) -> dict:
    """``{label: [calls, seconds]}`` of the phase counters in a metrics
    snapshot, and the engine's ``{kind: steps}``."""
    calls, ns, steps = {}, {}, {}
    for (name, labels), v in snapshot.counters.items():
        lab = dict(labels)
        if name in ("phase_ns", "phase_calls"):
            key = lab["phase"] + (f"{{kind={lab['kind']}}}" if "kind" in lab else "")
            (ns if name == "phase_ns" else calls)[key] = v
        elif name == "engine_steps":
            steps[lab["kind"]] = steps.get(lab["kind"], 0) + v
    return {k: [calls.get(k, 0), ns[k] * 1e-9] for k in ns}, steps


def window_delta(before, after) -> tuple:
    """The phase table and step counts of what happened between two reads."""
    (p0, s0), (p1, s1) = before, after
    phases = {k: [c - p0.get(k, [0, 0.0])[0], s - p0.get(k, [0, 0.0])[1]]
              for k, (c, s) in p1.items()}
    phases = {k: v for k, v in phases.items() if v[0]}
    steps = {k: n - s0.get(k, 0) for k, n in s1.items()}
    return phases, steps


def _seconds(phases: dict, name: str) -> float:
    """Seconds of a phase over every kind."""
    return sum(s for k, (_, s) in phases.items() if k == name or k.startswith(name + "{"))


def readings(phases: dict, steps_by_kind: dict) -> dict:
    """The three readings of the phase counters, in ms; None where the
    window held no such step."""
    steps = sum(steps_by_kind.values())
    decode = steps_by_kind.get("decode", 0)

    def per_step(names):
        return sum(_seconds(phases, n) for n in names) / steps * 1e3 if steps else None

    return {
        "engine.decode_step_ms": (phases.get(STEP + "{kind=decode}", [0, 0.0])[1] / decode * 1e3
                                  if decode else None),
        "engine.bookkeeping_ms_per_step": per_step(BOOKKEEPING),
        "tier.host_ms_per_step": per_step(TIER),
    }


def split(phases: dict, kind: str) -> dict:
    """Milliseconds per step of ``kind`` in each child phase of its steps,
    and what they leave of ``engine.step``."""
    n, step_s = phases.get(f"{STEP}{{kind={kind}}}", [0, 0.0])
    if not n:
        return {}
    tag = f"{{kind={kind}}}"
    out = {k[: -len(tag)]: s / n * 1e3 for k, (_, s) in phases.items()
           if k.endswith(tag) and not k.startswith(STEP + "{")}
    out["unspanned"] = step_s / n * 1e3 - sum(out.values())
    return out


# ---------------------------------------------------------------------------
# the trace's host plane


def program_spans(trace_dir, window) -> list:
    """``[(name, start_ns, end_ns, kind)]`` of the host events named
    ``engine.*`` or ``tier.*`` inside ``window``, sorted by start."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(trace.find_xplane(trace_dir)))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    a, b = int(e.start_ns), int(e.start_ns + e.duration_ns)
                    if b > window[0] and a < window[1]:
                        out.append((e.name, a, b, dict(e.stats).get("kind")))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def innermost(spans: list, t_ns: int):
    """The name of the innermost span covering ``t_ns``, or None."""
    best = None
    for name, a, b, _ in spans:
        if a > t_ns:
            break
        if t_ns < b:
            best = name  # sorted by start: a later start lies inside
    return best


def named_gaps(tr, spans: list, top: int = 10) -> list:
    """The longest idle gaps of the device, each named by the innermost
    program span covering its middle, else by the serving loop's phase."""
    gaps = sorted(trace.idle_gaps(tr) if tr.ops else [], key=lambda g: g[0] - g[1])[:top]
    out = []
    for a, b in gaps:
        mid = (a + b) // 2
        out.append([innermost(spans, mid) or trace.host_phase(tr, mid), (b - a) * 1e-9])
    return out


def _busy_ns(busy: list, a: int, b: int) -> int:
    return sum(max(0, min(y, b) - max(x, a)) for x, y in busy)


def exposed_host_ms_per_decode_step(tr, spans: list):
    """Device idle time from the start of each decode ``engine.step`` to the
    start of the next ``engine.step``, over the number of such intervals, in
    ms; None where the trace has no such interval or no device operations."""
    if not tr.ops:
        return None
    steps = [s for s in spans if s[0] == STEP]
    busy = trace.busy_intervals(tr.ops[0], tr.window)
    idle, n = 0, 0
    for (_, a, _, kind), (_, nxt, _, _) in zip(steps, steps[1:]):
        if kind == "decode":
            idle += (nxt - a) - _busy_ns(busy, a, nxt)
            n += 1
    return idle / n * 1e-6 if n else None


def coverage(spans: list) -> dict:
    """Per step kind: the share of ``engine.step`` time that its child
    phases cover, in %, and the steps seen."""
    steps = [s for s in spans if s[0] == STEP]
    kids = [s for s in spans if s[0] != STEP]
    out = {}
    for _, a, b, kind in steps:
        inside = sum(y - x for _, x, y, _ in kids if x >= a and y <= b)
        c = out.setdefault(kind, [0, 0, 0])
        c[0] += 1
        c[1] += b - a
        c[2] += inside
    return {k: {"steps": n, "covered_pct": 100.0 * inside / total if total else None}
            for k, (n, total, inside) in out.items()}


# ---------------------------------------------------------------------------
# one run


def measure(prog, cell: dict, conf: dict, mix: dict, limits: dict, seed: int, seconds: float,
            out_dir: Path, *, log=print, **run_kw) -> dict:
    """``harness.run`` with a recorder whose phases are on and a profiler
    trace of the window, and what the phases read there."""
    from bench import harness
    from repro.obs import FlightRecorder, default_recorder, set_default_recorder

    rec = FlightRecorder(phases=True)
    got = {}
    window = harness.window

    def counted(loop, secs, **kw):
        before = phase_table(rec.merged_snapshot())
        w = window(loop, secs, **kw)
        got["phases"], got["steps_by_kind"] = window_delta(before, phase_table(rec.merged_snapshot()))
        return w

    previous = default_recorder()
    set_default_recorder(rec)  # what the harness's engine attaches
    harness.window = counted
    try:
        result = harness.run(prog, cell, conf, mix, limits, seed, seconds, trace=True,
                             out_dir=out_dir, t_start=time.perf_counter(), log=log, **run_kw)
    finally:
        harness.window = window
        set_default_recorder(previous)
    tr = trace.load(out_dir / "trace")
    spans = program_spans(out_dir / "trace", tr.window)
    out = readings(got["phases"], got["steps_by_kind"])
    out["device.exposed_host_ms_per_decode_step"] = exposed_host_ms_per_decode_step(tr, spans)
    return {
        "correct": result["correct"], "checks": result["checks"], "device": result["device"],
        "metrics": result["metrics"], "phase_readings": out,
        "phases": got["phases"], "steps_by_kind": got["steps_by_kind"],
        "split_ms": {k: split(got["phases"], k) for k in got["steps_by_kind"]},
        "coverage": coverage(spans), "program_spans": len(spans),
        "idle_gaps": named_gaps(tr, spans), "device_ops": result["breakdown"]["device_ops"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    from bench import harness, spec

    prog = harness.program()
    cell = spec.cell(args.workload, spec.benchmark())
    harness.require_tpu(prog, cell["chips"])
    harness.compile_cache()
    work = ROOT / ".bench_out" / f"{args.workload}.phases"
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    try:
        r = measure(prog, cell, spec.config(cell["config"]), spec.traffic(cell["traffic"]),
                    spec.limits(args.workload), args.seed, args.seconds, work,
                    log=lambda m: print(f"[phases] {m}", file=sys.stderr, flush=True))
    finally:
        shutil.rmtree(work, ignore_errors=True)
    line = json.dumps(dict(r, workload=args.workload, seed=args.seed))
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with open(out, "a") as f:
        f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
