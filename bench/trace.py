"""Read a JAX profiler trace into the few lists the per-layer readers use.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``jax.profiler.ProfileData`` reads it: planes, their lines, and events with
a start and a duration in nanoseconds. Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds every operation that ran
and their ``XLA Modules`` line every executable. The serving loop marks its
own phases on the host with ``jax.profiler.TraceAnnotation`` named
``bench.<phase>``, on the same clock.
"""
from __future__ import annotations

import bisect
import dataclasses
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    window: tuple  # (start_ns, end_ns) of the traced window
    ops: list  # per device: [(name, start_ns, end_ns)], sorted by start
    modules: list  # per device: [(name, start_ns, end_ns)]
    spans: list  # host [(name, start_ns, end_ns)] of the serving loop

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line):
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in line.events]


def load(trace_dir) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(find_xplane(trace_dir)))
    ops, modules, spans = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            ops.append(sorted(_events(lines[OPS_LINE])) if OPS_LINE in lines else [])
            modules.append(_events(lines[MODULES_LINE]) if MODULES_LINE in lines else [])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in _events(line) if e[0].startswith(SPAN_PREFIX)]
    return from_events(ops, modules, spans)


def from_events(ops, modules, spans) -> Trace:
    """A ``Trace`` from event lists; the window is the ``bench.window`` span."""
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    w = (windows[0][1], windows[0][2])
    ops = [sorted((o for o in dev if o[2] > w[0] and o[1] < w[1]), key=lambda o: o[1]) for dev in ops]
    modules = [[m for m in dev if m[2] > w[0] and m[1] < w[1]] for dev in modules]
    spans = sorted((s for s in spans if s[0] != WINDOW_SPAN), key=lambda s: s[1])
    return Trace(w, ops, modules, spans)


def busy_intervals(ops, window) -> list:
    """Union of the operations' intervals, clipped to the window."""
    out = []
    for _, a, b in sorted(ops, key=lambda o: o[1]):
        a, b = max(a, window[0]), min(b, window[1])
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    per = [sum(b - a for a, b in busy_intervals(dev, trace.window)) for dev in trace.ops]
    return sum(per) / len(per) * 1e-9 if per else 0.0


def idle_gaps(trace: Trace, device: int = 0) -> list:
    """[(start_ns, end_ns)] of the window in which the device ran nothing."""
    busy = busy_intervals(trace.ops[device], trace.window)
    gaps, t = [], trace.window[0]
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < trace.window[1]:
        gaps.append((t, trace.window[1]))
    return gaps


def host_phase(trace: Trace, t_ns: int) -> str:
    """The serving-loop phase the host was in at ``t_ns``."""
    for name, a, b in trace.spans:
        if a <= t_ns < b:
            return name[len(SPAN_PREFIX):]
        if a > t_ns:
            break
    return "other"


def leaves(ops) -> list:
    """The operations that contain no other: a ``while`` op's interval
    covers the operations of its body, which the line also holds."""
    out, stack = [], []  # stack: [op, holds another]
    for op in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0][2] <= op[1]:
            done, inner = stack.pop()
            if not inner:
                out.append(done)
        if stack:
            stack[-1][1] = True
        stack.append([op, False])
    out += [o for o, inner in stack if not inner]
    return out


def short_name(op_text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return op_text.split(" = ", 1)[0].lstrip("%")


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time (innermost operations,
    by name), and the longest idle gaps named by what the host was doing
    in their middle."""
    total: dict = {}
    for name, a, b in leaves(trace.ops[0]) if trace.ops else []:
        name = short_name(name)
        total[name] = total.get(name, 0) + (min(b, trace.window[1]) - max(a, trace.window[0]))
    ops = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(trace) if trace.ops else [], key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[n, d * 1e-9] for n, d in ops],
        "idle_gaps": [[host_phase(trace, (a + b) // 2), (b - a) * 1e-9] for a, b in gaps],
    }


def module_times(trace: Trace, needle: str, device: int = 0) -> list:
    """Durations in seconds of the executables whose name contains ``needle``."""
    if not trace.modules:
        return []
    return [(b - a) * 1e-9 for n, a, b in trace.modules[device] if needle in n]


def op_times(trace: Trace, module: str, needle: str, device: int = 0) -> list:
    """Durations in seconds of the operations whose text contains
    ``needle`` and that ran inside an executable whose name contains
    ``module``."""
    if not trace.ops or not trace.modules:
        return []
    spans = sorted((a, b) for n, a, b in trace.modules[device] if module in n)
    starts = [a for a, _ in spans]
    out = []
    for n, a, b in trace.ops[device]:
        k = bisect.bisect_right(starts, a) - 1
        if k >= 0 and b <= spans[k][1] and needle in n:
            out.append((b - a) * 1e-9)
    return out
