"""Find a cell's parts by the names that ``BENCHMARK.json`` gives them.

Every configuration, traffic mix, generator kind and per-layer metric is a
file of its own, found by name:

- ``bench/configs/<config>.json``: model, engine settings, deployment;
- ``bench/traffic/<mix>.json``: the mix's parameters and its generator
  ``kind``, whose code is ``bench/traffic/<kind>.py``;
- ``bench/metrics/<metric>.py``: a reader with ``read(run) -> float | None``.

A later change adds a cell by adding such files and entries; nothing here
changes. An unknown name is an error that lists the known ones.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NAME_CHARS = set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789_.-")


class UnknownName(KeyError):
    pass


def _checked(name: str) -> str:
    if not name or len(name) > 64 or not set(name) <= NAME_CHARS or name[0] in ".-":
        raise UnknownName(f"{name!r} is not a benchmark name")
    return name


def benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _json(kind: str, name: str, bench: Path) -> dict:
    path = bench / kind / f"{_checked(name)}.json"
    if not path.is_file():
        known = sorted(p.stem for p in (bench / kind).glob("*.json"))
        raise UnknownName(f"no {kind[:-1] if kind.endswith('s') else kind} {name!r}; known: {known}")
    with open(path) as f:
        return json.load(f)


def _module(kind: str, name: str, bench: Path):
    path = bench / kind / f"{_checked(name)}.py"
    if not path.is_file():
        known = sorted(p.stem for p in (bench / kind).glob("*.py") if not p.stem.startswith("_"))
        raise UnknownName(f"no {kind} module {name!r}; known: {known}")
    modname = f"bench.{kind}.{name}"
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def config(name: str, bench: Path = BENCH) -> dict:
    return _json("configs", name, bench)


def traffic(name: str, bench: Path = BENCH) -> dict:
    return _json("traffic", name, bench)


def generator(kind: str, bench: Path = BENCH):
    """The traffic generator kind's module (``make(mix, ...)``)."""
    return _module("traffic", kind, bench)


def metric_reader(name: str, bench: Path = BENCH):
    """The per-layer metric's ``read(run)`` function."""
    return _module("metrics", name, bench).read


def cell(name: str, spec: dict) -> dict:
    """The ``workloads`` entry called ``name``, with its metrics: the
    end-to-end and per-layer entries that apply to it."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise UnknownName(f"no workload {name!r}; known: {sorted(cells)}")
    w = dict(cells[name])

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    w["end_to_end"] = [m for m in spec["end_to_end"] if applies(m)]
    w["per_layer"] = [m for m in spec["per_layer"] if applies(m)]
    return w


def peaks(device_kind: str, bench: Path = BENCH) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    with open(bench / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownName(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def reference(name: str, bench: Path = BENCH):
    """The plain reference module of a model family (weights and forward)."""
    return _module("references", name, bench)


def limits(cell_name: str, bench: Path = BENCH) -> dict:
    """The cell's correctness limits, each set from measured readings."""
    return _json("limits", cell_name, bench)
