"""Find a cell and everything it names, by name, from BENCHMARK.json.

A cell names a configuration and a traffic mix. Each lives in a file of
its own that is found by name, so a later change adds a cell by adding
files and one ``workloads`` entry:

  bench/configs/<config>.json        sizes as run, source, cuts, runner
  bench/references/<reference>.py    the plain float32 reference
  bench/runners/<runner>.py          drives the program for that kind
  bench/traffic/<traffic>.json       parameters the one generator reads
  bench/limits/<cell>.json           limits of the correctness comparison
  bench/metrics/<metric>.py          one reader per per-layer metric; a
                                     metric split by cell kind, such as
                                     idle_share.serve, may share the
                                     reader of its base name, idle_share
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple[str, ...]  # metric names this cell reports
    per_layer: tuple[str, ...]
    units: dict  # metric name -> unit


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a file by path (names may hold '.' and '-'), once."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, bench_json: Path | None = None) -> Cell:
    spec = _json(bench_json or ROOT / "BENCHMARK.json")
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(by_name)}")
    w = by_name[workload]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload)]
    per_layer = [m for m in spec["per_layer"] if _applies(m, workload)]
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=_json(ROOT / cfg_entry["file"]),
        traffic=_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
        limits=_json(BENCH_DIR / "limits" / f"{workload}.json"),
        end_to_end=tuple(m["name"] for m in e2e),
        per_layer=tuple(m["name"] for m in per_layer),
        units={m["name"]: m["unit"] for m in e2e + per_layer},
    )


def runner(cell: Cell):
    kind = cell.config["runner"]
    return load_module(BENCH_DIR / "runners" / f"{kind}.py",
                       f"bench_runner_{kind}")


def reference(config: dict):
    ref = config["reference"]
    return load_module(BENCH_DIR / "references" / f"{ref}.py",
                       f"bench_reference_{ref}")


def metric_reader(name: str):
    """``metrics/<name>.py``, else the reader of the name's base (the part
    before its first '.')."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    if not path.exists():
        name = name.split(".", 1)[0]
        path = BENCH_DIR / "metrics" / f"{name}.py"
    return load_module(path, "bench_metric_" + name.replace(".", "_"))
