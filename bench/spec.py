"""Resolve a cell of ``BENCHMARK.json`` to its files, by name alone.

- configuration ``c``: the ``file`` its entry names (``bench/configs/``),
  whose ``arch`` names ``bench/refs/<arch>.py`` (the plain reference) and
  ``bench/adapters/<arch>.py`` (how the program takes it);
- traffic ``t``: ``bench/traffic/<t>.json``, whose ``mode`` names the
  module ``bench/drive_<mode>.py``, which runs the cell;
- per-layer metric ``m``: the reader ``bench/metrics/<m>.py``, or, for
  ``base.suffix``, ``bench/metrics/<base>.py`` (the suffix only says which
  end-to-end metric it moves);
- the limits of cell ``w``: ``bench/limits/<w>.json``.

Adding any of these takes new files and new entries, never an edit.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List

BENCH = "bench"


@dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: Dict
    mix: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    readers: Dict[str, Callable] = field(default_factory=dict)


def load_spec(root: Path) -> Dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def reader_path(root: Path, metric: str) -> Path:
    own = Path(root) / BENCH / "metrics" / f"{metric}.py"
    if own.exists():
        return own
    return Path(root) / BENCH / "metrics" / f"{metric.split('.')[0]}.py"


def load_reader(root: Path, metric: str) -> Callable:
    path = reader_path(root, metric)
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell(root: Path, name: str) -> Cell:
    root = Path(root)
    spec = load_spec(root)
    wl = {w["name"]: w for w in spec["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = wl[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    mix = json.loads(
        (root / BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    limits_file = root / BENCH / "limits" / f"{name}.json"
    limits = json.loads(limits_file.read_text()) if limits_file.exists() \
        else {}
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if m["moves"] in e2e_names and _reports(m, name)]
    c = Cell(name, w["config"], w["traffic"], int(w["chips"]), config, mix,
             limits, e2e, per_layer)
    c.readers = {m["name"]: load_reader(root, m["name"]) for m in per_layer}
    return c


def mode_module(mode: str):
    return importlib.import_module(f"{BENCH}.drive_{mode}")


def arch(name: str):
    """(reference module, adapter module) of an architecture."""
    return (importlib.import_module(f"{BENCH}.refs.{name}"),
            importlib.import_module(f"{BENCH}.adapters.{name}"))


def peak(root: Path, device_kind: str) -> Dict:
    table = json.loads((Path(root) / BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in the peaks "
                       f"table ({sorted(table)})")
    return table[device_kind]
