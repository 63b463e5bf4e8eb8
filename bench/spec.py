"""Find a cell's configuration, traffic mix, metrics and readers by name.

A later change adds a configuration, a mix or a metric by adding files
and ``BENCHMARK.json`` entries; nothing here names one of them.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

#: The repository root: ``BENCHMARK.json`` and ``bench/`` live here.
REPO = Path(__file__).resolve().parent.parent


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything it names loaded."""

    name: str
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    chips: int
    end_to_end: tuple  # metric entries of BENCHMARK.json that this cell reports
    per_layer: tuple


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Bench:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: Path = REPO):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _file(self, *parts: str) -> Path:
        return self.root.joinpath("bench", *parts)

    def cell(self, name: str) -> Cell:
        """The workload ``name``; an unknown name lists the known ones."""
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if name not in cells:
            raise ValueError(f"unknown workload {name!r}; BENCHMARK.json has "
                             f"{sorted(cells)}")
        w = cells[name]
        return Cell(
            name=name, config_name=w["config"], config=self.config(w["config"]),
            traffic_name=w["traffic"], traffic=self.traffic(w["traffic"]),
            chips=int(w["chips"]),
            end_to_end=tuple(m for m in self.spec["end_to_end"] if _applies(m, name)),
            per_layer=tuple(m for m in self.spec["per_layer"] if _applies(m, name)))

    def config(self, name: str) -> dict:
        """The configuration file that ``configs`` names for ``name``."""
        entries = {c["name"]: c for c in self.spec["configs"]}
        if name not in entries:
            raise ValueError(f"unknown configuration {name!r}; BENCHMARK.json "
                             f"has {sorted(entries)}")
        return json.loads((self.root / entries[name]["file"]).read_text())

    def traffic(self, name: str) -> dict:
        """The parameters of traffic mix ``name`` (``bench/traffic/<name>.json``)."""
        path = self._file("traffic", f"{name}.json")
        if not path.is_file():
            raise ValueError(f"unknown traffic mix {name!r}: no {path}")
        return json.loads(path.read_text())

    def reader(self, metric: str):
        """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
        return _load(self._file("metrics", f"{metric}.py"), "metric",
                     metric).read

    def reference(self, program: str):
        """The plain reference of ``program`` (``bench/references/<program>.py``):
        ``reference(inputs, dtype) -> {store: array}``."""
        return _load(self._file("references", f"{program}.py"), "reference",
                     program).reference

    def peaks(self, device_kind: str) -> dict:
        """The published peaks of ``device_kind``; a kind not in
        ``bench/peaks.json`` is an error, never a default."""
        table = json.loads(self._file("peaks.json").read_text())
        if device_kind not in table:
            raise ValueError(f"device kind {device_kind!r} is not in the peaks "
                             f"table; it has {sorted(table)}")
        return table[device_kind]


def _load(path: Path, kind: str, name: str):
    if not path.is_file():
        raise ValueError(f"unknown {kind} {name!r}: no {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
