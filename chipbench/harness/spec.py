"""BENCHMARK.json and the files it names, resolved by name.

A cell ``<config>.<traffic>`` finds its configuration through the
``configs`` entry's ``file``, its traffic mix at ``traffic/<traffic>.json``,
its driver at ``drivers/<driver>.py`` (the configuration's ``driver`` key)
and every metric at ``metrics/<name>.py``. Adding a cell, a configuration,
a mix or a metric is adding files and entries; nothing here changes."""

from __future__ import annotations

import importlib.util
import json
import pathlib
import re
from dataclasses import dataclass, field
from types import ModuleType
from typing import Any

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES_E2E = ("host_clock", "device_trace")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    kind: str  # "end_to_end" | "per_layer"
    moves: str | None = None
    workloads: tuple[str, ...] | None = None


@dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[Metric] = field(default_factory=list)
    per_layer: list[Metric] = field(default_factory=list)


def load_module(path: pathlib.Path, name: str) -> ModuleType:
    """Import one file by path, under a name no other file shares."""
    if not path.is_file():
        raise SpecError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Benchmark:
    """BENCHMARK.json under ``root`` and the files under ``root/chipbench``."""

    def __init__(self, root: pathlib.Path):
        self.root = pathlib.Path(root)
        self.dir = self.root / "chipbench"
        path = self.root / "BENCHMARK.json"
        if not path.is_file():
            raise SpecError(f"no BENCHMARK.json in {self.root}")
        self.doc = json.loads(path.read_text())
        self.metrics: dict[str, Metric] = {}
        for kind in ("end_to_end", "per_layer"):
            for m in self.doc[kind]:
                wl = m.get("workloads")
                self.metrics[m["name"]] = Metric(
                    name=m["name"], unit=m["unit"], better=m["better"],
                    source=m["source"], kind=kind, moves=m.get("moves"),
                    workloads=tuple(wl) if wl is not None else None)
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.workloads = {w["name"]: w for w in self.doc["workloads"]}

    # -- resolution ---------------------------------------------------------
    def config_file(self, name: str) -> pathlib.Path:
        try:
            return self.root / self.configs[name]["file"]
        except KeyError:
            raise SpecError(f"no configuration named {name!r}") from None

    def traffic_file(self, name: str) -> pathlib.Path:
        return self.dir / "traffic" / f"{name}.json"

    def metric_file(self, name: str) -> pathlib.Path:
        return self.dir / "metrics" / f"{name}.py"

    def driver_file(self, driver: str) -> pathlib.Path:
        return self.dir / "drivers" / f"{driver}.py"

    def _e2e_in(self, cell: str) -> list[Metric]:
        return [m for m in self.metrics.values() if m.kind == "end_to_end"
                and (m.workloads is None or cell in m.workloads)]

    def _per_layer_in(self, cell: str) -> list[Metric]:
        e2e = {m.name for m in self._e2e_in(cell)}
        out = []
        for m in self.metrics.values():
            if m.kind != "per_layer":
                continue
            if m.workloads is None:
                if m.moves in e2e:
                    out.append(m)
            elif cell in m.workloads:
                out.append(m)
        return out

    def cell(self, name: str) -> Cell:
        try:
            w = self.workloads[name]
        except KeyError:
            raise SpecError(f"no workload named {name!r}") from None
        cfg_path = self.config_file(w["config"])
        tr_path = self.traffic_file(w["traffic"])
        for p in (cfg_path, tr_path):
            if not p.is_file():
                raise SpecError(f"workload {name}: missing {p}")
        return Cell(name=name, config_name=w["config"],
                    traffic_name=w["traffic"], chips=int(w["chips"]),
                    config=json.loads(cfg_path.read_text()),
                    traffic=json.loads(tr_path.read_text()),
                    end_to_end=self._e2e_in(name),
                    per_layer=self._per_layer_in(name))

    def driver(self, cell: Cell) -> ModuleType:
        drv = cell.config.get("driver")
        if not drv or not NAME_RE.match(drv):
            raise SpecError(f"configuration {cell.config_name}: bad driver "
                            f"{drv!r}")
        return load_module(self.driver_file(drv), f"chipbench_driver_{drv}")

    def reader(self, metric: str) -> Any:
        """The metric's ``read(run) -> float | None``."""
        mod = load_module(self.metric_file(metric),
                          "chipbench_metric_" + metric.replace(".", "_")
                          .replace("-", "_"))
        return mod.read

    # -- the contract's static checks ---------------------------------------
    def problems(self) -> list[str]:
        """What in BENCHMARK.json breaks the contract's naming and wiring
        rules (empty when none)."""
        out: list[str] = []
        d = self.doc
        names = ([c["name"] for c in d["configs"]]
                 + [w["name"] for w in d["workloads"]]
                 + [m["name"] for m in d["end_to_end"] + d["per_layer"]]
                 + [w["config"] for w in d["workloads"]]
                 + [w["traffic"] for w in d["workloads"]]
                 + [k for c in d["configs"] for k in c["reduced"]])
        out += [f"bad name {n!r}" for n in names if not NAME_RE.match(n)]
        for m in self.metrics.values():
            if not UNIT_RE.match(m.unit):
                out.append(f"{m.name}: bad unit {m.unit!r}")
            if m.better not in ("lower", "higher"):
                out.append(f"{m.name}: better={m.better!r}")
            allowed = SOURCES_E2E if m.kind == "end_to_end" else SOURCES
            if m.source not in allowed:
                out.append(f"{m.name}: source {m.source!r}")
            if m.kind == "per_layer":
                tgt = self.metrics.get(m.moves)
                if tgt is None or tgt.kind != "end_to_end":
                    out.append(f"{m.name}: moves {m.moves!r} is not an "
                               f"end-to-end metric")
                for cell in m.workloads or ():
                    if tgt is not None and tgt not in self._e2e_in(cell):
                        out.append(f"{m.name}: cell {cell} does not report "
                                   f"{m.moves}")
            for cell in m.workloads or ():
                if cell not in self.workloads:
                    out.append(f"{m.name}: unknown cell {cell}")
            if not self.metric_file(m.name).is_file():
                out.append(f"{m.name}: no reader {self.metric_file(m.name)}")
        for w in d["workloads"]:
            if w["name"] != f"{w['config']}.{w['traffic']}":
                out.append(f"workload {w['name']} is not "
                           f"<config>.<traffic>")
            try:
                cell = self.cell(w["name"])
                self.driver(cell)
            except SpecError as e:
                out.append(str(e))
                continue
            e2e = {m.name for m in cell.end_to_end}
            if "setup_s" not in e2e or len(e2e) < 2:
                out.append(f"{cell.name}: needs setup_s and another "
                           f"end-to-end metric")
            if not cell.per_layer:
                out.append(f"{cell.name}: no per-layer metric")
        return out
