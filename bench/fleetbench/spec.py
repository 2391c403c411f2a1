"""The benchmark as data: ``BENCHMARK.json`` at the repository's root,
and files found by name under ``bench/``:

- ``configs[].file``: the deployment (a JSON object);
- ``bench/traffic/<traffic>.json``: the traffic mix of a cell;
- ``bench/metrics/<metric>.py``: the reader of a metric, a module with
  ``read(ctx) -> float | None``.

Adding a cell, a deployment, a mix or a metric adds files and entries;
no code here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class SpecError(ValueError):
    pass


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    kind: str                # "end_to_end" | "per_layer"
    workloads: tuple | None
    reader: object           # module with read(ctx)

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


def _load_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def _load_reader(path: Path):
    if not path.is_file():
        raise SpecError(f"metric has no reader: {path}")
    spec = importlib.util.spec_from_file_location(
        f"_bench_metric_{path.stem.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path} defines no read(ctx)")
    return mod


class Benchmark:
    """``BENCHMARK.json`` with everything it names, checked."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench_dir = self.root / "bench"
        self.doc = _load_json(self.root / "BENCHMARK.json")
        self.configs = {
            c["name"]: _load_json(self.root / c["file"])
            for c in self.doc["configs"]
        }
        self.metrics: dict[str, Metric] = {}
        for kind in ("end_to_end", "per_layer"):
            for m in self.doc[kind]:
                wl = m.get("workloads")
                self.metrics[m["name"]] = Metric(
                    m["name"], m["unit"], m["better"], m["source"], kind,
                    tuple(wl) if wl is not None else None,
                    _load_reader(self.bench_dir / "metrics"
                                 / f"{m['name']}.py"),
                )
        self.validate()

    def validate(self) -> None:
        doc = self.doc
        names = [c["name"] for c in doc["configs"]]
        names += [w["name"] for w in doc["workloads"]]
        names += list(self.metrics)
        for w in doc["workloads"]:
            names += [w["config"], w["traffic"]]
        for c in doc["configs"]:
            names += list(c["reduced"])
        bad = [n for n in names if not NAME_RE.match(n)]
        if bad:
            raise SpecError(f"names outside [A-Za-z0-9_.-]: {bad}")
        for group in (doc["configs"], doc["workloads"]):
            seen = [x["name"] for x in group]
            if len(set(seen)) != len(seen):
                raise SpecError(f"duplicate names in {seen}")
        if len(self.metrics) != len(doc["end_to_end"]) + len(doc["per_layer"]):
            raise SpecError("two metrics share a name")
        for m in self.metrics.values():
            if not UNIT_RE.match(m.unit):
                raise SpecError(f"unit {m.unit!r} of {m.name}")
            if m.better not in ("lower", "higher"):
                raise SpecError(f"better {m.better!r} of {m.name}")
        cells = {w["name"] for w in doc["workloads"]}
        for w in doc["workloads"]:
            if w["config"] not in self.configs:
                raise SpecError(f"cell {w['name']} names no configuration "
                                f"{w['config']!r}")
            if w["chips"] not in (1, 4):
                raise SpecError(f"cell {w['name']}: chips {w['chips']}")
            self.traffic(w["traffic"])
        pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
        if len(set(pairs)) != len(pairs):
            raise SpecError("a (config, traffic) pair appears twice")
        four = sum(w["chips"] == 4 for w in doc["workloads"])
        if four > max(1, len(doc["workloads"]) // 2):
            raise SpecError(f"{four} of {len(doc['workloads'])} cells take "
                            f"4 chips")
        for m in self.metrics.values():
            for c in m.workloads or ():
                if c not in cells:
                    raise SpecError(f"metric {m.name} lists unknown cell {c}")
        used = {w["config"] for w in doc["workloads"]}
        if set(self.configs) - used:
            raise SpecError(f"configurations in no cell: "
                            f"{sorted(set(self.configs) - used)}")

    def traffic(self, name: str) -> dict:
        return _load_json(self.bench_dir / "traffic" / f"{name}.json")

    def cell(self, name: str) -> Cell:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return Cell(w["name"], w["config"], w["traffic"], w["chips"],
                            self.configs[w["config"]],
                            self.traffic(w["traffic"]))
        raise SpecError(f"no cell named {name!r}; cells: "
                        f"{[w['name'] for w in self.doc['workloads']]}")

    def metrics_for(self, cell: str, kind: str) -> list[Metric]:
        return [m for m in self.metrics.values()
                if m.kind == kind and m.applies_to(cell)]
