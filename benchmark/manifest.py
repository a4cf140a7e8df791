"""``BENCHMARK.json`` resolved into cells.

A cell is one entry of ``workloads``: a configuration file, a traffic file
and the metrics that list it.  Nothing here names a particular cell — a
later PR adds one by adding data files and manifest entries, and this
module finds them by name: data files through :class:`Manifest`, code that
belongs to one configuration, one reader kind or one driver through
:func:`find_module`.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import re
import sys
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    """The manifest, or a file it names, is missing or malformed."""


def _read_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"{path}: {e}") from e


def module_path(sub: str, name: str, bench_dir: str = BENCH_DIR) -> str:
    """``<bench_dir>/<sub>/<name>.py``, which has to be there: one that is
    not is a :class:`ManifestError`, never a default."""
    path = os.path.join(bench_dir, sub, name + ".py")
    if not NAME_RE.match(name) or not os.path.isfile(path):
        raise ManifestError(f"no {sub}/{name}.py under {bench_dir}: "
                            f"whatever names {name!r} needs that file")
    return path


def find_module(sub: str, name: str, bench_dir: str = BENCH_DIR):
    """The module ``<bench_dir>/<sub>/<name>.py``: a configuration's plain
    reference (``reference``) or count of multiply-adds (``model_flops``), a
    reader kind (``reader_kinds``), a formula (``formulas``), a driver
    (``drivers``).  A harness directory other than this package's own (a
    test's copy) is loaded from its files."""
    path = module_path(sub, name, bench_dir)
    if (os.path.realpath(bench_dir) == os.path.realpath(BENCH_DIR)
            and name.isidentifier()):
        return importlib.import_module(f"benchmark.{sub}.{name}")
    key = "_benchmark_file_" + re.sub(r"\W", "_", os.path.realpath(path))
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[key] = module
        try:
            spec.loader.exec_module(module)
        except BaseException:
            del sys.modules[key]
            raise
    return sys.modules[key]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]        # configs/<config>.json as read
    traffic: Dict[str, Any]       # traffic/<traffic>.json as read
    end_to_end: List[Dict[str, Any]]   # manifest entries this cell reports
    per_layer: List[Dict[str, Any]]    # layer_metrics/<name>.json as read
    bench_dir: str = BENCH_DIR         # where the cell's files were found


class Manifest:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.doc = _read_json(os.path.join(root, "BENCHMARK.json"))
        # the harness directory is where the command's program lives
        self.bench_dir = os.path.join(
            root, os.path.dirname(self.doc["command"][1]))
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.workloads = {w["name"]: w for w in self.doc["workloads"]}

    @property
    def run_seconds(self) -> int:
        return int(self.doc["run_seconds"])

    def _applies(self, metric: Dict[str, Any], cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def layer_metric(self, name: str) -> Dict[str, Any]:
        """``layer_metrics/<name>.json``: how the metric is read."""
        spec = _read_json(os.path.join(self.bench_dir, "layer_metrics",
                                       name + ".json"))
        spec.setdefault("name", name)
        return spec

    def module_file(self, sub: str, name: str) -> str:
        """``<sub>/<name>.py`` of this harness (:func:`module_path`)."""
        return module_path(sub, name, self.bench_dir)

    def config(self, name: str) -> Dict[str, Any]:
        """The configuration's file, as read."""
        if name not in self.configs:
            raise ManifestError(f"no config {name!r} in BENCHMARK.json")
        return _read_json(os.path.join(self.root, self.configs[name]["file"]))

    def cell(self, name: str) -> Cell:
        if name not in self.workloads:
            raise ManifestError(
                f"no workload {name!r} in BENCHMARK.json (have: "
                f"{', '.join(sorted(self.workloads))})")
        w = self.workloads[name]
        config = self.config(w["config"])
        traffic = _read_json(os.path.join(
            self.bench_dir, "traffic", w["traffic"] + ".json"))
        return Cell(
            name=name, chips=int(w["chips"]), config_name=w["config"],
            traffic_name=w["traffic"], config=config, traffic=traffic,
            end_to_end=[m for m in self.doc["end_to_end"]
                        if self._applies(m, name)],
            per_layer=[self.layer_metric(m["name"])
                       for m in self.doc["per_layer"]
                       if self._applies(m, name)],
            bench_dir=self.bench_dir)

    def validate(self) -> None:
        """Everything the driver refuses before a run that can be checked
        from the files alone; raises :class:`ManifestError`."""
        doc = self.doc
        want = {"command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end", "per_layer"}
        if set(doc) != want:
            raise ManifestError(f"keys {sorted(doc)} != {sorted(want)}")
        if not 1 <= doc["run_seconds"] <= 51:
            raise ManifestError("run_seconds outside 1..51")

        def names(entries, what):
            seen = set()
            for e in entries:
                if not NAME_RE.match(e["name"]):
                    raise ManifestError(f"illegal {what} name {e['name']!r}")
                if e["name"] in seen:
                    raise ManifestError(f"duplicate {what} {e['name']!r}")
                seen.add(e["name"])
            return seen

        names(doc["configs"], "config")
        for c in doc["configs"]:
            # what a configuration brings as code, found by its name: its
            # plain reference and its count of multiply-adds a frame
            for sub in ("reference", "model_flops"):
                self.module_file(sub, c["name"])
        cells = names(doc["workloads"], "workload")
        metrics = doc["end_to_end"] + doc["per_layer"]
        names(metrics, "metric")
        e2e = {m["name"]: m for m in doc["end_to_end"]}
        if "setup_s" not in e2e:
            raise ManifestError("no setup_s among end_to_end")
        for m in metrics:
            if not UNIT_RE.match(m["unit"]):
                raise ManifestError(f"illegal unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                raise ManifestError(f"{m['name']}: better={m['better']!r}")
            if m["source"] not in SOURCES:
                raise ManifestError(f"{m['name']}: source={m['source']!r}")
            for c in m.get("workloads", ()):
                if c not in cells:
                    raise ManifestError(
                        f"{m['name']} lists unknown workload {c!r}")
        for m in doc["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                raise ManifestError(f"{m['name']}: an end-to-end metric is "
                                    "taken by the benchmark itself")
            if not 0 < m["bound"] <= 0.1:
                raise ManifestError(f"{m['name']}: bound {m['bound']}")
        pairs = set()
        four = 0
        for w in doc["workloads"]:
            for key in ("config", "traffic"):
                if not NAME_RE.match(w[key]):
                    raise ManifestError(f"illegal {key} {w[key]!r}")
            if (w["config"], w["traffic"]) in pairs:
                raise ManifestError(f"pair repeated in {w['name']!r}")
            pairs.add((w["config"], w["traffic"]))
            if w["chips"] not in (1, 4):
                raise ManifestError(f"{w['name']}: chips={w['chips']}")
            four += w["chips"] == 4
            if not 1 <= len(w["why"]) <= 200:
                raise ManifestError(f"{w['name']}: why is too long")
            cell = self.cell(w["name"])     # resolves every file by name
            self.module_file("drivers", cell.traffic.get("driver", ""))
            reported = {m["name"] for m in cell.end_to_end}
            if len(reported) < 2 or not cell.per_layer:
                raise ManifestError(
                    f"{w['name']}: needs setup_s, another end-to-end "
                    "metric and a per-layer metric")
            for entry in doc["per_layer"]:
                if not self._applies(entry, w["name"]):
                    continue
                if entry["moves"] not in reported:
                    raise ManifestError(
                        f"{entry['name']} moves {entry['moves']!r}, which "
                        f"{w['name']!r} does not report")
                spec = self.layer_metric(entry["name"])
                for key in ("unit", "layer", "moves", "source"):
                    if spec.get(key) != entry[key]:
                        raise ManifestError(
                            f"layer_metrics/{entry['name']}.json: {key}="
                            f"{spec.get(key)!r} but the manifest says "
                            f"{entry[key]!r}")
        from benchmark import readers

        for entry in doc["per_layer"]:
            readers.resolve(self.layer_metric(entry["name"]), self.bench_dir)
        if four > max(1, len(doc["workloads"]) // 4):
            raise ManifestError("too many four-chip cells")
        used = {w["config"] for w in doc["workloads"]}
        for c in doc["configs"]:
            if c["name"] not in used:
                raise ManifestError(f"config {c['name']!r} has no cell")
