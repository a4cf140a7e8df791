"""``BENCHMARK.json`` resolved into cells.

A cell is one entry of ``workloads``: a configuration file, a traffic file
and the metrics that list it.  Nothing here names a particular cell — a
later PR adds one by adding data files and manifest entries, and this
module finds them by name.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
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

    def cell(self, name: str) -> Cell:
        if name not in self.workloads:
            raise ManifestError(
                f"no workload {name!r} in BENCHMARK.json (have: "
                f"{', '.join(sorted(self.workloads))})")
        w = self.workloads[name]
        if w["config"] not in self.configs:
            raise ManifestError(f"workload {name!r} names config "
                                f"{w['config']!r}, which is not listed")
        config = _read_json(os.path.join(
            self.root, self.configs[w["config"]]["file"]))
        traffic = _read_json(os.path.join(
            self.bench_dir, "traffic", w["traffic"] + ".json"))
        return Cell(
            name=name, chips=int(w["chips"]), config_name=w["config"],
            traffic_name=w["traffic"], config=config, traffic=traffic,
            end_to_end=[m for m in self.doc["end_to_end"]
                        if self._applies(m, name)],
            per_layer=[self.layer_metric(m["name"])
                       for m in self.doc["per_layer"]
                       if self._applies(m, name)])

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
        if four > max(1, len(doc["workloads"]) // 4):
            raise ManifestError("too many four-chip cells")
        used = {w["config"] for w in doc["workloads"]}
        for c in doc["configs"]:
            if c["name"] not in used:
                raise ManifestError(f"config {c['name']!r} has no cell")
