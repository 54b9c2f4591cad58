"""BENCHMARK.json and the data files it names, loaded and cross-checked.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name the manifest
gives it: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``layer_metrics/<metric>.json``. A later PR adds files and manifest
entries and edits no file that exists.
"""

from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
MANIFEST_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
                 "end_to_end", "per_layer"}


class ManifestError(Exception):
    """The manifest or one of its data files breaks the contract."""


def _load(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"{path}: {e}") from e


class Manifest:
    """The benchmark as data. ``root`` is the checkout; ``bench`` the
    benchmark's own directory (the first of ``paths``)."""

    def __init__(self, root: str):
        self.root = root
        self.doc = _load(os.path.join(root, "BENCHMARK.json"))
        self.bench = os.path.join(root, self.doc["paths"][0])
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.metrics = {m["name"]: m for m in
                        self.doc["end_to_end"] + self.doc["per_layer"]}

    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise ManifestError(f"no workload {name!r}; the manifest has "
                                f"{sorted(self.cells)}")
        return self.cells[name]

    def config_file(self, config: str) -> dict:
        return _load(os.path.join(self.root, self.configs[config]["file"]))

    def traffic_file(self, traffic: str) -> dict:
        return _load(os.path.join(self.bench, "traffic", f"{traffic}.json"))

    def layer_metric_file(self, metric: str) -> dict:
        return _load(os.path.join(self.bench, "layer_metrics",
                                  f"{metric}.json"))

    def metrics_of(self, cell: str, group: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports:
        those without a ``workloads`` list, and those that list it."""
        return [m for m in self.doc[group]
                if cell in m.get("workloads", [cell])]

    def validate(self) -> None:
        """Cross-reference everything. Raises ManifestError naming the
        first breach; the contract's own limits are the driver's to
        enforce, these are the ones a missing file would turn into a
        late failure on the chip."""
        doc = self.doc
        if set(doc) != MANIFEST_KEYS:
            raise ManifestError(f"keys {sorted(doc)} != "
                                f"{sorted(MANIFEST_KEYS)}")
        if not 1 <= int(doc["run_seconds"]) <= 51:
            raise ManifestError("run_seconds outside 1..51")
        seen = set()
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            for entry in doc[group]:
                name = entry["name"]
                if not NAME.match(name) or (group, name) in seen:
                    raise ManifestError(f"bad or repeated name {name!r}")
                seen.add((group, name))
        e2e = {m["name"] for m in doc["end_to_end"]}
        if "setup_s" not in e2e:
            raise ManifestError("no setup_s among end_to_end")
        for group in ("end_to_end", "per_layer"):
            for m in doc[group]:
                if not UNIT.match(m["unit"]):
                    raise ManifestError(f"{m['name']}: bad unit {m['unit']!r}")
                if m["better"] not in ("lower", "higher"):
                    raise ManifestError(f"{m['name']}: better={m['better']!r}")
                if m["source"] not in SOURCES:
                    raise ManifestError(f"{m['name']}: source {m['source']!r}")
                for w in m.get("workloads", []):
                    if w not in self.cells:
                        raise ManifestError(f"{m['name']}: unknown cell {w!r}")
        for m in doc["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                raise ManifestError(f"{m['name']}: an end-to-end metric "
                                    f"is the benchmark's own reading")
            if not 0.01 <= m["bound"] <= 0.25:
                raise ManifestError(f"{m['name']}: bound {m['bound']}")
        for m in doc["per_layer"]:
            if m["moves"] not in e2e:
                raise ManifestError(f"{m['name']}: moves {m['moves']!r}")
            spec = self.layer_metric_file(m["name"])
            for key in ("unit", "layer", "moves"):
                if spec[key] != m[key]:
                    raise ManifestError(f"{m['name']}: {key} differs "
                                        f"between manifest and its file")
        used = set()
        four = 0
        for w in doc["workloads"]:
            for key in ("config", "traffic"):
                if not NAME.match(w[key]):
                    raise ManifestError(f"{w['name']}: bad {key}")
            if w["config"] not in self.configs:
                raise ManifestError(f"{w['name']}: no config {w['config']!r}")
            if w["chips"] not in (1, 4):
                raise ManifestError(f"{w['name']}: chips {w['chips']}")
            four += w["chips"] == 4
            used.add(w["config"])
            cfg = self.config_file(w["config"])
            traffic = self.traffic_file(w["traffic"])
            if traffic["driver"] != cfg["driver"]:
                raise ManifestError(f"{w['name']}: traffic is for driver "
                                    f"{traffic['driver']!r}, config for "
                                    f"{cfg['driver']!r}")
            if not os.path.exists(os.path.join(
                    self.bench, "drivers", cfg["driver"] + ".py")):
                raise ManifestError(f"no driver {cfg['driver']!r}")
            names = {m["name"] for m in self.metrics_of(w["name"],
                                                        "end_to_end")}
            if "setup_s" not in names or len(names) < 2:
                raise ManifestError(f"{w['name']}: needs setup_s and "
                                    f"another end-to-end metric")
            layer = self.metrics_of(w["name"], "per_layer")
            if not layer:
                raise ManifestError(f"{w['name']}: no per-layer metric")
            for m in layer:
                if m["moves"] not in names:
                    raise ManifestError(
                        f"{m['name']} moves {m['moves']!r}, which "
                        f"{w['name']} does not report")
        if used != set(self.configs):
            raise ManifestError(f"unused configs {set(self.configs) - used}")
        if four > max(1, len(doc["workloads"]) // 2):
            raise ManifestError(f"{four} four-chip cells")
        for c in doc["configs"]:
            cfg = self.config_file(c["name"])
            for key in c["reduced"]:
                if not NAME.match(key) or key not in cfg:
                    raise ManifestError(f"{c['name']}: reduced key {key!r} "
                                        f"is not in {c['file']}")
