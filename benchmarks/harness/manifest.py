"""Reads `BENCHMARK.json` and finds, by name, the file that belongs to
each thing it names: a configuration, a traffic mix, a family, a kind of
run, a per-layer metric. Nothing here knows a cell by name, so a later PR
adds a cell, a mix or a metric as new files plus a manifest entry.
"""

import importlib.util
import json
import pathlib
import re

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH_DIR = ROOT / "benchmarks"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    """Import one file of the benchmark by path (metric readers have
    dots in their names, so they are no importable module names)."""
    path = pathlib.Path(path)
    if not path.is_file():
        raise ManifestError(f"no such file: {path}")
    name = "bench_" + re.sub(r"[^A-Za-z0-9_]", "_", str(path.relative_to(path.parents[1])))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_mix(bench_dir, name):
    """A traffic mix's file. Where it names its engine (`"engine":
    "<name>"`), that is `engines/<name>.json`: the deployment's geometry,
    a block of its own that every mix served from it shares."""
    bench_dir = pathlib.Path(bench_dir)
    mix = load_json(bench_dir / "mixes" / f"{name}.json")
    if isinstance(mix.get("engine"), str):
        mix["engine"] = load_json(
            bench_dir / "engines" / f"{mix['engine']}.json")
    return mix


class Manifest:
    def __init__(self, root=ROOT):
        self.root = pathlib.Path(root)
        self.bench_dir = self.root / "benchmarks"
        self.data = load_json(self.root / "BENCHMARK.json")
        self.configs = {c["name"]: c for c in self.data["configs"]}
        self.workloads = {w["name"]: w for w in self.data["workloads"]}
        self.end_to_end = {m["name"]: m for m in self.data["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.data["per_layer"]}

    # -- cells ---------------------------------------------------------

    def cell(self, name):
        """Everything one run needs: the manifest entry, the
        configuration's file, the mix's file and the metric names this
        cell reports."""
        if name not in self.workloads:
            raise ManifestError(
                f"unknown workload {name!r}; BENCHMARK.json has "
                f"{sorted(self.workloads)}"
            )
        entry = self.workloads[name]
        config_entry = self.configs[entry["config"]]
        config = load_json(self.root / config_entry["file"])
        mix = load_mix(self.bench_dir, entry["traffic"])
        return {
            "name": name,
            "chips": int(entry["chips"]),
            "config_name": entry["config"],
            "traffic": entry["traffic"],
            "config": config,
            "mix": mix,
            "end_to_end": self.metrics_of(name, self.end_to_end),
            "per_layer": self.per_layer_of(name),
        }

    def metrics_of(self, cell_name, table):
        """Names from ``table`` this cell reports: a metric without a
        `workloads` key belongs to every cell."""
        return [
            m["name"] for m in table.values()
            if "workloads" not in m or cell_name in m["workloads"]
        ]

    def per_layer_of(self, cell_name):
        """Per-layer metrics of a cell. One with no `workloads` key is
        due in every cell that reports the end-to-end metric it moves."""
        cell_e2e = set(self.metrics_of(cell_name, self.end_to_end))
        out = []
        for m in self.per_layer.values():
            if "workloads" in m:
                if cell_name in m["workloads"]:
                    out.append(m["name"])
            elif m["moves"] in cell_e2e:
                out.append(m["name"])
        return out

    # -- files found by name --------------------------------------------

    def family(self, config):
        return load_module(self.bench_dir / "families" / f"{config['family']}.py")

    def kind(self, mix):
        return load_module(self.bench_dir / "kinds" / f"{mix['kind']}.py")

    def layer_metric(self, name):
        return load_module(self.bench_dir / "layer_metrics" / f"{name}.py")

    # -- the contract's static rules ------------------------------------

    def problems(self):
        """Every breach of the manifest's own rules that can be seen
        without a run, as a list of sentences (empty = sound)."""
        d, out = self.data, []
        keys = {"command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end", "per_layer"}
        if set(d) != keys:
            out.append(f"keys {sorted(d)} != {sorted(keys)}")
        if not 1 <= int(d["run_seconds"]) <= 51:
            out.append("run_seconds outside 1..51")
        for word in d["command"]:
            if word.startswith("/") or ".." in word.split("/"):
                out.append(f"command word leaves the repo: {word}")
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            names = [e["name"] for e in d[group]]
            if len(set(names)) != len(names):
                out.append(f"duplicate name in {group}")
            for n in names:
                if not NAME_RE.match(n):
                    out.append(f"bad name {n!r} in {group}")
        metric_names = [m["name"] for m in d["end_to_end"] + d["per_layer"]]
        if len(set(metric_names)) != len(metric_names):
            out.append("a metric name is used twice")
        if "setup_s" not in self.end_to_end:
            out.append("no setup_s")
        for c in d["configs"]:
            if set(c) != {"name", "source", "file", "reduced", "why"}:
                out.append(f"config {c['name']} has keys {sorted(c)}")
            if not any(c["file"].startswith(p + "/") for p in d["paths"]):
                out.append(f"config file outside paths: {c['file']}")
            if not (self.root / c["file"]).is_file():
                out.append(f"config file missing: {c['file']}")
            if not any(w["config"] == c["name"] for w in d["workloads"]):
                out.append(f"config {c['name']} is used by no cell")
        pairs = set()
        for w in d["workloads"]:
            if set(w) != {"name", "config", "traffic", "chips", "why"}:
                out.append(f"workload {w['name']} has keys {sorted(w)}")
            if w["config"] not in self.configs:
                out.append(f"workload {w['name']}: unknown config")
            if w["chips"] not in (1, 4):
                out.append(f"workload {w['name']}: chips {w['chips']}")
            if not NAME_RE.match(w["traffic"]):
                out.append(f"workload {w['name']}: bad traffic name")
            if len(w["why"]) > 200 or "\n" in w["why"] or "\t" in w["why"]:
                out.append(f"workload {w['name']}: why too long")
            if not (self.bench_dir / "mixes" / f"{w['traffic']}.json").is_file():
                out.append(f"workload {w['name']}: no mix file")
            pair = (w["config"], w["traffic"])
            if pair in pairs:
                out.append(f"pair {pair} appears twice")
            pairs.add(pair)
        four = sum(1 for w in d["workloads"] if w["chips"] == 4)
        if four > max(1, len(d["workloads"]) // 4):
            out.append("too many four-chip cells")
        for m in d["end_to_end"]:
            allowed = {"name", "unit", "better", "bound", "source", "workloads"}
            if not set(m) <= allowed or not allowed - {"workloads"} <= set(m):
                out.append(f"end_to_end {m['name']} has keys {sorted(m)}")
            if m.get("source") not in ("host_clock", "device_trace"):
                out.append(f"end_to_end {m['name']}: source {m.get('source')}")
            if not 0 < m.get("bound", 0) <= 0.1:
                out.append(f"end_to_end {m['name']}: bound {m.get('bound')}")
        for m in d["per_layer"]:
            allowed = {"name", "unit", "better", "source", "layer", "moves",
                       "workloads"}
            if not set(m) <= allowed or not allowed - {"workloads"} <= set(m):
                out.append(f"per_layer {m['name']} has keys {sorted(m)}")
            if m.get("source") not in SOURCES:
                out.append(f"per_layer {m['name']}: source {m.get('source')}")
            if m.get("moves") not in self.end_to_end:
                out.append(f"per_layer {m['name']}: moves {m.get('moves')}")
            if not (self.bench_dir / "layer_metrics" / f"{m['name']}.py").is_file():
                out.append(f"per_layer {m['name']}: no reader file")
            moved = self.end_to_end.get(m.get("moves"), {})
            for cell in m.get("workloads", []):
                if cell not in self.workloads:
                    out.append(f"per_layer {m['name']}: unknown cell {cell}")
                elif "workloads" in moved and cell not in moved["workloads"]:
                    out.append(
                        f"per_layer {m['name']}: cell {cell} does not "
                        f"report {m['moves']}"
                    )
        for m in d["end_to_end"] + d["per_layer"]:
            if not UNIT_RE.match(m.get("unit", "")):
                out.append(f"metric {m['name']}: unit {m.get('unit')!r}")
            if m.get("better") not in ("lower", "higher"):
                out.append(f"metric {m['name']}: better {m.get('better')!r}")
            for cell in m.get("workloads", []):
                if cell not in self.workloads:
                    out.append(f"metric {m['name']}: unknown cell {cell}")
        for w in d["workloads"]:
            e2e = self.metrics_of(w["name"], self.end_to_end)
            if "setup_s" not in e2e or len(e2e) < 2:
                out.append(f"workload {w['name']}: needs setup_s and one more")
            if not self.per_layer_of(w["name"]):
                out.append(f"workload {w['name']}: no per-layer metric")
        return out
