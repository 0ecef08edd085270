"""BENCHMARK.json and the files its names point at.

A cell is one entry of `workloads`: `{name, config, traffic, chips, why}`.
Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found here by name and never by an
edit to the harness:

    config   -> the `file` of the `configs` entry with that name
    traffic  -> benchmarks/traffic/<traffic>.json
    runner   -> benchmarks/runners/<config file's "runner">.py
    metric   -> benchmarks/layer_metrics/<per_layer name>.py
    flops    -> benchmarks/flops/<config>.py
    reference-> benchmarks/reference/<config file's "reference">.py
"""
import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
KEYS = ("command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
MAX_BOUND = 0.1


class ManifestError(ValueError):
    """BENCHMARK.json, or a file it names, breaks the contract."""


def load(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    validate(manifest, root)
    return manifest


def _by_name(entries, what):
    out = {}
    for e in entries:
        name = e.get("name", "")
        if not NAME.match(name):
            raise ManifestError(f"{what} name {name!r} breaks the naming rule")
        if name in out:
            raise ManifestError(f"{what} name {name!r} is used twice")
        out[name] = e
    return out


def validate(manifest, root=ROOT):
    """The checks the driver makes before a run, as far as they can be
    made from the files alone."""
    if sorted(manifest) != sorted(KEYS):
        raise ManifestError(f"keys must be exactly {KEYS}, got "
                            f"{sorted(manifest)}")
    configs = _by_name(manifest["configs"], "config")
    cells = _by_name(manifest["workloads"], "workload")
    e2e = _by_name(manifest["end_to_end"], "end_to_end metric")
    layer = _by_name(manifest["per_layer"], "per_layer metric")
    if set(e2e) & set(layer):
        raise ManifestError("a metric name is both end_to_end and per_layer")
    if "setup_s" not in e2e:
        raise ManifestError("end_to_end must hold setup_s")
    for c in configs.values():
        path = os.path.join(root, c["file"])
        if not any(c["file"].startswith(p.rstrip("/") + "/")
                   for p in manifest["paths"]):
            raise ManifestError(f"{c['file']} lies outside paths")
        if not os.path.isfile(path):
            raise ManifestError(f"config file {c['file']} is missing")
        if len(c["why"]) > 200:
            raise ManifestError(f"{c['name']}: why is over 200 characters")
        absent = set(c["reduced"]) - set(_read_json(path))
        if absent:
            raise ManifestError(f"{c['name']}: reduced names {sorted(absent)},"
                                f" no key of {c['file']}")
    pairs = set()
    for w in cells.values():
        if w["config"] not in configs:
            raise ManifestError(f"{w['name']}: unknown config {w['config']!r}")
        if w["chips"] not in (1, 4):
            raise ManifestError(f"{w['name']}: chips must be 1 or 4")
        if len(w["why"]) > 200:
            raise ManifestError(f"{w['name']}: why is over 200 characters")
        pair = (w["config"], w["traffic"])
        if pair in pairs:
            raise ManifestError(f"{pair} appears twice")
        pairs.add(pair)
        if not os.path.isfile(traffic_path(w["traffic"], root)):
            raise ManifestError(f"{w['name']}: no traffic file for "
                                f"{w['traffic']!r}")
    for c in configs:
        if not any(w["config"] == c for w in cells.values()):
            raise ManifestError(f"config {c!r} is used by no cell")
    for m in e2e.values():
        if m["source"] not in ("host_clock", "device_trace"):
            raise ManifestError(f"{m['name']}: an end-to-end metric is taken "
                                f"by the benchmark itself")
        if not 0 < m["bound"] <= MAX_BOUND:
            raise ManifestError(f"{m['name']}: bound {m['bound']}")
    for m in list(e2e.values()) + list(layer.values()):
        if m["better"] not in ("higher", "lower"):
            raise ManifestError(f"{m['name']}: better {m['better']!r}")
        if m["source"] not in SOURCES:
            raise ManifestError(f"{m['name']}: source {m['source']!r}")
        for w in m.get("workloads", ()):
            if w not in cells:
                raise ManifestError(f"{m['name']}: unknown cell {w!r}")
    for m in layer.values():
        if not LAYER.match(m["layer"]):
            raise ManifestError(f"{m['name']}: layer {m['layer']!r} breaks "
                                f"the naming rule")
        if m["moves"] not in e2e:
            raise ManifestError(f"{m['name']}: moves {m['moves']!r}")
        moved_in = set(e2e[m["moves"]].get("workloads", cells))
        if not set(m.get("workloads", cells)) <= moved_in:
            raise ManifestError(
                f"{m['name']} is reported in a cell where {m['moves']} "
                f"is not")
    for name in cells:
        if len(metrics_for(manifest, "end_to_end", name)) < 2:
            raise ManifestError(f"{name}: needs setup_s and one more "
                                f"end-to-end metric")
        if not metrics_for(manifest, "per_layer", name):
            raise ManifestError(f"{name}: needs a per-layer metric")


def metrics_for(manifest, kind, cell_name):
    """The entries of `end_to_end` or `per_layer` that `cell_name`
    reports: those with no `workloads` key, or with the cell in it."""
    return [m for m in manifest[kind]
            if cell_name in m.get("workloads", (cell_name,))]


def traffic_path(traffic, root=ROOT):
    return os.path.join(root, "benchmarks", "traffic", traffic + ".json")


def _read_json(path):
    with open(path) as f:
        return json.load(f)


class Cell:
    """One workload of the manifest with its configuration and traffic
    files read."""

    def __init__(self, manifest, name, root=ROOT):
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise ManifestError(f"no workload {name!r}; have {sorted(cells)}")
        self.manifest = manifest
        self.root = root
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.config_name = self.entry["config"]
        config_entry = next(c for c in manifest["configs"]
                            if c["name"] == self.config_name)
        self.config = _read_json(os.path.join(root, config_entry["file"]))
        self.traffic = _read_json(traffic_path(self.entry["traffic"], root))
        self.end_to_end = metrics_for(manifest, "end_to_end", name)
        self.per_layer = metrics_for(manifest, "per_layer", name)

    def flops(self):
        """benchmarks/flops/<config>.py: operations and bytes from shapes."""
        return self.module("flops", self.config_name)

    def module(self, kind, name):
        """The module benchmarks/<kind>/<name>.py, loaded by path (metric
        and configuration names hold dots and dashes)."""
        path = os.path.join(self.root, "benchmarks", kind, name + ".py")
        if not os.path.isfile(path):
            raise ManifestError(f"{self.name}: no file {path}")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
