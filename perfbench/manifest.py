"""`BENCHMARK.json` and the files it names. Everything that belongs to
one configuration, traffic mix, cell or per-layer metric sits in a file
of its own, found here by the name the manifest gives it:

    configs/<config>.json   the deployment's sizes, its `kind` and `entry`
    traffic/<traffic>.json  parameters for `perfbench/generator.py`
    cells/<cell>.json       warm-up, sample sizes and the limits of `correct`
    metrics/<metric>.py     `read(ctx)` for one per-layer metric
    kinds/<kind>.py         payloads and comparison for one kind of system
    entries/<entry>.py      how the system under test is built
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
MANIFEST_PATH = os.path.join(ROOT, "BENCHMARK.json")


class ManifestError(ValueError):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(path: str = MANIFEST_PATH) -> dict:
    return load_json(path)


def load_module(folder: str, name: str):
    """`perfbench/<folder>/<name>.py` as a module (names may hold dots)."""
    path = os.path.join(BENCH_DIR, folder, name + ".py")
    if not os.path.isfile(path):
        raise ManifestError(f"no {folder} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench.{folder}.{name.replace('.', '_').replace('-', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


class Cell:
    """One entry of `workloads` with everything its names resolve to."""

    def __init__(self, manifest: dict, name: str):
        rows = [w for w in manifest["workloads"] if w["name"] == name]
        if not rows:
            have = [w["name"] for w in manifest["workloads"]]
            raise ManifestError(f"no workload {name!r} in BENCHMARK.json, have {have}")
        self.name = name
        self.workload = rows[0]
        self.chips = int(self.workload["chips"])
        config_row = next(c for c in manifest["configs"] if c["name"] == self.workload["config"])
        self.config = load_json(os.path.join(ROOT, config_row["file"]))
        self.traffic = load_json(
            os.path.join(BENCH_DIR, "traffic", self.workload["traffic"] + ".json")
        )
        self.spec = load_json(os.path.join(BENCH_DIR, "cells", name + ".json"))
        self.end_to_end = [m for m in manifest["end_to_end"] if _applies(m, name)]
        self.per_layer = [m for m in manifest["per_layer"] if _applies(m, name)]

    def kind(self):
        return load_module("kinds", self.config["kind"])

    def entry(self, name: str | None = None):
        return load_module("entries", name or self.config["entry"])

    def reader(self, metric_name: str):
        return load_module("metrics", metric_name).read


def load_cell(name: str, manifest: dict | None = None) -> Cell:
    return Cell(manifest or load_manifest(), name)
