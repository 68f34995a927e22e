"""BENCHMARK.json against the builder's contract, and every name in it
resolving to a file of its own. Each check takes the manifest it reads
(`MANIFEST_CHECKS`, `check_a_cell_resolves`), so that
`test_phase_readers.py` can hold a grown copy to the same checks; the
files are looked for where `perfbench.manifest` says the tree is."""

from __future__ import annotations

import os
import re

import pytest

from perfbench import generator, manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
M = manifest.load_manifest()
CELLS = [w["name"] for w in M["workloads"]]


def check_top_level_keys_are_exactly_the_contracts(M):
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert M["command"] == ["python3", "perfbench/run.py"]
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert os.path.getsize(manifest.MANIFEST_PATH) <= 64 * 1024


def check_paths_hold_the_benchmark_and_the_command_lies_inside(M):
    assert 1 <= len(M["paths"]) <= 16
    for p in M["paths"]:
        assert os.path.isdir(os.path.join(manifest.ROOT, p))
    assert any(M["command"][1].startswith(p + "/") for p in M["paths"])


def check_files_under_paths_are_named_from_name_characters(M):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in M["paths"]:
        for folder, dirs, files in os.walk(os.path.join(manifest.ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(folder, f), manifest.ROOT)
                assert ok.match(rel), rel


SECTIONS = ["configs", "workloads", "end_to_end", "per_layer"]


def check_names_are_unique_and_well_formed(M, sections=SECTIONS):
    for section in sections:
        names = [row["name"] for row in M[section]]
        assert len(set(names)) == len(names)
        for n in names:
            assert NAME.match(n), n


def check_configs_have_their_files_and_are_all_used(M):
    used = {w["config"] for w in M["workloads"]}
    files = [c["file"] for c in M["configs"]]
    assert len(set(files)) == len(files)
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in M["paths"])
        body = manifest.load_json(os.path.join(manifest.ROOT, c["file"]))
        assert body["name"] == c["name"]
        assert body["reduced"] == c["reduced"]
        assert body["guarantees"], "a deployment states its guarantees"
        for text in (c["source"], c["why"]):
            assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def check_workloads_are_one_chip_pairs_that_appear_once(M):
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(M["workloads"]) // 2)
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]


def check_end_to_end_metrics_have_bounds_and_setup_is_among_them(M):
    cells = [w["name"] for w in M["workloads"]]
    names = [m["name"] for m in M["end_to_end"]]
    assert "setup_s" in names
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        for w in m.get("workloads", []):
            assert w in cells


def check_per_layer_metrics_name_a_layer_and_an_end_to_end_metric(M):
    cells = [w["name"] for w in M["workloads"]]
    e2e = {m["name"]: m for m in M["end_to_end"]}
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in cells
            assert "workloads" not in moved or w in moved["workloads"], (m["name"], w)


def check_a_cell_resolves(M, name):
    cell = manifest.load_cell(name, M)
    generator.validate(cell.traffic)
    assert {"warm_calls", "trace_seconds", "correct"} <= set(cell.spec)
    kind = cell.kind()
    assert set(cell.traffic.get("faults", {})) <= set(kind.FAULTS)
    assert hasattr(kind, "Workload") and kind.SPAN.startswith("bench:")
    assert hasattr(cell.entry(), "boot")
    assert hasattr(cell.entry("reference"), "boot")
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]))
    # every cell reports setup_s, one other end-to-end metric and a per-layer metric
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    assert "setup_s" in [m["name"] for m in cell.end_to_end]


MANIFEST_CHECKS = [
    check_top_level_keys_are_exactly_the_contracts,
    check_paths_hold_the_benchmark_and_the_command_lies_inside,
    check_files_under_paths_are_named_from_name_characters,
    check_names_are_unique_and_well_formed,
    check_configs_have_their_files_and_are_all_used,
    check_workloads_are_one_chip_pairs_that_appear_once,
    check_end_to_end_metrics_have_bounds_and_setup_is_among_them,
    check_per_layer_metrics_name_a_layer_and_an_end_to_end_metric,
]


def test_top_level_keys_are_exactly_the_contracts():
    check_top_level_keys_are_exactly_the_contracts(M)


def test_paths_hold_the_benchmark_and_the_command_lies_inside():
    check_paths_hold_the_benchmark_and_the_command_lies_inside(M)


def test_files_under_paths_are_named_from_name_characters():
    check_files_under_paths_are_named_from_name_characters(M)


@pytest.mark.parametrize("section", SECTIONS)
def test_names_are_unique_and_well_formed(section):
    check_names_are_unique_and_well_formed(M, [section])


def test_configs_have_their_files_and_are_all_used():
    check_configs_have_their_files_and_are_all_used(M)


def test_workloads_are_one_chip_pairs_that_appear_once():
    check_workloads_are_one_chip_pairs_that_appear_once(M)


def test_end_to_end_metrics_have_bounds_and_setup_is_among_them():
    check_end_to_end_metrics_have_bounds_and_setup_is_among_them(M)


def test_per_layer_metrics_name_a_layer_and_an_end_to_end_metric():
    check_per_layer_metrics_name_a_layer_and_an_end_to_end_metric(M)


@pytest.mark.parametrize("name", CELLS)
def test_every_name_of_a_cell_resolves_to_a_file_of_its_own(name):
    check_a_cell_resolves(M, name)


def test_an_unknown_workload_or_metric_is_an_error():
    with pytest.raises(manifest.ManifestError):
        manifest.load_cell("no-such-cell")
    with pytest.raises(manifest.ManifestError):
        manifest.load_module("metrics", "no_such_metric")
