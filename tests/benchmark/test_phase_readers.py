"""The per-layer metrics that read the program's own names: each reader
on a context made by hand (a value, and nothing where the program
records no phases or steps), the manifest's rows for them, which a
manifest may grow around (a row appended, a cell listed under them), and
the trace reduction putting an idle gap under a program span on the
profiler's clock instead of the harness's outer one."""

from __future__ import annotations

import copy
import json
import os
import shutil

import pytest

from perfbench import generator, manifest, phase_readers, trace

from . import test_manifest, test_stage_readers

M = manifest.load_manifest()
NEW = {
    "host_prep_us_per_set": ("us", "program_counter", "verify schedules", "sigs_per_s", ["node-range-sync"]),
    "launch_host_ms.bulk": ("ms", "program_span", "pool", "sigs_per_s", ["node-range-sync"]),
    "launch_host_ms.block": ("ms", "program_span", "pool", "verdict_p50_ms", ["node-block-import"]),
    "flush_step_ms.index": ("ms", "program_span", "state root", "root_flush_ms", ["root-epoch-sweep"]),
    "flush_step_ms.gather": ("ms", "program_span", "state root", "root_flush_ms", ["root-epoch-sweep"]),
    "flush_step_ms.device": ("ms", "program_span", "state root", "root_flush_ms", ["root-epoch-sweep"]),
    "flush_step_ms.scatter": ("ms", "program_span", "state root", "root_flush_ms", ["root-epoch-sweep"]),
}


def reader(name: str):
    return manifest.load_module("metrics", name).read


def launch(program, seconds, phases=None, compile_=False) -> dict:
    entry = {"program": program, "size_class": 128, "seconds": seconds, "compile": compile_,
             "t_mono_ns": 0, "lane": "dev0"}
    if phases is not None:
        entry.update(phases=phases, tid=1, parent=None)
    return entry


def flush_record(detail: dict, error=None):
    rec = generator.Record(call=0, entry=0, issued=0.0, done=0.3)
    rec.detail, rec.error = detail, error
    return rec


def check_the_manifest_lists_the_metric(m: dict, name: str) -> None:
    """The row is there once, as PR 26 added it, its cells among the
    row's, and its layer is one that another row names. Where it stands
    among the rows, and which cells joined it since, is free."""
    unit, source, layer, moves, cells = NEW[name]
    (row,) = [r for r in m["per_layer"] if r["name"] == name]
    assert {k: v for k, v in row.items() if k != "workloads"} == {
        "name": name, "unit": unit, "better": "lower", "source": source, "layer": layer, "moves": moves}
    assert set(cells) <= set(row["workloads"])
    assert layer in {r["layer"] for r in m["per_layer"] if r["name"] not in NEW}


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_manifest_lists_the_metric_after_the_accepted_ones(name):
    check_the_manifest_lists_the_metric(M, name)


def grow_a_tree(tmp_path) -> None:
    """A copy of the benchmark in which a later PR has added a cell and a
    per-layer metric: files and manifest entries only, every file that
    was there left as it was."""
    shutil.copytree(manifest.BENCH_DIR, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "data", "reference"))
    os.makedirs(tmp_path / "tests" / "benchmark")
    bench = tmp_path / "perfbench"
    shutil.copy(bench / "traffic" / "range-sync-segments.json", bench / "traffic" / "later-traffic.json")
    shutil.copy(bench / "cells" / "node-range-sync.json", bench / "cells" / "later-cell.json")
    (bench / "metrics" / "later_metric.py").write_text("def read(ctx):\n    return None\n")
    grown = copy.deepcopy(M)
    grown["workloads"].append({"name": "later-cell", "config": "mainnet-solo-node", "traffic": "later-traffic",
                               "chips": 1, "why": "a cell that a later PR adds"})
    grown["per_layer"].append({"name": "later_metric", "unit": "ms", "better": "lower",
                               "source": "program_span", "layer": "pool", "moves": "sigs_per_s",
                               "workloads": ["later-cell"]})
    for section, name in (("end_to_end", "sigs_per_s"), ("per_layer", "first_call_s"),
                          ("per_layer", "launch_host_ms.bulk"),
                          ("end_to_end", "verdict_p50_ms"), ("per_layer", "jobs_per_launch"),
                          ("per_layer", "stage_device_ms.miller")):  # rows of PR 28, and what they move
        (row,) = [r for r in grown[section] if r["name"] == name]
        row["workloads"].append("later-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(grown, indent=1) + "\n")


def test_a_manifest_grown_by_a_row_and_a_cell_still_passes_every_check(tmp_path, monkeypatch):
    """What `perfbench/README.md` promises a later PR: it appends a row,
    adds a cell and lists the cell under the metrics it reports, and no
    test pins the table's tail or a row's cells."""
    grow_a_tree(tmp_path)
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    monkeypatch.setattr(manifest, "BENCH_DIR", str(tmp_path / "perfbench"))
    monkeypatch.setattr(manifest, "MANIFEST_PATH", str(tmp_path / "BENCHMARK.json"))
    grown = manifest.load_manifest(manifest.MANIFEST_PATH)
    assert [r["name"] for r in grown["per_layer"]][-1] == "later_metric"
    for check in test_manifest.MANIFEST_CHECKS:
        check(grown)
    for w in grown["workloads"]:
        test_manifest.check_a_cell_resolves(grown, w["name"])
    later = manifest.load_cell("later-cell", grown)
    assert {m["name"] for m in later.end_to_end} == {"sigs_per_s", "verdict_p50_ms", "setup_s"}
    assert {m["name"] for m in later.per_layer} == {"first_call_s", "launch_host_ms.bulk", "jobs_per_launch",
                                                    "stage_device_ms.miller", "later_metric"}
    for name in sorted(NEW):
        check_the_manifest_lists_the_metric(grown, name)
    for name in sorted(test_stage_readers.ROWS):
        test_stage_readers.check_the_manifest_lists_the_row(grown, name)


@pytest.mark.parametrize("name", ["launch_host_ms.bulk", "launch_host_ms.block"])
def test_launch_host_ms_is_the_median_of_parse_plus_dispatch(name):
    ledger = [
        launch("bls_lane_verify", 9.0, {"bls.parse": 5.0, "bls.dispatch": 5.0}, compile_=True),  # a first call
        launch("bls_lane_verify", 0.104, {"bls.parse": 0.004, "bls.dispatch": 0.002, "bls.wait": 0.09}),
        launch("bls_lane_verify", 0.105, {"bls.parse": 0.005, "bls.dispatch": 0.003, "bls.wait": 0.09}),
        launch("bls_lane_verify", 0.110, {"bls.dispatch": 0.010, "bls.wait": 0.09}),  # parsed on no thread it saw
        launch("_single_launch_verify", 0.002, {}),
    ]
    assert reader(name)({"ledger": ledger}) == pytest.approx(8.0)


@pytest.mark.parametrize("name", ["launch_host_ms.bulk", "launch_host_ms.block"])
def test_launch_host_ms_reads_nothing_from_entries_without_phases(name):
    parent = [launch("bls_lane_verify", 0.104), launch("bls_lane_verify", 0.105)]
    assert reader(name)({"ledger": parent}) is None
    assert reader(name)({"ledger": []}) is None
    empty = [launch("bls_lane_verify", 0.104, {})]  # a launch that opened no phase has no split to report
    assert reader(name)({"ledger": empty}) is None


@pytest.mark.parametrize("step", ["index", "gather", "device", "scatter"])
def test_flush_step_ms_is_the_mean_over_the_windows_flushes(step):
    steps = {"htr.index": 0.09, "htr.gather": 0.12, "htr.device": 0.03, "htr.scatter": 0.04, "htr.host_hash": 0.001}
    twice = {k: 2 * v for k, v in steps.items()}
    records = [
        flush_record({"launches": 9, "seconds": 0.3, "steps": steps}),
        flush_record({"launches": 9, "seconds": 0.6, "steps": twice}),
        flush_record({"launches": 9, "seconds": 9.9, "steps": twice}, error="RuntimeError: lost"),
    ]
    want = 1000.0 * 1.5 * steps[f"htr.{step}"]
    assert reader(f"flush_step_ms.{step}")({"records": records}) == pytest.approx(want)


@pytest.mark.parametrize("step", ["index", "gather", "device", "scatter"])
def test_flush_step_ms_reads_nothing_without_steps(step):
    read = reader(f"flush_step_ms.{step}")
    assert read({"records": [flush_record({"launches": 9, "seconds": 0.3})]}) is None  # the parent commit
    assert read({"records": [flush_record({"launches": 0, "seconds": 0.3, "steps": {}})]}) is None  # telemetry off
    assert read({"records": [flush_record({})]}) is None  # the reference entry
    assert read({"records": []}) is None


def test_host_prep_us_per_set_is_the_windows_seconds_over_its_sets():
    before = {"lodestar_bls_prep_seconds_sum": 1.0, "lodestar_bls_prep_sets_total": 1000.0}
    after = {"lodestar_bls_prep_seconds_sum": 1.5, "lodestar_bls_prep_sets_total": 11000.0}
    read = reader("host_prep_us_per_set")
    assert read({"counters_before": before, "counters_after": after}) == pytest.approx(50.0)
    assert read({"counters_before": after, "counters_after": after}) is None
    assert read({"counters_before": {}, "counters_after": {}}) is None
    assert phase_readers.HOST_BEFORE_DEVICE == ("bls.parse", "bls.dispatch")


def spans_by_hand() -> dict:
    """Two runs of the verify program with 20 ms of idle between them:
    the harness's `bench:verify` covers everything on the loop thread; on
    an executor thread the program's own launch span holds its phases."""
    dev = "/device:TPU:0"
    op = "%redc.1 = s32[128,33]{1,0} custom-call(s32[128,66]{1,0} %a), custom_call_target=\"tpu_custom_call\""
    ms = 1_000_000
    return {
        "devices": {dev: {
            "ops": [[op, 0, 90 * ms], [op, 110 * ms, 90 * ms]],
            "modules": [["jit__single_launch_verify(1)", 0, 90 * ms], ["jit__single_launch_verify(1)", 110 * ms, 90 * ms]],
        }},
        "host": {
            "loop/1": [["bench:verify", -5 * ms, 215 * ms], ["bls.next_package", 91 * ms, ms // 10],
                       ["bls.place", 92 * ms, ms // 10]],
            "executor/2": [["bls_lane_verify", 93 * ms, 108 * ms], ["bls.parse", 93 * ms, 5 * ms],
                           ["bls.dispatch", 98 * ms, 13 * ms], ["bls.wait", 111 * ms, 89 * ms]],
        },
    }


def test_a_gap_between_launches_goes_under_the_programs_phase_not_the_harnesss_span():
    gaps = trace.Reduced.from_events(spans_by_hand(), window_s=0.5).idle_gaps()
    assert gaps == pytest.approx({"bls.dispatch": 0.020})
    assert "bench:verify" not in gaps and "no_host_span" not in gaps


def test_without_the_programs_spans_the_same_gap_stands_under_the_harnesss():
    parent = spans_by_hand()
    del parent["host"]["executor/2"]
    parent["host"]["loop/1"] = parent["host"]["loop/1"][:1]
    assert trace.Reduced.from_events(parent, window_s=0.5).idle_gaps() == pytest.approx({"bench:verify": 0.020})


def test_step_names_with_a_dot_survive_the_reductions_squash():
    for name in ("htr.index", "htr.gather", "htr.device", "htr.scatter", "htr.host_hash", "bls.parse",
                 "bls.dispatch", "bls.wait", "bls.resolve", "bls.place", "bls.next_package"):
        assert trace._squash(name) == name
