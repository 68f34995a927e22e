"""`launch_row_fill_share` (PR 34): the share of the verify launches'
rows that carry a signature set, read from the pool's tally of sets
started and the size class (the launch's rows) of the window's steady
`bls_lane_verify` ledger entries. The reader on contexts made by hand
(a value at the rows the slot rule gives and at the parent's; nothing
from a window without a launch or without a set), and the manifest's
row for it, held to what it is and not to where it stands."""

from __future__ import annotations

import pytest

from perfbench import manifest

M = manifest.load_manifest()
NAME = "launch_row_fill_share"
# the one-chip `sigs_per_s` cells. The four-lane cell runs the same launches and would read the same, but
# `test_lane_readers.py` holds that cell's rows to an exact set, and a file the benchmark has is not this PR's to edit
CELLS = {"node-range-sync", "offload-four-node-blocks"}


def launch(rows: int, compile_: bool = False, program: str = "bls_lane_verify") -> dict:
    return {"program": program, "size_class": rows, "seconds": 0.12, "compile": compile_, "t_mono_ns": 0,
            "lane": "dev0", "phases": {}, "tid": 1, "parent": None}


def read(ledger: list[dict], sets_before: float | None, sets_after: float | None):
    before = {} if sets_before is None else {"pool.sig_sets_started": sets_before}
    after = {} if sets_after is None else {"pool.sig_sets_started": sets_after}
    ctx = {"ledger": ledger, "counters_before": before, "counters_after": after}
    return manifest.load_module("metrics", NAME).read(ctx)


@pytest.mark.parametrize(
    "ledger, sets, want",
    [
        ([launch(288)], 262, 90.97),  # two blocks' halves in (288, 4)
        ([launch(512)], 262, 51.17),  # the same four jobs at the parent, (512, 4)
        ([launch(144)] * 3, 3 * 131, 90.97),  # a block a launch, (144, 2)
        ([launch(288), launch(144), launch(288)], 262 + 131 + 262, 90.97),  # a fleet wave that split
        ([launch(288), launch(128)], 262 + 66, 78.85),  # a job alone rides the flat 128 rows
        ([launch(288), launch(288, compile_=True)], 262, 90.97),  # a first call is no steady launch
        ([launch(288), launch(512, program="_grouped_launch_verify")], 262, 90.97),  # the program's own entry is not the lane's
    ],
    ids=["four-jobs", "four-jobs-at-the-parent", "blocks", "split-wave", "a-job-alone", "first-call", "inner-entry"],
)
def test_it_is_the_windows_sets_over_the_windows_launched_rows(ledger, sets, want):
    assert read(ledger, 1000.0, 1000.0 + sets) == pytest.approx(want, abs=0.005)


@pytest.mark.parametrize(
    "ledger, before, after",
    [
        ([], 1000.0, 1262.0),
        ([launch(288, compile_=True)], 1000.0, 1262.0),
        ([launch(512, program="batch_verify_sharded")], 1000.0, 1262.0),
        ([launch(288)], 1000.0, 1000.0),
        ([launch(288)], None, None),
        ([], None, None),
    ],
    ids=["no-launches", "first-calls-only", "the-collective-road", "no-sets-started", "a-pool-without-the-counter",
         "the-reference-entry"],
)
def test_it_reads_nothing_where_nothing_was_launched_or_started(ledger, before, after):
    assert read(ledger, before, after) is None


def test_the_manifest_lists_the_row():
    (row,) = [r for r in M["per_layer"] if r["name"] == NAME]
    assert {k: v for k, v in row.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter", "layer": "verify schedules",
        "moves": "sigs_per_s"}
    assert set(row["workloads"]) == CELLS
    assert "verify schedules" in {r["layer"] for r in M["per_layer"] if r["name"] != NAME}
    (moved,) = [r for r in M["end_to_end"] if r["name"] == "sigs_per_s"]
    assert set(row["workloads"]) <= set(moved["workloads"])
    for cell in row["workloads"]:
        assert NAME in [m["name"] for m in manifest.load_cell(cell, M).per_layer]
