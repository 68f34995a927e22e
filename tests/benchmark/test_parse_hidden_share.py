"""`parse_hidden_share` (PR 30): the share of the pool's staged host
parse during which a lane had a launch in flight, read from two of the
pool's own tallies over the window. The reader on contexts made by hand
(a value; nothing from a commit whose pool keeps no such counters, and
nothing from a window in which nothing was staged), and the manifest's
row for it, held to what it is and not to where it stands."""

from __future__ import annotations

import pytest

from perfbench import manifest

M = manifest.load_manifest()
NAME = "parse_hidden_share"
POOL_AT_THE_PARENT = {"pool.jobs_started": 960.0, "pool.sig_sets_started": 31440.0, "pool.errors": 0.0}


def read(before: dict, after: dict):
    return manifest.load_module("metrics", NAME).read({"counters_before": before, "counters_after": after})


def test_it_is_the_windows_hidden_parse_over_the_windows_parse():
    before = {**POOL_AT_THE_PARENT, "pool.parse_ns": 4.0e9, "pool.parse_hidden_ns": 1.0e9}
    after = {**POOL_AT_THE_PARENT, "pool.parse_ns": 7.5e9, "pool.parse_hidden_ns": 4.29e9}
    assert read(before, after) == pytest.approx(94.0)  # what the warm-up hid is not the window's


@pytest.mark.parametrize(
    "before, after",
    [
        (POOL_AT_THE_PARENT, {**POOL_AT_THE_PARENT, "pool.jobs_started": 1920.0}),
        ({**POOL_AT_THE_PARENT, "pool.parse_ns": 0.0, "pool.parse_hidden_ns": 0.0},
         {**POOL_AT_THE_PARENT, "pool.parse_ns": 0.0, "pool.parse_hidden_ns": 0.0}),
        ({**POOL_AT_THE_PARENT, "pool.parse_ns": 4.0e9, "pool.parse_hidden_ns": 1.0e9},
         {**POOL_AT_THE_PARENT, "pool.parse_ns": 4.0e9, "pool.parse_hidden_ns": 1.0e9}),
        ({}, {}),
    ],
    ids=["a-pool-without-the-counters", "a-pool-that-staged-nothing", "a-window-that-staged-nothing",
         "the-reference-entry"],
)
def test_it_reads_nothing_where_no_parse_was_staged(before, after):
    assert read(before, after) is None


def test_a_parse_that_nothing_hid_reads_zero_not_nothing():
    """One caller, one block at a time, forced through the stage: the
    parse is counted and none of it hidden. That is a reading."""
    after = {"pool.parse_ns": 2.0e9, "pool.parse_hidden_ns": 0.0}
    assert read({}, after) == 0.0


def test_the_manifest_lists_the_row():
    (row,) = [r for r in M["per_layer"] if r["name"] == NAME]
    assert {k: v for k, v in row.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter", "layer": "pool",
        "moves": "sigs_per_s"}
    assert {"node-range-sync", "offload-four-node-blocks"} <= set(row["workloads"])
    assert "pool" in {r["layer"] for r in M["per_layer"] if r["name"] != NAME}
    for cell in row["workloads"]:
        assert NAME in [m["name"] for m in manifest.load_cell(cell, M).per_layer]
