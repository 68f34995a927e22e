"""The four-chip cell (PR 33): the manifest with a `chips: 4` cell held
to every check of `test_manifest.py`, and the four per-layer readers the
cell brings, each on a context made by hand: a launch ledger with lane
labels, the pool's tallies, and a `Reduced` of two chips (one busy, one
idle). A program that lacks what PR 33 adds (the parent: no
`pool.parse_wait_ns`, no `bls.parse_wait` phase, bulk on the collective
road) reads nothing and raises nothing."""

from __future__ import annotations

import pytest

from perfbench import generator, manifest, trace

from . import test_manifest

M = manifest.load_manifest()
CELL = "backfill-window-four-lanes"
NEW = {
    "lane_launch_share.min": ("%", "higher", "program_span", "pool"),
    "chip_busy_share.min": ("%", "higher", "device_trace", "pool"),
    "parse_busy_share": ("%", "lower", "program_counter", "verify schedules"),
    "parse_wait_ms": ("ms", "lower", "program_span", "pool"),
}
REPORTED = ["sigs_per_s", "first_call_s", "persistent_cache_hits", "sets_per_launch", "launch_wall_ms.bulk",
            "launch_host_ms.bulk", "host_prep_us_per_set", "fp_kernels_hbm_share.bulk", "parse_hidden_share"]


class Workload:
    def __init__(self, lanes):
        self.config = {"lanes": lanes} if lanes else {}


def reader(name: str):
    return manifest.load_module("metrics", name).read


def launch(lane: str, phases: dict | None = None, compile_: bool = False, program: str = "bls_lane_verify") -> dict:
    return {"program": program, "size_class": 512, "seconds": 0.177, "compile": compile_, "t_mono_ns": 0,
            "lane": lane, "phases": phases or {}, "tid": 1, "parent": None}


def record(done: float):
    return generator.Record(call=0, entry=0, issued=0.0, done=done)


# -- the manifest ---------------------------------------------------------------


@pytest.mark.parametrize("check", test_manifest.MANIFEST_CHECKS, ids=lambda c: c.__name__)
def test_the_manifest_with_a_four_chip_cell_passes_every_check(check):
    (row,) = [w for w in M["workloads"] if w["name"] == CELL]
    assert row["chips"] == 4 and row["config"] == "mainnet-backfill-v5e4" and row["traffic"] == "backfill-window-32"
    check(M)


def test_the_four_chip_cell_resolves_and_is_listed_under_the_rows_it_reports():
    test_manifest.check_a_cell_resolves(M, CELL)
    cell = manifest.load_cell(CELL, M)
    assert cell.chips == 4 and cell.config["lanes"] == 4 and cell.config["reduced"] == []
    assert cell.spec["warm_calls"] == cell.traffic["wave_calls"] == 32  # one whole window before the window
    names = [m["name"] for m in cell.end_to_end + cell.per_layer]
    assert set(REPORTED) | set(NEW) | {"setup_s"} == set(names)
    solo = manifest.load_cell("node-range-sync", M).config
    assert cell.config["boot"] == solo["boot"] | {"departs": cell.config["boot"]["departs"]}
    assert cell.config["pool"] == solo["pool"]


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_manifest_lists_the_row(name):
    unit, better, source, layer = NEW[name]
    (row,) = [r for r in M["per_layer"] if r["name"] == name]
    assert {k: v for k, v in row.items() if k != "workloads"} == {
        "name": name, "unit": unit, "better": better, "source": source, "layer": layer, "moves": "sigs_per_s"}
    assert CELL in row["workloads"]
    assert layer in {r["layer"] for r in M["per_layer"] if r["name"] not in NEW}


# -- lane_launch_share.min --------------------------------------------------------


@pytest.mark.parametrize(
    "served, lanes, want",
    [
        ({"dev0": 40, "dev1": 40, "dev2": 40, "dev3": 40}, 4, 100.0),
        ({"dev0": 50, "dev1": 50, "dev2": 50, "dev3": 10}, 4, 25.0),
        ({"dev0": 60, "dev1": 60, "dev2": 40}, 4, 0.0),  # a lane of the configuration's that served nothing
        ({"dev0": 30, "dev1": 10}, None, 50.0),  # no stated count: the lanes that served
    ],
    ids=["even", "one-lane-starved", "one-lane-never-served", "no-stated-count"],
)
def test_lane_launch_share_is_the_least_served_lanes_share_times_the_lanes(served, lanes, want):
    ledger = [launch(lane) for lane, n in served.items() for _ in range(n)]
    ledger += [launch("dev3", compile_=True), launch(None, program="_grouped_launch_verify")]
    assert reader("lane_launch_share.min")({"ledger": ledger, "workload": Workload(lanes)}) == pytest.approx(want)


def test_lane_launch_share_reads_nothing_without_a_steady_lane_launch():
    ledger = [launch("dev0", compile_=True), launch("0,1,2,3", program="batch_verify_sharded")]
    assert reader("lane_launch_share.min")({"ledger": ledger, "workload": Workload(4)}) is None
    assert reader("lane_launch_share.min")({"ledger": [], "workload": Workload(4)}) is None


# -- chip_busy_share.min ----------------------------------------------------------


def two_chips(busy_ns: dict[str, list[tuple[int, int]]]) -> trace.Reduced:
    reduced = trace.Reduced(window_s=0.5)
    for device, spans in busy_ns.items():
        for start, dur in spans:
            reduced.add_op(device, "%mul_acc.1 = s32[8]{0} custom-call()", start, dur)
    return reduced.close()


def test_chip_busy_share_reads_the_idlest_chip_where_busy_s_is_the_mean():
    reduced = two_chips({
        "/device:TPU:0": [(0, 200_000_000), (250_000_000, 200_000_000)],  # 0.4 s of 0.5
        "/device:TPU:1": [(100_000_000, 50_000_000)],  # 0.05 s: the idle one
    })
    got = reader("chip_busy_share.min")({"trace": reduced, "workload": Workload(2)})
    assert got == pytest.approx(10.0)
    assert reduced.busy_s == pytest.approx(0.225)  # the result line's: the mean over the chips


def test_a_chip_the_trace_does_not_hold_ran_nothing():
    reduced = two_chips({"/device:TPU:0": [(0, 400_000_000)]})
    assert reader("chip_busy_share.min")({"trace": reduced, "workload": Workload(4)}) == 0.0
    assert reader("chip_busy_share.min")({"trace": reduced, "workload": Workload(None)}) == pytest.approx(80.0)


def test_chip_busy_share_reads_nothing_without_a_trace():
    assert reader("chip_busy_share.min")({"trace": None, "workload": Workload(4)}) is None


# -- parse_busy_share -------------------------------------------------------------


def test_parse_busy_share_is_the_staged_parse_over_the_span_the_counters_cover():
    ctx = {"counters_before": {"pool.parse_ns": 2.0e9}, "counters_after": {"pool.parse_ns": 29.0e9},
           "start": 100.0, "end": 130.0, "records": [record(110.0), record(131.5), record(120.0)]}
    assert reader("parse_busy_share")(ctx) == pytest.approx(100.0 * 27.0 / 31.5)


@pytest.mark.parametrize(
    "before, after, records",
    [
        ({}, {}, [record(131.0)]),
        ({"pool.parse_ns": 0.0}, {"pool.parse_ns": 0.0}, [record(131.0)]),  # the parent: bulk on the collective road
        ({"pool.parse_ns": 1.0e9}, {"pool.parse_ns": 5.0e9}, []),
    ],
    ids=["the-reference-entry", "a-pool-that-staged-nothing", "a-window-without-an-answer"],
)
def test_parse_busy_share_reads_nothing_where_no_parse_was_staged(before, after, records):
    ctx = {"counters_before": before, "counters_after": after, "start": 100.0, "end": 130.0, "records": records}
    assert reader("parse_busy_share")(ctx) is None


# -- parse_wait_ms ----------------------------------------------------------------


def test_parse_wait_ms_is_the_mean_wait_a_steady_launch():
    ledger = [launch("dev0", {"bls.parse": 0.04, "bls.parse_wait": 0.030}), launch("dev1", {"bls.parse": 0.04}),
              launch("dev2", {"bls.parse_wait": 0.010}), launch("dev3"),
              launch("dev3", {"bls.parse_wait": 9.0}, compile_=True)]
    ctx = {"ledger": ledger, "counters_after": {"pool.parse_wait_ns": 4.0e7}}
    assert reader("parse_wait_ms")(ctx) == pytest.approx(10.0)


def test_a_window_in_which_nobody_waited_reads_zero_not_nothing():
    ctx = {"ledger": [launch("dev0"), launch("dev1")], "counters_after": {"pool.parse_wait_ns": 0.0}}
    assert reader("parse_wait_ms")(ctx) == 0.0


@pytest.mark.parametrize(
    "ledger, counters",
    [([launch("dev0", {"bls.parse": 0.04})], {"pool.parse_ns": 1.0}), ([], {"pool.parse_wait_ns": 0.0}), ([], {})],
    ids=["a-pool-without-the-counter", "no-steady-launch", "the-reference-entry"],
)
def test_parse_wait_ms_reads_nothing_from_a_program_that_has_no_such_span(ledger, counters):
    assert reader("parse_wait_ms")({"ledger": ledger, "counters_after": counters}) is None
