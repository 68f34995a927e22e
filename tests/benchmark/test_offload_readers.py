"""The per-layer metrics of the offload wire and host: each reader on a
recorded ledger (a window of two waves of four RPCs as the program
writes them: `offload_rpc` on the tenants' side, `offload_serve` on the
host's, the pool's `bls_lane_verify` between), nothing where the
program writes no such entry, and the manifest's rows for them."""

from __future__ import annotations

import pytest

from perfbench import manifest, offload_readers

M = manifest.load_manifest()
CELL = "offload-four-node-blocks"
ROWS = {
    "offload_rpc_ms": ("ms", "lower", "program_span", "offload wire"),
    "offload_wire_ms": ("ms", "lower", "program_span", "offload wire"),
    "offload_decode_ms": ("ms", "lower", "program_span", "offload host"),
    "offload_slot_wait_ms": ("ms", "lower", "program_span", "offload host"),
    "offload_backend_ms": ("ms", "lower", "program_span", "offload host"),
    "offload_jobs_per_launch": ("jobs", "higher", "program_counter", "pool"),
    "offload_rpc_ms.lower": ("ms", "lower", "program_span", "offload wire"),
    "offload_rpc_ms.upper": ("ms", "lower", "program_span", "offload wire"),
    "offload_backend_ms.lower": ("ms", "lower", "program_span", "offload host"),
    "offload_backend_ms.upper": ("ms", "lower", "program_span", "offload host"),
}


def reader(name: str):
    return manifest.load_module("metrics", name).read


def entry(program, seconds, phases=None, compile_=False, size_class=256) -> dict:
    return {"program": program, "size_class": size_class, "seconds": seconds, "compile": compile_,
            "t_mono_ns": 0, "lane": None, "tid": 1, "parent": None, "phases": phases or {}}


def serve(seconds, decode, slot_wait, backend, **kw) -> dict:
    return entry("offload_serve", seconds, {"offload.decode": decode, "offload.slot_wait": slot_wait,
                                            "offload.backend": backend, "offload.reply": 0.0001}, **kw)


def rpc(seconds, **kw) -> dict:
    return entry("offload_rpc", seconds, {"offload.encode": 0.0001, "offload.call": seconds - 0.0001,
                                          "offload.check": 0.00005}, **kw)


def recorded_window() -> dict:
    """Two waves: in each, two tenants' verdicts come with the first
    512-row launch and two with the second."""
    ledger = []
    for _ in range(2):
        ledger += [serve(0.2520, 0.0004, 0.00002, 0.2510), serve(0.2530, 0.0005, 0.00003, 0.2520),
                   serve(0.5040, 0.0006, 0.00002, 0.5030), serve(0.5050, 0.0004, 0.00004, 0.5040)]
        ledger += [rpc(0.2535), rpc(0.2545), rpc(0.5056), rpc(0.5066)]
        ledger += [entry("bls_lane_verify", 0.2500, size_class=512), entry("bls_lane_verify", 0.2510, size_class=512)]
    # a first call of each: left out of every median
    ledger += [serve(9.0, 9.0, 9.0, 9.0, compile_=True), rpc(9.0, compile_=True)]
    return {"ledger": ledger, "counters_before": {"pool.jobs_started": 40.0},
            "counters_after": {"pool.jobs_started": 56.0}}


def test_each_reader_on_the_recorded_window():
    ctx = recorded_window()
    rpc_mean = (253.5 + 254.5 + 505.6 + 506.6) / 4
    serve_mean = (252.0 + 253.0 + 504.0 + 505.0) / 4
    assert reader("offload_rpc_ms")(ctx) == pytest.approx(rpc_mean)
    assert reader("offload_wire_ms")(ctx) == pytest.approx(rpc_mean - serve_mean)
    assert reader("offload_decode_ms")(ctx) == pytest.approx(0.45)
    assert reader("offload_slot_wait_ms")(ctx) == pytest.approx(0.025)
    assert reader("offload_backend_ms")(ctx) == pytest.approx((251.0 + 252.0 + 503.0 + 504.0) / 4)
    assert reader("offload_jobs_per_launch")(ctx) == pytest.approx(4.0)
    # the groups' own medians: the RPCs of the first launch, and of the last
    assert reader("offload_rpc_ms.lower")(ctx) == pytest.approx(254.0)
    assert reader("offload_rpc_ms.upper")(ctx) == pytest.approx(506.1)
    assert reader("offload_backend_ms.lower")(ctx) == pytest.approx(251.5)
    assert reader("offload_backend_ms.upper")(ctx) == pytest.approx(503.5)


@pytest.mark.parametrize("extra", ["rpc", "serve"])
def test_one_more_entry_at_the_windows_edge_moves_no_row_by_a_group(extra):
    """A serve entry ends a few milliseconds before its rpc entry, so a
    window can hold one more of either, from either group. The wire is a
    difference of means over (all but one of) the same entries and moves
    by that one entry's share; the half medians stay inside their groups."""
    ctx = recorded_window()
    ctx["ledger"].insert(0, rpc(0.2540) if extra == "rpc" else serve(0.2525, 0.0004, 0.00002, 0.2515))
    wire = reader("offload_wire_ms")(ctx)
    assert 1.6 - 126.0 / 8 < wire < 1.6 + 126.0 / 8  # a median of medians would read 1.6 +- 126
    assert reader("offload_rpc_ms.lower")(ctx) == pytest.approx(254.0, abs=0.6)
    assert reader("offload_rpc_ms.upper")(ctx) == pytest.approx(506.1, abs=0.6)
    assert reader("offload_backend_ms.lower")(ctx) == pytest.approx(251.5, abs=0.6)
    assert reader("offload_backend_ms.upper")(ctx) == pytest.approx(503.5, abs=0.6)


def test_a_half_needs_two_entries():
    ctx = {"ledger": [rpc(0.25)], "counters_before": {}, "counters_after": {}}
    assert reader("offload_rpc_ms")(ctx) == pytest.approx(250.0)
    assert reader("offload_rpc_ms.lower")(ctx) is None and reader("offload_rpc_ms.upper")(ctx) is None


@pytest.mark.parametrize("name", sorted(ROWS))
def test_a_program_without_the_spans_gives_nothing(name):
    """The parent commit, or the reference entry: launches but no RPC
    entries, no pool tallies; and a ledger that is empty."""
    bare = {"ledger": [entry("bls_lane_verify", 0.15)], "counters_before": {}, "counters_after": {}}
    if name != "offload_jobs_per_launch":
        assert reader(name)(bare) is None
    assert reader(name)({"ledger": [], "counters_before": {}, "counters_after": {}}) is None


def test_the_wire_needs_both_sides():
    ctx = recorded_window()
    ctx["ledger"] = [e for e in ctx["ledger"] if e["program"] != "offload_serve"]
    assert reader("offload_wire_ms")(ctx) is None and reader("offload_rpc_ms")(ctx) is not None


def test_a_phase_missing_from_some_entries_is_read_from_the_rest():
    """A shed RPC has a decode and no backend."""
    ctx = recorded_window()
    ctx["ledger"].append(entry("offload_serve", 0.001, {"offload.decode": 0.0004}))
    assert len(offload_readers.serve_phases_ms(ctx, "offload.backend")) == 8
    assert len(offload_readers.serve_phases_ms(ctx, "offload.decode")) == 9
    assert reader("offload_backend_ms")(ctx) == pytest.approx((251.0 + 252.0 + 503.0 + 504.0) / 4)
    assert offload_readers.serve_phases_ms(ctx, "offload.no_such_phase") == []


@pytest.mark.parametrize("name", sorted(ROWS))
def test_the_manifest_lists_the_row(name):
    """The row as this PR added it (where it stands and which cells join
    it later is free), moving what its cell reports."""
    unit, better, source, layer = ROWS[name]
    (row,) = [r for r in M["per_layer"] if r["name"] == name]
    assert {k: v for k, v in row.items() if k != "workloads"} == {
        "name": name, "unit": unit, "better": better, "source": source, "layer": layer,
        "moves": "sigs_per_s"}
    assert CELL in row["workloads"]
    (moved,) = [m for m in M["end_to_end"] if m["name"] == "sigs_per_s"]
    assert CELL in moved["workloads"]


def test_the_cell_is_the_issues():
    cell = manifest.load_cell(CELL)
    assert (cell.workload["config"], cell.workload["traffic"], cell.chips) == (
        "mainnet-offload-host", "four-node-blocks", 1)
    assert cell.traffic["wave_calls"] == cell.config["tenants"] == 4
    assert cell.traffic["call"] == {"sets": 131, "batchable": False, "priority": "GOSSIP_BLOCK"}
    assert cell.traffic["faults"] == {"tampered_pair.first": 1, "tampered_pair.last": 1,
                                      "off_subgroup.first": 1, "off_subgroup.last": 1}
    assert {m["name"] for m in cell.end_to_end} == {"sigs_per_s", "setup_s"}
    assert cell.config["pool"] == manifest.load_cell("node-block-import").config["pool"]
    assert cell.entry().NEEDS_CHIP and cell.config["reduced"] == []
