"""The per-layer metrics that read the program's own names: each reader
on a context made by hand (a value, and nothing where the program
records no phases or steps), the manifest's rows for them, and the
trace reduction putting an idle gap under a program span on the
profiler's clock instead of the harness's outer one."""

from __future__ import annotations

import pytest

from perfbench import generator, manifest, phase_readers, trace

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


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_manifest_lists_the_metric_after_the_accepted_ones(name):
    unit, source, layer, moves, cells = NEW[name]
    rows = [m for m in M["per_layer"] if m["name"] == name]
    assert rows == [{"name": name, "unit": unit, "better": "lower", "source": source, "layer": layer,
                     "moves": moves, "workloads": cells}]
    assert [m["name"] for m in M["per_layer"]][-len(NEW):] == list(NEW)
    assert layer in {m["layer"] for m in M["per_layer"][: -len(NEW)]}  # a layer the benchmark already names


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
