"""The reduction from a profiler trace to metrics, on two small
recorded v5e traces (`perfbench/data/`, cut by `trace.record` from runs
of the harness on the chip at PR 25) and on events made by hand."""

from __future__ import annotations

import os

import pytest

from perfbench import manifest, readers, trace

DATA = os.path.join(manifest.BENCH_DIR, "data")
RECORDED = {
    "root_flush": {"programs": 9, "program": "jit_hash_pairs", "kernels": False},
    "verify_launch": {"programs": 1, "program": "jit__single_launch_verify", "kernels": True},
}


def by_hand() -> dict:
    dev = "/device:TPU:0"
    mul = ('%mul_acc.3 = s32[512,66]{1,0:T(8,128)} custom-call(s32[512,33]{1,0} %a, s32[512,33]{1,0} %b), '
           'custom_call_target="tpu_custom_call"')
    return {
        "devices": {dev: {
            "ops": [[mul, 1_000, 4_000], ["%fusion.7 = u32[8]{0} fusion(u32[8]{0} %x), kind=kLoop", 4_000, 2_000],
                    [mul, 100_000, 4_000], ["%while.2 = (s32[]) while((s32[]) %t)", 100_000, 9_000]],
            "modules": [["jit_step(123)", 1_000, 5_000], ["jit_step(123)", 100_000, 9_000]],
        }},
        "host": {"main/1": [["bench:verify", 0, 200_000], ["inner", 50_000, 20_000]],
                 "worker/2": [["elsewhere", 300_000, 10]]},
    }


def brute_busy_ns(events) -> int:
    marks = sorted([(s, 1) for _, s, d in events] + [(s + d, -1) for _, s, d in events])
    busy = depth = 0
    last = None
    for t, step in marks:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_reduction_of_events_made_by_hand():
    r = trace.Reduced.from_events(by_hand(), window_s=1.0)
    assert r.busy_s == pytest.approx((5_000 + 9_000) / 1e9)
    assert r.op_seconds() == pytest.approx({"mul_acc": 8e-6, "fusion": 2e-6, "while": 9e-6})
    assert {trace.program_short_name(n) for n, _ in r.modules} == {"jit_step"}
    assert r.program_runs("step") == pytest.approx([5e-6, 9e-6])
    # one gap, 6,000..100,000 ns: its middle lies in `inner`, the innermost span
    assert r.idle_gaps() == pytest.approx({"inner": 94_000 / 1e9})
    names = [k for k, _ in r.breakdown()["device_ops"]]
    assert names == ["mul_acc", "fusion"]  # a while's body is listed by itself


def test_kernel_bytes_come_from_the_shapes_in_the_hlo_text():
    mul = by_hand()["devices"]["/device:TPU:0"]["ops"][0][0]
    assert trace.op_short_name(mul) == "mul_acc"
    assert trace.hlo_io_bytes(mul) == 4 * 512 * (66 + 33 + 33)
    assert trace.hlo_io_bytes("%copy = u32[524288,8]{1,0:T(8,128)} copy(u32[524288,8]{0,1} %n.1)") == 2 * 4 * 524288 * 8
    assert trace.program_short_name("jit_hash_pairs(1258)") == "jit_hash_pairs"
    r = trace.Reduced.from_events(by_hand(), window_s=1.0)
    ctx = {"trace": r, "peaks": {"hbm_bytes_per_s": 819e9}}
    want = 100.0 * 2 * 4 * 512 * 132 / 819e9 / 8e-6
    assert readers.fp_kernels_hbm_share(ctx) == pytest.approx(want)
    assert readers.fp_kernels_hbm_share({"trace": None}) is None


def test_a_trace_with_no_device_plane_is_refused():
    with pytest.raises(ValueError):
        trace.Reduced.from_events({"devices": {}, "host": {}}, 1.0)


def test_a_reader_with_nothing_to_read_returns_nothing():
    empty = trace.Reduced.from_events(
        {"devices": {"/device:TPU:0": {"ops": [["%copy.1 = u8[4] copy(u8[4] %x)", 0, 10]], "modules": []}},
         "host": {}}, 1.0)
    ctx = {"trace": empty, "peaks": {"hbm_bytes_per_s": 819e9}, "ledger": [], "trace_span": (0.0, 1.0)}
    assert readers.fp_kernels_hbm_share(ctx) is None
    assert manifest.load_module("metrics", "sha256_hbm_share").read(ctx) is None


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_reduction_of_a_recorded_chip_trace(name):
    facts = RECORDED[name]
    recorded = trace.load_recorded(os.path.join(DATA, name + ".trace.json"))
    r = trace.Reduced.from_events(recorded, window_s=1.0)
    (device, lines), = recorded["devices"].items()
    assert len(lines["modules"]) == facts["programs"]
    assert {trace.program_short_name(n) for n, _ in r.modules} == {facts["program"]}
    # busy time against a sweep written apart from the reduction
    assert r.busy_s * 1e9 == pytest.approx(brute_busy_ns(lines["ops"]), rel=1e-9)
    assert r.busy_s <= sum(r.program_runs(""))  # operations run inside program runs
    assert sum(r.op_seconds().values()) == pytest.approx(sum(d for _, _, d in lines["ops"]) / 1e9)
    kernels = r.ops_named(readers.FP_KERNELS)
    assert bool(kernels) == facts["kernels"]
    if kernels:
        share = readers.fp_kernels_hbm_share({"trace": r, "peaks": {"hbm_bytes_per_s": 819e9}})
        assert 0 < share < 100
        for text, _, _ in kernels:
            assert trace.hlo_io_bytes(text) > 0
    gaps = r.idle_gaps()
    assert all(v >= 0 for v in gaps.values())
    assert len(r.breakdown()["device_ops"]) <= 10


def test_sha256_share_is_bytes_over_peak_over_device_time():
    recorded = trace.load_recorded(os.path.join(DATA, "root_flush.trace.json"))
    r = trace.Reduced.from_events(recorded, window_s=1.0)
    ledger = [{"program": "merkle_level", "size_class": 262144, "t_mono_ns": 5e8, "compile": False},
              {"program": "merkle_level", "size_class": 2048, "t_mono_ns": 5e8, "compile": False},
              {"program": "merkle_level", "size_class": 4096, "t_mono_ns": 2e9, "compile": False}]
    ctx = {"trace": r, "ledger": ledger, "trace_span": (0.0, 1.0), "peaks": {"hbm_bytes_per_s": 819e9}}
    want = 100.0 * (262144 + 2048) * 96 / 819e9 / sum(r.program_runs("hash_pairs"))
    assert manifest.load_module("metrics", "sha256_hbm_share").read(ctx) == pytest.approx(want)
