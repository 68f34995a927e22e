"""Device time by stage of a verify launch, and jobs a launch: the wire
reader on an `XSpace` built by hand (an operation's `tf_op` stat as a
string and as a reference), the reduction's seconds by scope, the readers
on contexts made by hand, and all of it on a recorded launch of the
(256 rows, 2 slots) program (`perfbench/data/`, cut by `trace.record`
from a run of `node-block-import` on the chip at PR 28)."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

from perfbench import manifest, readers, trace, xplane

DATA = os.path.join(manifest.BENCH_DIR, "data")
STAGES = ("prep_field", "hash_finish", "prep_subgroup", "blind", "miller", "final_exp")
DEV = "/device:TPU:0"
MS = 1_000_000


def reader(name: str):
    return manifest.load_module("metrics", name).read


# --- the wire format ------------------------------------------------------------


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def number(field: int, value: int) -> bytes:
    return varint(field << 3) + varint(value)


def nested(field: int, body: bytes) -> bytes:
    return varint(field << 3 | 2) + varint(len(body)) + body


def stat(metadata_id: int, text: str | None = None, ref: int | None = None, integer: int | None = None) -> bytes:
    body = number(1, metadata_id)
    if text is not None:
        body += nested(5, text.encode())
    if ref is not None:
        body += number(7, ref)
    if integer is not None:
        body += number(3, integer)
    return body


def plane(name: str, operations: dict, stat_names: dict, lines: dict) -> bytes:
    """`operations`: id -> (name, [stats]); `lines`: name -> [(operation id, offset_ps, duration_ps)],
    or such pairs in a list where two lines share a name."""
    body = nested(2, name.encode())
    for line_name, events in (lines.items() if isinstance(lines, dict) else lines):
        line = nested(2, line_name.encode()) + number(3, 1_000)  # timestamp_ns
        for op, offset, duration in events:
            line += nested(4, number(1, op) + number(2, offset) + number(3, duration))
        body += nested(3, line)
    for op, (op_name, stats) in operations.items():
        meta = number(1, op) + nested(2, op_name.encode()) + b"".join(nested(5, s) for s in stats)
        body += nested(4, number(1, op) + nested(2, meta))
    for stat_id, stat_name in stat_names.items():
        body += nested(5, number(1, stat_id) + nested(2, number(1, stat_id) + nested(2, stat_name.encode())))
    return body


def xspace_by_hand() -> bytes:
    stat_names = {1: "flops", 2: "tf_op", 3: "hlo_category", 9: "jit(f)/bls.blind/while/body/mul:"}
    operations = {
        11: ("%mul_acc.1 = s32[8] custom-call()", [stat(1, integer=7), stat(2, text="jit(f)/bls.miller/while/body/dot:")]),
        12: ("%copy.4 = s32[8] copy(s32[8] %a)", [stat(3, text="data formatting"), stat(2, ref=9)]),
        13: ("%fusion.2 = s32[8] fusion(s32[8] %a)", [stat(1, integer=3)]),
        14: ("jit_step(5)", []),
    }
    device = plane(DEV, operations, stat_names,
                   {"XLA Ops": [(11, 0, 2_000_000), (12, 2_000_000, 1_000_000), (13, 3_000_000, 500_000)],
                    "XLA Modules": [(14, 0, 3_500_000)]})
    host = plane("/host:CPU", {1: ("bls_lane_verify", [stat(2, text="jit(f)/bls.miller/x:")])}, {2: "tf_op"},
                 {"worker/7": [(1, 0, 9_000_000)]})
    return nested(1, device) + nested(1, host)


def test_xplane_reads_an_operations_scope_stat_as_a_string_and_as_a_reference():
    lines = {(p, line): (list(events), scopes) for p, line, events, scopes in xplane.read(xspace_by_hand())}
    ops, scopes = lines[(DEV, "XLA Ops")]
    assert [(n.split(" ")[0], s, d) for n, s, d in ops] == [
        ("%mul_acc.1", 1_000.0, 2_000.0), ("%copy.4", 3_000.0, 1_000.0), ("%fusion.2", 4_000.0, 500.0)]
    assert scopes == {
        "%mul_acc.1 = s32[8] custom-call()": "jit(f)/bls.miller/while/body/dot:",  # str_value
        "%copy.4 = s32[8] copy(s32[8] %a)": "jit(f)/bls.blind/while/body/mul:",  # ref_value
    }  # and nothing for the operation that has no `tf_op` stat
    assert lines[(DEV, "XLA Modules")][1] is scopes  # one table a plane
    host_events, host_scopes = lines[("/host:CPU", "worker/7")]
    assert host_events == [("bls_lane_verify", 1_000.0, 9_000.0)]
    assert host_scopes == {}  # stats are decoded for the device planes only


def test_the_reduction_of_an_xspace_sums_device_seconds_by_scope():
    r = trace.read_xplane(xspace_by_hand(), window_s=1.0)
    found = r.scope_seconds("bls_lane_verify", "bls.")
    assert found.spans == 1
    assert found.seconds == pytest.approx({"bls.miller": 2e-6, "bls.blind": 1e-6, None: 0.5e-6})
    assert (found.between_s, found.lost) == (0.0, 0)
    assert r.scope_seconds("bls_lane_verify", "htr.").seconds == pytest.approx({None: 3.5e-6})  # another layer's names
    assert r.busy_s == pytest.approx(3.5e-6)
    assert r.breakdown()["device_ops"] == [["mul_acc", 2e-6], ["copy", 1e-6], ["fusion", 0.5e-6]]


def test_the_plain_form_keeps_the_events_of_threads_that_share_a_name():
    """A launch runs on an executor thread that the profiler names as it
    names the main one; a recording has to keep both threads' spans."""
    device = plane(DEV, {11: ("%mul_acc.1 = s32[8] custom-call()", [stat(2, text="jit(f)/bls.miller/dot:")]),
                         14: ("jit_step(5)", [])}, {2: "tf_op"},
                   {"XLA Ops": [(11, 2_000_000, 2_000_000)], "XLA Modules": [(14, 2_000_000, 2_000_000)]})
    host = plane("/host:CPU", {1: ("bench:verify", []), 2: ("bls_lane_verify", [])}, {},
                 [("python3", [(1, 0, 9_000_000)]), ("python3", [(2, 1_000_000, 5_000_000)])])
    plain = trace.events_of(nested(1, device) + nested(1, host))
    assert sorted(e[0] for e in plain["host"]["python3"]) == ["bench:verify", "bls_lane_verify"]
    cut = trace.record(plain, program_runs=1, min_host_ns=1_000)
    found = trace.Reduced.from_events(cut, window_s=1.0).scope_seconds("bls_lane_verify", "bls.")
    assert (found.seconds, found.spans) == ({"bls.miller": pytest.approx(2e-6)}, 1)


@pytest.mark.parametrize("stack,scope", [
    ("jit(_single_launch_verify)/jit(miller_loop)/bls.miller/while/body/closed_call/concatenate:", "bls.miller"),
    ("jit(f)/bls.final_exp/easy/mul:", "bls.final_exp"),
    ("jit(f)/bls.final_exp/hard/bls.fold/mul", "bls.fold"),  # the deepest wins
    ("bls.assemble", "bls.assemble"),
    ("jit(bls.miller)/while/body/mul:", None),  # a function's name is no scope
    ("jit(f)/jnp.add/bls.x2/mul:", None),  # nor is another library's dotted name, or a name that only begins so
    ("jit(f)/bls.miller/jnp.add/mul:", "bls.miller"),
    ("", None), (None, None),
])
def test_an_operations_scope_is_the_deepest_stage_of_its_stack(stack, scope):
    assert trace.scope_of(stack, "bls.") == scope


# --- the readers on contexts made by hand ---------------------------------------


def op(name: str, stack: str | None) -> tuple[str, str | None]:
    return f"%{name} = s32[256,33]{{1,0}} fusion(s32[256,33]{{1,0}} %a)", stack


def launches_by_hand() -> dict:
    """The tail of a launch the profiler started in, two whole launches of
    100 ms on the device, and the head of one it stopped in. A whole
    launch: 40 ms of `bls.prep_field`, a `while` of 30 ms whose body's
    two operations (20 + 8 ms, `bls.miller`) are events of their own and
    leave it 2 ms to itself, 10 ms under `bls.final_exp/hard`, 12 ms of
    `bls.final_exp` again under a deeper `bls.fold`, 8 ms with no stack."""
    prep, prep_stack = op("fusion.1", "jit(v)/bls.prep_field/mul:")
    loop, loop_stack = op("while.3", "jit(v)/jit(miller_loop)/bls.miller/while:")
    body_a, body_a_stack = op("mul_acc.5", "jit(v)/jit(miller_loop)/bls.miller/while/body/dot:")
    body_b, body_b_stack = op("copy.6", "jit(v)/jit(miller_loop)/bls.miller/while/body/copy:")
    hard, hard_stack = op("fusion.7", "jit(v)/bls.final_exp/hard/mul:")
    fold, fold_stack = op("fusion.8", "jit(v)/bls.final_exp/hard/bls.fold/mul:")
    bare, _ = op("copy.9", None)
    ops, programs, host = [], [], []
    for n, at in enumerate((-60, 100, 250, 400)):  # launch starts, ms
        t = at * MS
        whole = [[prep, t, 40 * MS], [loop, t + 40 * MS, 30 * MS], [body_a, t + 40 * MS, 20 * MS],
                 [body_b, t + 61 * MS, 8 * MS], [hard, t + 70 * MS, 10 * MS], [fold, t + 80 * MS, 12 * MS],
                 [bare, t + 92 * MS, 8 * MS]]
        if n == 0:
            whole = [e for e in whole if e[1] >= 0]  # the trace began at 0
        if n == 3:
            whole = [e for e in whole if e[1] + e[2] <= 450 * MS]  # and ended at 450 ms
        ops += whole
        programs.append(["jit__grouped_launch_verify(7)", whole[0][1], t + 100 * MS - whole[0][1]])
        if n in (1, 2):  # a span is kept when it opened and closed while the profiler was on
            host += [["bls_lane_verify", t - 25 * MS, 130 * MS], ["bls.parse", t - 25 * MS, 20 * MS],
                     ["bls.wait", t - 2 * MS, 105 * MS]]
    return {
        "devices": {DEV: {"ops": ops, "modules": programs,
                          "scopes": {prep: prep_stack, loop: loop_stack, body_a: body_a_stack,
                                     body_b: body_b_stack, hard: hard_stack, fold: fold_stack}}},
        "host": {"executor/2": host, "loop/1": [["bench:verify", 0, 450 * MS]]},
    }


def ctx_of(recorded: dict) -> dict:
    return {"trace": trace.Reduced.from_events(recorded, window_s=0.45), "trace_span": (10.0, 10.45)}


def test_stage_ms_is_a_launchs_device_time_by_its_deepest_stage():
    found = readers.launch_scopes(ctx_of(launches_by_hand()))
    assert (found.spans, found.lost) == (2, 0)  # the launches cut at either end have no span
    per_launch = {scope: 1000.0 * s / found.spans for scope, s in found.seconds.items()}
    assert per_launch == pytest.approx(
        {"bls.prep_field": 40.0, "bls.miller": 28.0, "bls.final_exp": 10.0, "bls.fold": 12.0, None: 8.0})
    ctx = ctx_of(launches_by_hand())
    assert reader("stage_device_ms.prep_field")(ctx) == pytest.approx(40.0)
    assert reader("stage_device_ms.miller")(ctx) == pytest.approx(28.0)  # the container is not counted beside its children
    assert reader("stage_device_ms.final_exp")(ctx) == pytest.approx(10.0)
    assert reader("stage_device_ms.containers")(ctx) == pytest.approx(2.0)  # but what it has to itself is
    for absent in ("hash_finish", "prep_subgroup", "blind"):  # no operation carries the stage
        assert reader(f"stage_device_ms.{absent}")(ctx) is None
    assert reader("stage_scoped_share")(ctx) == pytest.approx(90.0)  # the operation without `tf_op` is unscoped
    # a launch's busy time, each nanosecond once
    assert sum(per_launch.values()) + reader("stage_device_ms.containers")(ctx) == pytest.approx(100.0)


ROW_READERS = tuple(f"stage_device_ms.{stage}" for stage in STAGES + ("containers",)) + ("stage_scoped_share",)


def test_a_launch_whose_events_the_trace_lost_is_left_out():
    """The profiler can drop a stretch of device events (a cold run of
    this cell at PR 28 kept 0.314 of 0.427 busy seconds and two of three
    program runs; the driver's traced run at PR 27 kept 7 ms, ledger):
    over every span, what is left would read as a faster stage."""
    lossy = launches_by_hand()
    lines = lossy["devices"][DEV]
    lines["ops"] = [e for e in lines["ops"] if not (e[0].startswith("%fusion.1 ") and e[1] == 250 * MS)]
    found = ctx_of(lossy)["trace"].scope_seconds(readers.VERIFY_LAUNCH, readers.STAGE_PREFIX)
    assert (found.spans, found.lost) == (1, 1)  # 60 of the second launch's 100 ms are left
    assert reader("stage_device_ms.prep_field")(ctx_of(lossy)) == pytest.approx(40.0)  # not 20
    assert reader("stage_device_ms.containers")(ctx_of(lossy)) == pytest.approx(2.0)
    assert reader("stage_scoped_share")(ctx_of(lossy)) == pytest.approx(90.0)
    lines["modules"] = [m for m in lines["modules"] if m[1] != 250 * MS]  # its program run's event lost as well
    found = ctx_of(lossy)["trace"].scope_seconds(readers.VERIFY_LAUNCH, readers.STAGE_PREFIX)
    assert (found.spans, found.lost) == (1, 1)
    lines["ops"] = [e for e in lines["ops"] if not (e[0].startswith("%copy.9 ") and e[1] == 192 * MS)]
    for name in ROW_READERS:  # no launch is whole: nothing to read
        assert reader(name)(ctx_of(lossy)) is None
    no_runs = launches_by_hand()
    no_runs["devices"][DEV]["modules"] = []  # no program run to hold the operations against
    assert readers.launch_scopes(ctx_of(no_runs)) is None


def test_a_recording_spans_its_program_runs_whatever_their_length():
    """`record` keeps every n-th operation, with the least n that fits."""
    whole = launches_by_hand()
    whole["devices"][DEV]["modules"] = whole["devices"][DEV]["modules"][1:3]  # the two whole launches: 14 operations
    kept = lambda cut: [(trace.op_short_name(n), s // MS) for n, s, _ in cut["devices"][DEV]["ops"]]  # noqa: E731
    assert len(kept(trace.record(whole, program_runs=2, max_ops=14))) == 14
    assert kept(trace.record(whole, program_runs=2, max_ops=5)) == [
        ("fusion", 100), ("copy", 161), ("copy", 192), ("mul_acc", 290), ("fusion", 330)]  # every third
    short = trace.record(whole, program_runs=1, max_ops=2)  # every fourth of the first run's seven
    assert kept(short) == [("fusion", 100), ("fusion", 170)]
    assert set(short["devices"][DEV]["scopes"]) == {n for n, _, _ in short["devices"][DEV]["ops"]}


def test_the_command_line_cuts_a_recording_from_a_profiler_file(tmp_path):
    (tmp_path / "in.xplane.pb").write_bytes(xspace_by_hand())
    for counts, kept in ((["1"], ["mul_acc", "copy", "fusion"]), (["1", "2"], ["mul_acc", "fusion"])):
        done = subprocess.run([sys.executable, "-m", "perfbench.trace", "record", str(tmp_path / "in.xplane.pb"),
                               str(tmp_path / "out.json"), *counts], cwd=manifest.ROOT, capture_output=True, text=True)
        assert done.returncode == 0, done.stderr
        (lines,) = trace.load_recorded(str(tmp_path / "out.json"))["devices"].values()
        assert [trace.op_short_name(n) for n, _, _ in lines["ops"]] == kept
        assert set(lines["scopes"]) <= {n for n, _, _ in lines["ops"]}
    assert subprocess.run([sys.executable, "-m", "perfbench.trace", "record"], cwd=manifest.ROOT,
                          capture_output=True).returncode != 0


@pytest.mark.parametrize("name", ROW_READERS)
def test_a_stage_reader_reads_nothing_where_there_is_nothing_to_read(name):
    assert reader(name)({"trace": None, "trace_span": None}) is None  # no chip, or an untraced run
    unscoped = launches_by_hand()
    del unscoped["devices"][DEV]["scopes"]  # a recording from before the stages, or a program without scopes
    assert reader(name)(ctx_of(unscoped)) is None
    no_launch = launches_by_hand()
    no_launch["host"]["executor/2"] = []  # no launch lies whole in the traced span
    assert reader(name)(ctx_of(no_launch)) is None


def launch(program, compile_=False) -> dict:
    return {"program": program, "size_class": 256, "seconds": 0.15, "compile": compile_, "t_mono_ns": 0, "lane": "dev0"}


def test_jobs_per_launch_is_the_windows_jobs_over_its_steady_launches():
    read = reader("jobs_per_launch")
    before, after = {"pool.jobs_started": 10.0}, {"pool.jobs_started": 14.0}
    ledger = [launch("bls_lane_verify", compile_=True),  # a first call is no steady launch
              launch("bls_lane_verify"), launch("bls_lane_verify"),
              launch("_grouped_launch_verify"), launch("_grouped_launch_verify")]
    assert read({"ledger": ledger, "counters_before": before, "counters_after": after}) == 2.0
    assert read({"ledger": [launch("_grouped_launch_verify")], "counters_before": before,
                 "counters_after": after}) is None  # no launch
    assert read({"ledger": [], "counters_before": {}, "counters_after": {}}) is None  # the reference entry
    assert read({"ledger": ledger, "counters_before": after, "counters_after": after}) is None  # no job counted


ROWS = {"jobs_per_launch": ("jobs", "higher", "program_counter", "pool"),
        "stage_scoped_share": ("%", "higher", "device_trace", "field/curve/pairing ops"),
        **{f"stage_device_ms.{stage}": ("ms", "lower", "device_trace", "field/curve/pairing ops")
           for stage in STAGES + ("containers",)}}


def check_the_manifest_lists_the_row(m: dict, name: str) -> None:
    """The row is there once, as PR 28 added it, and block import is among
    its cells. Which cells joined it since, and where it stands, is free."""
    unit, better, source, layer = ROWS[name]
    (row,) = [r for r in m["per_layer"] if r["name"] == name]
    assert {k: v for k, v in row.items() if k != "workloads"} == {
        "name": name, "unit": unit, "better": better, "source": source, "layer": layer, "moves": "verdict_p50_ms"}
    assert "node-block-import" in row["workloads"]


@pytest.mark.parametrize("name", sorted(ROWS))
def test_the_manifest_lists_the_row_for_block_import(name):
    check_the_manifest_lists_the_row(manifest.load_manifest(), name)


def test_the_root_cell_reports_none_of_the_rows():
    reported = {m["name"] for m in manifest.load_cell("root-epoch-sweep").per_layer}
    assert not reported & set(ROWS)


# --- a recorded launch ------------------------------------------------------------


def recorded_ctx(name: str) -> dict:
    recorded = trace.load_recorded(os.path.join(DATA, name + ".trace.json"))
    return {"trace": trace.Reduced.from_events(recorded, window_s=0.5), "trace_span": (0.0, 0.5),
            "recorded": recorded}


def test_the_stages_of_a_recorded_launch_of_the_two_slot_program():
    """One operation in every 160 or so of one launch of `node-block-import`
    on the v5e, with the launch's own host span and program run: the
    stride keeps the stages' order of size, not their milliseconds."""
    ctx = recorded_ctx("grouped_launch_stages")
    (lines,) = ctx["recorded"]["devices"].values()
    assert [trace.program_short_name(n) for n, _, _ in lines["modules"]] == ["jit__grouped_launch_verify"]
    assert len(lines["scopes"]) > 1000 and all("bls." in stack for stack in lines["scopes"].values())
    # a recording is a trace that lost events, and the readers treat it so
    found = ctx["trace"].scope_seconds(readers.VERIFY_LAUNCH, readers.STAGE_PREFIX)
    assert (found.seconds, found.spans, found.lost) == ({}, 0, 1)
    for name in ROW_READERS:
        assert reader(name)(ctx) is None
    # held against a program run as long as what was kept, they read it
    (run,) = lines["modules"]
    assert ctx["trace"].busy_s < 0.05 * run[2] / 1e9
    run[2] = ctx["trace"].busy_s * 1e9
    ctx = {"trace": trace.Reduced.from_events(ctx["recorded"], window_s=0.5), "trace_span": (0.0, 0.5)}
    found = readers.launch_scopes(ctx)
    assert (found.spans, found.lost) == (1, 0)
    assert {f"bls.{stage}" for stage in STAGES} <= set(found.seconds)
    total = sum(found.seconds.values())
    counted = sum(d for n, _, d in lines["ops"] if trace.op_short_name(n) not in trace.CONTAINER_OPS)
    assert total == pytest.approx(counted / 1e9)  # every operation once, under one scope or none
    prep = sum(found.seconds[f"bls.{stage}"] for stage in ("prep_field", "prep_subgroup", "hash_finish"))
    assert prep > 0.5 * total  # input prep is most of a launch
    assert found.seconds["bls.prep_field"] == max(found.seconds.values())
    for stage in STAGES:
        assert reader(f"stage_device_ms.{stage}")(ctx) == pytest.approx(1000.0 * found.seconds[f"bls.{stage}"])
    assert reader("stage_device_ms.containers")(ctx) == pytest.approx(1000.0 * found.between_s)
    assert 95.0 < reader("stage_scoped_share")(ctx) < 100.0


@pytest.mark.parametrize("name", ["verify_launch", "root_flush"])
def test_the_recordings_from_before_the_stages_read_as_before_and_carry_none(name):
    ctx = recorded_ctx(name)
    assert all("scopes" not in lines for lines in ctx["recorded"]["devices"].values())
    assert ctx["trace"].scope_seconds(readers.VERIFY_LAUNCH, readers.STAGE_PREFIX).seconds == {}
    assert readers.launch_scopes(ctx) is None
    for name in ROW_READERS:
        assert reader(name)(ctx) is None
