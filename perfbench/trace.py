"""Reduction of a JAX profiler trace (`.xplane.pb`) to what the metrics
read: device busy time, time per device operation and per program, the
idle gaps by what the host was doing, and the bytes a kernel call moves
as its shapes say. Checked against `perfbench/data/root_flush.trace.json`
(a recorded v5e trace in this module's own reduced form) by the tests.

The file is read by `perfbench/xplane.py`. On a TPU the trace has one
plane per chip, `/device:TPU:<n>`, with the lines `XLA Modules` (one event per program run) and `XLA Ops` (one per
operation, named by its HLO text), and a `/host:CPU` plane with a line
per host thread. All events share one clock, in nanoseconds.

An operation's scope is the deepest component of the `jax.named_scope`
stack it was traced under that begins with the prefix a reader asks for
(`bls.miller` in `jit(f)/jit(miller_loop)/bls.miller/while/body/mul` for
`bls.`): the names the program gives its device stages, PERF.md section 3.
"""

from __future__ import annotations

import array
import bisect
import json
import math
import re
from typing import NamedTuple

import numpy as np

from perfbench import xplane

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
SHORT_GAP_NS = 2_000
LONG_HOST_NS = 1_000_000
CONTAINER_OPS = ("while", "conditional", "cond", "call")  # their bodies' operations are listed themselves
KEPT = 0.98  # of a span's program runs, filled by its operations: 0.9999 to 1.0000 in whole traces (my chip runs, PR 28)
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
_OP_NAME = re.compile(r"^%?([^\s=(]+)")


def op_short_name(hlo_text: str) -> str:
    """`%mul_acc.12 = s32[...] custom-call(...)` -> `mul_acc`."""
    m = _OP_NAME.match(hlo_text.strip())
    name = m.group(1) if m else hlo_text
    return re.sub(r"(\.\d+)+$", "", name)


def scope_of(stack: str | None, prefix: str) -> str | None:
    """`jit(v)/jit(miller_loop)/bls.miller/while/body/mul:`, `bls.` -> `bls.miller`:
    the deepest whole component that begins with the prefix."""
    found = re.findall(r"(?:^|/)(" + re.escape(prefix) + r"[a-z_]+)(?=[/:]|$)", stack) if stack else None
    return found[-1] if found else None


def program_short_name(module_name: str) -> str:
    """`jit_hash_pairs(1258...)` -> `jit_hash_pairs`."""
    return module_name.split("(")[0]


def hlo_io_bytes(hlo_text: str) -> int:
    """Bytes of every result and operand array the operation's HLO text
    names: what one call has to move between HBM and the core at least."""
    head = hlo_text.split(", custom_call_target")[0].split(", kind=")[0]
    total = 0
    for dtype, dims in _SHAPE.findall(head):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


def read_xplane(source: str | bytes, window_s: float) -> "Reduced":
    """Reduce a profiler trace as it is read: a second of the verify
    path holds millions of device events named by kilobytes of HLO text,
    so nothing is kept for each event but its interval and which
    operation it is."""
    reduced = Reduced(window_s)
    for plane, line, events, scopes in xplane.read(source):
        if DEVICE_PLANE.match(plane):
            if line == OPS_LINE:
                reduced.add_scopes(scopes)
                for name, start, dur in events:
                    reduced.add_op(plane, name, start, dur)
            elif line == MODULES_LINE:
                for name, start, dur in events:
                    reduced.add_module(plane, name, start, dur)
        elif plane == HOST_PLANE:
            for name, start, dur in events:
                reduced.add_host(line, name, start, dur)
    return reduced.close()


def events_of(path: str) -> dict:
    """A trace in the plain form `record` cuts from and the tests keep:
    {"devices": {plane: {"ops": [[name, start_ns, dur_ns]], "modules": [...], "scopes": {name: stack}}},
     "host": {line: [[name, start_ns, dur_ns]]}}; `scopes`, which a
    recording from before PR 28 lacks, holds the `jax.named_scope` stack
    of each operation that has one."""
    out: dict = {"devices": {}, "host": {}}
    for plane, line, events, scopes in xplane.read(path):
        if DEVICE_PLANE.match(plane):
            dev = out["devices"].setdefault(plane, {"ops": [], "modules": [], "scopes": {}})
            key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line)
            if key:
                dev[key] = [list(e) for e in events]
            if key == "ops":
                dev["scopes"] = scopes
        elif plane == HOST_PLANE:
            kept = [list(e) for e in events]
            if kept:  # threads may share a name: their events share the line, as in `Reduced`
                out["host"].setdefault(line, []).extend(kept)
    return out


def record(trace: dict, program_runs: int, max_ops: int = 4000, max_name: int = 320,
           min_host_ns: int = 20_000) -> dict:
    """A small cut of a trace to keep with the tests: the first
    `program_runs` program runs of the first chip, at most `max_ops` of
    the operations inside them (all of them, or every n-th with the
    least n that fits, so that a short recording spans a long program),
    their scopes, and the host events that overlap that span and last
    `min_host_ns` or more. Names longer than `max_name` are cut."""
    device = sorted(trace["devices"])[0]
    modules = sorted(trace["devices"][device]["modules"], key=lambda e: e[1])[:program_runs]
    lo, hi = modules[0][1], modules[-1][1] + modules[-1][2]
    inside = sorted((e for e in trace["devices"][device]["ops"] if lo <= e[1] and e[1] + e[2] <= hi),
                    key=lambda e: e[1])
    inside = inside[::max(1, math.ceil(len(inside) / max_ops))]
    ops = [[n[:max_name], s, d] for n, s, d in inside]
    stacks = trace["devices"][device].get("scopes", {})
    scopes = {n[:max_name]: stacks[n][:max_name] for n, _, _ in inside if n in stacks}
    host = {}
    for line, events in trace["host"].items():
        kept = [[n[:max_name], s, d] for n, s, d in events if s < hi and s + d > lo and d >= min_host_ns]
        if kept:
            host[line] = kept
    cut = {"ops": ops, "modules": [[n[:max_name], s, d] for n, s, d in modules]}
    if scopes:
        cut["scopes"] = scopes
    return {"devices": {device: cut}, "host": host}


def load_recorded(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def _union_ns(starts, durations) -> float:
    """Nanoseconds in which any of the events (numpy arrays) runs."""
    if not len(starts):
        return 0.0
    order = np.argsort(starts, kind="stable")
    starts, ends = starts[order], (starts + durations)[order]
    reach = np.maximum.accumulate(ends)
    first = np.ones(len(starts), dtype=bool)  # of a run of events that touch
    first[1:] = starts[1:] > reach[:-1]
    last = np.append(first[1:], True)
    return float((reach[last] - starts[first]).sum())


class ByScope(NamedTuple):
    seconds: dict  # scope, or None -> device seconds of the operations under it
    spans: int  # host spans they were counted within
    between_s: float  # seconds of those spans in which only a container ran
    lost: int  # host spans left out: the trace did not keep their operations


def _squash(name: str) -> str:
    return re.sub(r"\d+", "", name).strip()[:60] or "unnamed"


class Reduced:
    """What the metric readers and the result line take from a trace."""

    def __init__(self, window_s: float):
        self.window_s = window_s
        self.devices: list[str] = []
        self._intervals: dict[str, list[tuple[float, float]]] = {}
        self._module_intervals: dict[str, list[tuple[float, float]]] = {}
        self._busy: dict[str, list[tuple[float, float]]] = {}
        self.op_calls: dict[str, list] = {}  # HLO text -> [calls, seconds, index], over the chips
        self.modules: list[tuple[str, float]] = []  # (program, seconds) of every run
        self._host: dict[str, list] = {}
        self._stacks: dict[str, str] = {}  # HLO text -> the operation's named-scope stack
        self._op_of_event: dict[str, array.array] = {}  # per chip, the index of each event's operation
        self._events: dict[str, tuple] = {}  # per chip: starts, durations, operation indices
        self._scope_seconds: dict = {}

    @classmethod
    def from_events(cls, trace: dict, window_s: float) -> "Reduced":
        reduced = cls(window_s)
        for device, lines in trace["devices"].items():
            reduced.add_scopes(lines.get("scopes", {}))
            for name, start, dur in lines["ops"]:
                reduced.add_op(device, name, start, dur)
            for name, start, dur in lines["modules"]:
                reduced.add_module(device, name, start, dur)
        for line, events in trace["host"].items():
            for name, start, dur in events:
                reduced.add_host(line, name, start, dur)
        return reduced.close()

    def add_scopes(self, stacks: dict[str, str]) -> None:
        self._stacks.update(stacks)

    def add_op(self, device: str, name: str, start: float, dur: float) -> None:
        self._intervals.setdefault(device, []).append((start, start + dur))
        row = self.op_calls.get(name)
        if row is None:
            row = self.op_calls[name] = [1, dur / 1e9, len(self.op_calls)]
        else:
            row[0] += 1
            row[1] += dur / 1e9
        self._op_of_event.setdefault(device, array.array("I")).append(row[2])

    def add_module(self, device: str, name: str, start: float, dur: float) -> None:
        self._module_intervals.setdefault(device, []).append((start, start + dur))
        self.modules.append((name, dur / 1e9))

    def add_host(self, line: str, name: str, start: float, dur: float) -> None:
        self._host.setdefault(line, []).append((name, start, dur))

    def close(self) -> "Reduced":
        self.devices = sorted(set(self._intervals) | set(self._module_intervals))
        if not self.devices:
            raise ValueError("the trace has no /device:TPU plane: nothing ran on a chip")
        for d in self.devices:
            self._busy[d] = _union(self._intervals.get(d) or self._module_intervals.get(d, []))
        if self._stacks:  # only a trace with scopes is asked for seconds by scope
            for d, intervals in self._intervals.items():
                spans = np.asarray(intervals, dtype=np.float64)
                self._events[d] = (spans[:, 0].copy(), spans[:, 1] - spans[:, 0],
                                   np.frombuffer(self._op_of_event[d], dtype=np.uint32))
        self._intervals.clear()
        return self

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        per_chip = [sum(b - a for a, b in self._busy[d]) for d in self.devices]
        return sum(per_chip) / len(per_chip) / 1e9

    def op_seconds(self) -> dict[str, float]:
        """Device seconds by operation, summed over the chips."""
        out: dict[str, float] = {}
        for name, (_, seconds, _) in self.op_calls.items():
            key = op_short_name(name)
            out[key] = out.get(key, 0.0) + seconds
        return out

    def ops_named(self, short_names) -> list[tuple[str, int, float]]:
        """(HLO text, calls, seconds) of every operation whose short
        name is one of `short_names`."""
        wanted = set(short_names)
        return [
            (name, calls, seconds)
            for name, (calls, seconds, _) in self.op_calls.items()
            if op_short_name(name) in wanted
        ]

    def host_spans(self, name: str) -> list[tuple[float, float]]:
        """(start, end) of every host event of that name, on any thread,
        by start. The profiler keeps a span that opened and closed while
        it was on, so each is whole."""
        return sorted((s, s + d) for events in self._host.values() for n, s, d in events if n == name)

    def scope_seconds(self, inside: str, prefix: str) -> "ByScope":
        """Device seconds by scope (`scope_of` with that prefix; None:
        under no such scope) of the operations that start within a host
        span of the name `inside`, summed over the chips and over the
        spans whose operations the trace kept: the profiler can drop a
        stretch of device events, so a span counts only where the time
        in which some operation that starts in it runs is the time of the
        program runs that start in it, to `KEPT`. A container (`while`,
        `conditional`, `call`) counts nothing, because its children are
        events of their own; the time in which a container runs and none
        of its children does comes apart, as `between_s`. Empty for a
        trace that carries no scopes."""
        if (inside, prefix) in self._scope_seconds:
            return self._scope_seconds[inside, prefix]
        windows = self.host_spans(inside)
        if not windows or not self._events:
            return ByScope({}, 0, 0.0, len(windows))
        opened = np.asarray([w[0] for w in windows])
        closed = np.asarray([w[1] for w in windows])

        def span_of(starts):
            at = np.searchsorted(opened, starts, side="right") - 1
            return np.where((at >= 0) & (starts < closed[np.maximum(at, 0)]), at, -1)

        keys: list = [None]
        code = np.empty(len(self.op_calls), dtype=np.int64)
        for name, (_, _, index) in self.op_calls.items():
            if op_short_name(name) in CONTAINER_OPS:
                code[index] = -1
                continue
            scope = scope_of(self._stacks.get(name), prefix)
            if scope not in keys:
                keys.append(scope)
            code[index] = keys.index(scope)
        chips = []
        for device, (starts, durations, ops) in self._events.items():
            runs = np.asarray(self._module_intervals.get(device, [])).reshape(-1, 2)
            chips.append((starts, durations, code[ops], span_of(starts), runs[:, 1] - runs[:, 0], span_of(runs[:, 0])))
        totals, spans, between = np.zeros(len(keys)), 0, 0.0
        for span in range(len(windows)):
            part, busy, leaves, programs = np.zeros(len(keys)), 0.0, 0.0, 0.0
            for starts, durations, codes, at, run_durations, run_at in chips:
                held = at == span
                counted = held & (codes >= 0)
                part += np.bincount(codes[counted], weights=durations[counted], minlength=len(keys))
                busy += _union_ns(starts[held], durations[held])
                leaves += _union_ns(starts[counted], durations[counted])
                programs += float(run_durations[run_at == span].sum())
            if programs and KEPT <= busy / programs <= 1 / KEPT:
                totals, spans, between = totals + part, spans + 1, between + busy - leaves
        seconds = {key: float(total) / 1e9 for key, total in zip(keys, totals) if total}
        out = self._scope_seconds[inside, prefix] = ByScope(seconds, spans, between / 1e9, len(windows) - spans)
        return out

    def program_runs(self, contains: str) -> list[float]:
        return [seconds for name, seconds in self.modules if contains in name]

    def idle_gaps(self) -> dict[str, float]:
        """Idle seconds of the first chip between its first and last
        operation, by the host event that covers each gap's middle (the
        shortest one that does: the innermost span), `no_host_span`
        where none does. Gaps under 2 us lie between the operations of
        one program and are no host's doing: `between_device_ops`."""
        busy = self._busy[self.devices[0]]
        out: dict[str, float] = {}
        lines = []
        for events in self._host.values():
            events = sorted(events, key=lambda e: e[1])
            long_events = [e for e in events if e[2] >= LONG_HOST_NS]
            lines.append(([e[1] for e in events], events, long_events))
        for (_, start), (end, _) in zip(busy, busy[1:]):
            if end - start < SHORT_GAP_NS:
                out["between_device_ops"] = out.get("between_device_ops", 0.0) + (end - start) / 1e9
                continue
            mid = (start + end) / 2
            best = None
            for starts, events, long_events in lines:
                i = bisect.bisect_right(starts, mid)
                # short events nest closely, so a few steps back reach any
                # that covers the middle; the long ones are all looked at
                for name, s, dur in list(reversed(events[max(0, i - 32):i])) + long_events:
                    if s <= mid <= s + dur and (best is None or dur < best[1]):
                        best = (name, dur)
            key = _squash(best[0]) if best else "no_host_span"
            out[key] = out.get(key, 0.0) + (end - start) / 1e9
        return out

    def breakdown(self, top: int = 10) -> dict:
        ops = [kv for kv in self.op_seconds().items() if kv[0] not in CONTAINER_OPS]
        ops = sorted(ops, key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps().items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": [[k, v] for k, v in gaps]}


if __name__ == "__main__":
    import sys

    usage = "usage: python3 -m perfbench.trace record <in.xplane.pb> <out.json> <program runs> [<most operations>]"
    if not 5 <= len(sys.argv) <= 6 or sys.argv[1] != "record":
        raise SystemExit(usage)
    src, dst, *counts = sys.argv[2:]
    with open(dst, "w") as f:
        json.dump(record(events_of(src), *map(int, counts)), f, separators=(",", ":"))
        f.write("\n")
