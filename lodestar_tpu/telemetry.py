"""Device launch telemetry: what every counted dispatch cost on the host's
clock, what the wall was spent on, and the same names on the profiler's clock.

Two context managers are the one seam every counted dispatch reports through
(`ops/prep.py:_dispatch`, `ssz/device_htr.py:_device_level`,
`chain/bls/mesh.py:mesh_launch`, and the verify core and the sharded
collective in `models/batch_verify.py`):

* `launch(program, size_class, lane=None)` — one **ledger entry** per
  dispatch: wall seconds inside the `with` block (on an async backend:
  dispatch plus whatever blocking transfer the block performs), the
  program's name, the pow-2 size class its executable was compiled for,
  the lane, first-call-per-(program, size class, lane) **compile** detection,
  the thread (`tid`), the launch that encloses it on that thread
  (`parent`, its `seq`; None at top level) and `phases`. Launches nest
  (`bls_lane_verify` holds `_single_launch_verify`), so sums are taken
  over top-level entries.
* `phase(name, into=None)` — elapsed seconds added to `phases[name]` of
  the innermost open launch on the thread, or to `into` where a caller
  keeps its own dict (the dirty collector's `steps`). A phase on a thread
  with no open launch leaves no number behind.

Both also open a `jax.profiler.TraceAnnotation` of the same name, so a
profile of a node (XProf, Perfetto, or the benchmark's `--trace 1`, which
puts every idle gap of the device under the innermost host span) shows
them on the device trace's clock. Span names carry no digits and no
metadata: sizes and levels go on the ledger entry. The vocabulary is in
PERF.md §3.

Sinks:

* Prometheus (`DeviceLaunchMetrics`, installed by the node):
  `lodestar_device_launch_seconds{program,size_class}`,
  `lodestar_device_compile_seconds_total` (top-level first calls only, so
  it can be summed), `lodestar_device_compile_{hits,misses}_total{program}`.
* A bounded in-process **launch ledger** (deque, default 256 entries)
  surfaced by `GET /eth/v0/debug/launches` and folded into slow-slot
  dumps (`slow_slot_launches`) — a slow slot names its launches.

Mode (`--launch-telemetry {auto,on,off}`, process-global like the prep
and HTR modes): "auto" records once a metrics sink is installed (every
node) and stays off in bare library use; "on" records even without
metrics (ledger + process-local counters — tests, benches); "off"
disables everything, leaving the seams one flag-check from free.

This module imports the standard library only and never touches a JAX
backend (`chain/bls/mesh.py` relies on it): the annotation class is taken
from `sys.modules` at first use, and where JAX is not loaded there is no
profiler to write to.
"""

from __future__ import annotations

import sys
import threading
import time
from collections import deque

__all__ = [
    "TELEMETRY_MODES",
    "DEFAULT_LEDGER_SIZE",
    "configure_launch_telemetry",
    "launch_telemetry_active",
    "launch",
    "phase",
    "record_launch",
    "launch_size_class",
    "size_class_of",
    "GROUP_SLOT_RUNG",
    "group_slot_rows",
    "launch_ledger",
    "launch_totals",
    "known_programs",
    "slow_slot_launches",
    "reset_launch_telemetry",
]

TELEMETRY_MODES = ("auto", "on", "off")

#: ledger bound: big enough to hold every dispatch of a slow slot
#: (a worst-case import is tens of launches), small enough that the
#: debug route and the slow-slot dump stay cheap to serialize
DEFAULT_LEDGER_SIZE = 256

_mode = "auto"  # guarded by: config-time (node init / test setup writes; hot-path reads tolerate either value)
_metrics = None  # guarded by: config-time (DeviceLaunchMetrics slot, set once at node init)

_lock = threading.Lock()
_ledger: deque = deque(maxlen=DEFAULT_LEDGER_SIZE)  # guarded by: _lock
_seen_keys: set = set()  # guarded by: _lock — (program, size_class, lane) compile-detection keys
_seq = 0  # guarded by: _lock — monotonic dispatch sequence number
_compiles = 0  # guarded by: _lock — first-call dispatches observed
_tls = threading.local()  # .open: this thread's stack of open launches


def configure_launch_telemetry(
    mode: str | None = None, metrics=None, ledger_size: int | None = None
) -> str:
    """Set the process-wide telemetry mode and/or install the
    `lodestar_device_launch_*` metric family (node init; tests flip the
    mode around calls). Returns the PREVIOUS mode so callers can
    save/restore."""
    global _mode, _metrics, _ledger
    prev = _mode
    if mode is not None:
        if mode not in TELEMETRY_MODES:
            raise ValueError(
                f"launch_telemetry must be one of {TELEMETRY_MODES}, got {mode!r}"
            )
        _mode = mode
    if metrics is not None:
        _metrics = metrics
    if ledger_size is not None:
        with _lock:
            _ledger = deque(_ledger, maxlen=ledger_size)
    return prev


def launch_telemetry_active() -> bool:
    """Whether the dispatch seams should pay the clock reads: "on"
    always, "off" never, "auto" once a metrics sink is installed (the
    node installs one at init; bare library use stays free)."""
    if _mode == "on":
        return True
    if _mode == "off":
        return False
    return _metrics is not None


def size_class_of(n: int, floor: int = 8) -> int:
    """Pow-2 size-class bucketing for a raw batch size — the same
    shape-bucket the compile caches key on (`ops/prep.pad_pow2`,
    reimplemented here so jax-free seams like chain/bls/mesh.py can
    label without importing the ops layer)."""
    return max(floor, 1 << (max(1, int(n)) - 1).bit_length())


#: the one rung of the multi-job launch's slot ladder that is no power
#: of two: the 64 class and one 8-row grain. The reference cuts a list
#: of 129 to 144 sets into halves of 65 to 72
#: (`chunkify_maximize_chunk_size` under MAX_SIGNATURE_SETS_PER_JOB), a
#: full mainnet block's 131 into 66 and 65. One rung and no more: a slot
#: length is a traced program (TUNING.md has the provenance).
GROUP_SLOT_RUNG = 72


def group_slot_rows(job_sizes) -> int:
    """THE slot rule of the multi-job launch: the rows of a slot, from
    the set counts of the jobs that ride. A slot is as long as the
    longest job's size class, except that jobs of the 128 class which
    all fit GROUP_SLOT_RUNG rows get that many: a block's halves ride
    (144, 2) and four of them (288, 4), jobs of 73 to 128 sets (256, 2)
    and (512, 4). The host parse that lays the slots out
    (`models/batch_verify.prepare_grouped_launch_inputs`), the launch's
    ledger label (`chain/bls/mesh.mesh_launch`) and the offload host's
    warm list (`offload/known_answer.programs_of`) all ask here; it
    stands beside `size_class_of` for the reason that one does."""
    longest = max(job_sizes)
    size_class = size_class_of(longest)
    return GROUP_SLOT_RUNG if longest <= GROUP_SLOT_RUNG < size_class else size_class


def launch_size_class(args) -> int:
    """Leading-axis size of the first array-shaped thing in `args`
    (recursing into tuples/lists — device programs take point tuples).
    The dispatch seams hand padded arrays in, so this IS the size
    class; returns 0 when nothing array-shaped is found."""
    for a in args:
        shape = getattr(a, "shape", None)
        if shape:
            return int(shape[0])
        if isinstance(a, (tuple, list)) and a:
            n = launch_size_class(a)
            if n:
                return n
    return 0


def program_name(program) -> str:
    """Stable identity label for a dispatched callable (jit wrappers
    preserve `__name__` via functools.wraps)."""
    name = getattr(program, "__name__", None)
    if name:
        return name
    return type(program).__name__


def _annotate(name: str):
    """An entered `jax.profiler.TraceAnnotation(name)`, or None where JAX
    is not loaded (no profiler to write to). Outside a profiler session
    the annotation is one flag check inside TraceMe."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    note = jax.profiler.TraceAnnotation(name)
    note.__enter__()
    return note


class _Off:
    """What `launch` and `phase` return while telemetry is inactive."""

    __slots__ = ()
    entry = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def add_phase(self, name: str, seconds: float) -> None:
        pass


_OFF = _Off()


class _Launch:
    __slots__ = ("program", "size_class", "lane", "attrs", "phases", "seq", "parent", "entry", "_t0", "_note")

    def __init__(self, program: str, size_class: int, lane: str | None, attrs: dict):
        self.program, self.size_class, self.lane, self.attrs = program, size_class, lane, attrs
        self.phases: dict[str, float] = {}
        self.entry: dict | None = None

    def add_phase(self, name: str, seconds: float) -> None:
        """Seconds spent for this launch where no `phase` block on this
        thread could see them (prep staged on another thread)."""
        self.phases[name] = self.phases.get(name, 0.0) + seconds

    def __enter__(self):
        global _seq
        stack = getattr(_tls, "open", None)
        if stack is None:
            stack = _tls.open = []
        self.parent = stack[-1].seq if stack else None
        with _lock:
            _seq += 1
            self.seq = _seq
        stack.append(self)
        self._note = _annotate(self.program)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        seconds = time.perf_counter() - self._t0
        if self._note is not None:
            self._note.__exit__(exc_type, exc, tb)
        _tls.open.pop()
        if exc_type is None:  # a dispatch that raised is the caller's fallback to count, not a launch
            self.entry = _record(
                self.program, self.size_class, seconds, self.lane,
                seq=self.seq, parent=self.parent, phases=self.phases, attrs=self.attrs,
            )
        return False


class _Phase:
    __slots__ = ("name", "into", "_t0", "_note")

    def __init__(self, name: str, into: dict | None):
        self.name, self.into = name, into

    def __enter__(self):
        self._t0 = time.perf_counter()  # the span's own cost counts as the phase's
        self._note = _annotate(self.name)
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._note is not None:
            self._note.__exit__(exc_type, exc, tb)
        seconds = time.perf_counter() - self._t0
        into = self.into
        if into is None:
            stack = getattr(_tls, "open", None)
            if not stack:
                return False
            into = stack[-1].phases
        into[self.name] = into.get(self.name, 0.0) + seconds
        return False


def launch(program: str, size_class: int, lane: str | None = None, **attrs):
    """`with launch(...)` around one device dispatch: a ledger entry and
    the metric observations for its extent (see `record_launch`), with
    the phases opened inside it on this thread, under a profiler span
    named `program`. `.entry` is the ledger entry after the block; a
    block that raises writes none. `attrs` are further facts of the
    launch, kept on its entry (`indexed_rows` of a verify launch); the
    ones that are set say which variant of the program ran, so they
    count for first-call detection. Inactive: one flag check."""
    if not launch_telemetry_active():
        return _OFF
    return _Launch(program, size_class, lane, attrs)


def phase(name: str, into: dict | None = None):
    """`with phase(name)` around a stretch of host work: a profiler span
    named `name`, and its seconds added to `phases[name]` of the
    innermost launch open on this thread (dropped where there is none),
    or to `into[name]` where the caller keeps the dict."""
    if not launch_telemetry_active():
        return _OFF
    return _Phase(name, into)


def record_launch(
    program: str,
    size_class: int,
    seconds: float,
    *,
    lane: str | None = None,
) -> dict | None:
    """Record one top-level device dispatch whose wall the caller took
    itself: ledger entry + metric observations. Returns the ledger entry
    (tests), or None when inactive."""
    if not launch_telemetry_active():
        return None
    return _record(program, size_class, seconds, lane)


def _record(program, size_class, seconds, lane, *, seq=None, parent=None, phases=None, attrs=None) -> dict:
    """Compile detection is first-call-per-(program, size_class, lane)
    and, where the launch carries attributes, per set of those that are
    set (a verify launch with indexed rows is another program than the
    byte-only one of its size class):
    the jit caches hold one executable per key and chip, so the first
    dispatch of a key in this process carries trace+compile (or the
    persistent-cache load), a lane's first carries the lowering and the
    compile (or load) for its chip, and each is counted as a miss; every
    later dispatch of the key is a hit. First calls nest like their
    launches, so only a top-level one adds its seconds to the compile
    counter."""
    global _seq, _compiles
    key = (program, size_class, lane)
    if attrs:
        key += tuple(sorted(k for k, v in attrs.items() if v))
    with _lock:
        if seq is None:
            _seq += 1
            seq = _seq
        compile_ = key not in _seen_keys
        _seen_keys.add(key)
        if compile_:
            _compiles += 1
        entry = {
            "seq": seq,
            "program": program,
            "size_class": size_class,
            "seconds": seconds,
            "lane": lane,
            "compile": compile_,
            "t_mono_ns": time.monotonic_ns(),
            "tid": threading.get_ident(),
            "parent": parent,
            "phases": phases if phases is not None else {},
        }
        if attrs:
            entry.update(attrs)
        _ledger.append(entry)
    m = _metrics
    if m is not None:
        try:
            m.launch_seconds.labels(program, str(size_class)).observe(seconds)
            if compile_:
                m.compile_misses.labels(program).inc()
                if parent is None:
                    m.compile_seconds.inc(seconds)
            else:
                m.compile_hits.labels(program).inc()
        except Exception:
            pass  # the metric bridge must never fail a device dispatch
    return entry


def launch_ledger(n: int | None = None) -> list[dict]:
    """The most recent `n` ledger entries (all when None), oldest
    first by the time they were written (a launch takes its `seq` when
    it opens, so an enclosing launch follows the ones it holds).
    Entries are copies — callers can't corrupt the ledger."""
    with _lock:
        entries = list(_ledger)
    if n is not None and n >= 0:
        entries = entries[-n:] if n else []
    return [{**e, "phases": dict(e["phases"])} for e in entries]


def launch_totals() -> dict:
    """Cumulative view for the debug route: launches opened (one that
    raised took its number and wrote no entry), compile count, distinct
    (program, size_class) keys, and per-program launch counts and
    per-phase seconds over the CURRENT ledger window (the full-history
    numbers are the Prometheus counters)."""
    with _lock:
        entries = list(_ledger)
        seq = _seq
        compiles = _compiles
        keys = len(_seen_keys)
    by_program: dict[str, int] = {}
    by_phase: dict[str, float] = {}
    for e in entries:
        by_program[e["program"]] = by_program.get(e["program"], 0) + 1
        for name, seconds in e["phases"].items():
            by_phase[name] = by_phase.get(name, 0.0) + seconds
    return {
        "launches": seq,
        "compiles": compiles,
        "distinct_keys": keys,
        "ledger_entries": len(entries),
        "ledger_by_program": by_program,
        "ledger_phase_seconds": by_phase,
    }


def known_programs() -> set[str]:
    """Program names that have dispatched at least once in this process
    (the compile-detection key universe) — the validation set for the
    debug route's `?program=` filter."""
    with _lock:
        return {k[0] for k in _seen_keys}


def slow_slot_launches(n: int = 12) -> dict:
    """Compact launch view for slow-slot dumps: the trailing `n`
    dispatches as one-line strings plus the cumulative counts — a slow
    slot names its launches without a second query. When the SLO layer
    is configured, the dump also names the per-class remaining deadline
    slack at dump time ("did we still make the cutoff" inline)."""
    entries = launch_ledger(n)
    recent = [
        "{program}/{size_class} {ms:.1f}ms{lane}{comp}".format(
            program=e["program"],
            size_class=e["size_class"],
            ms=e["seconds"] * 1000.0,
            lane=f" @{e['lane']}" if e["lane"] else "",
            comp=" [compile]" if e["compile"] else "",
        )
        for e in entries
    ]
    with _lock:
        out = {"launches_total": _seq, "compiles_total": _compiles, "recent": recent}
    # lazy one-way import (slo never imports telemetry); stdlib-only on
    # both sides, so the import-hygiene doctrine holds
    from lodestar_tpu import slo

    slack = slo.slow_slot_slack()
    if slack:
        out["deadline_slack"] = slack
    return out


def reset_launch_telemetry() -> None:
    """Fresh disabled-ish state (test isolation): mode back to auto,
    metrics detached, ledger/keys/counters cleared."""
    global _mode, _metrics, _ledger, _seq, _compiles
    with _lock:
        _mode = "auto"
        _metrics = None
        _ledger = deque(maxlen=DEFAULT_LEDGER_SIZE)
        _seen_keys.clear()
        _seq = 0
        _compiles = 0
