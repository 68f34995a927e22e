"""Device hashTreeRoot: dirty-subtree collector with one batched SHA-256
launch per tree level.

The second compute-bound hot loop of the reference (after BLS) is SSZ
Merkle re-hashing: `packages/state-transition/src/stateTransition.ts:100`
re-roots the BeaconState through incremental as-sha256 inside
persistent-merkle-tree, thousands of 2-to-1 hashes per slot on the CPU.
PERF.md config 4 measured the device SHA-256 kernel (`ops/sha256.py`) at
10.1M pair-hashes/s on the 2^20-chunk 1M-validator shape — 14.1x a host
core — but until this module the state-transition hot path never used it
incrementally: only from-scratch merkleization of big levels did.

This module is the seam between the two: mutated chunks (recorded by the
tree views' dirty tracking or diffed by the state-root tracker in
`state_transition/htr.py`) are collected with their sibling roots into
level-ordered pair batches and flushed through `ops.sha256:hash_pairs`
with **one device launch per tree level**, regardless of how many fields
or subtrees went dirty in the slot. Batches are padded to power-of-two
size classes (same compile-cache doctrine as `ops/prep.py`: one jitted
program per class, shared by every caller, amortized by the persistent
JAX cache). A level's pairs are gathered straight into the class's
launch buffer, kept across flushes, byteswapped to words there, and its
digests converted back into the class's roots buffer, from which they
are scattered: one host pass each way and no fresh temporaries.

Degradation doctrine (mirrors `chain/bls/fallback.py` and the BLS prep
fallback): a device **error** degrades the whole flush to the CPU level
hasher — the CPU pass recomputes every dirty node from its leaf inputs,
so no partially-device-computed root is ever trusted on the degradation
trial. Each fallback bumps `lodestar_ssz_htr_fallback_total` and warns.
Verdicts don't exist here — a root is a root — so unlike BLS there is
no "False is final" leg; the only failure mode is an error, and errors
always degrade.

Mode selection is process-global like the BLS prep mode
(`--htr-device {auto,on,off}` through cli ↔ BeaconNodeOptions ↔ node):
"auto" rides the device when the process that configured it observed a
TPU backend (node init passes what it saw, once; a process that never
configures stays on the host), "on" forces the device kernel (tests /
benches on any backend), "off" restores the pure host path everywhere.

Importing this module never initializes a JAX backend — `ops.sha256` is
imported lazily inside the launch path (the r3 multichip-gate
regression class; same doctrine as `ssz/hash.py`).
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Iterable

import numpy as np

from lodestar_tpu import telemetry

from .hash import hash_nodes_cpu

__all__ = [
    "HTR_MODES",
    "FLUSH_STEPS",
    "configure_device_htr",
    "device_htr_active",
    "DirtyCollector",
    "compute_root_node",
    "hash_level",
    "launch_count",
    "pad_pow2_pairs",
    "note_fallback",
]

HTR_MODES = ("auto", "on", "off")

# Process-global placement mode + metrics sink, set once at node init by
# `configure_device_htr` (tests/benches flip the mode around calls, like
# `configure_device_prep`). Reads race benignly: a flush observes either
# the old or the new mode, both of which produce correct roots.
_htr_mode = "auto"  # guarded by: config-time (node init / test setup writes; hot-path reads tolerate either value)
_htr_accelerator = False  # guarded by: config-time (what "auto" resolves to: the backend node init observed)
_htr_metrics = None  # guarded by: config-time (node init / test setup writes; hot-path reads tolerate either value)

# Cumulative device-level launch counter: every padded `hash_pairs`
# dispatch issued by this module increments it. Tests assert the
# one-launch-per-level invariant by diffing it around a flush; it is a
# plain int mutated with += (GIL-atomic enough for a test counter —
# production observability rides the lodestar_ssz_htr_* family).
_launch_count = 0  # guarded by: advisory-only (test/debug counter; metrics are the production signal)

#: pad every device batch to a power-of-two pair count of at least this,
#: so the number of distinct compiled programs stays logarithmic in the
#: largest level ever flushed (the ops/prep.py size-class doctrine).
_MIN_PAIR_CLASS = 8

#: what a collector flush spends its wall on, as `DirtyCollector.steps`
#: and the profiler's host spans name it: index arithmetic over the
#: dirty set, gathering pairs into the launch buffer and turning them
#: into words there, the device call and the wait for it, converting
#: the digests and writing roots back, and levels hashed on the host
FLUSH_STEPS = ("htr.index", "htr.gather", "htr.device", "htr.scatter", "htr.host_hash")


class _LevelBuffers:
    """One size class's launch buffers: `pairs` (size, 64) uint8, a
    level's pairs gathered row by row (the rows after them hold whatever
    an earlier level left: their digests are discarded), and `roots`
    (size, 32) uint8, the digests converted back to bytes."""

    __slots__ = ("size", "pairs", "roots")

    def __init__(self, size: int):
        self.size = size  # guarded by: flush-thread (held by one flush between take and give back)
        self.pairs = np.zeros((size, 64), dtype=np.uint8)  # guarded by: flush-thread (same)
        self.roots = np.zeros((size, 32), dtype=np.uint8)  # guarded by: flush-thread (same)


#: launch buffers kept across flushes, at most one `_LevelBuffers` a
#: size class (~50 MB at a 250,000-leaf flush of a 2^20 stack). A level
#: takes its class's buffers out and gives them back once its roots are
#: written, so two flushes on different threads never hold the same
#: ones: the one that finds its class taken allocates its own.
_launch_buffers: dict[int, _LevelBuffers] = {}  # guarded by: _launch_buffers_lock
_launch_buffers_lock = threading.Lock()


def _take_buffers(size: int) -> tuple[_LevelBuffers, bool]:
    """The pool's buffers of a size class, or new ones where the pool
    has none free; the flag says whether they were allocated."""
    with _launch_buffers_lock:
        bufs = _launch_buffers.pop(size, None)
    if bufs is not None:
        return bufs, False
    return _LevelBuffers(size), True


def _give_back(bufs: _LevelBuffers) -> None:
    with _launch_buffers_lock:
        _launch_buffers.setdefault(bufs.size, bufs)


def _rows(a: np.ndarray, width: int) -> np.ndarray:
    """(R, width) uint8 as R opaque rows: a view, so that one fancy
    assignment moves whole rows."""
    return a.view(np.dtype((np.void, width)))[:, 0]


#: below this many pairs a level stays on the host hasher even when the
#: device backend is selected — a tiny level is far cheaper as a couple
#: of host digests than as a padded dispatch round trip (the same
#: asymmetry as ssz.hash.DEVICE_MIN_PAIRS, which is the default).
#: None = follow ssz.hash.DEVICE_MIN_PAIRS; tests/benches override.
DEVICE_MIN_FLUSH_PAIRS: int | None = None  # guarded by: config-time (test/bench override; hot-path reads tolerate either value)


def _min_flush_pairs() -> int:
    if DEVICE_MIN_FLUSH_PAIRS is not None:
        return DEVICE_MIN_FLUSH_PAIRS
    from .hash import DEVICE_MIN_PAIRS

    return DEVICE_MIN_PAIRS


def configure_device_htr(
    mode: str | None = None, metrics=None, accelerator: bool | None = None
) -> str:
    """Set the process-wide HTR placement mode, what "auto" resolves to
    (`accelerator`: whether the configuring process observed a TPU
    backend) and/or the lodestar_ssz_htr_* metric family (node init;
    tests and benches flip the mode around calls). Returns the PREVIOUS
    mode so callers can save/restore."""
    global _htr_mode, _htr_metrics, _htr_accelerator
    prev = _htr_mode
    if mode is not None:
        if mode not in HTR_MODES:
            raise ValueError(f"htr_device must be one of {HTR_MODES}, got {mode!r}")
        _htr_mode = mode
    if accelerator is not None:
        _htr_accelerator = bool(accelerator)
    if metrics is not None:
        _htr_metrics = metrics
    return prev


def device_htr_active(mode: str | None = None) -> bool:
    """Resolve an HTR mode. "auto" is what `configure_device_htr` was
    told about the backend — never probed here: this runs per root on
    pure-host consumers too (db serdes hash through ssz.batch), which
    must neither import JAX nor take the chip."""
    mode = mode or _htr_mode
    if mode == "on":
        return True
    if mode == "off":
        return False
    return _htr_accelerator


def launch_count() -> int:
    """Cumulative device `hash_pairs` dispatches issued by this module
    (the launch-count invariant is asserted by diffing this around a
    flush)."""
    return _launch_count


def pad_pow2_pairs(n: int) -> int:
    """Size class for an n-pair batch: next power of two >= max(n, 8)."""
    n = max(n, _MIN_PAIR_CLASS)
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def _device_level(
    data: np.ndarray, steps: dict | None = None, bufs: _LevelBuffers | None = None
) -> np.ndarray:
    """One merkle level on the device: (2N, 32) uint8 -> (N, 32) uint8,
    launched at a power-of-two pair size class. The pad pairs after the
    N are whatever bytes the class's launch buffer holds: their digests
    are computed and discarded, so any initialized bytes will do, and
    padding never manufactures a new compile shape. A flush hands in
    `bufs` with the N pairs already gathered into `bufs.pairs[:N]`
    (`data` is that view) and gets back a view of `bufs.roots`, valid
    until the buffers go back to the pool; without it the pairs are
    copied into the pool's buffers and the roots come back as a fresh
    array. `steps` is the collector's tally of a flush (see
    `DirtyCollector.steps`); without one the step seconds go to the
    `merkle_level` ledger entry's `phases`."""
    global _launch_count
    from lodestar_tpu.ops import sha256 as ops

    n = data.shape[0] // 2
    size = pad_pow2_pairs(n)
    own = bufs is None
    if own:
        with telemetry.phase("htr.gather", into=steps):
            bufs, _ = _take_buffers(size)
            bufs.pairs[:n] = data.reshape(n, 64)
    _launch_count += 1
    m = _htr_metrics
    if m is not None:
        # counted HERE so hash_level dispatches (batch_container_roots
        # levels) and collector flushes feed the same launches metric
        m.launches.inc()
    # launch telemetry at the same dispatch site as the counter: one
    # record per padded merkle_level launch, size class = the padded
    # pair count (the compiled program's shape bucket)
    with telemetry.launch("merkle_level", size):
        with telemetry.phase("htr.gather", into=steps):
            # the program reads big-endian words: swap the pairs in place
            words = bufs.pairs.view(np.uint32)
            words[:n].byteswap(inplace=True)
        with telemetry.phase("htr.device", into=steps):
            # np.asarray waits for the program, so the buffer is free
            # again; the fetched words may be a read-only view of the
            # program's output, and nothing writes into them
            out = np.asarray(ops.merkle_level(words.reshape(2 * size, 8)))
    with telemetry.phase("htr.scatter", into=steps):
        bufs.roots.view(">u4")[:n] = out[:n]  # back to bytes in one pass
        del out
    roots = bufs.roots[:n]
    if own:
        roots = roots.copy()
        _give_back(bufs)
    return roots


def note_fallback(err: Exception, where: str = "flush") -> None:
    """Count + warn an HTR degradation, labeled by leg: "flush" =
    device error degraded to the CPU level hasher, "tracker" = a
    tracker bug degraded to the value path (a different failure class
    with a different remedy — the label keeps device-fault alerts from
    firing on logic bugs). The caller is responsible for actually
    recomputing on the fallback path."""
    m = _htr_metrics
    if m is not None:
        m.fallbacks.labels(where).inc()
    from lodestar_tpu.logger import get_logger

    get_logger(name="lodestar.ssz-htr").warn(
        "device hashTreeRoot failed, recomputing on the CPU path",
        {"where": where, "error": str(err)[:120]},
    )


def hash_level(data: np.ndarray) -> np.ndarray:
    """One merkle level through the shared backend switch: the device
    kernel (padded size classes) when HTR placement is active AND the
    level is big enough to beat a dispatch round trip — the same
    `DEVICE_MIN_PAIRS` asymmetry `ssz.hash` applies; small levels stay
    on the host hasher regardless of mode. `ssz.batch` routes its
    internal levels here so list merkleization and the dirty collector
    share one backend selection; device errors degrade to the host
    hasher (counted)."""
    from .hash import hash_nodes

    if data.shape[0] // 2 >= _min_flush_pairs() and device_htr_active():
        try:
            return _device_level(data)
        except Exception as e:
            note_fallback(e)
            # degrade to the STRICT host hasher: hash_nodes would
            # re-dispatch any >=DEVICE_MIN_PAIRS level to the same
            # broken device and the error would escape the chain
            return hash_nodes_cpu(data)
    return hash_nodes(data)


class _StackJob:
    """A retained level stack (power-of-two row counts, leaf level first)
    plus the dirty leaf rows whose ancestor paths must re-hash. The
    collector owns writing levels[k>=1]; level 0 was already updated by
    the caller (leaf chunks are inputs, not outputs)."""

    __slots__ = ("levels", "dirty")

    def __init__(self, levels: list[np.ndarray], dirty: np.ndarray):
        self.levels = levels  # guarded by: flush-thread (jobs are built and flushed on one thread per root call)
        self.dirty = np.asarray(dirty, dtype=np.int64)  # guarded by: flush-thread (same confinement as levels)


class _NodeJob:
    """Unhashed `ssz.tree.Node`s grouped by dirty-subgraph height (the
    grouping `tree.compute_root` computes): height h nodes hash in
    launch h, after every dirty child (height < h) has its root."""

    __slots__ = ("groups",)

    def __init__(self, groups: dict[int, list]):
        self.groups = groups  # guarded by: flush-thread (jobs are built and flushed on one thread per root call)


class DirtyCollector:
    """Collects dirty subtrees from any number of sources (tree-view
    node walks, state-tracker level stacks) and flushes them with ONE
    `hash_pairs` dispatch per tree level.

    Lifecycle: a collector instance is built, fed, flushed, and read on
    a single thread per hash_tree_root call — instances are never
    shared (the process-global pieces are the mode/metrics above)."""

    def __init__(self) -> None:
        self.stack_jobs: list[_StackJob] = []  # guarded by: flush-thread (per-call instance, single owner)
        self.node_jobs: list[_NodeJob] = []  # guarded by: flush-thread (per-call instance, single owner)
        self.launches = 0  # guarded by: flush-thread (per-call instance, single owner)
        self.levels = 0  # guarded by: flush-thread (per-call instance, single owner)
        self.dirty_chunks = 0  # guarded by: flush-thread (per-call instance, single owner)
        self.buffers_allocated = 0  # guarded by: flush-thread (per-call instance, single owner)
        self.backend = "cpu"  # guarded by: flush-thread (per-call instance, single owner)
        # seconds by FLUSH_STEPS name while launch telemetry is active, else empty
        self.steps: dict[str, float] = {}  # guarded by: flush-thread (per-call instance, single owner)

    # -- feeding ---------------------------------------------------------------

    def add_stack_job(self, levels: list[np.ndarray], dirty: Iterable[int]) -> None:
        """A retained level stack (each level half the rows of the one
        below) and its dirty leaf rows, as an array or any iterable of
        ints, in any order, repeats allowed."""
        with telemetry.phase("htr.index", into=self.steps):
            if isinstance(dirty, np.ndarray):
                dirty = np.asarray(dirty, dtype=np.int64)
            else:
                dirty = np.fromiter(dirty, dtype=np.int64)
            dirty = np.unique(dirty)
        if dirty.size == 0:
            return
        if dirty[0] < 0 or dirty[-1] >= levels[0].shape[0]:
            raise IndexError(f"dirty leaves {dirty[0]}..{dirty[-1]} outside {levels[0].shape[0]} rows")
        self.dirty_chunks += int(dirty.size)
        self.stack_jobs.append(_StackJob(levels, dirty))

    def add_node_job(self, groups: dict[int, list], dirty_chunks: int | None = None) -> None:
        if not groups:
            return
        # exact mutated-chunk count when the caller tracked it (the tree
        # views' dirty-gindex sets); else estimated from the height-1
        # pair inputs of the unhashed frontier
        self.dirty_chunks += (
            dirty_chunks if dirty_chunks is not None else 2 * len(groups.get(1, ()))
        )
        self.node_jobs.append(_NodeJob(groups))

    # -- flushing --------------------------------------------------------------

    def _max_level(self) -> int:
        lv = 0
        for j in self.stack_jobs:
            lv = max(lv, len(j.levels) - 1)
        for j in self.node_jobs:
            if j.groups:
                lv = max(lv, max(j.groups))
        return lv

    def _flush_with(self, level_fn, count_launches: bool) -> None:
        """Re-hash every dirty path bottom-up, one `level_fn` call per
        level. Idempotent: every row/node written is a pure function of
        the level below, so a degraded re-run recomputes identical
        values from the pristine leaf inputs. `count_launches` is True
        only on the device pass — `launches` means DEVICE dispatches,
        and a CPU fallback storm must read as zero launches, not as a
        healthy tree-depth count."""
        max_level = self._max_level()
        self.levels = max_level
        steps = self.steps
        # per stack job: dirty node indices at the current level, sorted
        frontiers = [j.dirty for j in self.stack_jobs]
        for lvl in range(1, max_level + 1):
            # (levels of a stack job, parents) | (None, nodes), in launch order
            sinks: list[tuple] = []
            with telemetry.phase("htr.index", into=steps):
                for ji, job in enumerate(self.stack_jobs):
                    f = frontiers[ji]
                    if lvl >= len(job.levels) or f.size == 0:
                        continue
                    p = f >> 1
                    parents = p[np.r_[True, p[1:] != p[:-1]]]  # f is sorted: drop repeats
                    sinks.append((job.levels, parents))
                    frontiers[ji] = parents
                for job in self.node_jobs:
                    nodes = job.groups.get(lvl)
                    if nodes:
                        sinks.append((None, nodes))
                n = sum(len(rows) for _, rows in sinks)
            if n == 0:
                continue
            with telemetry.phase("htr.gather", into=steps):
                bufs, allocated = _take_buffers(pad_pow2_pairs(n))
                self.buffers_allocated += 2 * allocated
                off = 0
                for levels, rows in sinks:
                    out = bufs.pairs[off : off + len(rows)]
                    if levels is not None:
                        # a pair is two adjacent rows: one 64-byte row of the level below
                        np.take(levels[lvl - 1].reshape(-1, 64), rows, axis=0, out=out, mode="clip")
                    else:
                        out[:] = np.frombuffer(
                            b"".join(r for nd in rows for r in (nd.left._root, nd.right._root)),
                            dtype=np.uint8,
                        ).reshape(-1, 64)
                    off += len(rows)
                data = bufs.pairs[:n].reshape(2 * n, 32)
            # the size asymmetry applies per level even on the device
            # pass: a sparse flush's 1-2-pair tail levels are far
            # cheaper as host digests than as padded dispatches (the
            # invariant is "at most one DEVICE launch per level", so
            # host-hashing a tiny level only tightens it)
            if count_launches and n >= _min_flush_pairs():
                roots = level_fn(data, bufs=bufs)  # `_device_level` names its own steps
                self.launches += 1
            else:
                with telemetry.phase("htr.host_hash", into=steps):
                    roots = hash_nodes_cpu(data) if count_launches else level_fn(data)
            with telemetry.phase("htr.scatter", into=steps):
                off = 0
                for levels, rows in sinks:
                    if levels is not None:
                        _rows(levels[lvl], 32)[rows] = _rows(roots[off : off + len(rows)], 32)
                    else:
                        for i, nd in enumerate(rows):
                            nd._root = roots[off + i].tobytes()
                    off += len(rows)
            _give_back(bufs)  # a level that raised keeps its buffers out of the pool

    def flush(self) -> dict:
        """One collector flush: at most one `hash_pairs` dispatch per
        tree level across EVERY job. Device errors degrade the whole
        flush to the CPU level hasher (recomputed from leaf inputs —
        partially-grafted device roots are overwritten, never trusted).
        Returns the flush stats for span/metric attribution; `steps` is
        where `seconds` (and `add_stack_job` before it) went, by
        FLUSH_STEPS name, and empty while launch telemetry is inactive;
        `buffers_allocated` counts the launch buffers the flush could not
        take from the pool (0 once every size class it uses is there)."""
        t0 = time.monotonic()
        device = device_htr_active()
        self.launches = 0
        self.buffers_allocated = 0
        if telemetry.launch_telemetry_active():
            for name in FLUSH_STEPS:
                self.steps.setdefault(name, 0.0)
        if device:
            self.backend = "device"
            try:
                self._flush_with(
                    functools.partial(_device_level, steps=self.steps), count_launches=True
                )
            except Exception as e:
                note_fallback(e)
                self.backend = "cpu"
                self.launches = 0
                self._flush_with(hash_nodes_cpu, count_launches=False)
        else:
            self.backend = "cpu"
            self._flush_with(hash_nodes_cpu, count_launches=False)
        stats = {
            "backend": self.backend,
            "levels": self.levels,
            "launches": self.launches,
            "dirty_chunks": self.dirty_chunks,
            "buffers_allocated": self.buffers_allocated,
            "seconds": time.monotonic() - t0,
            "steps": self.steps,
        }
        m = _htr_metrics
        if m is not None:
            # launches are counted at the dispatch site (_device_level)
            # so hash_level and collector dispatches share one metric
            m.flushes.labels(self.backend).inc()
            m.dirty_chunks.inc(self.dirty_chunks)
            m.seconds.labels(self.backend).observe(stats["seconds"])
        return stats


def compute_root_node(node, dirty_hint: int | None = None) -> bytes:
    """Root of an `ssz.tree.Node`, flushing its dirty subtrees through
    a collector (one launch per level). `dirty_hint` is the caller's
    mutated-chunk count (the tree views' dirty-gindex tracking) and
    feeds the `lodestar_ssz_htr_dirty_chunks_total` attribution. The
    device/CPU choice and the error degradation live in
    `DirtyCollector.flush`."""
    if node._root is not None:
        return node._root
    # lazy import: tree.py lazily imports this module for routing, so
    # the shared walk is pulled at call time to keep imports one-way
    from .tree import collect_unhashed

    coll = DirtyCollector()
    coll.add_node_job(collect_unhashed(node), dirty_chunks=dirty_hint)
    coll.flush()
    return node._root
