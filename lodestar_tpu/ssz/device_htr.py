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
JAX cache).

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
#: dirty set, gathering pairs into launch layout, the device call and
#: the wait for it, writing roots back, and levels hashed on the host
FLUSH_STEPS = ("htr.index", "htr.gather", "htr.device", "htr.scatter", "htr.host_hash")

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


def _device_level(data: np.ndarray, steps: dict | None = None) -> np.ndarray:
    """One merkle level on the device: (2N, 32) uint8 -> (N, 32) uint8,
    padded to a power-of-two pair size class (pad pairs repeat pair 0 so
    padding never manufactures new compile shapes or NaN-style hazards —
    their digests are computed and discarded). `steps` is the collector's
    tally of a flush (see `DirtyCollector.steps`); without one the step
    seconds go to the `merkle_level` ledger entry's `phases`."""
    global _launch_count
    from lodestar_tpu.ops import sha256 as ops

    n = data.shape[0] // 2
    size = pad_pow2_pairs(n)
    if size != n:
        with telemetry.phase("htr.gather", into=steps):
            padded = np.empty((2 * size, 32), dtype=np.uint8)
            padded[: 2 * n] = data
            padded[2 * n :] = np.tile(data[:2], (size - n, 1))
            data = padded
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
            words = ops.words_from_bytes(data.tobytes())
        with telemetry.phase("htr.device", into=steps):
            out = np.asarray(ops.merkle_level(words))
    with telemetry.phase("htr.scatter", into=steps):
        roots = np.frombuffer(ops.bytes_from_words(out), dtype=np.uint8).reshape(-1, 32)
    return roots[:n]


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
        self.backend = "cpu"  # guarded by: flush-thread (per-call instance, single owner)
        # seconds by FLUSH_STEPS name while launch telemetry is active, else empty
        self.steps: dict[str, float] = {}  # guarded by: flush-thread (per-call instance, single owner)

    # -- feeding ---------------------------------------------------------------

    def add_stack_job(self, levels: list[np.ndarray], dirty: Iterable[int]) -> None:
        with telemetry.phase("htr.index", into=self.steps):
            dirty = np.asarray(sorted(set(int(i) for i in dirty)), dtype=np.int64)
        if dirty.size == 0:
            return
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
        # per stack job: dirty node indices at the current level
        frontiers = [j.dirty for j in self.stack_jobs]
        for lvl in range(1, max_level + 1):
            sinks: list[tuple] = []  # ("stack", job, parents) | ("node", nodes)
            with telemetry.phase("htr.index", into=steps):
                picks: list[tuple[np.ndarray, np.ndarray]] = []  # (level below, its dirty pairs' rows)
                for ji, job in enumerate(self.stack_jobs):
                    if lvl >= len(job.levels) or frontiers[ji].size == 0:
                        continue
                    parents = np.unique(frontiers[ji] >> 1)
                    pair_idx = np.empty(2 * parents.size, dtype=np.int64)
                    pair_idx[0::2] = 2 * parents
                    pair_idx[1::2] = 2 * parents + 1
                    picks.append((job.levels[lvl - 1], pair_idx))
                    sinks.append(("stack", ji, parents))
                    frontiers[ji] = parents
            with telemetry.phase("htr.gather", into=steps):
                chunks = [below[pair_idx] for below, pair_idx in picks]
                for job in self.node_jobs:
                    nodes = job.groups.get(lvl)
                    if not nodes:
                        continue
                    data = np.empty((2 * len(nodes), 32), dtype=np.uint8)
                    for i, n in enumerate(nodes):
                        data[2 * i] = np.frombuffer(n.left._root, dtype=np.uint8)
                        data[2 * i + 1] = np.frombuffer(n.right._root, dtype=np.uint8)
                    chunks.append(data)
                    sinks.append(("node", nodes))
                if not chunks:
                    continue
                data = chunks[0] if len(chunks) == 1 else np.concatenate(chunks, axis=0)
            # the size asymmetry applies per level even on the device
            # pass: a sparse flush's 1-2-pair tail levels are far
            # cheaper as host digests than as padded dispatches (the
            # invariant is "at most one DEVICE launch per level", so
            # host-hashing a tiny level only tightens it)
            if count_launches and data.shape[0] // 2 >= _min_flush_pairs():
                roots = level_fn(data)  # `_device_level` names its own steps
                self.launches += 1
            else:
                with telemetry.phase("htr.host_hash", into=steps):
                    roots = hash_nodes_cpu(data) if count_launches else level_fn(data)
            with telemetry.phase("htr.scatter", into=steps):
                off = 0
                for sink in sinks:
                    if sink[0] == "stack":
                        _, ji, parents = sink
                        self.stack_jobs[ji].levels[lvl][parents] = roots[off : off + parents.size]
                        off += parents.size
                    else:
                        _, nodes = sink
                        for i, n in enumerate(nodes):
                            n._root = roots[off + i].tobytes()
                        off += len(nodes)

    def flush(self) -> dict:
        """One collector flush: at most one `hash_pairs` dispatch per
        tree level across EVERY job. Device errors degrade the whole
        flush to the CPU level hasher (recomputed from leaf inputs —
        partially-grafted device roots are overwritten, never trusted).
        Returns the flush stats for span/metric attribution; `steps` is
        where `seconds` (and `add_stack_job` before it) went, by
        FLUSH_STEPS name, and empty while launch telemetry is inactive."""
        t0 = time.monotonic()
        device = device_htr_active()
        self.launches = 0
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
