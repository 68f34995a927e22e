"""Device BLS verifier pool: buffering, chunking, retry, fail-closed.

Asyncio re-design of `BlsMultiThreadWorkerPool`
(reference `beacon-node/src/chain/bls/multithread/index.ts:103`) with the
N-worker thread pool replaced by one device pipeline:

* **Buffering** (`index.ts:277-291`): batchable jobs accumulate up to
  MAX_BUFFER_WAIT_MS (100 ms) or MAX_BUFFERED_SIGS (32), then flush as one
  batch — gossip bursts amortize into single device launches.
* **Chunking** (`index.ts:34-39`): big arrays (sync submits ~8k sets) are
  split ≤ MAX_SIGNATURE_SETS_PER_JOB (128) per job; jobs queue
  independently so a long sync batch never head-of-line-blocks gossip.
* **Batch-then-retry** (`worker.ts:52-96`): batchable chunks ≥
  BATCHABLE_MIN_PER_CHUNK are RLC-batch-verified; an invalid batch is
  re-verified per-job so one bad signature can't poison its neighbors.
  `batch_retries` / `batch_sigs_success` counters keep the reference's
  metric semantics.
* **Fail-closed** (`index.ts:386-393` analogue): any backend error rejects
  the job with the error — it never resolves True. Callers treat rejection
  as invalid-block/peer-downscore, exactly like the reference.
* **Multi-job launches** (`_launch_units`): a package's non-batchable
  jobs of the 128 size class (more than 64 sets: the two halves of a
  block's 131 sets, a sync committee's four jobs) ride ONE launch, up
  to MAX_PACKAGE_SETS // MAX_SIGNATURE_SETS_PER_JOB = 4 a launch, a slot
  of the program's rows and a verdict each — on one chip the reason
  for the reference's cut (a job a core) is gone, and what a launch
  costs before its first row (a dispatch, a round trip, the serial
  depth of its chains) is paid once. A slot is as long as
  `telemetry.group_slot_rows` says for the jobs that ride: 72 rows for
  a block's halves of 66 and 65 sets, (144, 2) and (288, 4); 128 rows
  for jobs of 73 sets and more, (256, 2) and (512, 4). Smaller jobs
  and batchable jobs keep their own launches.
* **Mesh lanes** (`chain/bls/mesh.py`): the pool serves a `VerifierMesh`
  of per-device launch lanes. One dispatcher waits for a free lane,
  dequeues through the shared priority queue, and places the package on
  the least-occupied free chip, whatever its class. On a TPU that is
  the whole policy: a bulk range-sync/backfill package is four jobs in
  one (288, 4) launch on one lane, its parse staged, and N lanes run N
  such launches side by side — four lanes are fed the way one lane is
  fed, and one host thread's parses set their pace. Only a mesh built
  with the collective (the lanes of the split schedule: a forced CPU
  mesh) sends a bulk job big enough to amortize it data-parallel
  (`verify_signature_sets_sharded`) across the idle chips.
  With a single visible device the mesh is one lane and the launch
  schedule is bit-identical to the pre-mesh pool (regression-tested).
* **Staged prep** (`_staging`): where it can be hidden, a package's
  prep — under the single launch the host byte parse — runs on another
  thread, launch unit by launch unit, while the device runs the unit
  before: the mesh has a sibling lane to stage prep on, or — one lane —
  the lanes say their staged prep touches no device. One lane under
  the split schedule keeps the exact pre-pipeline launch schedule:
  staged prep there is device launches on the die that verifies. A
  package that finds every lane free and is one launch unit has no
  launch to hide its prep behind and takes the inline road; with a
  sibling lane at work it is staged, so that the parses of the lanes'
  packages run one after another in launch order and not side by side
  under one GIL, all ending late together. Where the dispatcher then
  waits for a parse with a lane free, that is `bls.parse_wait`. The dispatcher
  takes the next package while the lanes are busy only once the queue
  holds all of it (nothing that arrives later could join it, so no
  launch's composition changes); an urgent arrival still overtakes the
  package taken ahead.
* **The registry table** (`pubkey_table`, `chain/bls/pubkey_table.py`):
  the pool owns the validator registry's pubkeys, one copy on every
  lane's chip where the lanes run the single launch. A set may name
  its signers by registry index (`IndexedSignatureSet`, the
  reference's aggregate form of ISignatureSet): it is a row like any
  other to everything here (size classes, slots, units, staging), its
  parse is an index row, and its launch gathers and sums its signers
  on the chip. A set with more signers than a launch's index matrix
  has columns, or lanes without the table, takes host aggregation,
  counted in `lodestar_bls_aggregate_fallback_total`.
* **Wedge detection** (`offload/resilience.CircuitBreaker`): each lane
  carries its OWN wedge breaker — consecutive launch errors on a chip
  open it, the dispatcher stops placing work there, and in-flight work
  retries on a sibling lane, so one sick device degrades the pool to an
  (N-1)-chip mesh. Only when EVERY lane is wedged does the pool report
  is_down() and the degradation chain routes around it; after the reset
  delay a wedged lane self-offers again.
* **Admission** (`index.ts:143-149`): can_accept_work() false once
  MAX_JOBS_CAN_ACCEPT_WORK (512) jobs are outstanding — backpressure
  signal for the gossip processor.
* **Scheduling** (`lodestar_tpu/scheduler`): launches dequeue through a
  priority-class queue (gossip block > gossip attestation > API >
  range sync > backfill; stride-weighted-fair + starvation aging)
  instead of FIFO, so a slot-deadline block never queues behind a
  backfill batch. A bulk-class package is ONE launch — the bound on
  how long it can head-of-line-block an arriving urgent job. Device
  launches feed per-lane EWMA occupancy trackers whose mesh aggregate
  backs a graded ACCEPT/SHED_BULK/REJECT admission view the offload
  server ships to clients. `scheduler_enabled=False` restores arrival
  order (the control arm for the saturation tests).

The verify backend is injected as a callable (default: the lanes
`mesh.build_device_mesh` makes of the device model), which keeps the seam
mockable and lets tests drive the retry paths deterministically; passing
an explicit callable pins the pool to a single lane (a mock cannot be
enumerated per device). Tests inject multi-lane topologies via `mesh=`.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import contextlib
import itertools
import threading
import time
from typing import Awaitable, Callable, Sequence

from lodestar_tpu import slo, telemetry, tracing
from lodestar_tpu.crypto.bls.api import IndexedSignatureSet, SignatureSet
from lodestar_tpu.logger import get_logger
from lodestar_tpu.scheduler import (
    BULK_CLASSES,
    AdmissionController,
    PriorityClass,
    PriorityWorkQueue,
)

from .interface import IBlsVerifier, VerifySignatureOpts
from .mesh import (
    LANE_WEDGE_THRESHOLD,
    MESH_MODES,
    SHARD_MIN_SETS_PER_LANE,
    MeshLane,
    PreparedSets,
    VerifierMesh,
    build_device_mesh,
    single_lane_mesh,
)

__all__ = [
    "BlsDeviceVerifierPool",
    "chunkify_maximize_chunk_size",
    "MAX_SIGNATURE_SETS_PER_JOB",
    "MAX_BUFFERED_SIGS",
    "MAX_BUFFER_WAIT_MS",
    "MAX_JOBS_CAN_ACCEPT_WORK",
    "BATCHABLE_MIN_PER_CHUNK",
]

# tuning constants — same values/rationale as the reference (index.ts:30-62)
MAX_SIGNATURE_SETS_PER_JOB = 128
MAX_BUFFERED_SIGS = 32
MAX_BUFFER_WAIT_MS = 100
MAX_JOBS_CAN_ACCEPT_WORK = 512
BATCHABLE_MIN_PER_CHUNK = 16  # worker.ts:11-17
# consecutive backend errors before ONE LANE reports itself wedged —
# the pre-mesh pool-wide threshold carried over per chip. THE value
# lives in mesh.py (LANE_WEDGE_THRESHOLD, shared with the standalone
# offload host); this alias keeps the pre-mesh export name
DEVICE_WEDGE_THRESHOLD = LANE_WEDGE_THRESHOLD
# sets per launch package under the scheduler: a queued attestation
# flood must not coalesce into one giant package that head-of-line
# blocks an arriving gossip block for its whole duration
MAX_PACKAGE_SETS = 4 * MAX_SIGNATURE_SETS_PER_JOB
# jobs of the largest size class that share one launch, a slot each
MAX_GROUP_JOBS = MAX_PACKAGE_SETS // MAX_SIGNATURE_SETS_PER_JOB


def chunkify_maximize_chunk_size(arr: Sequence, max_len: int) -> list[list]:
    """Split into the fewest chunks of size ≤ max_len, sizes as equal as
    possible (reference `multithread/utils.ts` chunkifyMaximizeChunkSize)."""
    if not arr:
        return []
    n_chunks = (len(arr) + max_len - 1) // max_len
    base = len(arr) // n_chunks
    extra = len(arr) % n_chunks
    out, pos = [], 0
    for i in range(n_chunks):
        size = base + (1 if i < extra else 0)
        out.append(list(arr[pos : pos + size]))
        pos += size
    return out


class _Job:
    __slots__ = ("sets", "batchable", "priority", "future", "added_ns", "trace_parent", "slo")

    def __init__(
        self,
        sets: list[SignatureSet],
        batchable: bool,
        priority: PriorityClass = PriorityClass.API,
        slot: int | None = None,
    ):
        self.sets = sets
        self.batchable = batchable
        self.priority = priority
        self.future: asyncio.Future[bool] = asyncio.get_event_loop().create_future()
        # the submitting task's span (None when tracing is off): the
        # executor thread parents its buffer-wait/device-launch spans on
        # it explicitly, since run_in_executor drops contextvars. The
        # clock read rides the same gate — untraced jobs pay nothing
        self.trace_parent = tracing.current()
        self.added_ns = time.monotonic_ns() if self.trace_parent is not None else 0
        # slot-deadline slack ledger (None when the SLO layer is off —
        # the unconfigured path pays one None check per lifecycle edge)
        self.slo = slo.job_begin(priority, slot)


def _groupable(job: _Job) -> bool:
    """A job that may share a launch: non-batchable (it needs a verdict
    of its own) and of the largest size class, so its slot is one the
    job would fill alone; a smaller job keeps its smaller program."""
    return (
        not job.batchable
        and telemetry.size_class_of(len(job.sets)) == MAX_SIGNATURE_SETS_PER_JOB
    )


def _launch_units(package: list[_Job], grouping: bool) -> tuple[list[list[_Job]], list[list[_Job]]]:
    """THE unit boundaries of a package, for the staged prep and the
    launches alike: (chunks, units). `chunks` are the RLC chunks of the
    batchable jobs. `units` cover the non-batchable jobs in queue order:
    one job (its own launch), or — where `grouping` — up to
    MAX_GROUP_JOBS groupable jobs that ride one multi-job launch (a unit
    stands where its first job stood)."""
    chunks = chunkify_maximize_chunk_size(
        [j for j in package if j.batchable], BATCHABLE_MIN_PER_CHUNK
    )
    units: list[list[_Job]] = []
    filling: list[_Job] | None = None
    for j in package:
        if j.batchable:
            continue
        if not (grouping and _groupable(j)):
            units.append([j])
        elif filling is None or len(filling) == MAX_GROUP_JOBS:
            filling = [j]
            units.append(filling)
        else:
            filling.append(j)
    return chunks, units


def _unit_sets(unit: list[_Job]) -> list:
    """What a unit's launch is handed: its job's sets, or for a
    multi-job unit the list of its jobs' sets."""
    return [j.sets for j in unit] if len(unit) > 1 else unit[0].sets


class _OverlapTracker:
    """Wall-clock pipeline accounting: how much of the verify stages'
    busy time had a prep stage in flight — the number behind the
    `prep_verify_overlap_occupancy_pct` bench line (and the tier-1
    overlap test). Count-based interval algebra: every begin/end of
    either stage advances the three accumulators by the elapsed window,
    attributed to whichever stages were active during it."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._prep_n = 0  # guarded by: _lock
        self._verify_n = 0  # guarded by: _lock
        self._last_ns = 0  # guarded by: _lock
        self._prep_ns = 0  # guarded by: _lock
        self._verify_ns = 0  # guarded by: _lock
        self._overlap_ns = 0  # guarded by: _lock

    def _transition(self, dprep: int, dverify: int) -> None:
        with self._lock:
            now = time.monotonic_ns()
            if self._last_ns:
                dt = now - self._last_ns
                if self._prep_n:
                    self._prep_ns += dt
                if self._verify_n:
                    self._verify_ns += dt
                if self._prep_n and self._verify_n:
                    self._overlap_ns += dt
            self._last_ns = now
            self._prep_n += dprep
            self._verify_n += dverify

    @contextlib.contextmanager
    def prep(self):
        self._transition(1, 0)
        try:
            yield
        finally:
            self._transition(-1, 0)

    @contextlib.contextmanager
    def verify(self):
        self._transition(0, 1)
        try:
            yield
        finally:
            self._transition(0, -1)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "prep_ns": self._prep_ns,
                "verify_ns": self._verify_ns,
                "overlap_ns": self._overlap_ns,
            }


class _PrepUnit:
    """One launch unit of a staged package: the jobs it covers, their
    flattened sets (a multi-job unit: the list of its jobs' sets), and
    `prepared`, which the prep thread resolves to the unit's prep
    outcome (PreparedSets: inputs / reject / error) when it gets there."""

    __slots__ = ("jobs", "sets", "grouped", "prepared")

    def __init__(self, jobs: list[_Job], sets: list, grouped: bool = False):
        self.jobs = jobs
        self.sets = sets
        self.grouped = grouped  # a multi-job launch: `sets` is a list of jobs' sets
        self.prepared: concurrent.futures.Future = concurrent.futures.Future()
        # the prep thread always delivers: a dispatcher cancelled while
        # it waits for the first unit must not cancel the hand-over
        self.prepared.set_running_or_notify_cancel()


class _PreppedPackage:
    """A staged package's launch units, in the order the verify stage
    launches them; each is handed over as its prep is ready."""

    __slots__ = ("chunks", "units")

    def __init__(self, chunks: list[_PrepUnit], units: list[_PrepUnit]):
        self.chunks = chunks  # batchable RLC chunks
        self.units = units  # non-batchable jobs, one or a group a unit


class BlsDeviceVerifierPool(IBlsVerifier):
    def __init__(
        self,
        verify_fn: Callable[[list[SignatureSet]], bool] | None = None,
        *,
        buffer_wait_ms: float = MAX_BUFFER_WAIT_MS,
        max_buffered_sigs: int = MAX_BUFFERED_SIGS,
        scheduler_enabled: bool = True,
        aging_ms: float | None = None,
        sched_metrics=None,
        mesh: VerifierMesh | None = None,
        mesh_mode: str | None = None,
        prep_fn: Callable | None = None,
        pipeline_metrics=None,
    ) -> None:
        self._buffer_wait_ms = buffer_wait_ms
        self._max_buffered_sigs = max_buffered_sigs
        self._log = get_logger(name="lodestar.bls-pool")

        # mesh construction: an injected mesh wins (tests/topologies);
        # an explicit verify_fn is ONE lane that only speaks sets (a
        # mock can't be enumerated per device, and mesh_launch re-preps
        # inline through it); otherwise the lanes are the device
        # model's, from the one place they are made
        if mesh is not None:
            self.mesh = mesh
        elif mesh_mode is not None and mesh_mode not in MESH_MODES:
            raise ValueError(f"bls_mesh must be one of {MESH_MODES}, got {mesh_mode!r}")
        elif verify_fn is not None:
            self.mesh = single_lane_mesh(verify_fn, wedge_threshold=DEVICE_WEDGE_THRESHOLD)
        else:
            from .pubkey_table import PubkeyTable

            self.mesh = build_device_mesh(
                mesh_mode or "off", wedge_threshold=DEVICE_WEDGE_THRESHOLD, table=PubkeyTable()
            )
        # the registry's pubkeys, which indexed sets are resolved from:
        # the lanes' own (None where they speak pubkey bytes only). Its
        # owner fills it: node init from the anchor state, the chain on
        # every deposit
        self.pubkey_table = self.mesh.table

        # prep→verify double buffering: whether packages' prep is
        # staged. Staging requires lanes that can CONSUME staged inputs
        # (or an injected prep_fn): a mesh of plain verify callables
        # would pay real prep for inputs nobody uses — and a prep-stage
        # structural reject would overrule a backend that never saw the
        # sets. And it must have somewhere to hide: a sibling lane to
        # stage on or — one lane — staged prep that touches no device.
        # All facts the lanes were built with, so read once
        self._staging = (
            prep_fn is not None
            or all(lane.verify_prepared_fn is not None for lane in self.mesh.lanes)
        ) and (len(self.mesh) > 1 or self.mesh.staged_prep_is_host_only())
        self._prep_fn = prep_fn if prep_fn is not None else self._default_prep_fn
        self._overlap = _OverlapTracker()
        self._staged_packages = 0  # guarded by: advisory-only (monotonic count, prep threads under the GIL)
        if pipeline_metrics is not None:
            # scrape-time evaluation (the occupancy-gauge pattern): the
            # previously process-trapped pipeline_stats() numbers become
            # live lodestar_bls_pipeline_* gauges — overlap occupancy,
            # staged packages, and the prep/verify busy accumulators
            pipeline_metrics.overlap_occupancy_pct.set_function(
                lambda: self.pipeline_stats()["overlap_occupancy_pct"]
            )
            pipeline_metrics.staged_packages.set_function(
                lambda: self._staged_packages
            )
            pipeline_metrics.prep_seconds.set_function(
                lambda: self._overlap.snapshot()["prep_ns"] / 1e9
            )
            pipeline_metrics.verify_seconds.set_function(
                lambda: self._overlap.snapshot()["verify_ns"] / 1e9
            )

        self.scheduler_enabled = scheduler_enabled
        self._sched_metrics = sched_metrics
        queue_kwargs = {"fifo": not scheduler_enabled, "metrics": sched_metrics}
        if aging_ms is not None:
            queue_kwargs["aging_ms"] = aging_ms
        self._jobs: PriorityWorkQueue = PriorityWorkQueue(**queue_kwargs)
        # the mesh IS the occupancy view: mean busy fraction over
        # available lanes (one lane -> exactly the pre-mesh tracker)
        self.occupancy = self.mesh
        self.admission = AdmissionController(
            self.mesh,
            depth_fn=lambda: self._outstanding,
            shed_bulk_depth=MAX_JOBS_CAN_ACCEPT_WORK // 2,
            reject_depth=MAX_JOBS_CAN_ACCEPT_WORK,
            can_accept=lambda: not self._closed,
        )
        self._outstanding = 0  # guarded by: event-loop (writers; scrape-time depth_fn readers tolerate a stale int)
        if sched_metrics is not None:
            # scrape-time evaluation: the EWMA decays on read, so an idle
            # pool reports decaying occupancy instead of freezing at the
            # last launch's value
            sched_metrics.occupancy_permille.set_function(
                lambda: self.mesh.occupancy_permille()
            )
            sched_metrics.admission_state.set_function(lambda: int(self.admission.state()))
            sched_metrics.mesh_lanes.set_function(lambda: len(self.mesh.available()))
            for lane in self.mesh.lanes:
                sched_metrics.lane_occupancy.labels(lane.label).set_function(
                    lambda lane=lane: lane.occupancy.occupancy_permille()
                )
        self._buffered: list[_Job] = []  # guarded by: event-loop (single-threaded)
        self._buffered_sigs = 0  # guarded by: event-loop (single-threaded)
        self._buffer_timer: asyncio.TimerHandle | None = None  # guarded by: event-loop (single-threaded)
        self._closed = False  # guarded by: event-loop (one-way flag; executor readers see it at worst one package late)
        self._runner: asyncio.Task | None = None  # guarded by: event-loop (single-threaded)
        self._launch_tasks: set[asyncio.Task] = set()  # guarded by: event-loop (single-threaded)
        # what the parked dispatcher re-reads its state for: a lane
        # freed, a job reached the queue, the pool closed
        self._wake = asyncio.Event()  # guarded by: event-loop (single-threaded)
        self._wake.set()

        # metric counters (reference blsThreadPool.* taxonomy)
        self.metrics = {  # guarded by: advisory-only (incremented from executor threads under the GIL; scrapers read stale-by-one)
            "jobs_started": 0,
            "sig_sets_started": 0,
            "batch_retries": 0,
            "batch_sigs_success": 0,
            "errors": 0,
            "sharded_launches": 0,
            "sharded_fallbacks": 0,
            # staged prep: its wall time, and the part of it during which
            # a launch was in flight (`_OverlapTracker`, written as each
            # unit's prep ends); inline prep is in neither
            "parse_ns": 0,
            "parse_hidden_ns": 0,
            # what the dispatcher waited for a staged parse with a lane free
            "parse_wait_ns": 0,
            # sets that name their signers by registry index, and the
            # signers they name (padding not counted)
            "indexed_rows_started": 0,
            "aggregate_points_started": 0,
        }

    @property
    def device_breaker(self):
        """Back-compat alias: the first lane's wedge breaker (THE wedge
        breaker on a single-lane pool)."""
        return self.mesh.lanes[0].breaker

    # -- IBlsVerifier ---------------------------------------------------------

    @property
    def takes_indexed_sets(self) -> bool:
        """Whether the lanes sum a set's signers from the registry table
        on their chip: the producers then name signers by index
        (`IndexedSignatureSet`) instead of summing pubkeys on the host."""
        return self.pubkey_table is not None and self.pubkey_table.on_device

    def is_down(self) -> bool:
        """Every lane wedged (breaker open) or closed — the degradation
        chain routes around the pool; mere queue saturation is NOT down
        (that's backpressure, handled by can_accept_work). One wedged
        chip out of N is NOT down: the mesh serves on the rest."""
        return self._closed or not self.mesh.available()

    def can_accept_work(self) -> bool:
        return not self.is_down() and self._outstanding < MAX_JOBS_CAN_ACCEPT_WORK

    async def verify_signature_sets(
        self, sets: list[SignatureSet], opts: VerifySignatureOpts | None = None
    ) -> bool:
        if self._closed:
            raise RuntimeError("verifier pool is closed")
        if not sets:
            raise ValueError("empty signature-set array")
        opts = opts or VerifySignatureOpts()

        if opts.verify_on_main_thread:
            # inline path for cheap time-critical single sets
            from lodestar_tpu.crypto.bls.api import verify_signature_sets

            table = self.pubkey_table
            return verify_signature_sets(sets, table.pubkey_at if table is not None else None)

        priority = (
            PriorityClass(opts.priority) if opts.priority is not None else PriorityClass.API
        )
        self._ensure_runner()
        jobs = [
            self._enqueue(_Job(chunk, opts.batchable, priority, opts.slot))
            for chunk in chunkify_maximize_chunk_size(sets, MAX_SIGNATURE_SETS_PER_JOB)
        ]
        results = await asyncio.gather(*(j.future for j in jobs))
        return all(results)

    async def close(self) -> None:
        self._closed = True
        if self._buffer_timer is not None:
            self._buffer_timer.cancel()
        err = asyncio.CancelledError("bls pool closed")
        for job in self._buffered:
            if not job.future.done():
                job.future.set_exception(err)
        self._buffered.clear()
        for job, _cls, _waited in self._jobs.drain():
            if not job.future.done():
                job.future.set_exception(err)
        self._wake.set()  # unblock a dispatcher parked on a busy mesh
        if self._runner is not None:
            self._runner.cancel()
            try:
                await self._runner
            except asyncio.CancelledError:
                pass
            self._runner = None
        # in-flight launches: cancel the awaiting tasks (the executor
        # threads run to completion and resolve futures thread-safe,
        # exactly like the pre-mesh abandoned run_in_executor)
        for t in list(self._launch_tasks):
            t.cancel()
        if self._launch_tasks:
            await asyncio.gather(*self._launch_tasks, return_exceptions=True)
        self._launch_tasks.clear()

    # -- queueing -------------------------------------------------------------

    def _ensure_runner(self) -> None:
        if self._runner is None or self._runner.done():
            self._runner = asyncio.get_event_loop().create_task(self._run_jobs())

    def _enqueue(self, job: _Job) -> _Job:
        self._outstanding += 1
        job.future.add_done_callback(lambda f, j=job: self._on_job_done(j, f))
        if job.batchable:
            self._buffered.append(job)
            self._buffered_sigs += len(job.sets)
            if self._buffered_sigs > self._max_buffered_sigs:
                self._flush_buffer()
            elif self._buffer_timer is None:
                loop = asyncio.get_event_loop()
                self._buffer_timer = loop.call_later(
                    self._buffer_wait_ms / 1000.0, self._flush_buffer
                )
        else:
            self._jobs.put_nowait(job, job.priority)
            self._wake.set()
        return job

    def _dec_outstanding(self) -> None:
        self._outstanding -= 1

    def _on_job_done(self, job: _Job, f: "asyncio.Future[bool]") -> None:
        """The job future resolves exactly once — however many batch
        retries the verdict took — so this callback is the one place a
        per-job SLO verdict can't double-count. Cancellation (shutdown)
        is not a deadline miss and records nothing."""
        self._dec_outstanding()
        if job.slo is not None and not f.cancelled():
            ok = f.exception() is None and f.result() is True
            slo.job_verdict(job.slo, ok)

    def _flush_buffer(self) -> None:
        if self._buffer_timer is not None:
            self._buffer_timer.cancel()
            self._buffer_timer = None
        jobs, self._buffered = self._buffered, []
        self._buffered_sigs = 0
        for job in jobs:
            slo.job_flushed(job.slo)
            self._jobs.put_nowait(job, job.priority)
        self._wake.set()

    # -- execution ------------------------------------------------------------

    def _record_sched_dequeue(self, job: _Job, cls: PriorityClass, waited_ns: int) -> None:
        """`sched_queue_wait` span per traced job: enqueue -> dequeue —
        the number the saturation acceptance test bounds."""
        slo.job_dequeued(job.slo, waited_ns)
        if job.trace_parent is not None:
            end_ns = time.monotonic_ns()
            tracing.record(
                job.trace_parent,
                "sched_queue_wait",
                end_ns - waited_ns,
                end_ns,
                {"class": cls.label, "sets": len(job.sets)},
            )

    # -- lane placement --------------------------------------------------------

    def _free_lanes(self) -> list[MeshLane]:
        """Lanes eligible for a new package. While ANY healthy lane
        exists, only healthy free lanes count — a busy-but-healthy mesh
        makes the dispatcher WAIT rather than dispatch onto an idle
        wedged chip (which would feed a launch storm into the hung
        driver the breaker just isolated). Only when every lane is
        wedged does the dispatcher place work on a sick chip: it fails
        fast, tripping futures with the error — the pre-mesh
        wedged-pool behavior, and how a wedged breaker earns its
        half-open retrial."""
        avail = self.mesh.available()
        if avail:
            return [lane for lane in avail if lane.inflight == 0]
        return [lane for lane in self.mesh.lanes if lane.inflight == 0]

    async def _wait_free_lane(self) -> None:
        """Park the dispatcher until some lane can take a package."""
        while not self._free_lanes():
            self._wake.clear()
            await self._wake.wait()

    def _pick_placement(
        self, cls: PriorityClass, package: list[_Job], free: list[MeshLane]
    ) -> tuple[str, list[MeshLane]]:
        """("sharded", lanes) for a bulk package big enough to amortize
        a collective launch over >=2 idle healthy chips; otherwise
        ("single", [least-occupied free lane]). `free` is non-empty by
        contract (the dispatcher re-waits when a lane wedges out from
        under it). Sharded lane sets are occupancy-CHOSEN but
        index-ORDERED: the sharded executable cache keys on device
        order, so a canonical ordering keeps one compile per subset
        instead of one per occupancy permutation."""
        if (
            self.scheduler_enabled
            and cls in BULK_CLASSES
            and self.mesh.sharding_available()
        ):
            healthy_free = [lane for lane in free if not lane.wedged]
            n_sets = sum(len(j.sets) for j in package)
            want = n_sets // SHARD_MIN_SETS_PER_LANE
            if len(healthy_free) >= 2 and want >= 2:
                chosen = sorted(healthy_free, key=lambda l: l.occupancy.occupancy())
                picked = chosen[: min(len(chosen), want)]
                return "sharded", sorted(picked, key=lambda l: l.index)
        lane = min(free, key=lambda l: (l.wedged, l.occupancy.occupancy()))
        return "single", [lane]

    def _package_extent(self, cls: PriorityClass, jobs) -> tuple[int, bool]:
        """Under the scheduler, what a package of class `cls` takes of
        `jobs` (the class in queue order, not empty): (the first n,
        whether it is closed, i.e. nothing that arrives later could join
        it). Same class only, capped at MAX_PACKAGE_SETS (and a bulk
        package is ONE launch) — both bound how long an arriving gossip
        block can wait behind the in-flight launch. What one bulk launch
        can carry: one job, or, from a groupable head job on, further
        jobs while each is groupable too and a slot is left — so a queue
        of small backfill jobs never becomes a package of many launches.
        (Where the mesh can shard, bulk stays one job a package: the
        collective's units are that road's own.)"""
        n = 0
        if cls in BULK_CLASSES:
            slots = (
                MAX_GROUP_JOBS
                if self.mesh.grouping_available() and not self.mesh.sharding_available()
                else 1
            )
            for j in jobs:
                if n and not _groupable(j):
                    return n, True  # the next launch's job
                n += 1
                if n == slots or not _groupable(j):
                    return n, True
            return n, False
        sets = 0
        for j in jobs:
            n += 1
            sets += len(j.sets)
            if sets >= MAX_PACKAGE_SETS:
                return n, True
        return n, False

    def _package_formed(self) -> bool:
        """Whether the package `_next_package()` would take now is
        closed: taking it ahead of a free lane then changes no launch's
        composition. An open one stays in the queue, where work that
        arrives during the launch in flight still joins it."""
        if not self.scheduler_enabled:
            return False  # the FIFO arm drains whatever is there
        cls = self._jobs.next_class()
        return cls is not None and self._package_extent(cls, self._jobs.queued(cls))[1]

    async def _next_package(self) -> tuple[list[_Job], PriorityClass]:
        """Dequeue one job and drain immediately-available work into the
        package: under the scheduler its class's `_package_extent`;
        everything available in FIFO mode (the pre-scheduler arm)."""
        job, cls, waited_ns = await self._jobs.get()
        with telemetry.phase("bls.next_package"):
            self._record_sched_dequeue(job, cls, waited_ns)
            package = [job]
            if self.scheduler_enabled:
                queued = itertools.chain(package, self._jobs.queued(cls))
                more, drain_cls = self._package_extent(cls, queued)[0] - 1, cls
            else:
                more, drain_cls = len(self._jobs), None
            for _ in range(more):
                nxt = self._jobs.get_nowait(drain_cls)
                self._record_sched_dequeue(*nxt)
                package.append(nxt[0])
        return package, cls

    async def _place_and_launch(self, package, cls, prepped=None) -> None:
        """Shared dispatch tail: the in-hand wait-for-capacity /
        placement / launch-task sequence, with the in-hand cancellation
        contract — from here to create_task, any await must fail the
        package's futures on cancellation (close() only drains the
        queue, it cannot see this package)."""
        try:
            while True:
                free = self._free_lanes()
                if free:
                    break
                # a free lane wedged between the capacity check and
                # placement (a cross-lane retry on an executor
                # thread can trip any breaker): healthy lanes exist
                # but are busy — their in-flight completions set
                # _wake, so this wait always terminates
                self._wake.clear()
                await self._wake.wait()
                if self._closed:
                    raise asyncio.CancelledError("bls pool closed")
        except asyncio.CancelledError:
            err = asyncio.CancelledError("bls pool closed")
            for j in package:
                if not j.future.done():
                    j.future.set_exception(err)
            raise
        with telemetry.phase("bls.place"):  # no await inside
            mode, lanes = self._pick_placement(cls, package, free)
            for lane in lanes:
                lane.inflight += 1
            task = asyncio.get_event_loop().create_task(
                self._launch(package, mode, lanes, prepped=prepped)
            )
            self._launch_tasks.add(task)
            task.add_done_callback(self._launch_tasks.discard)

    async def _run_jobs(self) -> None:
        """THE dispatcher: wait for a lane, take a package, place it.

        The wait comes BEFORE the dequeue, so jobs stay in the priority
        queue (keep reordering under arriving urgent work, and keep
        being joined by work of their class) until the mesh has
        capacity. Where packages are staged (`_staging`) one exception:
        a package the queue already holds all of (`_package_formed`) is
        taken while the lanes are busy and its prep goes to another
        thread, unit by unit, so that a freed lane finds the first unit
        ready — the look-ahead beyond the launches in flight is exactly
        one package, and it is the package the freed lane would have
        taken. It must not lengthen what an urgent job waits behind:
        when the package in hand has waited for its lane and the
        queue's own pick is by then of a more urgent class, that pick
        is served first, and the package in hand keeps its prep and
        goes after it — unless it has been in hand for the queue's
        starvation bound, which the queue can no longer apply to it."""
        package: list[_Job] | None = None  # in hand: out of the queue, not yet placed
        err: BaseException = asyncio.CancelledError("bls pool closed")
        try:
            while not self._closed:
                if package is None:
                    while not (
                        self._free_lanes() or (self._staging and self._package_formed())
                    ):
                        self._wake.clear()
                        await self._wake.wait()
                        if self._closed:
                            return
                    package, cls = await self._next_package()
                    taken_ns = time.monotonic_ns()
                    prepped = self._stage(package, cls) if self._staging else None
                if not self._free_lanes():
                    await self._wait_free_lane()
                    if self._closed:
                        return
                    pick = self._jobs.next_class()
                    if (
                        self.scheduler_enabled
                        and pick is not None
                        and pick < cls
                        and not self._jobs.aged(taken_ns)
                    ):
                        await self._place_and_launch(*await self._next_package())
                        continue
                if prepped is not None:
                    # only the first unit: the launch takes the rest as it gets to them
                    await self._await_staged((prepped.chunks + prepped.units)[0])
                placing, package = package, None  # _place_and_launch answers for it from here
                await self._place_and_launch(placing, cls, prepped=prepped)
                # the launch reaches its thread, and its dispatch the GIL,
                # before the next package's prep reaches another
                await asyncio.sleep(0)
        except asyncio.CancelledError:
            raise
        except BaseException as e:
            err = e
            raise
        finally:
            # close() only drains the queue: it cannot see the package in hand
            for j in package or ():
                if not j.future.done():
                    j.future.set_exception(err)

    # -- prep→verify pipeline ---------------------------------------------------

    async def _await_staged(self, unit: _PrepUnit) -> None:
        """A lane is free and the package in hand waits for its first
        unit's staged prep: the host holds that chip idle. The wait is
        the span `bls.parse_wait`, `parse_wait_ns` counts it, and the
        unit's launch carries it as a phase of its ledger entry."""
        if unit.prepared.done():
            return
        t0_ns = time.monotonic_ns()
        with telemetry.phase("bls.parse_wait"):
            staged = await asyncio.wrap_future(unit.prepared)
        waited_ns = time.monotonic_ns() - t0_ns
        self.metrics["parse_wait_ns"] += waited_ns
        staged.waited_s = waited_ns / 1e9

    def _stage(self, package: list[_Job], cls: PriorityClass) -> _PreppedPackage | None:
        """Form the package's launch units and hand their prep to an
        executor thread; None where the package keeps its inline prep: a
        bulk package on a mesh that can shard (the collective launch
        preps inline), and a package of one launch unit that finds
        every lane free (no launch to hide its prep behind, so staging
        would only add two thread hops to its verdict). With a sibling
        lane at work it is staged: prep inline on four launch threads
        runs side by side under one GIL and ends late on all of them."""
        if self.scheduler_enabled and cls in BULK_CLASSES and self.mesh.sharding_available():
            return None
        chunks, units = _launch_units(package, self.mesh.grouping_available())
        if len(chunks) + len(units) == 1 and not any(lane.inflight for lane in self.mesh.lanes):
            return None
        prepped = _PreppedPackage(
            [_PrepUnit(chunk, [s for j in chunk for s in j.sets]) for chunk in chunks],
            [_PrepUnit(unit, _unit_sets(unit), grouped=len(unit) > 1) for unit in units],
        )
        asyncio.get_event_loop().run_in_executor(None, self._prep_package, prepped)
        return prepped

    def _default_prep_fn(self, sets: list, lane_hint: int | None):
        from lodestar_tpu.models.batch_verify import prepare_inputs_for_lane

        return prepare_inputs_for_lane(sets, lane_hint, self.pubkey_table)

    def _prep_lane_hint(self) -> int | None:
        """A free sibling lane to stage prep on (mesh with >1 chip);
        None interleaves prep on whatever chip is current. Advisory
        read of dispatcher-owned state from the prep thread: a stale
        pick costs placement quality, never correctness."""
        if len(self.mesh.lanes) < 2:
            return None
        free = [l for l in self.mesh.available() if l.inflight == 0]
        if not free:
            return None
        return min(free, key=lambda l: l.occupancy.occupancy()).index

    def _prep_unit(self, sets: list, grouped: bool = False) -> PreparedSets:
        """Stage prep for one launch unit (prep executor thread). Errors
        are CAPTURED, not raised: the launch re-preps through the plain
        verify path so a prep fault takes the exact pre-pipeline
        degradation road (device→host inside build_device_inputs;
        anything worse raises at launch time and fails closed). A
        multi-job unit (`sets`: its jobs' sets) stages the host parse
        of every slot; its launch is the single-launch program's."""
        from lodestar_tpu.models.batch_verify import (
            consume_prep_info,
            prepare_grouped_launch_inputs,
        )

        t0_ns = time.monotonic_ns()
        inputs = None
        error: Exception | None = None
        with self._overlap.prep():
            try:
                if grouped:
                    inputs = prepare_grouped_launch_inputs(sets, self.pubkey_table)
                else:
                    inputs = self._prep_fn(sets, self._prep_lane_hint())
            except Exception as e:
                error = e
        spent = self._overlap.snapshot()
        self.metrics["parse_ns"] = spent["prep_ns"]
        self.metrics["parse_hidden_ns"] = spent["overlap_ns"]
        info = consume_prep_info()
        if info is not None and info["end_ns"] < t0_ns:
            info = None  # stale record from an earlier launch on this thread
        return PreparedSets(inputs, error, info)

    def _prep_package(self, prepped: _PreppedPackage) -> None:
        """Prep executor thread: the package's units in the order the
        verify stage launches them (`_launch_units`' boundaries, the
        ones `_verify_package` launches unstaged, so the launch schedule
        is unchanged), each handed over as it is ready. Every unit gets
        an outcome, whatever happens here: a launch may be waiting on it."""
        self._staged_packages += 1
        for unit in prepped.chunks + prepped.units:
            try:
                outcome = self._prep_unit(unit.sets, unit.grouped)
            except Exception as e:  # not a prep fault (those are captured): the launch re-preps inline
                outcome = PreparedSets(error=e)
            unit.prepared.set_result(outcome)

    def pipeline_stats(self) -> dict:
        """Pipeline wall-clock accounting: prep/verify busy time, their
        overlap, the overlap share of verify time, and the staged
        package count (0 = pipeline never engaged). The device path per
        batch is either the split schedule (3-launch fused prep + the
        RLC verify dispatch) or, on an accelerator, ONE
        resident program — in which case the prep accumulator measures
        the staged host byte-parse and the verify accumulator the
        single launch."""
        s = self._overlap.snapshot()
        v = s["verify_ns"]
        s["overlap_occupancy_pct"] = (100.0 * s["overlap_ns"] / v) if v else 0.0
        s["staged_packages"] = self._staged_packages
        s["pipeline_enabled"] = self._staging
        return s

    def _release_lanes_early(self, to_release: list[MeshLane], held: list[MeshLane]) -> None:
        """Loop-side early release: the sharded fallback returns unused
        lanes to the dispatcher before its (possibly long) single-lane
        retry finishes. `held` is the launch's live accounting — the
        finally below decrements exactly what is still held."""
        for lane in to_release:
            if lane in held:
                held.remove(lane)
                lane.inflight -= 1
        self._wake.set()

    async def _launch(
        self,
        package: list[_Job],
        mode: str,
        lanes: list[MeshLane],
        prepped: _PreppedPackage | None = None,
    ) -> None:
        held = list(lanes)  # guarded by: event-loop (early releases and the finally both run on the loop)
        try:
            if mode == "sharded":
                await asyncio.get_event_loop().run_in_executor(
                    None, self._verify_package_sharded, package, lanes, held
                )
            else:
                await asyncio.get_event_loop().run_in_executor(
                    None, self._verify_package, package, lanes[0], False, prepped
                )
        except asyncio.CancelledError:
            # close() cancels launch tasks; if the executor work item
            # had not STARTED yet it never runs and nobody else will
            # resolve these futures — fail them closed (done futures,
            # resolved by an already-running executor thread, no-op)
            err = asyncio.CancelledError("bls pool closed")
            for j in package:
                if not j.future.done():
                    j.future.set_exception(err)
            raise
        except Exception as e:  # fail closed: reject, never resolve True
            self.metrics["errors"] += len(package)
            self._log.error(f"bls verify package failed: {e!r}")
            for j in package:
                if not j.future.done():
                    j.future.set_exception(e)
        finally:
            for lane in held:
                lane.inflight -= 1
            # clear so a LATE _release_lanes_early (scheduled by an
            # executor thread that outlives a cancelled launch task)
            # finds nothing left to double-decrement
            held.clear()
            self._wake.set()

    # -- device launches (executor threads) ------------------------------------

    def _on_lane_wedge(self, lane: MeshLane) -> None:
        """closed->open transition on one chip's wedge breaker."""
        self._log.warn(
            "device lane wedged, degrading to remaining chips",
            {"device": lane.label, "lanes_left": len(self.mesh.available())},
        )
        m = self._sched_metrics
        if m is not None:
            m.lane_wedge_trips.labels(lane.label).inc()

    def _count_lane_launch(self, lane: MeshLane, mode: str) -> None:
        m = self._sched_metrics
        if m is not None:
            m.lane_launches.labels(lane.label, mode).inc()

    def _count_started(self, package: list[_Job]) -> None:
        self.metrics["jobs_started"] += len(package)
        self.metrics["sig_sets_started"] += sum(len(j.sets) for j in package)
        indexed = [s for j in package for s in j.sets if isinstance(s, IndexedSignatureSet)]
        if indexed:
            self.metrics["indexed_rows_started"] += len(indexed)
            self.metrics["aggregate_points_started"] += sum(len(s.indices) for s in indexed)

    def _launch_sets(
        self,
        lane: MeshLane,
        sets: list,
        prepared: PreparedSets | None = None,
        grouped: bool = False,
    ):
        """One verify launch, preferring `lane` (mesh_launch: breaker
        accounting + cross-lane error retry — a sick chip degrades its
        work onto the rest of the mesh with the verdict unchanged;
        raises only when every candidate lane errored, which with one
        lane is exactly the pre-mesh fail-closed behavior). `prepared`
        carries staged pipeline inputs (see mesh_launch). `grouped`: the
        multi-job launch (`sets` a list of jobs' sets, `ok` a verdict a
        job). Returns (ok, lane_that_served). The launch is the overlap
        tracker's verify window: it opens once the unit's inputs are in
        hand, so prep a launch thread is still waiting for is not
        counted as hidden."""
        from .mesh import mesh_launch

        with self._overlap.verify():
            return mesh_launch(
                self.mesh,
                sets,
                prefer=lane,
                prepared=prepared,
                grouped=grouped,
                on_launch=lambda l: self._count_lane_launch(l, "grouped" if grouped else "single"),
                on_wedge=self._on_lane_wedge,
            )

    def _verify_package(
        self,
        package: list[_Job],
        lane: MeshLane,
        counted: bool = False,
        prepped: _PreppedPackage | None = None,
    ) -> None:
        """Runs in a thread executor (device dispatch releases the GIL).

        `prepped` carries the pipeline's staged launch units — the SAME
        unit boundaries as the inline path, so the launch schedule is
        identical; only where prep ran differs. A unit's staged inputs
        are taken when the launches get to it: while unit i runs, the
        prep thread is at unit i+1. The batch-then-retry
        road always re-preps INLINE (fresh blinding, fresh prep — one
        bad signature can't poison its neighbors, and a stale staged
        prep can't poison the retry)."""
        if not counted:
            self._count_started(package)
            # SLO launch stamp once per job: the sharded fallback road
            # (counted=True) already stamped at its collective launch
            for j in package:
                slo.job_launch(j.slo)

        # tracing work (incl. the clock reads) only when some job in the
        # package was submitted under an active trace — the disabled path
        # pays the flag checks hidden in trace_parent alone
        traced = any(j.trace_parent is not None for j in package)
        if traced:
            # buffer-wait spans: from job submission to the launch this
            # thread is about to perform (buffering + queue time)
            launch_ns = time.monotonic_ns()
            for j in package:
                if j.trace_parent is not None:
                    tracing.record(
                        j.trace_parent, "bls_buffer_wait", j.added_ns, launch_ns,
                        {"sets": len(j.sets)},
                    )

        if prepped is None:
            chunks, units = _launch_units(package, self.mesh.grouping_available())
            chunk_units = [
                (chunk, [s for j in chunk for s in j.sets], None) for chunk in chunks
            ]
            job_units = [(unit, _unit_sets(unit), None) for unit in units]
        else:
            chunk_units = [(u.jobs, u.sets, u.prepared) for u in prepped.chunks]
            job_units = [(u.jobs, u.sets, u.prepared) for u in prepped.units]

        # RLC-batch the batchable jobs in ≥16-set chunks; invalid batch →
        # retry each job individually (worker.ts:52-96)
        retries: list[_Job] = []
        for jobs, all_sets, handed in chunk_units:
            staged = handed.result() if handed is not None else None  # waits where the prep thread is not there yet
            t0 = time.monotonic_ns() if traced else 0
            try:
                ok, served = self._launch_sets(lane, all_sets, prepared=staged)
            except Exception:
                self.metrics["batch_retries"] += 1
                if traced:
                    self._trace_unit_prep(jobs, staged, t0)
                    self._trace_launch(
                        jobs, t0, len(all_sets), "batch_error", lane.label,
                        lane=str(lane.index),
                    )
                retries.extend(jobs)
                continue
            if traced:
                self._trace_unit_prep(jobs, staged, t0)
                self._trace_launch(
                    jobs, t0, len(all_sets), "batch", served.label,
                    lane=str(served.index),
                )
            if ok:
                self.metrics["batch_sigs_success"] += len(all_sets)
                for j in jobs:
                    self._resolve(j, True)
            else:
                self.metrics["batch_retries"] += 1
                retries.extend(jobs)

        for jobs, sets_, handed in job_units + [([j], j.sets, None) for j in retries]:
            staged = handed.result() if handed is not None else None
            if len(jobs) > 1:
                self._verify_grouped_unit(jobs, sets_, staged, lane, traced)
                continue
            j = jobs[0]
            t0 = time.monotonic_ns() if traced else 0
            try:
                ok, served = self._launch_sets(lane, sets_, prepared=staged)
                if traced:
                    self._trace_unit_prep([j], staged, t0)
                    self._trace_launch(
                        [j], t0, len(sets_), "single", served.label,
                        lane=str(served.index),
                    )
                self._resolve(j, ok)
            except Exception as e:
                if traced:
                    self._trace_unit_prep([j], staged, t0)
                    self._trace_launch(
                        [j], t0, len(sets_), "single_error", lane.label,
                        lane=str(lane.index),
                    )
                if not j.future.done():
                    j.future.get_loop().call_soon_threadsafe(self._reject, j, e)

    def _verify_grouped_unit(
        self, jobs: list[_Job], job_sets: list, staged, lane: MeshLane, traced: bool
    ) -> None:
        """One multi-job launch: each job's future gets its own slot's
        verdict. An error (every candidate lane failed) fails the
        unit's jobs closed, as a single launch's does its one."""
        t0 = time.monotonic_ns() if traced else 0
        n_sets = sum(len(j.sets) for j in jobs)
        try:
            verdicts, served = self._launch_sets(lane, job_sets, prepared=staged, grouped=True)
            if len(verdicts) != len(jobs):
                raise RuntimeError(
                    f"multi-job launch answered {len(verdicts)} verdicts for {len(jobs)} jobs"
                )
        except Exception as e:
            if traced:
                self._trace_unit_prep(jobs, staged, t0)
                self._trace_launch(
                    jobs, t0, n_sets, "grouped_error", lane.label, lane=str(lane.index)
                )
            for j in jobs:
                if not j.future.done():
                    j.future.get_loop().call_soon_threadsafe(self._reject, j, e)
            return
        if traced:
            self._trace_unit_prep(jobs, staged, t0)
            self._trace_launch(
                jobs, t0, n_sets, "grouped", served.label, lane=str(served.index)
            )
        for j, ok in zip(jobs, verdicts):
            self._resolve(j, ok)

    def _trace_unit_prep(self, jobs: list[_Job], staged, t0: int) -> None:
        """`bls_prep` span for one launch unit: from the thread-local
        record for inline prep, or from the record the prep STAGE
        carried across threads on its PreparedSets."""
        if staged is None:
            self._trace_prep(jobs, t0)
        else:
            self._trace_prep_info(jobs, staged.info)

    @staticmethod
    def _trace_prep_info(jobs: list[_Job], info) -> None:
        """`bls_prep` span from a record the prep STAGE carried across
        threads (the pipelined twin of `_trace_prep`, which reads the
        launch thread's TLS): staged prep ran on the prep executor, so
        the record rides the _PrepUnit instead."""
        if info is None:
            return
        attrs = {"layer": info["layer"], "sets": info["sets"], "staged": True}
        if info["rejected"]:
            attrs["rejected"] = True
        for j in jobs:
            if j.trace_parent is not None:
                tracing.record(
                    j.trace_parent, "bls_prep", info["start_ns"], info["end_ns"], attrs
                )

    def _verify_package_sharded(
        self, package: list[_Job], lanes: list[MeshLane], held: list[MeshLane] | None = None
    ) -> None:
        """One data-parallel launch over idle lanes (executor thread).
        A collective ERROR cannot name the sick chip, so it feeds the
        mesh's sharded breaker (parking the collective path) and the
        package degrades to the attributable single-lane path; an
        invalid VERDICT takes the same retry road the RLC batch does —
        re-verified per job so one bad signature can't poison its
        package (and so a lying collective can't be weaker than the
        single-device policy)."""
        self._count_started(package)
        for j in package:
            slo.job_launch(j.slo)
        all_sets = [s for j in package for s in j.sets]
        traced = any(j.trace_parent is not None for j in package)
        if traced:
            launch_ns = time.monotonic_ns()
            for j in package:
                if j.trace_parent is not None:
                    tracing.record(
                        j.trace_parent, "bls_buffer_wait", j.added_ns, launch_ns,
                        {"sets": len(j.sets)},
                    )
        t0 = time.monotonic_ns() if traced else 0
        try:
            with contextlib.ExitStack() as stack, self._overlap.verify():
                for lane in lanes:
                    stack.enter_context(lane.occupancy.launch())
                ok = bool(
                    self.mesh.sharded_fn(all_sets, [lane.index for lane in lanes])
                )
            self.mesh.sharded_breaker.record_success()
            self.metrics["sharded_launches"] += 1
            for lane in lanes:
                lane.launches += 1
                self._count_lane_launch(lane, "sharded")
        except Exception:
            self.mesh.sharded_breaker.record_failure()
            self.metrics["sharded_fallbacks"] += 1
            self.metrics["batch_retries"] += 1
            if traced:
                self._trace_launch(
                    package, t0, len(all_sets), "sharded_error",
                    ",".join(lane.label for lane in lanes),
                    lane=",".join(str(lane.index) for lane in lanes),
                )
            fallback = min(lanes, key=lambda l: l.occupancy.occupancy())
            self._release_unused(lanes, fallback, held, package)
            self._verify_package(package, fallback, counted=True)
            return
        if traced:
            self._trace_launch(
                package, t0, len(all_sets), "sharded",
                ",".join(lane.label for lane in lanes),
                lane=",".join(str(lane.index) for lane in lanes),
            )
        if ok:
            self.metrics["batch_sigs_success"] += len(all_sets)
            for j in package:
                self._resolve(j, True)
        else:
            self.metrics["batch_retries"] += 1
            fallback = min(lanes, key=lambda l: l.occupancy.occupancy())
            self._release_unused(lanes, fallback, held, package)
            self._verify_package(package, fallback, counted=True)

    def _release_unused(
        self,
        lanes: list[MeshLane],
        fallback: MeshLane,
        held: "list[MeshLane] | None",
        package: list[_Job],
    ) -> None:
        """Executor-side entry to the loop-side early release: the
        sharded fallback keeps ONE lane for its (possibly long)
        single-lane retry — the other chips go back to the dispatcher
        now instead of idling behind this package's finally."""
        if held is None:
            return
        unused = [lane for lane in lanes if lane is not fallback]
        if unused:
            package[0].future.get_loop().call_soon_threadsafe(
                self._release_lanes_early, unused, held
            )

    @staticmethod
    def _trace_prep(jobs: list[_Job], launch_start_ns: int) -> None:
        """`bls_prep` span per traced job: input preparation inside the
        launch this thread just performed, with the serving layer
        (device on-chip pipeline vs host native/python) stamped as an
        attribute — mirroring how `verifier_layer` attributes the verify.
        The model layer leaves the timing in a thread-local (it runs on
        this executor thread, below any tracer context); consuming it
        here keeps untraced launches free of tracer work. Records that
        predate this launch are discarded: untraced launches (and mock
        backends layered over earlier real ones) leave stale info on the
        executor thread, and attributing an old prep's timestamps to this
        trace would corrupt its span window."""
        from lodestar_tpu.models.batch_verify import consume_prep_info

        info = consume_prep_info()
        if info is None or info["end_ns"] < launch_start_ns:
            return
        attrs = {"layer": info["layer"], "sets": info["sets"]}
        if info["rejected"]:
            attrs["rejected"] = True
        for j in jobs:
            if j.trace_parent is not None:
                tracing.record(
                    j.trace_parent, "bls_prep", info["start_ns"], info["end_ns"], attrs
                )

    @staticmethod
    def _trace_launch(
        jobs: list[_Job],
        start_ns: int,
        n_sets: int,
        mode: str,
        device: str = "dev0",
        lane: str | None = None,
    ) -> None:
        """Per-traced-job device-launch span; a batch covering jobs from
        several traces lands one identically-timed span in each. A
        batchable job verified in the single pass got there because its
        batch failed — that's the reference's batch-then-retry path, so
        it's labeled bls_batch_retry to keep the decomposition visible.
        The serving lane rides along as the `device` attribute (plus the
        `lane` index when known), and is ALSO stamped onto the job's
        trace parent — for chain imports that is the `bls_verify` span,
        so a Chrome-trace export of a mesh slot names its chips at the
        top level (a job served across several launches keeps the last
        serving lane, the one that produced its verdict)."""
        end_ns = time.monotonic_ns()
        attrs = {"sets": n_sets, "mode": mode, "device": device}
        if lane is not None:
            attrs["lane"] = lane
        for j in jobs:
            if j.trace_parent is not None:
                retried = j.batchable and mode.startswith("single")
                tracing.record(
                    j.trace_parent,
                    "bls_batch_retry" if retried else "bls_device_launch",
                    start_ns,
                    end_ns,
                    attrs,
                )
                j.trace_parent.set(device=device, **({"lane": lane} if lane is not None else {}))

    def _resolve(self, job: _Job, result: bool) -> None:
        if not job.future.done():
            with telemetry.phase("bls.resolve"):  # the hand-back to the loop thread
                job.future.get_loop().call_soon_threadsafe(self._set_result, job, result)

    @staticmethod
    def _set_result(job: _Job, result: bool) -> None:
        if not job.future.done():
            job.future.set_result(result)

    @staticmethod
    def _reject(job: _Job, err: Exception) -> None:
        if not job.future.done():
            job.future.set_exception(err)
