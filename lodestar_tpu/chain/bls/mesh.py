"""Verifier mesh: per-device launch lanes behind one verifier pool.

The device core passed the 8-device dryrun (`verify_signature_sets_sharded`,
`__graft_entry__.dryrun_multichip`) but until PR 8 the production pool
drove one chip. This module is the mesh's serving shape:

* `MeshLane` — one chip: its own verify callable, its own EWMA
  `OccupancyTracker`, and its own wedge `CircuitBreaker` so a sick
  device (driver hang, OOM loop) degrades the pool to an (N-1)-chip
  mesh instead of tripping the whole pool.
* `VerifierMesh` — the lane set plus, under the split schedule only,
  a data-parallel sharded verify callable (one bulk job, one launch
  across several idle chips). The mesh also answers the fleet-level
  questions the offload Status frame ships to clients: aggregate
  occupancy over *available* chips and the per-chip table (a wedged
  chip drops out of the advertised capacity).
* `build_device_mesh` — production construction from the models layer's
  device enumeration, and the one place a production lane is made: the
  models layer says which verify schedule the backend runs, and the
  lanes carry the answer (what they can take, whether prep staged
  for them touches a device, and whether the mesh has the collective
  at all). `"auto"` engages only when the backend is
  a TPU AND more than one device is visible: on the CPU-forced 8-device
  test platform auto stays single-lane, so a default pool behaves
  exactly like the pre-mesh code unless a test asks for the mesh
  explicitly. A backend that cannot initialise raises — it is never
  read as "one CPU lane".

Placement policy lives in the pool (`chain/bls/pool.py`): a package
goes to the least-occupied free lane, whatever its class. On a TPU
(the single launch) that is the only road: a bulk package is four
jobs in one launch on one chip ((288, 4) for a block's halves, the
slot rule's rows: `telemetry.group_slot_rows`), its parse staged, and N
lanes run N such launches side by side — the road one chip runs, a
lane at a time (the backfill cell of the benchmark measures it on a
v5e-4). Only where the lanes run the split schedule (a forced CPU
mesh) does a bulk job big enough to amortize a collective launch
shard across the idle lanes.
"""

from __future__ import annotations

from typing import Callable, Sequence

from lodestar_tpu import telemetry
from lodestar_tpu.scheduler import OccupancyTracker

__all__ = [
    "MeshLane",
    "VerifierMesh",
    "PreparedSets",
    "build_device_mesh",
    "single_lane_mesh",
    "mesh_launch",
    "MESH_MODES",
    "LANE_WEDGE_THRESHOLD",
    "SHARD_MIN_SETS_PER_LANE",
    "SHARD_DISABLE_THRESHOLD",
]

#: pool-facing mesh modes. cli.py keeps a literal copy: importing this
#: module at argparse time would pull the chain.bls package __init__
#: and with it the crypto self-check asserts (~2s on --help); the
#: wiring doctrine is that node/BeaconNodeOptions validates against
#: THIS tuple post-parse, so a drifted CLI copy fails loudly there
MESH_MODES = ("auto", "on", "off")

# consecutive launch errors before ONE lane reports itself wedged —
# same rationale as the pre-mesh pool-wide DEVICE_WEDGE_THRESHOLD:
# high enough that one bad batch + its retries can't trip it, low
# enough to stop a launch storm against a hung driver
LANE_WEDGE_THRESHOLD = 8
LANE_WEDGE_RESET_S = 5.0
LANE_WEDGE_MAX_RESET_S = 60.0

#: a bulk batch shards over at most len(sets)//this lanes — a 32-set
#: batch across 8 chips would pay 8 collective dispatches to save one
#: small launch
SHARD_MIN_SETS_PER_LANE = 16

#: consecutive sharded-launch errors before the mesh stops trying the
#: collective program (single-lane launches attribute errors to the
#: exact sick chip; the sharded launch cannot, so it gets its own gate)
SHARD_DISABLE_THRESHOLD = 3


class PreparedSets:
    """Staged prep output for one launch unit (the pipelined pool's
    hand-off between its prep and verify stages).

    `inputs` is the `build_device_inputs` tuple, or None when prep
    REJECTED the batch (a structural verdict — final, never re-prepped).
    `error` carries a prep-stage exception; a launch seeing one re-preps
    through the lane's plain `verify_fn`, which re-raises through the
    exact pre-pipeline fail-closed path. `waited_s` is what the
    dispatcher waited for this outcome with a lane free; the launch
    carries it as its `bls.parse_wait` phase."""

    __slots__ = ("inputs", "error", "info", "waited_s")

    def __init__(self, inputs=None, error: Exception | None = None, info=None):
        self.inputs = inputs
        self.error = error
        self.info = info  # prep span record carried across threads
        self.waited_s = 0.0


class MeshLane:
    """One device lane: verify callable + occupancy + wedge breaker.

    `inflight` is dispatcher state (how many packages the pool has in
    flight on this lane) and is only touched on the event loop; the
    occupancy tracker and breaker are thread-safe because the launches
    themselves run on executor threads. `verify_prepared_fn` (optional)
    verifies a `PreparedSets.inputs` staged by the pipelined pool's
    prep stage (either staged shape — see models verify_prepared);
    lanes without one always re-prep inline. `verify_grouped_fn`
    (optional) is the multi-job entry (models
    `make_lane_verify_grouped_fn`): a list of jobs' sets in, ONE launch,
    a list of verdicts out; the pool forms multi-job units only where
    every lane has one. `staged_prep_host_only` is a fact fixed here,
    by whoever built the lane: prep staged for `verify_prepared_fn`
    touches no device (the single launch's host byte parse), so it can
    be hidden behind a launch on this lane's own die."""

    def __init__(
        self,
        index: int,
        verify_fn: Callable,
        *,
        label: str | None = None,
        wedge_threshold: int = LANE_WEDGE_THRESHOLD,
        wedge_reset_s: float = LANE_WEDGE_RESET_S,
        verify_prepared_fn: Callable | None = None,
        verify_grouped_fn: Callable | None = None,
        staged_prep_host_only: bool = False,
    ) -> None:
        from lodestar_tpu.offload.resilience import CircuitBreaker

        self.index = index
        self.label = label if label is not None else f"dev{index}"
        self.verify_fn = verify_fn
        self.verify_prepared_fn = verify_prepared_fn
        self.verify_grouped_fn = verify_grouped_fn
        self.staged_prep_host_only = staged_prep_host_only
        self.occupancy = OccupancyTracker()
        self.breaker = CircuitBreaker(
            failure_threshold=wedge_threshold,
            reset_timeout_s=wedge_reset_s,
            max_reset_timeout_s=LANE_WEDGE_MAX_RESET_S,
        )
        self.inflight = 0  # guarded by: event-loop (dispatcher-owned)
        self.wedge_trips = 0  # guarded by: advisory-only (monotonic trip count, read by tests/metrics)
        self.launches = 0  # guarded by: advisory-only (monotonic launch count)

    @property
    def wedged(self) -> bool:
        return self.breaker.is_open

    def state(self) -> dict:
        return {
            "device": self.label,
            "occupancy_permille": self.occupancy.occupancy_permille(),
            "wedged": self.wedged,
            "inflight": self.inflight,
            "wedge_trips": self.wedge_trips,
            "launches": self.launches,
        }


class VerifierMesh:
    """Lane set + optional sharded collective. Duck-types the occupancy
    interface `AdmissionController` expects (`occupancy()`), reporting
    the MEAN busy fraction over available lanes — the admission
    thresholds (0.75 / 0.95) grade fleet headroom, not "any chip busy".
    With one lane this is exactly that lane's tracker value, so the
    pre-mesh admission behavior is unchanged."""

    def __init__(
        self, lanes: Sequence[MeshLane], *, sharded_fn: Callable | None = None, table=None
    ):
        if not lanes:
            raise ValueError("a verifier mesh needs at least one lane")
        self.lanes = list(lanes)
        #: sharded_fn(sets, device_indices) -> bool over >=2 lanes
        self.sharded_fn = sharded_fn
        #: the `PubkeyTable` the lanes' entries resolve indexed sets from
        #: (`verify_fn(sets, table=...)`, `verify_grouped_fn` likewise);
        #: None for lanes that speak pubkey bytes only (mocks, an
        #: offload host: it holds no registry)
        self.table = table
        from lodestar_tpu.offload.resilience import CircuitBreaker

        # gates the collective program only: a sharded error cannot name
        # the sick chip, so it must not wedge per-lane breakers — instead
        # repeated collective failures park the sharded path while
        # single-lane launches keep attributing errors per chip
        self.sharded_breaker = CircuitBreaker(
            failure_threshold=SHARD_DISABLE_THRESHOLD,
            reset_timeout_s=LANE_WEDGE_RESET_S,
            max_reset_timeout_s=LANE_WEDGE_MAX_RESET_S,
        )

    def __len__(self) -> int:
        return len(self.lanes)

    def available(self) -> list[MeshLane]:
        """Lanes whose wedge breaker admits work (the (N-1) degradation
        set). May be empty — the pool then fails fast like the pre-mesh
        wedged-device path."""
        return [lane for lane in self.lanes if not lane.wedged]

    def sharding_available(self) -> bool:
        return self.sharded_fn is not None and not self.sharded_breaker.is_open

    def grouping_available(self) -> bool:
        """Whether a multi-job unit is ONE launch on whichever lane
        serves it: every lane has the grouped entry (the single-launch
        program with a slot a job; a lane of the split schedule is
        built without one)."""
        return all(lane.verify_grouped_fn is not None for lane in self.lanes)

    def staged_prep_is_host_only(self) -> bool:
        """Whether prep staged ahead of a launch touches no device: every
        lane takes staged inputs and says so of them."""
        return all(
            lane.verify_prepared_fn is not None and lane.staged_prep_host_only
            for lane in self.lanes
        )

    def occupancy(self) -> float:
        lanes = self.available() or self.lanes
        return sum(lane.occupancy.occupancy() for lane in lanes) / len(lanes)

    def occupancy_permille(self) -> int:
        return max(0, min(1000, int(round(self.occupancy() * 1000.0))))

    def chip_table(self) -> list[tuple[int, bool]]:
        """(occupancy_permille, wedged) per chip — the Status frame's
        mesh trailer. A wedged chip stays listed (so operators see it)
        but flagged, and clients drop it from advertised capacity."""
        return [
            (lane.occupancy.occupancy_permille(), lane.wedged) for lane in self.lanes
        ]

    def lane_states(self) -> list[dict]:
        return [lane.state() for lane in self.lanes]


def mesh_launch(
    mesh: VerifierMesh,
    sets,
    *,
    prefer: MeshLane | None = None,
    on_launch: Callable | None = None,
    on_wedge: Callable | None = None,
    prepared: "PreparedSets | None" = None,
    grouped: bool = False,
) -> tuple["bool | list[bool]", MeshLane]:
    """One verify launch with per-lane wedge accounting and cross-lane
    error retry — the single-launch core shared by the pool's executor
    path and the standalone offload host's backend.

    Starts on `prefer` (default: the least-occupied available lane;
    every lane when all are wedged, failing fast through the sick chip
    so its breaker earns the half-open retrial). A backend ERROR
    records the failing lane's breaker — firing `on_wedge(lane)` on the
    closed→open transition — and retries on each remaining available
    sibling, least-occupied first; the verdict is unchanged and the
    call raises only when every candidate errored. `on_launch(lane)`
    fires per attempt (metrics). Returns (ok, lane_that_served).

    `prepared` (pipelined pool) short-circuits the prep half: a staged
    structural REJECT is the final verdict (ok=False, no re-prep); clean
    staged inputs go through the lane's `verify_prepared_fn`; a staged
    prep ERROR — or a lane without a prepared callable — re-preps
    through the plain `verify_fn`, so the fail-closed degradation chain
    is byte-for-byte the pre-pipeline one.

    `grouped` makes it the multi-job launch: `sets` is then a list of
    jobs' sets, the lane's `verify_grouped_fn` (or `verify_prepared_fn`
    on staged inputs) serves them in ONE launch, and `ok` is a list of
    verdicts, one a job. Breaker accounting, cross-lane retry and the
    one `bls_lane_verify` ledger entry are the same; its size class is
    the launch's rows (slots times a slot's rows, the slot rule's:
    `telemetry.group_slot_rows`). The entry carries `indexed_rows`, the
    sets of the launch that name their signers by registry index."""
    from lodestar_tpu.crypto.bls.api import IndexedSignatureSet

    indexed_rows = sum(
        isinstance(s, IndexedSignatureSet) for job in (sets if grouped else [sets]) for s in job
    )
    if grouped:
        size_class = telemetry.size_class_of(len(sets), floor=2) * telemetry.group_slot_rows(
            len(job) for job in sets
        )
    else:
        size_class = telemetry.size_class_of(len(sets))
    if prefer is None or (prefer.wedged and mesh.available()):
        # no preference, or the preferred lane wedged since dispatch
        # (mid-package: chunk N trips the breaker, chunk N+1 must not
        # keep feeding the hung driver): start on a healthy lane
        lanes = mesh.available() or mesh.lanes
        prefer = min(lanes, key=lambda l: l.occupancy.occupancy())
    tried: list[MeshLane] = []
    current = prefer
    resolving = {"table": mesh.table} if mesh.table is not None else {}
    while True:
        tried.append(current)
        try:
            # launch telemetry at the lane seam: wall time of the whole
            # verify launch this lane serves (staged-inputs verify, or
            # the full re-prep + verify chain), labeled with the lane so
            # a mesh slot's launches name their chips. Size class is the
            # pow-2 bucket of the set count — the verify programs' own
            # compile-cache bucketing.
            use_staged = prepared is not None and prepared.error is None
            if use_staged and prepared.inputs is None:
                # prep rejected the batch: verdict final, no backend
                # call — not a launch
                with current.occupancy.launch():
                    ok = [False] * len(sets) if grouped else False
            else:
                with telemetry.launch(
                    "bls_lane_verify", size_class, lane=current.label, indexed_rows=indexed_rows
                ) as tel, current.occupancy.launch():
                    if use_staged and current.verify_prepared_fn is not None:
                        info = prepared.info
                        if info is not None:
                            # staged on the prep thread: its parse seconds
                            # cross threads with the inputs
                            tel.add_phase("bls.parse", (info["end_ns"] - info["start_ns"]) / 1e9)
                        if prepared.waited_s:
                            tel.add_phase("bls.parse_wait", prepared.waited_s)
                        ok = current.verify_prepared_fn(prepared.inputs)
                    elif grouped:
                        ok = current.verify_grouped_fn(sets, **resolving)
                    else:
                        ok = bool(current.verify_fn(sets, **resolving))
                    ok = [bool(v) for v in ok] if grouped else bool(ok)
        except Exception:
            # an error on a staged-inputs attempt may be input-bound
            # (arrays committed to the sick die, a malformed staging) —
            # sibling retries re-prep inline so the cross-lane recovery
            # is exactly the pre-pipeline one, not N copies of the same
            # poisoned inputs wedging every healthy breaker
            prepared = None
            was_open = current.breaker.is_open
            current.breaker.record_failure()
            if not was_open and current.breaker.is_open:
                current.wedge_trips += 1
                if on_wedge is not None:
                    on_wedge(current)
            current.launches += 1
            if on_launch is not None:
                on_launch(current)
            candidates = [l for l in mesh.available() if l not in tried]
            if not candidates:
                raise
            current = min(candidates, key=lambda l: l.occupancy.occupancy())
            continue
        current.breaker.record_success()
        current.launches += 1
        if on_launch is not None:
            on_launch(current)
        return ok, current


def single_lane_mesh(verify_fn: Callable, **lane_kwargs) -> VerifierMesh:
    """The pre-mesh shape: one lane (`MeshLane`'s keywords), no sharded
    collective."""
    return VerifierMesh([MeshLane(0, verify_fn, **lane_kwargs)])


def build_device_mesh(
    mode: str = "auto",
    *,
    fallback_verify_fn: Callable | None = None,
    wedge_threshold: int = LANE_WEDGE_THRESHOLD,
    table=None,
) -> VerifierMesh:
    """Production mesh from the models layer's device enumeration.

    mode "off" (or a single visible device) yields the single-lane
    shape around `fallback_verify_fn` (default:
    `verify_signature_sets_device`) — bit-identical to the pre-mesh
    pool. mode "auto" requires the TPU backend; mode "on" forces the
    mesh whenever more than one device is visible. Import and
    backend-initialisation errors propagate: the caller asked for a
    device verifier, and a host whose chip is taken must say so instead
    of serving something else unseen.

    The verify schedule is asked of the models layer HERE, once a mesh,
    and the lanes carry it: where the backend runs the single launch they
    take multi-job units, their staged prep is the host byte parse, and
    the mesh has no collective (a bulk package is one multi-job launch
    on one lane, as on one chip); where it runs the split schedule they
    have no grouped entry, their staged prep is device work, and a bulk
    job may shard over the idle lanes.

    `table` (the pool's `PubkeyTable`) is what the lanes resolve indexed
    sets from. Lanes of the single launch sum a set's signers on their
    chip, so each gets a copy of it here (`place_on`, one a device);
    lanes of the split schedule take pubkey bytes and leave it on the
    host (the counted fallback)."""
    if mode not in MESH_MODES:
        raise ValueError(f"bls_mesh must be one of {MESH_MODES}, got {mode!r}")
    from lodestar_tpu.models import batch_verify as bv

    single_launch = bv.single_launch_active()

    def _lanes(entries, devices) -> list[MeshLane]:
        """Lanes over (verify_fn, verify_prepared_fn, verify_grouped_fn)
        entries, one a device, with the backend's schedule as facts."""
        lanes = [
            MeshLane(
                index,
                verify_fn,
                wedge_threshold=wedge_threshold,
                verify_prepared_fn=verify_prepared_fn,
                verify_grouped_fn=verify_grouped_fn if single_launch else None,
                staged_prep_host_only=single_launch,
            )
            for index, (verify_fn, verify_prepared_fn, verify_grouped_fn) in enumerate(entries)
        ]
        if table is not None and single_launch:
            table.place_on(devices, [lane.label for lane in lanes])
        return lanes

    def _single() -> VerifierMesh:
        if fallback_verify_fn is not None:
            return single_lane_mesh(fallback_verify_fn, wedge_threshold=wedge_threshold)
        return VerifierMesh(
            _lanes(
                [(bv.verify_signature_sets_device, bv.verify_prepared, bv.verify_sets_grouped_launch)],
                [None],  # wherever JAX puts it
            ),
            table=table,
        )

    if mode == "off":
        return _single()
    if mode == "auto":
        from lodestar_tpu.ops import fp_pallas

        if not fp_pallas.use_pallas():
            return _single()
    n = bv.mesh_device_count()
    if n <= 1:
        return _single()
    lanes = _lanes(
        [
            (
                bv.make_lane_verify_fn(i),
                bv.make_lane_verify_prepared_fn(i),
                bv.make_lane_verify_grouped_fn(i),
            )
            for i in range(n)
        ],
        bv.mesh_devices(),
    )
    return VerifierMesh(
        lanes, sharded_fn=None if single_launch else bv.make_mesh_sharded_fn(table), table=table
    )
