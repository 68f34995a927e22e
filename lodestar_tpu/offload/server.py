"""Offload service host: the process that owns the accelerator fleet.

Exposes the verify backend over gRPC generic handlers (opaque-bytes
methods — no proto codegen needed in this environment):

  /lodestar.BlsOffload/VerifySignatureSets   sets frame -> verdict frame
  /lodestar.BlsOffload/Status                b"" -> occupancy status frame

Status grades the old binary can-accept byte into an occupancy frame
(EWMA busy-ns/wall-ns around device launches, in-flight depth, and an
ACCEPT/SHED_BULK/REJECT admission state) so a multi-endpoint client can
prefer the least-occupied host and keep bulk work off a shedding one.
Byte 0 keeps the legacy meaning — old clients read it unchanged. A
mesh-backed host appends the per-chip table (occupancy + wedged flag
per lane) so client routing sees FLEET headroom: a wedged/quarantined
chip drops out of the advertised capacity within one probe interval.

Multi-tenant front-end (`offload/tenancy.py`): verify frames may carry
a tenant trailer (identity + launch class). Per-tenant admission quotas
layer on the graded admission — a tenant over its depth quota gets the
shed frame instead of service — and admitted work is granted backend
slots in stride-fair cross-tenant order, so one greedy beacon node
cannot starve the rest. Legacy clients (no trailer) account to the
`default` tenant and parse every reply they always did.

Run standalone (`python -m lodestar_tpu.offload.server`) next to the
TPU, with beacon nodes connecting via `client.BlsOffloadClient` over
DCN (SURVEY §2d). `boot_host` is the one way a host is built: on a TPU
backend it puts a `BlsDeviceVerifierPool` behind the wire (`PoolBackend`:
the node's own job cut, launch units and priority classes, on a loop
thread the host owns), and binds the port only after every verify
program a block's jobs can ride has answered its known batches
(`offload/known_answer.py`).

What a verify RPC spends its time on is named as the launches are
(`telemetry.launch` / `telemetry.phase`, PERF.md §3): one ledger entry
`offload_serve` an RPC with the phases `offload.decode`,
`offload.slot_wait`, `offload.backend` and `offload.reply`.
"""

from __future__ import annotations

import asyncio
import threading
import time
from concurrent import futures
from typing import Callable, NamedTuple

import grpc

from lodestar_tpu import telemetry, tracing
from lodestar_tpu.logger import get_logger
from lodestar_tpu.scheduler import (
    AdmissionController,
    AdmissionState,
    OccupancyTracker,
    PriorityClass,
)

from . import (
    DEFAULT_TENANT,
    SET_BYTES,
    decode_sets_ex,
    encode_shed,
    encode_status,
    encode_verdict,
)
from .tenancy import TenantScheduler

__all__ = [
    "BlsOffloadServer",
    "VerifyBackend",
    "PoolBackend",
    "OffloadHost",
    "build_backend",
    "boot_host",
    "SERVICE_NAME",
    "VERIFY_METHOD",
    "STATUS_METHOD",
    "LocalStub",
    "local_transports",
]

SERVICE_NAME = "lodestar.BlsOffload"
VERIFY_METHOD = f"/{SERVICE_NAME}/VerifySignatureSets"
STATUS_METHOD = f"/{SERVICE_NAME}/Status"


def _identity(b: bytes) -> bytes:
    return b


# -- in-process transport seam --------------------------------------------------
#
# The fleet chaos harness (testing/fleet.py) runs N clients against M
# servers IN ONE PROCESS: dialing real sockets there would add kernel
# scheduling noise to a simulation whose whole contract is determinism.
# These shims dispatch a client's stub calls straight into the server's
# handlers — the exact `_verify`/`_status` code paths the wire exercises
# (tenancy, admission, trailing-metadata trace spans, digest-checked
# verdicts), minus the socket. They plug into `BlsOffloadClient`'s
# `transport_wrapper` hook, the same seam the fault injector uses, so a
# `FaultInjector` chains IN FRONT of the local dispatch and every edge
# still sees its faults.


class _LocalContext:
    """Duck-typed grpc.ServicerContext for in-process dispatch: carries
    invocation metadata in, a deadline for `time_remaining()`, and the
    trailing metadata the handler sets back out."""

    def __init__(self, metadata=None, timeout_s: float | None = None, clock=None):
        self._metadata = tuple(metadata or ())
        self._clock = clock if clock is not None else time.monotonic
        self._deadline = self._clock() + timeout_s if timeout_s is not None else None
        self.trailing = ()

    def invocation_metadata(self):
        return self._metadata

    def time_remaining(self):
        if self._deadline is None:
            return None
        return max(0.0, self._deadline - self._clock())

    def set_trailing_metadata(self, md) -> None:
        self.trailing = tuple(md or ())


class _LocalCall:
    """grpc.Call twin for `.with_call`: hands back the trailing metadata
    the handler set on its context."""

    def __init__(self, ctx: _LocalContext):
        self._ctx = ctx

    def trailing_metadata(self):
        return self._ctx.trailing


class LocalStub:
    """In-process unary-unary callable: the shapes the client uses
    (`__call__` and `.with_call`) dispatched straight into a server
    handler on the calling thread."""

    def __init__(self, handler, clock=None):
        self._handler = handler
        self._clock = clock

    def __call__(self, request: bytes, timeout=None, metadata=None) -> bytes:
        resp, _call = self.with_call(request, timeout=timeout, metadata=metadata)
        return resp

    def with_call(self, request: bytes, timeout=None, metadata=None):
        ctx = _LocalContext(metadata, timeout, self._clock)
        return self._handler(request, ctx), _LocalCall(ctx)


def local_transports(servers: dict, *, wrap=None, clock=None):
    """Build a `BlsOffloadClient(transport_wrapper=...)` that serves
    `servers[target]` in-process instead of dialing. `wrap(target,
    method, fn)` — e.g. `FaultInjector.wrap_transport` — chains a fault
    seam in front of the local dispatch; unknown targets keep the dialed
    stub (mixed local/remote deployments still work). `clock` feeds the
    local contexts' `time_remaining()` (a `SimClock.monotonic` under the
    fleet harness)."""

    def wrapper(target: str, method: str, fn):
        server = servers.get(target)
        if server is not None:
            fn = LocalStub(
                server._verify if method == "verify" else server._status, clock=clock
            )
        return fn if wrap is None else wrap(target, method, fn)

    return wrapper


# How long a caller's sets are held for callers that gRPC has accepted
# and that have not handed theirs over (`PoolBackend`). What a launch
# holds is decided by the count (a caller lands a fraction of a
# millisecond after it was accepted); this bounds what a sender that
# stalls between its call's headers and its request can cost the
# others, which without it would wait until that call's deadline.
HANDOVER_HOLD_CAP_S = 0.010

_rpc_tls = threading.local()  # .counted: this handler thread's RPC was counted when gRPC accepted it


def _take_count() -> bool:
    """Whether the RPC this thread handles was counted at acceptance;
    the caller takes the count over."""
    taken = getattr(_rpc_tls, "counted", False)
    _rpc_tls.counted = False
    return taken


class _CountingExecutor(futures.ThreadPoolExecutor):
    """The handler pool of a host whose backend forms launches from
    several callers (`PoolBackend`). gRPC submits an RPC's handler here
    when it accepts the call, before the request has been read and well
    before `_verify` runs, and the handlers of RPCs sent together enter
    `_verify` one after another (the decode holds the GIL): counted
    here, all of them are known to the backend before the first hands
    its sets over. (Should gRPC come to submit a handler later, the
    count is later too and the hand-over merely holds nothing back.) A
    count `_verify` did not take over (a Status probe, a call cancelled
    before its handler ran) is given back when the handler ends."""

    def __init__(self, max_workers: int, backend) -> None:
        super().__init__(max_workers=max_workers, thread_name_prefix="offload-rpc")
        self._backend = backend
        self._workers = max_workers
        self._count_lock = threading.Lock()
        self._handlers = 0  # guarded by: _count_lock — submitted and not yet ended

    def submit(self, fn, /, *args, **kwargs):
        backend = self._backend
        with self._count_lock:
            # a handler that has to queue for a worker waits for an earlier
            # caller's verdict: it is not on its way, `_verify` counts it
            # when it runs
            counted = self._handlers < self._workers
            self._handlers += 1
        if counted:
            backend.accepted()

        def handler():
            _rpc_tls.counted = counted
            try:
                return fn(*args, **kwargs)
            finally:
                with self._count_lock:
                    self._handlers -= 1
                if _take_count():
                    backend.left()

        try:
            return super().submit(handler)
        except BaseException:  # shut down: the handler will not run
            with self._count_lock:
                self._handlers -= 1
            if counted:
                backend.left()
            raise


class _Replied(Exception):
    """Internal _verify control flow: the reply (`out`) is already
    built — skip the verify leg but still run the finally + trailing-
    metadata blocks every reply path shares."""


def fleet_occupancy_permille(chips) -> int:
    """THE fleet-occupancy aggregate: mean over healthy (non-wedged)
    chips, 1000 (pinned) when none is healthy. Shared by the Status
    frame and the admission grader so the two can never diverge."""
    healthy = [int(occ) for occ, wedged in chips if not wedged]
    if not healthy:
        return 1000
    return max(0, min(1000, int(round(sum(healthy) / len(healthy)))))


class _FleetOccupancyView:
    """Admission-grading occupancy for a mesh-backed host: mean busy
    fraction over HEALTHY chips (matching the Status frame's fleet
    field). The server-level tracker measures "any RPC in flight",
    which saturates toward 1.0 under modest multi-chip load and would
    advertise REJECT while chips idle. Falls back to the server-level
    tracker if the chip table errors."""

    def __init__(self, chip_status_fn, fallback: OccupancyTracker) -> None:
        self._fn = chip_status_fn
        self._fallback = fallback

    def occupancy(self) -> float:
        try:
            return fleet_occupancy_permille(self._fn()) / 1000.0
        except Exception:
            return self._fallback.occupancy()


class BlsOffloadServer:
    """gRPC host around a verify backend.

    backend(sets) -> bool may be sync or return an awaitable-free bool;
    can_accept_work() -> bool stays the hard veto (mirrors the pool's
    MAX_JOBS semantics when the backend is a BlsDeviceVerifierPool);
    on top of it the server tracks per-launch occupancy and grades
    admission — injectable `admission` (anything with .state()) lets
    tests and smarter hosts replace the policy.

    `tenancy` (a TenantScheduler, or None to build a default one from
    the tenant_* kwargs) owns per-tenant quotas + stride-fair service.
    `chip_status_fn` () -> [(occupancy_permille, wedged)] feeds the
    Status frame's mesh trailer; default: one pseudo-chip from the
    server-level tracker (single-die hosts advertise exactly what they
    are). Hosts serving a `BlsDeviceVerifierPool` pass the pool mesh's
    `chip_table`."""

    def __init__(
        self,
        backend,
        *,
        can_accept_work=None,
        host: str = "127.0.0.1",
        port: int = 0,
        max_workers: int = 4,
        occupancy_tracker: OccupancyTracker | None = None,
        admission=None,
        shed_bulk_at: float = 0.75,
        reject_at: float = 0.95,
        tenancy: TenantScheduler | None = None,
        tenant_weights: dict[str, int] | None = None,
        tenant_default_weight: int | None = None,
        tenant_slots: int | None = None,
        tenant_shed_depth: int | None = None,
        tenant_reject_depth: int | None = None,
        tenant_metrics=None,
        chip_status_fn=None,
        slot_wait_margin_s: float = 0.5,
        deadline_model=None,
    ) -> None:
        self.backend = backend
        # a backend with priority classes of its own (`PoolBackend`) is
        # handed the trailer's class, and counts the callers on their way
        self._backend_takes_class = bool(getattr(backend, "takes_class", False))
        self._can_accept_work = can_accept_work or (lambda: True)
        self.occupancy = occupancy_tracker or OccupancyTracker()
        self._pending = 0  # guarded by: _pending_lock
        self._pending_lock = threading.Lock()
        self.admission = admission or AdmissionController(
            # a mesh-backed host grades FLEET occupancy, not the
            # single overlapped RPC tracker
            _FleetOccupancyView(chip_status_fn, self.occupancy)
            if chip_status_fn is not None
            else self.occupancy,
            shed_bulk_at=shed_bulk_at,
            reject_at=reject_at,
            depth_fn=self._depth,
            # _pending counts RPCs already ON the gRPC worker threads —
            # the executor queues the rest invisibly, so it never exceeds
            # max_workers. All-workers-busy is therefore the depth signal
            # for SHED_BULK; deeper backlog surfaces as occupancy, which
            # alone drives REJECT (depth-based REJECT is unreachable)
            shed_bulk_depth=max(1, max_workers),
            reject_depth=1 << 30,
            can_accept=self._can_accept_work,
        )
        tenancy_kwargs = {
            # service slots default to the worker count: the scheduler
            # then never blocks beyond what gRPC already bounds, so a
            # single-tenant deployment behaves exactly like the
            # pre-tenancy server
            "slots": max_workers if tenant_slots is None else tenant_slots,
            "weights": tenant_weights,
            "metrics": tenant_metrics,
        }
        if tenant_default_weight is not None:
            tenancy_kwargs["default_weight"] = tenant_default_weight
        if tenant_shed_depth is not None:
            tenancy_kwargs["shed_depth"] = tenant_shed_depth
        if tenant_reject_depth is not None:
            tenancy_kwargs["reject_depth"] = tenant_reject_depth
        self.tenancy = tenancy or TenantScheduler(**tenancy_kwargs)
        self._tenant_metrics = tenant_metrics
        # slot-deadline model (lodestar_tpu/slo.SlotDeadlineModel, or
        # None when the host wasn't launched with --genesis-time): lets
        # a multi-tenant host observe per-tenant remaining deadline
        # slack at verdict time — "which tenant are we serving too late"
        self._deadline_model = deadline_model
        self._chip_status_fn = chip_status_fn
        # reply-wire + expected-backend-launch reserve subtracted from
        # the caller's RPC deadline when waiting for a service slot
        self.slot_wait_margin_s = slot_wait_margin_s
        self.log = get_logger(name="lodestar.offload")
        if self._backend_takes_class:
            self._executor = _CountingExecutor(max_workers, backend)
        else:
            self._executor = futures.ThreadPoolExecutor(
                max_workers=max_workers, thread_name_prefix="offload-rpc"
            )
        self._server = grpc.server(self._executor)
        handlers = {
            "VerifySignatureSets": grpc.unary_unary_rpc_method_handler(
                self._verify, request_deserializer=_identity, response_serializer=_identity
            ),
            "Status": grpc.unary_unary_rpc_method_handler(
                self._status, request_deserializer=_identity, response_serializer=_identity
            ),
        }
        self._server.add_generic_rpc_handlers(
            (grpc.method_handlers_generic_handler(SERVICE_NAME, handlers),)
        )
        self.port = self._server.add_insecure_port(f"{host}:{port}")
        self.host = host

    def _depth(self) -> int:
        """In-flight RPC count for admission/status — locked, so the
        grader never folds a torn read into its thresholds."""
        with self._pending_lock:
            return self._pending

    def _chip_table(self) -> list[tuple[int, bool]]:
        """Per-chip (occupancy_permille, wedged) for the Status mesh
        trailer; errors degrade to the single-die view rather than
        failing the probe."""
        if self._chip_status_fn is not None:
            try:
                return [(int(occ), bool(w)) for occ, w in self._chip_status_fn()]
            except Exception:
                pass
        return [(self.occupancy.occupancy_permille(), False)]

    # -- handlers --------------------------------------------------------------

    def _verify(self, request: bytes, context) -> bytes:
        # a backend that forms launches from several callers counts this
        # one as on its way until it hands its sets over or leaves: callers
        # accepted together reach its queue together (`PoolBackend`)
        counted = self._backend_takes_class
        if counted and not _take_count():
            self.backend.accepted()  # reached in process (`LocalStub`): nobody counted it earlier

        def leave() -> None:
            nonlocal counted
            if counted:
                counted = False
                self.backend.left()

        # caller-propagated trace context: when present, record the
        # server-side decode/verify spans and ship them back in trailing
        # metadata so the client grafts them under its RPC span
        hdr = None
        try:
            for k, v in context.invocation_metadata() or ():
                if k == tracing.TRACE_CONTEXT_KEY:
                    hdr = v
        except Exception:
            hdr = None
        rec = tracing.remote_recorder(hdr)
        with self._pending_lock:
            self._pending += 1
        tenant = DEFAULT_TENANT
        granted = False
        # the frame's own count, before the decode that checks it: the
        # ledger entry's size class
        claimed = min(int.from_bytes(request[:4], "little"), len(request) // SET_BYTES)
        with telemetry.launch("offload_serve", telemetry.size_class_of(claimed)):
            try:
                with rec.span("offload_decode"), telemetry.phase("offload.decode"):
                    sets, trailer = decode_sets_ex(request)
                priority = PriorityClass.API
                if trailer is not None:
                    tenant = trailer.tenant
                    priority = trailer.priority
                # per-tenant quota grading, then the stride-fair slot wait —
                # both sheds answer with the shed frame (alive, refusing),
                # never an error frame (sick)
                if not self.tenancy.admits(tenant, priority):
                    state = self.tenancy.admission_for(tenant)
                    self.tenancy.count_shed(tenant, priority, "quota")
                    self.log.info(
                        "offload admission shed",
                        {"tenant": tenant, "class": priority.label, "state": state.label},
                    )
                    # NOT an early return: shed replies fall through to the
                    # trailing-metadata block too — a shed storm is exactly
                    # when the operator needs the server-side trace legs
                    out = encode_shed(
                        state, f"tenant quota ({state.label})", request=request
                    )
                    raise _Replied()
                # the slot wait must resolve INSIDE the caller's RPC
                # deadline: a shed frame the client never receives becomes
                # DEADLINE_EXCEEDED on its side — a transport failure that
                # charges the endpoint's breaker as sick, exactly what the
                # shed frame exists to prevent. The margin must also cover
                # the BACKEND launch after a grant — a grant at deadline
                # minus epsilon converts the shed into the same
                # DEADLINE_EXCEEDED mid-verify. slot_wait_margin_s should
                # therefore sit above the host's typical launch time; no
                # deadline metadata = scheduler cap.
                slot_wait = None
                try:
                    remaining = context.time_remaining()
                    if remaining is not None:
                        slot_wait = max(0.0, remaining - self.slot_wait_margin_s)
                except Exception:
                    pass
                with telemetry.phase("offload.slot_wait"):
                    granted = counted and self.tenancy.acquire(tenant, priority, timeout_s=0.0)
                    if not granted:
                        # no slot free: this caller waits for an earlier
                        # caller's verdict and is no longer on its way
                        leave()
                        granted = self.tenancy.acquire(tenant, priority, timeout_s=slot_wait)
                if not granted:
                    self.tenancy.count_shed(tenant, priority, "slot_timeout")
                    out = encode_shed(
                        AdmissionState.REJECT,
                        "service slot wait timed out",
                        request=request,
                    )
                    raise _Replied()
                # tenant identity rides the server-side span home: a Chrome
                # trace of a multi-tenant slot names who each verify served
                with rec.span("offload_device_verify", sets=len(sets), tenant=tenant):
                    with telemetry.phase("offload.backend"), self.occupancy.launch():
                        if self._backend_takes_class:
                            handed, counted = counted, False  # the backend takes the count over
                            ok = bool(self.backend(sets, priority, counted=handed))
                        else:
                            ok = bool(self.backend(sets))
                m = self._tenant_metrics
                if m is not None:
                    m.served_sets.labels(tenant).inc(len(sets))
                    dm = self._deadline_model
                    if dm is not None:
                        try:
                            # anchored at the wall-clock slot: the wire
                            # trailer carries tenant+class, not the subject
                            # slot, so the host measures "slack left in the
                            # slot being served right now" — negative means
                            # this tenant's verdicts are landing past the
                            # class cutoff
                            m.slack.labels(tenant, priority.label).observe(
                                dm.slack_s(priority)
                            )
                        except Exception:
                            pass  # slack observation must never fail a verdict
                # digest-checked verdict: binds this reply to this request
                # frame so corruption/splicing fails closed at the client
                with telemetry.phase("offload.reply"):
                    out = encode_verdict(ok, request=request)
            except _Replied:
                pass  # `out` already holds the shed frame
            except Exception as e:  # error frame, not a transport abort
                self.log.warn("verify job failed", {"error": str(e), "tenant": tenant})
                out = encode_verdict(None, error=f"{type(e).__name__}: {e}")
            finally:
                leave()  # a caller that never reached the backend
                if granted:
                    self.tenancy.release(tenant)
                with self._pending_lock:
                    self._pending -= 1
            with telemetry.phase("offload.reply"):
                payload = rec.serialize()
                if payload:
                    try:
                        context.set_trailing_metadata(((tracing.TRACE_SPANS_KEY, payload),))
                    except Exception:
                        pass  # a metadata-less transport must not fail the verdict
        return out

    def _status(self, request: bytes, context) -> bytes:
        chips = self._chip_table()
        # fleet occupancy (healthy-chip mean, same helper the admission
        # grader uses): legacy v1-prefix readers also rank this host by
        # its headroom, not one die
        return encode_status(
            occupancy_permille=fleet_occupancy_permille(chips),
            queue_depth=self._depth(),
            admission=self.admission.state(),
            chips=chips,
            tenant_capable=True,
        )

    # -- lifecycle -------------------------------------------------------------

    def start(self) -> None:
        self._server.start()
        self.log.info("offload service up", {"port": self.port})

    def stop(self, grace: float = 0.5) -> None:
        """Returns once the RPCs in flight are answered or `grace` is
        over, with the handler threads told to end."""
        self.tenancy.close()
        self._server.stop(grace).wait()
        self._executor.shutdown(wait=False)


class PoolBackend:
    """A `BlsDeviceVerifierPool` behind the wire, on an event-loop thread
    the host owns: each RPC is one non-batchable job list of its
    trailer's class (the wire carries no `batchable`; an RPC is a job
    whose verdict its caller needs), so four nodes' blocks are cut,
    queued by class and formed into launch units exactly as one node's
    own pool would. The gRPC handler threads block on the loop's answer;
    the launches run on the loop's executor threads.

    Callers reach the pool from several threads, a fraction of a
    millisecond apart, where a node's own callers enqueue from the loop
    thread before the runner wakes. So that what a launch holds follows
    from which callers came together and not from which thread was
    first, the host counts the callers on their way (`accepted`, from
    gRPC's acceptance of the call; `left` for one that will not come),
    a caller's sets are held while others are on their way, and the
    last one enqueues all of them in one callback of the loop: their
    jobs are in the queue before the runner wakes. No caller is held
    longer than `HANDOVER_HOLD_CAP_S`."""

    takes_class = True  # BlsOffloadServer hands `(sets, priority, counted=)` and counts its callers

    def __init__(self, pool) -> None:
        self.pool = pool
        self._loop: asyncio.AbstractEventLoop | None = None  # guarded by: start/close (one owner thread)
        self._thread: threading.Thread | None = None  # guarded by: start/close (one owner thread)
        self._lock = threading.Lock()
        self._on_the_way = 0  # guarded by: _lock — callers accepted and not yet handed over
        self._held: list[tuple] = []  # guarded by: _lock — (sets, opts, answer) of callers handed over and not yet enqueued

    @property
    def mesh(self):
        return self.pool.mesh

    def start(self) -> None:
        self._loop = asyncio.new_event_loop()
        self._thread = threading.Thread(
            target=self._loop.run_forever, name="offload-pool-loop", daemon=True
        )
        self._thread.start()

    def run(self, coro, timeout: float | None = None):
        """Run `coro` on the pool's loop from another thread; its result."""
        return asyncio.run_coroutine_threadsafe(coro, self._loop).result(timeout)

    def accepted(self) -> None:
        """Any thread: a caller is on its way. Paired with exactly one
        `__call__(..., counted=True)` or `left()`, and nothing that can
        wait on a verdict may lie between (a caller about to wait for a
        service slot leaves first)."""
        with self._lock:
            self._on_the_way += 1

    def left(self) -> None:
        """Any thread: a counted caller will not hand anything over."""
        with self._lock:
            self._on_the_way -= 1
            batch = self._take_held()
        self._enqueue_soon(batch)

    def __call__(self, sets, priority: PriorityClass = PriorityClass.API, counted: bool = False) -> bool:
        from lodestar_tpu.chain.bls import VerifySignatureOpts

        answer: futures.Future = futures.Future()
        # gRPC accepts calls on a thread of its own, which needs the
        # interpreter lock that this handler has held since its request was
        # read (a decode: a fraction of a millisecond). Give the lock up once
        # before looking, so that calls accepted meanwhile are counted first.
        # No time passes here: it is a yield, not a wait.
        time.sleep(0)
        with self._lock:
            self._held.append((sets, VerifySignatureOpts(batchable=False, priority=priority), answer))
            if counted:
                self._on_the_way -= 1
            batch = self._take_held()
        self._enqueue_soon(batch)
        if not batch:
            try:
                return answer.result(HANDOVER_HOLD_CAP_S)
            except TimeoutError:
                with self._lock:
                    # still held: a sender has stalled, go on with what has
                    # come (otherwise the verdict is simply not in yet)
                    if any(a is answer for _, _, a in self._held):
                        batch, self._held = self._held, []
                self._enqueue_soon(batch)
        return answer.result()

    def _take_held(self) -> list[tuple]:  # lint: allow(lock-discipline) — every caller holds _lock
        """What is held, once nobody is on the way; nothing before."""
        if self._on_the_way > 0:
            return []
        batch, self._held = self._held, []
        return batch

    def _enqueue_soon(self, batch: list[tuple]) -> None:
        if batch:
            self._loop.call_soon_threadsafe(self._enqueue, batch)

    def _enqueue(self, batch: list[tuple]) -> None:
        """Loop thread: every caller of `batch` enters the pool in this
        one callback, so each one's jobs are queued (the first step of
        its task) before the runner, woken by the first, takes a
        package."""
        for sets, opts, answer in batch:
            task = self._loop.create_task(self.pool.verify_signature_sets(sets, opts))
            task.add_done_callback(lambda t, answer=answer: _settle(answer, t))

    def can_accept_work(self) -> bool:
        return self.pool.can_accept_work()

    def close(self) -> None:
        """Close the pool, end the loop and join its thread."""
        if self._loop is None:
            return
        try:
            self.run(self.pool.close(), timeout=30.0)
            self.run(self._loop.shutdown_default_executor(), timeout=30.0)
        finally:
            self._loop.call_soon_threadsafe(self._loop.stop)
            self._thread.join(30.0)
            if not self._thread.is_alive():
                self._loop.close()
            self._loop = None


def _settle(answer: futures.Future, task: asyncio.Task) -> None:
    """Hand a finished task's outcome to the thread that waits on `answer`."""
    if task.cancelled():
        answer.cancel()
    elif task.exception() is not None:
        answer.set_exception(task.exception())
    else:
        answer.set_result(task.result())


class VerifyBackend(NamedTuple):
    """What `build_backend` resolved: the verify callable (a
    `PoolBackend` on a device, the CPU oracle otherwise), the mesh
    behind it (None on the CPU oracle; its `chip_table` feeds the Status
    frame) and the description the process logs once at start."""

    verify: Callable
    mesh: object | None
    description: dict

    @property
    def chip_status_fn(self) -> Callable | None:
        return None if self.mesh is None else self.mesh.chip_table


def build_backend(
    bls_mesh: str = "auto",
    *,
    pool_factory: Callable | None = None,
    sched_metrics=None,
    pipeline_metrics=None,
) -> VerifyBackend:
    """The standalone host's verify backend, chosen from what the
    process observes. Initialises the JAX backend, i.e. takes the chip
    (`utils.probe_accelerator`; a chip owned by another process raises
    `AcceleratorUnavailable`).

    On a TPU backend a `BlsDeviceVerifierPool` with default options
    serves, whatever the device count: `auto`/`on` give one launch lane
    per chip plus the sharded collective when more than one is visible,
    `off` (as on the node) one lane on the device. On a CPU backend the
    CPU oracle serves — a jax-on-CPU lane would trade it for
    minutes-long first-use XLA compiles — unless `on` forces the mesh
    (tests on the virtual mesh). `pool_factory()` puts another pool
    behind the wire on any backend (tests: a pool whose lane is faked).
    The `PoolBackend` comes back not yet started: `boot_host` starts
    and warms it."""
    from lodestar_tpu.utils import probe_accelerator

    accel = probe_accelerator()
    if pool_factory is None and accel["platform"] != "tpu" and bls_mesh != "on":
        from lodestar_tpu.crypto.bls.api import verify_signature_sets

        return VerifyBackend(
            verify_signature_sets, None, {**accel, "verifier": "cpu-oracle", "lanes": 0}
        )
    if pool_factory is not None:
        pool = pool_factory()
    else:
        from lodestar_tpu.chain.bls import BlsDeviceVerifierPool

        # the pool keeps mesh_launch's per-chip wedge accounting and
        # cross-lane error retry (a sick chip trips ITS breaker, drops out
        # of the advertised chip table, and self-offers after the reset
        # delay); the server's slot scheduler bounds concurrency per
        # tenant above it
        pool = BlsDeviceVerifierPool(
            mesh_mode=bls_mesh, sched_metrics=sched_metrics, pipeline_metrics=pipeline_metrics
        )
    return VerifyBackend(
        PoolBackend(pool), pool.mesh, {**accel, "verifier": "device", "lanes": len(pool.mesh)}
    )


class OffloadHost:
    """What `boot_host` built: the server (started, its port bound), the
    backend it resolved, the host's metric registry and what the warm
    start answered. `stop()` ends all of it."""

    def __init__(self, server, backend: VerifyBackend, creator, metrics_server, warmed: list[dict]):
        self.server = server
        self.backend = backend
        self.creator = creator  # RegistryMetricCreator: the host's own registry
        self.metrics_server = metrics_server
        self.warmed = warmed  # one record a verify program that answered its known batches

    @property
    def port(self) -> int:
        return self.server.port

    @property
    def pool(self):
        """The pool behind the wire, or None where the CPU oracle serves."""
        return getattr(self.backend.verify, "pool", None)

    def stop(self, grace: float = 0.5) -> None:
        self.server.stop(grace)
        close = getattr(self.backend.verify, "close", None)
        if close is not None:
            close()
        if self.metrics_server is not None:
            self.metrics_server.stop()


def boot_host(
    *,
    port: int = 50051,
    host: str = "127.0.0.1",
    workers: int = 4,
    metrics_port: int = 0,
    bls_mesh: str = "auto",
    tenant_weights: dict[str, int] | None = None,
    tenant_default_weight: int = 1,
    tenant_slots: int | None = None,
    tenant_shed_depth: int | None = None,
    tenant_reject_depth: int | None = None,
    deadline_model=None,
    pool_factory: Callable | None = None,
) -> OffloadHost:
    """Build and start an offload host: THE way one is built (`main()`,
    `chip_smoke.py`, `perfbench/entries/offload.py`, the tests). The
    defaults are the command's.

    Order: the compile cache, the backend as the process observes it
    (`build_backend`), the host's metric families on a registry of its
    own (served where `metrics_port` is set), then, where a pool serves,
    the known-answer warm start (`offload/known_answer.py`: one log line
    a program; a program that disagrees with the CPU oracle raises
    `KnownAnswerError` and nothing is served), and only then the server:
    its port is bound after every warmed program has answered, so an RPC
    sent earlier is refused by the transport and not answered late."""
    from lodestar_tpu.metrics import (
        MetricsServer,
        RegistryMetricCreator,
        create_bls_pipeline_metrics,
        create_bls_prep_metrics,
        create_device_launch_metrics,
        create_sched_metrics,
        create_tenant_metrics,
    )
    from lodestar_tpu.utils import enable_compile_cache

    enable_compile_cache()
    log = get_logger(name="lodestar.offload")
    creator = RegistryMetricCreator()
    tenant_metrics = create_tenant_metrics(creator)
    backend = build_backend(
        bls_mesh,
        pool_factory=pool_factory,
        sched_metrics=create_sched_metrics(creator),
        pipeline_metrics=create_bls_pipeline_metrics(creator),
    )
    # what this process runs on and what serves, once, at start
    log.info("offload device runtime", backend.description)
    metrics_server = None
    if metrics_port:
        metrics_server = MetricsServer(creator, port=metrics_port)
        metrics_server.start()

    warmed: list[dict] = []
    pooled = isinstance(backend.verify, PoolBackend)
    try:
        if pooled:
            # the process-global device seams report to this host's registry,
            # as `node.configure_device_runtime` points them at a node's
            from lodestar_tpu.models.batch_verify import configure_device_prep

            from .known_answer import check_known_answers

            configure_device_prep(create_bls_prep_metrics(creator))
            telemetry.configure_launch_telemetry(metrics=create_device_launch_metrics(creator))
            backend.verify.start()
            warmed = backend.verify.run(check_known_answers(backend.verify.pool, log=log))
        server = BlsOffloadServer(
            backend.verify,
            can_accept_work=backend.verify.can_accept_work if pooled else None,
            host=host,
            port=port,
            max_workers=workers,
            tenant_weights=tenant_weights,
            tenant_default_weight=tenant_default_weight,
            # default: workers (never queues — single-tenant hosts behave
            # exactly like the pre-tenancy server); fairness enforcement
            # needs slots < concurrent demand, e.g. the mesh's chip count
            tenant_slots=workers if tenant_slots is None else tenant_slots,
            tenant_shed_depth=tenant_shed_depth,
            tenant_reject_depth=tenant_reject_depth,
            tenant_metrics=tenant_metrics,
            chip_status_fn=backend.chip_status_fn,
            deadline_model=deadline_model,
        )
        server.start()
    except BaseException:
        if pooled:
            backend.verify.close()
        if metrics_server is not None:
            metrics_server.stop()
        raise
    return OffloadHost(server, backend, creator, metrics_server, warmed)


def main() -> int:
    """Standalone entry: host the repo's own verifier through
    `boot_host` (the device verifier pool on a TPU backend, the CPU
    oracle on a CPU backend)."""
    import argparse
    import json
    import sys

    from .tenancy import (
        DEFAULT_TENANT_REJECT_DEPTH,
        DEFAULT_TENANT_SHED_DEPTH,
        parse_tenant_weights,
    )

    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, default=50051)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument(
        "--metrics-port", type=int, default=0,
        help="serve the host's registry (lodestar_offload_tenant_*, lodestar_bls_prep_*, "
        "lodestar_sched_*, lodestar_device_launch_*) and /healthz here (0 = off)",
    )
    ap.add_argument(
        "--bls-mesh", choices=["auto", "on", "off"], default="auto",
        help="lane layout of the served device verifier: auto = one launch "
        "lane per chip plus data-parallel bulk sharding when the backend is "
        "a TPU with more than one device, on = the same on any backend, "
        "off = one lane on the device. A CPU backend serves the CPU oracle "
        "unless this is on.",
    )
    ap.add_argument(
        "--tenant-weight", action="append", default=[], metavar="NAME=WEIGHT",
        help="stride-fair service share for a tenant (repeatable); unlisted "
        "tenants get --tenant-default-weight",
    )
    ap.add_argument("--tenant-default-weight", type=int, default=1)
    ap.add_argument(
        "--tenant-slots", type=int, default=None,
        help="concurrent backend service slots the stride scheduler grants "
        "(default: --workers, which never queues — set BELOW --workers to "
        "make cross-tenant fairness and quota sheds actually arbitrate; "
        "e.g. the chip count of the served mesh)",
    )
    ap.add_argument(
        "--tenant-shed-depth", type=int, default=DEFAULT_TENANT_SHED_DEPTH,
        help="per-tenant pending+running depth at which bulk classes shed",
    )
    ap.add_argument(
        "--tenant-reject-depth", type=int, default=DEFAULT_TENANT_REJECT_DEPTH,
        help="per-tenant pending+running depth at which everything sheds",
    )
    ap.add_argument(
        "--genesis-time", type=int, default=None,
        help="chain genesis timestamp (unix seconds): enables the "
        "lodestar_offload_tenant_slack_seconds histogram — per-tenant "
        "remaining slot-deadline slack at verdict time",
    )
    ap.add_argument(
        "--seconds-per-slot", type=int, default=12,
        help="slot length for the deadline model (with --genesis-time)",
    )
    args = ap.parse_args()

    from lodestar_tpu.utils import AcceleratorUnavailable

    from .known_answer import KnownAnswerError

    deadline_model = None
    if args.genesis_time is not None:
        from lodestar_tpu.slo import SlotDeadlineModel

        deadline_model = SlotDeadlineModel(
            genesis_time=args.genesis_time,
            seconds_per_slot=args.seconds_per_slot,
        )
    try:
        host = boot_host(
            port=args.port,
            workers=args.workers,
            metrics_port=args.metrics_port,
            bls_mesh=args.bls_mesh,
            tenant_weights=parse_tenant_weights(args.tenant_weight),
            tenant_default_weight=args.tenant_default_weight,
            tenant_slots=args.tenant_slots,
            tenant_shed_depth=args.tenant_shed_depth,
            tenant_reject_depth=args.tenant_reject_depth,
            deadline_model=deadline_model,
        )
    except (AcceleratorUnavailable, KnownAnswerError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    # surface the effective tenancy config once, for operators' logs
    host.server.log.info(
        "offload tenancy",
        {
            "weights": json.dumps(parse_tenant_weights(args.tenant_weight)),
            "default_weight": args.tenant_default_weight,
        },
    )
    import signal

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    stop.wait()
    host.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
