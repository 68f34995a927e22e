"""BeaconNode composition root (reference `beacon-node/src/node/nodejs.ts:141`).

`BeaconNode.init` wires the full runtime in the reference's order: db →
metrics (+ scrape server) → chain (BLS verifier pool + fork choice +
pools) → clock → REST API → status notifier. `close()` runs the abort
cascade in reverse (`nodejs.ts:146-152`).
"""

from __future__ import annotations

import asyncio

from lodestar_tpu.api import BeaconApiImpl, BeaconRestApiServer
from lodestar_tpu.chain.bls import BlsSingleThreadVerifier, IBlsVerifier
from lodestar_tpu.chain.chain import BeaconChain
from lodestar_tpu.chain.clock import Clock
from lodestar_tpu.db import DbController, FileDbController, MemoryDbController
from lodestar_tpu.logger import get_logger
from lodestar_tpu.metrics import BeaconMetrics, MetricsServer, create_metrics
from lodestar_tpu.params import BeaconPreset, active_preset

__all__ = ["BeaconNode", "BeaconNodeOptions", "configure_device_runtime"]


class BeaconNodeOptions:
    def __init__(
        self,
        *,
        db_path: str | None = None,
        rest_port: int = 9596,
        rest_enabled: bool = True,
        metrics_port: int = 8008,
        metrics_enabled: bool = False,
        use_device_verifier: bool | None = None,
        manual_clock: bool = False,
        p2p_enabled: bool = False,
        p2p_port: int = 0,
        bootnodes: list[tuple[str, int]] | None = None,
        on_shutdown_request=None,
        tracing_enabled: bool = False,
        tracing_slow_slot_ms: float = 2000.0,
        tracing_export_dir: str | None = None,
        tracing_export_max_files: int = 256,
        tracing_export_max_age_s: float | None = None,
        offload_endpoints: list[str] | None = None,
        offload_breaker_threshold: int | None = None,
        offload_breaker_reset_s: float | None = None,
        offload_hedge_delay_ms: float | None = None,
        offload_fallback: str = "cpu",
        offload_audit_rate: float | None = None,
        offload_audit_budget: float | None = None,
        offload_audit_via: str = "cpu",
        offload_audit_seed: int | None = None,
        offload_quarantine_cooloff_s: float | None = None,
        offload_unquarantine: list[str] | None = None,
        scheduler_enabled: bool = True,
        htr_device: str = "auto",
        bls_mesh: str = "auto",
        offload_tenant: str | None = None,
        launch_telemetry: str = "auto",
        slo_enabled: bool = True,
        slo_slack_floor_ms: float = 0.0,
    ):
        self.db_path = db_path
        self.rest_port = rest_port
        self.rest_enabled = rest_enabled
        self.metrics_port = metrics_port
        self.metrics_enabled = metrics_enabled
        # local BLS verifier when no offload endpoint is given: None
        # (the default) resolves from the backend node init observes —
        # BlsDeviceVerifierPool on a TPU, BlsSingleThreadVerifier on a
        # CPU; True/False force (tests boot the device pool on the CPU)
        self.use_device_verifier = use_device_verifier
        self.manual_clock = manual_clock
        self.p2p_enabled = p2p_enabled
        self.p2p_port = p2p_port
        self.bootnodes = list(bootnodes or [])
        # fatal-error callback (reference ProcessShutdownCallback): the
        # embedding process decides how to die; None = log only
        self.on_shutdown_request = on_shutdown_request
        # per-slot pipeline tracing (lodestar_tpu.tracing): off by default
        self.tracing_enabled = tracing_enabled
        self.tracing_slow_slot_ms = tracing_slow_slot_ms
        self.tracing_export_dir = tracing_export_dir
        self.tracing_export_max_files = tracing_export_max_files
        self.tracing_export_max_age_s = tracing_export_max_age_s
        # BLS offload endpoints (host:port); non-empty routes the chain's
        # verifier through BlsOffloadClient with load-aware routing
        self.offload_endpoints = list(offload_endpoints or [])
        # per-endpoint circuit breaker tuning; None = the resilience
        # module's defaults (the one definition of those numbers)
        from lodestar_tpu.offload.resilience import (
            DEFAULT_FAILURE_THRESHOLD,
            DEFAULT_RESET_TIMEOUT_S,
        )

        self.offload_breaker_threshold = (
            DEFAULT_FAILURE_THRESHOLD
            if offload_breaker_threshold is None
            else offload_breaker_threshold
        )
        self.offload_breaker_reset_s = (
            DEFAULT_RESET_TIMEOUT_S
            if offload_breaker_reset_s is None
            else offload_breaker_reset_s
        )
        # true hedged requests: a concurrent second RPC fires when the
        # primary is silent past this delay (first verdict wins, the
        # loser's verdict is discarded). None/<=0 = sequential
        # split-budget retry (the legacy hedge). The shipped default
        # lives in resilience.py with TUNING.md provenance.
        self.offload_hedge_delay_ms = (
            None
            if offload_hedge_delay_ms is None or offload_hedge_delay_ms <= 0
            else float(offload_hedge_delay_ms)
        )
        # degradation chain below the offload client: "cpu" (offload →
        # CPU oracle), "device" (offload → local device pool → CPU), or
        # "none" (offload errors reject blocks until the host returns)
        if offload_fallback not in ("none", "cpu", "device"):
            raise ValueError(f"offload_fallback must be none|cpu|device, got {offload_fallback!r}")
        self.offload_fallback = offload_fallback
        # Byzantine audit (offload/audit.py): randomized cross-checking
        # of offload verdicts against an independent verifier. rate 0
        # disables; "helper" re-verifies on a second endpoint (CPU
        # arbitration) when more than one is configured.
        from lodestar_tpu.offload.audit import DEFAULT_AUDIT_BUDGET, DEFAULT_AUDIT_RATE
        from lodestar_tpu.offload.resilience import DEFAULT_QUARANTINE_COOLOFF_S

        self.offload_audit_rate = (
            DEFAULT_AUDIT_RATE if offload_audit_rate is None else offload_audit_rate
        )
        self.offload_audit_budget = (
            DEFAULT_AUDIT_BUDGET if offload_audit_budget is None else offload_audit_budget
        )
        if offload_audit_via not in ("cpu", "helper"):
            raise ValueError(f"offload_audit_via must be cpu|helper, got {offload_audit_via!r}")
        self.offload_audit_via = offload_audit_via
        self.offload_audit_seed = offload_audit_seed
        # quarantine cool-off after a Byzantine event; 0 = until the
        # operator lifts it (--offload-unquarantine)
        self.offload_quarantine_cooloff_s = (
            DEFAULT_QUARANTINE_COOLOFF_S
            if offload_quarantine_cooloff_s is None
            else offload_quarantine_cooloff_s
        )
        self.offload_unquarantine = list(offload_unquarantine or [])
        # device work scheduler (lodestar_tpu.scheduler) for the in-process
        # pool; False restores FIFO launches (debug/comparison only)
        self.scheduler_enabled = scheduler_enabled
        # state hashTreeRoot placement (ssz/device_htr.py collector):
        # "auto" flushes dirty subtrees through the device SHA-256
        # kernel only when the Pallas backend is live; "on"/"off" force.
        # Device errors degrade to the CPU incremental path (counted).
        from lodestar_tpu.ssz.device_htr import HTR_MODES

        if htr_device not in HTR_MODES:
            raise ValueError(
                f"htr_device must be one of {HTR_MODES}, got {htr_device!r}"
            )
        self.htr_device = htr_device
        # verifier mesh placement (chain/bls/mesh.py): "auto" serves the
        # local pool on per-chip launch lanes only when the Pallas
        # backend is live and >1 device is visible; "on"/"off" force.
        # A wedged chip degrades the pool to the remaining lanes.
        from lodestar_tpu.chain.bls.mesh import MESH_MODES

        if bls_mesh not in MESH_MODES:
            raise ValueError(f"bls_mesh must be one of {MESH_MODES}, got {bls_mesh!r}")
        self.bls_mesh = bls_mesh
        # tenant identity for the offload client (multi-tenant serving
        # hosts meter quotas and stride-fair shares per tenant) —
        # validated here so a config typo is a startup error, not a
        # per-verify offload outage
        if offload_tenant is not None:
            from lodestar_tpu.offload import validate_tenant

            try:
                validate_tenant(offload_tenant)
            except Exception as e:
                raise ValueError(f"offload_tenant: {e}") from e
        self.offload_tenant = offload_tenant
        # device launch telemetry (lodestar_tpu/telemetry.py): per-
        # dispatch wall time / program / size class / compile detection
        # at the counted launch seams. "auto" records once the node
        # installs the metric sink (i.e. on every node); "off" leaves
        # the seams one flag check from free. Validated against the
        # telemetry module's canonical tuple (cli.py keeps a literal
        # copy per the argparse-import doctrine)
        from lodestar_tpu.telemetry import TELEMETRY_MODES

        if launch_telemetry not in TELEMETRY_MODES:
            raise ValueError(
                f"launch_telemetry must be one of {TELEMETRY_MODES}, got {launch_telemetry!r}"
            )
        self.launch_telemetry = launch_telemetry
        # slot-deadline SLO accounting (lodestar_tpu/slo): per-priority-
        # class deadline slack at enqueue/dispatch/verdict plus the
        # good/total SLI pairs. The slack floor widens the miss margin
        # (0 = miss only when the deadline is actually blown); negative
        # would silently forgive real misses, so it is a startup error
        if slo_slack_floor_ms < 0:
            raise ValueError(
                f"slo_slack_floor_ms must be >= 0, got {slo_slack_floor_ms!r}"
            )
        self.slo_enabled = slo_enabled
        self.slo_slack_floor_ms = slo_slack_floor_ms


def _device_pool(opts: BeaconNodeOptions, metrics: BeaconMetrics):
    """The in-process device verifier as this node's options shape it."""
    from lodestar_tpu.chain.bls import BlsDeviceVerifierPool

    pool = BlsDeviceVerifierPool(
        scheduler_enabled=opts.scheduler_enabled,
        sched_metrics=metrics.sched,
        mesh_mode=opts.bls_mesh,
        pipeline_metrics=metrics.bls_pipeline,
    )
    if pool.pubkey_table is not None:
        pool.pubkey_table.entries_gauge = metrics.bls_prep.table_entries
    return pool


def load_pubkey_table(bls, anchor_state) -> None:
    """Fill a device verifier's pubkey table from the anchor state's
    registry, where its lanes sum signers from it (the reference builds
    `index2pubkey` from the anchor state the same way). The registry's
    keys passed KeyValidate when they were deposited, so they are
    decompressed without a second subgroup check (`trusted`)."""
    if getattr(bls, "takes_indexed_sets", False):
        validators = anchor_state.validators
        bls.pubkey_table.extend(
            [bytes(validators[i].pubkey) for i in range(len(validators))], trusted=True
        )


def _offload_verifier(opts: BeaconNodeOptions, metrics: BeaconMetrics) -> IBlsVerifier:
    """The verifier of a node with `--bls-offload` endpoints, as its
    options shape it: the breaker-guarded client with its Byzantine
    auditor and persisted quarantines, then the verified degradation
    chain (every layer re-verifies; errors degrade, verdicts are
    final)."""
    from lodestar_tpu.offload.client import BlsOffloadClient

    # Byzantine audit: seeded sampler + background
    # re-verification. Forensics + quarantine persistence:
    # prefer the tracing export dir (next to the slow-slot
    # dumps), else a subdirectory of the data dir — only a
    # fully in-memory node runs without persistence
    audit_dir = opts.tracing_export_dir
    if audit_dir is None and opts.db_path:
        import os as _os

        # db_path is the WAL *file* (cli passes <dir>/wal.log):
        # persist beside it, inside the data directory
        audit_dir = _os.path.join(
            _os.path.dirname(_os.path.abspath(opts.db_path)), "offload-audit"
        )
    from lodestar_tpu.offload.audit import AuditSampler, OffloadAuditor

    # ALWAYS constructed: with --offload-audit-rate 0 it is
    # passive (no sampling thread) but still owns quarantine
    # persistence, gauges and rehabilitation — a standing
    # Byzantine verdict keeps its lifecycle regardless of the
    # sampling knob
    auditor = OffloadAuditor(
        sampler=AuditSampler(
            opts.offload_audit_rate, seed=opts.offload_audit_seed
        ),
        budget=opts.offload_audit_budget,
        dump_dir=audit_dir,
        quarantine_cooloff_s=opts.offload_quarantine_cooloff_s or None,
        metrics=metrics.audit,
        start=opts.offload_audit_rate > 0,
    )
    client = BlsOffloadClient(
        opts.offload_endpoints,
        breaker_threshold=opts.offload_breaker_threshold,
        breaker_reset_s=opts.offload_breaker_reset_s,
        hedge_delay_ms=opts.offload_hedge_delay_ms,
        metrics=metrics.resilience,
        auditor=auditor,
        quarantine_cooloff_s=opts.offload_quarantine_cooloff_s or None,
        tenant=opts.offload_tenant,
    )
    if opts.offload_audit_via == "helper" and len(opts.offload_endpoints) > 1:
        from lodestar_tpu.offload.audit import cross_helper_reference

        auditor.set_reference(cross_helper_reference(client))
    # operator lifts first, then re-apply persisted Byzantine
    # quarantines — a restart must not silently re-trust a caught
    # liar, and that holds even at --offload-audit-rate 0 (the
    # passive auditor still reads/writes the quarantine file)
    persisted_before = set(auditor.load_quarantined())
    for target in opts.offload_unquarantine:
        if target not in opts.offload_endpoints and target not in persisted_before:
            # a typo'd lift silently no-opping would leave the
            # operator believing the quarantine was cleared
            client.log.warn(
                "--offload-unquarantine target matches no configured "
                "endpoint and no persisted quarantine record",
                {"target": target},
            )
            continue
        # clears breaker state AND (via the bound auditor) the
        # persisted record — the lift logic lives in one place
        client.unquarantine_endpoint(target)
    import time as _time

    from lodestar_tpu.offload.audit import remaining_cooloff

    cool = opts.offload_quarantine_cooloff_s or None
    now = _time.time()
    for target, rec in auditor.load_quarantined().items():
        if target in opts.offload_endpoints:
            client.quarantine_endpoint(
                target,
                cooloff_s=remaining_cooloff(rec, cool, now),
                reason="persisted_byzantine",
            )
    if opts.offload_fallback == "none":
        return client
    from lodestar_tpu.chain.bls import DegradingBlsVerifier

    layers: list = [("offload", client)]
    if opts.offload_fallback == "device":
        layers.append(("device_pool", _device_pool(opts, metrics)))
    layers.append(("cpu", BlsSingleThreadVerifier()))
    return DegradingBlsVerifier(layers, metrics=metrics.resilience)


def configure_device_runtime(opts: BeaconNodeOptions, metrics: BeaconMetrics) -> dict:
    """Observe the backend once and configure the process-global device
    seams from it (they live in the model/ssz/ops layers, below any
    node object): the batch-verify prep metrics, state hashTreeRoot
    placement, the KZG fallback counter and launch telemetry, each with
    its metric family. The verify schedule is not among them: it is
    what the backend is (models/batch_verify `single_launch_active`).
    Returns what the node logs once at start: platform, device_kind,
    count, verifier, hasher (and, once its device pool is built, lanes).

    A node that verifies through `--bls-offload` without a local device
    fallback leaves the chip to the process that owns it: it
    initialises no backend and every "auto" resolves to the host."""
    from lodestar_tpu import telemetry
    from lodestar_tpu.crypto.kzg import configure_kzg_fallback_counter
    from lodestar_tpu.models.batch_verify import configure_device_prep
    from lodestar_tpu.ssz.device_htr import configure_device_htr, device_htr_active
    from lodestar_tpu.utils import probe_accelerator

    owns_device = not opts.offload_endpoints or opts.offload_fallback == "device"
    accel = (
        probe_accelerator()
        if owns_device
        else {"platform": "none", "device_kind": "none", "count": 0}
    )
    on_tpu = accel["platform"] == "tpu"

    configure_device_prep(metrics.bls_prep)
    configure_device_htr(
        mode=opts.htr_device, metrics=metrics.ssz_htr, accelerator=on_tpu
    )
    configure_kzg_fallback_counter(metrics.kzg.device_fallbacks)
    telemetry.configure_launch_telemetry(
        mode=opts.launch_telemetry, metrics=metrics.device_launch
    )

    if opts.offload_endpoints:
        verifier = "offload+" + opts.offload_fallback
    elif opts.use_device_verifier or (opts.use_device_verifier is None and on_tpu):
        verifier = "device"
    else:
        verifier = "cpu"
    return {
        **accel,
        "verifier": verifier,
        "hasher": "device" if device_htr_active() else "cpu",
    }


class BeaconNode:
    def __init__(
        self, *, chain, clock, db, metrics, rest_server, metrics_server, bls, processor=None
    ):
        self.chain = chain
        self.clock = clock
        self.db = db
        self.metrics = metrics
        self.rest_server = rest_server
        self.metrics_server = metrics_server
        self.bls = bls
        self.processor = processor
        self.network = None  # Libp2pBeaconNetwork when p2p is enabled
        self.device_runtime: dict = {}  # configure_device_runtime's answer, set by init
        self._drain_task = None
        self.log = get_logger(name="lodestar.node")

    def on_gossip(self, topic: str, message, peer: str = "") -> bool:
        """Ingress point for the network layer: enqueue a gossip message
        for validated processing (reference network -> NetworkProcessor)."""
        return self.processor.push(topic, message, peer) if self.processor else False

    def start_gossip_drain(self, interval_s: float = 0.05) -> None:
        """Background drain loop over the processor's queues (reference
        NetworkProcessor executeWork scheduling)."""
        if self.processor is None or self._drain_task is not None:
            return

        async def loop():
            while True:
                try:
                    n = await self.processor.execute_work()
                except Exception as e:  # keep draining through handler storms
                    self.log.warn("gossip drain error", {"error": str(e)[:120]})
                    n = 0
                await asyncio.sleep(0 if n else interval_s)

        self._drain_task = asyncio.ensure_future(loop())

    @classmethod
    async def init(
        cls,
        *,
        anchor_state,
        chain_config=None,
        opts: BeaconNodeOptions | None = None,
        p: BeaconPreset | None = None,
        time_fn=None,
        db: DbController | None = None,
    ) -> "BeaconNode":
        opts = opts or BeaconNodeOptions()
        p = p or active_preset()

        # 1. db (a pre-opened controller — e.g. from the restart-from-db
        # anchor probe — takes precedence; the WAL replays only once)
        if db is None:
            if opts.db_path:
                db = FileDbController(opts.db_path)
            else:
                db = MemoryDbController()

        # 2. metrics
        metrics: BeaconMetrics = create_metrics()
        metrics_server = None
        if opts.metrics_enabled:
            metrics_server = MetricsServer(metrics, port=opts.metrics_port)
            metrics_server.start()

        # 2b. pipeline tracing: the span tracer is process-global (the
        # pipeline crosses layers that never see the node object); only
        # an explicit opt-in reconfigures it, so embedded/test tracers
        # set up by the caller are left alone
        if opts.tracing_enabled:
            from lodestar_tpu import tracing as _tracing

            _tracing.configure(
                enabled=True,
                slow_slot_ms=opts.tracing_slow_slot_ms,
                export_dir=opts.tracing_export_dir,
                export_max_files=opts.tracing_export_max_files,
                export_max_age_s=opts.tracing_export_max_age_s,
                metrics=metrics.trace,
            )

        # 2c. event-loop lag sampler: a fixed-interval sleep whose
        # overshoot IS the scheduling lag — feeds the (previously
        # unobserved) lodestar_event_loop_lag_seconds histogram and the
        # slow-slot dumps, separating loop starvation from device slowness
        from lodestar_tpu.metrics.monitoring import EventLoopLagSampler

        lag_sampler = EventLoopLagSampler(metrics.process.event_loop_lag)
        if opts.tracing_enabled:
            from lodestar_tpu import tracing as _tracing

            _tracing.configure(lag_ms_supplier=lag_sampler.last_lag_ms)

        # 2d. the one backend observation + the process-global device
        # seams configured from it (prep / single launch / HTR / KZG
        # counter / launch telemetry)
        device_runtime = configure_device_runtime(opts, metrics)
        if opts.tracing_enabled:
            from lodestar_tpu import telemetry as _telemetry
            from lodestar_tpu import tracing as _tracing

            # the slow-slot dump hook makes a slow slot name its launches
            _tracing.configure(launches_supplier=_telemetry.slow_slot_launches)

        # 3. bls verifier — offload endpoints get the resilience stack
        # (`_offload_verifier`)
        bls: IBlsVerifier
        if opts.offload_endpoints:
            bls = _offload_verifier(opts, metrics)
        elif device_runtime["verifier"] == "device":
            bls = _device_pool(opts, metrics)
            device_runtime["lanes"] = len(bls.mesh)  # one a chip the pool serves on (--bls-mesh)
            load_pubkey_table(bls, anchor_state)
        else:
            bls = BlsSingleThreadVerifier()

        # 4. clock from genesis time
        clock_kwargs = dict(
            genesis_time=anchor_state.genesis_time,
            seconds_per_slot=chain_config.SECONDS_PER_SLOT if chain_config else 12,
            slots_per_epoch=p.SLOTS_PER_EPOCH,
        )
        if time_fn is not None:
            clock_kwargs["time_fn"] = time_fn
        clock = Clock(**clock_kwargs)

        # 4b. slot-deadline SLO accounting: process-global like the
        # tracer (the verify pool and gossip processor live below any
        # node object). Configured here because this is the first point
        # where genesis_time is known; shares the clock's time_fn so a
        # manual/dev clock keeps the deadline math deterministic
        from lodestar_tpu import slo as _slo

        slo_kwargs = dict(
            enabled=opts.slo_enabled,
            genesis_time=anchor_state.genesis_time,
            seconds_per_slot=clock_kwargs["seconds_per_slot"],
            slots_per_epoch=p.SLOTS_PER_EPOCH,
            metrics=metrics.slo,
            slack_floor_ms=opts.slo_slack_floor_ms,
        )
        if time_fn is not None:
            slo_kwargs["time_fn"] = time_fn
        _slo.configure_slo(**slo_kwargs)

        # 5. chain
        chain = BeaconChain(
            anchor_state=anchor_state,
            bls_verifier=bls,
            db=db,
            p=p,
            cfg=chain_config,
            current_slot=max(clock.current_slot, anchor_state.slot),
            metrics=metrics,
        )
        # light-client server: serves bootstraps/updates once the chain
        # runs altair+ (reference chain/lightClient/index.ts wired in
        # BeaconChain's constructor)
        from lodestar_tpu.params import FAR_FUTURE_EPOCH

        if chain_config is not None and chain_config.ALTAIR_FORK_EPOCH != FAR_FUTURE_EPOCH:
            from lodestar_tpu.chain.light_client_server import LightClientServer

            chain.light_client_server = LightClientServer(chain)
        clock.on_slot(chain.on_slot)
        if not opts.manual_clock:
            clock.start()

        # 6. gossip processor (network ingress -> validated dispatch);
        # the chain remembers the node's loop so REST handler threads can
        # route mutations onto it (single-threaded chain semantics)
        import asyncio as _asyncio

        chain.loop = _asyncio.get_running_loop()
        from lodestar_tpu.network.processor import NetworkProcessor

        processor = NetworkProcessor(chain, metrics=metrics)

        # 7. REST API
        rest_server = None
        if opts.rest_enabled:
            rest_server = BeaconRestApiServer(BeaconApiImpl(chain), port=opts.rest_port)
            rest_server.start()

        node = cls(
            chain=chain, clock=clock, db=db, metrics=metrics,
            rest_server=rest_server, metrics_server=metrics_server, bls=bls,
            processor=processor,
        )

        # status notifier + fatal-error policy (reference node/notifier.ts
        # + chain/chain.ts processShutdownCallback)
        from lodestar_tpu.node.notifier import ProcessFaultPolicy, StatusNotifier

        node.fault = ProcessFaultPolicy(opts.on_shutdown_request)
        chain.fault = node.fault
        node.notifier = StatusNotifier(chain)
        node.lag_sampler = lag_sampler
        if not opts.manual_clock:
            clock.on_slot(node.notifier.on_slot)
            node.start_gossip_drain()
            lag_sampler.start()

        # 8. P2P network (TCP + noise + mplex + gossipsub + reqresp)
        if opts.p2p_enabled:
            from lodestar_tpu.network.service import Libp2pBeaconNetwork

            node.network = Libp2pBeaconNetwork(
                node=node,
                chain=chain,
                listen_port=opts.p2p_port,
                bootnodes=opts.bootnodes,
            )
            await node.network.start()
            node.notifier.network = node.network
            # reqresp + router metric bridges (ReqRespMetrics hook; the
            # notifier's per-slot tick snapshots router/peer gauges)
            node.network.reqresp.metrics = metrics.reqresp
        node.device_runtime = device_runtime
        node.log.info("device runtime", device_runtime)
        node.log.info(
            f"beacon node up: slot {clock.current_slot}, "
            f"rest {'on :' + str(rest_server.port) if rest_server else 'off'}"
        )
        return node

    async def close(self) -> None:
        """Abort cascade, reverse init order (nodejs.ts:146-152)."""
        if self.network is not None:
            try:
                await self.network.stop()
            except Exception:
                pass
            self.network = None
        if self._drain_task is not None:
            self._drain_task.cancel()
            try:
                await self._drain_task  # let a mid-import handler finish/unwind
            except BaseException:
                pass
            self._drain_task = None
        if self.rest_server is not None:
            self.rest_server.stop()
        if getattr(self, "lag_sampler", None) is not None:
            await self.lag_sampler.stop()
        await self.clock.stop()
        await self.bls.close()
        if self.metrics_server is not None:
            self.metrics_server.stop()
        self.db.close()
