"""The served fleet on the CPU: an offload host built by
`offload.server.boot_host` with a `BlsDeviceVerifierPool` behind the wire
whose one lane is the CPU oracle (a faked lane, as
`tests/chain/test_grouped_units.py` fakes its), real gRPC on localhost,
four tenants built by `node._offload_verifier` as `BeaconNode.init`
builds a node's, waves of four calls. Seeded; every verdict is held
against `perfbench.reference.bls`."""

from __future__ import annotations

import asyncio
import random
import socket
import threading
import time

import grpc
import pytest

from lodestar_tpu import telemetry
from lodestar_tpu.chain.bls import BlsDeviceVerifierPool, VerifySignatureOpts
from lodestar_tpu.chain.bls.mesh import MeshLane, VerifierMesh
from lodestar_tpu.crypto.bls.api import SignatureSet, verify_signature_sets
from lodestar_tpu.metrics import create_metrics
from lodestar_tpu.node import BeaconNodeOptions, _offload_verifier
from lodestar_tpu.offload import encode_sets, known_answer
from lodestar_tpu.offload import server as offload_server
from lodestar_tpu.offload.client import BlsOffloadClient
from lodestar_tpu.offload.known_answer import KnownAnswerError
from lodestar_tpu.scheduler import PriorityClass
from perfbench.reference import bls as ref

SEED = 2_900_000_029
TENANTS = 4
BLOCK = 131  # sets a call: jobs of 66 and 65, the 128 size class, 72 rows a slot


class OracleLane:
    """One lane whose verdicts are the CPU oracle's, each distinct set
    judged once. `lie` makes the grouped entry answer True for a job
    that holds an invalid set (a program that disagrees with the oracle).
    `gate`, where set, holds every launch until it is released."""

    def __init__(self, lie: bool = False, gate: threading.Event | None = None):
        self.memo: dict = {}
        self.lie, self.gate = lie, gate
        self.launch_s = 0.0  # a launch lasts at least this long: the rest of a wave queues behind it
        self.grouped: list[list[bool]] = []  # the verdicts of every multi-job launch, a job each
        self.mesh = VerifierMesh([MeshLane(0, self.verify, verify_grouped_fn=self.verify_grouped)])

    def _judge(self, s: SignatureSet) -> bool:
        key = (s.pubkey, s.message, s.signature)
        if key not in self.memo:
            self.memo[key] = verify_signature_sets([s])
        return self.memo[key]

    def verify(self, sets) -> bool:
        if self.gate is not None:
            self.gate.wait(60)
        return all(self._judge(s) for s in sets)

    def verify_grouped(self, jobs) -> list[bool]:
        if self.gate is not None:
            self.gate.wait(60)
        time.sleep(self.launch_s)
        verdicts = [True if self.lie else all(self._judge(s) for s in job) for job in jobs]
        self.grouped.append(verdicts)
        return verdicts


@pytest.fixture
def seams():
    """`boot_host` writes a process-global seam (the launch ledger's
    sink)."""
    yield
    telemetry.reset_launch_telemetry()


def boot(lane: OracleLane, **kw):
    return offload_server.boot_host(
        port=kw.pop("port", 0),
        pool_factory=lambda: BlsDeviceVerifierPool(mesh=lane.mesh), **kw,
    )


def make_tenants(port: int) -> list:
    return [
        _offload_verifier(
            BeaconNodeOptions(offload_endpoints=[f"127.0.0.1:{port}"], offload_tenant=f"node-{i}",
                              offload_audit_rate=0.0),
            create_metrics(),
        )
        for i in range(TENANTS)
    ]


async def probed(tenants) -> None:
    """A node has probed its host before its first block: only then do
    its frames carry the tenant trailer."""
    deadline = time.monotonic() + 10.0
    while not all(s["tenant_capable"] for t in tenants for s in t.layers[0][1].endpoint_states()):
        assert time.monotonic() < deadline
        await asyncio.sleep(0.02)


def blocks(seed: int):
    """Four honest blocks of few distinct sets, and the same four with a
    cancelling pair of shifted signatures in the first job of tenant 1's
    and an off-subgroup pubkey in the last job of tenant 2's. Returns
    (honest, faulty, the reference's verdict for each faulty block)."""
    rng = random.Random(seed)
    scalars = [rng.randrange(1, ref.F.R) for _ in range(5)]
    base = [(ref.pubkey(s), rng.randbytes(32), None) for s in scalars]
    base = [(pk, m, ref.sign(s, m)) for (pk, m, _), s in zip(base, scalars)]
    honest = []
    for t in range(TENANTS):
        sets = [base[(t + i) % len(base)] for i in range(BLOCK)]
        honest.append(sets)
    faulty = [list(b) for b in honest]
    a, b = 3, 40  # both inside the first job (positions 0..65)
    shift = base[4][2]
    faulty[1][a] = (faulty[1][a][0], faulty[1][a][1], ref.shift_signature(faulty[1][a][2], shift, False))
    faulty[1][b] = (faulty[1][b][0], faulty[1][b][1], ref.shift_signature(faulty[1][b][2], shift, True))
    k = 100  # inside the last job (positions 66..130)
    faulty[2][k] = (ref.shift_pubkey_off_subgroup(faulty[2][k][0], seed), faulty[2][k][1], faulty[2][k][2])
    judged: dict = {}
    want = []
    for block in faulty:
        for t in block:
            if t not in judged:
                judged[t] = ref.judge(*t)
        want.append(ref.reference_verdict([judged[t] for t in block]))
    return honest, faulty, want


def payload(block):
    return [SignatureSet(pubkey=pk, message=m, signature=s) for pk, m, s in block]


def lane_launches(since: int) -> list[dict]:
    """The verify launches among the ledger's entries from the `since`-th on
    (an entry is written when its launch ends, so between waves none is open)."""
    return [e for e in telemetry.launch_ledger()[since:] if e["program"] == "bls_lane_verify"]


def test_four_tenants_get_the_references_verdicts_and_a_fault_fails_its_tenant_alone(seams):
    lane = OracleLane()
    before = {t.ident for t in threading.enumerate()}
    host = boot(lane)
    # three first calls: the programs a 66-set job rides under the slot rule (72 rows a slot)
    assert [(w["rows"], w["jobs"]) for w in host.warmed] == [(128, 1), (144, 2), (288, 4)]
    started: list[tuple[int, int]] = []
    enqueue = host.pool._enqueue
    host.pool._enqueue = lambda job: (started.append((len(job.sets), int(job.priority))), enqueue(job))[1]
    honest, faulty, want = blocks(SEED)
    assert want == [True, False, False, True]
    lane.launch_s = 0.05
    opts = VerifySignatureOpts(batchable=False, priority=PriorityClass.GOSSIP_BLOCK)

    async def drive():
        tenants = make_tenants(host.port)
        try:
            await probed(tenants)
            waves = []
            for blocks_ in (honest, faulty, honest):
                since = len(telemetry.launch_ledger())
                got = await asyncio.gather(
                    *(t.verify_signature_sets(payload(b), opts) for t, b in zip(tenants, blocks_))
                )
                waves.append((list(got), lane_launches(since)))
            return waves, [dict(t.layers[0][1].endpoint_states()[0]) for t in tenants]
        finally:
            for t in tenants:
                await t.close()

    try:
        waves, states = asyncio.run(drive())
    finally:
        host.stop()
    (ok1, l1), (got, l2), (ok3, l3) = waves
    assert ok1 == ok3 == [True] * TENANTS
    assert got == want  # tenants 1 and 2 fail, 0 and 3 do not
    for launches in (l1, l2, l3):
        # every job of a wave rode a multi-job launch (the ledger's size class is
        # the launch's rows), one of them with more than one tenant's jobs
        assert all(e["size_class"] in (144, 288) for e in launches)
        assert any(e["size_class"] == 288 for e in launches)
    served = lane.grouped[-len(l1 + l2 + l3):]
    assert sum(len(v) for v in served) == 3 * TENANTS * 2
    # the two faulty jobs failed inside launches they shared, and nothing else failed
    assert sum(v.count(False) for v in served) == 2
    assert all(len(v) >= 2 and any(v) for v in served if False in v)
    # the trailer's class reached the pool: every job of a served block is GOSSIP_BLOCK
    assert len(started) == 3 * TENANTS * 2
    assert {cls for _, cls in started} == {int(PriorityClass.GOSSIP_BLOCK)}
    assert sorted({n for n, _ in started}) == [65, 66]
    assert all(s["healthy"] and s["breaker"] == "closed" for s in states)
    # one ledger entry an RPC on each side, with its phases
    ledger = telemetry.launch_ledger()
    serve = [e for e in ledger if e["program"] == "offload_serve"]
    rpc = [e for e in ledger if e["program"] == "offload_rpc"]
    assert len(serve) == len(rpc) == 3 * TENANTS
    assert all({"offload.decode", "offload.slot_wait", "offload.backend", "offload.reply"} <= set(e["phases"])
               for e in serve)
    assert all({"offload.encode", "offload.call", "offload.check"} <= set(e["phases"]) for e in rpc)
    assert all(e["size_class"] == 256 for e in serve + rpc)
    # stop() leaves no thread of the host's behind
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        left = [t for t in threading.enumerate() if t.ident not in before and t.name.startswith("offload-")]
        if not left:
            break
        time.sleep(0.05)
    assert left == []


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def refused(port: int) -> bool:
    """A verify RPC to `port` fails at the transport, at once."""
    channel = grpc.insecure_channel(f"127.0.0.1:{port}")
    stub = channel.unary_unary(offload_server.VERIFY_METHOD, request_serializer=lambda b: b,
                               response_deserializer=lambda b: b)
    frame = encode_sets(payload([(bytes(48), bytes(32), bytes(96))]))
    try:
        stub(frame, timeout=2.0)
        return False
    except grpc.RpcError as e:
        return e.code() == grpc.StatusCode.UNAVAILABLE
    finally:
        channel.close()


def test_a_host_whose_known_answer_check_disagrees_does_not_bind_its_port(seams):
    port = free_port()
    before = {t.ident for t in threading.enumerate()}
    with pytest.raises(KnownAnswerError, match="144-row"):
        boot(OracleLane(lie=True), port=port)
    assert refused(port)
    time.sleep(0.2)
    assert [t for t in threading.enumerate() if t.ident not in before and t.name.startswith("offload-")] == []


@pytest.fixture(scope="module")
def known_answer_run():
    """One known-answer check of a pool over the oracle lane, with the
    launch ledger on: (the records, the verify launches it made)."""
    lane = OracleLane()
    telemetry.reset_launch_telemetry()
    telemetry.configure_launch_telemetry("on")

    async def go():
        pool = BlsDeviceVerifierPool(mesh=lane.mesh)
        try:
            return known_answer.programs_of(pool), await known_answer.check_known_answers(pool)
        finally:
            await pool.close()

    try:
        programs, records = asyncio.run(go())
        return programs, records, lane_launches(0)
    finally:
        telemetry.reset_launch_telemetry()


@pytest.mark.parametrize("at, rows, jobs", [(0, 128, 1), (1, 144, 2), (2, 288, 4)], ids=["flat", "two", "four"])
def test_the_warm_list_is_what_a_blocks_half_rides_and_each_launch_has_its_rows(known_answer_run, at, rows, jobs):
    """`programs_of` asks the slot rule about jobs of `KNOWN_JOB_SETS`
    sets, and the check holds each known batch's one launch to the rows
    the list named: three programs, two launches each."""
    programs, records, launches = known_answer_run
    assert len(programs) == len(records) == 3 and len(launches) == 6
    assert programs[at] == (rows, jobs)
    assert (records[at]["rows"], records[at]["jobs"]) == (rows, jobs)
    assert [e["size_class"] for e in launches[2 * at : 2 * at + 2]] == [rows, rows]


def test_a_known_batch_answered_by_a_launch_of_other_rows_stops_the_boot(seams, monkeypatch):
    """A list and a launch that disagree on the slot (two rules where
    there is one) do not pass for a warmed program."""
    monkeypatch.setattr(known_answer, "programs_of", lambda pool: [(128, 1), (256, 2)])
    with pytest.raises(KnownAnswerError, match=r"256-row program was answered by launches of \[144\] rows"):
        boot(OracleLane())


def test_a_pool_that_cannot_group_warms_the_flat_program_alone():
    mesh = VerifierMesh([MeshLane(0, lambda sets: True)])

    async def go():
        pool = BlsDeviceVerifierPool(mesh=mesh)
        try:
            return known_answer.programs_of(pool)
        finally:
            await pool.close()

    assert asyncio.run(go()) == [(128, 1)]


def test_an_rpc_sent_during_the_warm_start_is_refused_not_answered_late(seams):
    port = free_port()
    gate = threading.Event()
    lane = OracleLane(gate=gate)
    hosts: list = []
    booting = threading.Thread(target=lambda: hosts.append(boot(lane, port=port)))
    booting.start()
    try:
        time.sleep(0.5)  # the warm start is parked in its first known batch
        assert booting.is_alive() and not hosts
        assert refused(port)
    finally:
        gate.set()
        booting.join(120)
    assert hosts and hosts[0].port == port
    try:
        client = BlsOffloadClient(f"127.0.0.1:{port}")
        valid, tampered = known_answer.known_sets()

        async def ask():
            try:
                return [await client.verify_signature_sets(valid), await client.verify_signature_sets([tampered])]
            finally:
                await client.close()

        assert asyncio.run(ask()) == [True, False]
    finally:
        hosts[0].stop()


def test_a_caller_that_waits_for_a_slot_does_not_hold_the_others(seams):
    """Two service slots, four callers together: the two that wait for a
    slot wait for the first two verdicts, so the first two must not be
    held for them."""
    lane = OracleLane()
    host = boot(lane, tenant_slots=2)
    honest, _, _ = blocks(SEED + 1)
    opts = VerifySignatureOpts(batchable=False, priority=PriorityClass.GOSSIP_BLOCK)

    async def drive():
        tenants = make_tenants(host.port)
        try:
            await probed(tenants)
            return await asyncio.wait_for(
                asyncio.gather(*(t.verify_signature_sets(payload(b), opts) for t, b in zip(tenants, honest))), 60
            )
        finally:
            for t in tenants:
                await t.close()

    try:
        assert asyncio.run(drive()) == [True] * TENANTS
    finally:
        host.stop()


def test_callers_counted_together_are_enqueued_together_and_a_stalled_one_holds_nobody_long(seams, monkeypatch):
    """The hand-over itself, on the host's backend: four callers on four
    threads, all counted before the first hands over, are in the pool's
    queue before its runner takes a package (one package of eight jobs,
    two 288-row launches); a counted caller that never comes costs the
    others the cap and no more."""
    lane = OracleLane()
    host = boot(lane)
    backend = host.backend.verify
    honest, _, _ = blocks(SEED + 2)
    # the waves below are held to the count alone, however slowly a loaded
    # machine starts their threads
    monkeypatch.setattr(offload_server, "HANDOVER_HOLD_CAP_S", 30.0)
    packages: list[int] = []
    next_package = host.pool._next_package

    async def watched():
        package, cls = await next_package()
        packages.append(len(package))
        return package, cls

    host.pool._next_package = watched

    def wave() -> list[bool]:
        got: list = [None] * TENANTS
        for _ in range(TENANTS):
            backend.accepted()

        def call(i: int) -> None:
            time.sleep(0.002 * i)  # one after another, as handlers enter `_verify`
            got[i] = backend(payload(honest[i]), PriorityClass.GOSSIP_BLOCK, counted=True)

        threads = [threading.Thread(target=call, args=(i,)) for i in range(TENANTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        return got

    try:
        # the runner waits inside the package it asked for before the watch was set
        assert backend(payload(honest[0]), PriorityClass.GOSSIP_BLOCK) is True
        since = len(telemetry.launch_ledger())
        assert wave() == [True] * TENANTS
        assert packages == [2 * TENANTS]
        assert [e["size_class"] for e in lane_launches(since)] == [288, 288]
        # a sender stalled between its call's headers and its request
        monkeypatch.setattr(offload_server, "HANDOVER_HOLD_CAP_S", 0.010)
        backend.accepted()
        t0 = time.monotonic()
        assert backend(payload(honest[0]), PriorityClass.GOSSIP_BLOCK) is True
        assert time.monotonic() - t0 < 5.0  # the cap and a launch, not the stalled call's deadline
        backend.left()
        monkeypatch.setattr(offload_server, "HANDOVER_HOLD_CAP_S", 30.0)
        assert wave() == [True] * TENANTS  # the count is whole again
        assert packages[-1] == 2 * TENANTS
    finally:
        host.stop()
