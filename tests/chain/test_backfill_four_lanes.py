"""BASELINE config 5 on the CPU: one pool over four lanes, fed the way
one lane is fed (PR 33). The lanes' verify, prepared and grouped
callables are the CPU oracle's (as `tests/offload/test_served_fleet.py`
builds its lane); the traffic is the benchmark cell's own
(`backfill-window-four-lanes`) cut to 8 calls of 131 sets with its four
faults, made and judged by the benchmark's `verify` kind, so every
verdict is held against `perfbench/reference/bls.py`. And the lanes'
placement: a single-launch program placed by its inputs is traced once
for all lanes."""

from __future__ import annotations

import asyncio
import copy
import threading

import jax
import numpy as np
import pytest

from lodestar_tpu import telemetry
from lodestar_tpu.chain.bls import BlsDeviceVerifierPool, VerifySignatureOpts
from lodestar_tpu.chain.bls.mesh import MeshLane, VerifierMesh
from lodestar_tpu.crypto.bls.api import SignatureSet, verify_signature_sets
from lodestar_tpu.models import batch_verify as bv
from lodestar_tpu.scheduler import PriorityClass
from perfbench import generator, manifest
from perfbench.reference import parallel

parallel.MAX_WORKERS = 2  # as tests/benchmark/tiny.py: the tier-1 run has six workers on eight cores

CELL = "backfill-window-four-lanes"
LANES = 4
CALLS = 8  # 16 jobs of 66 and 65 sets: four launches of four jobs
SEED = 3_300_000_033


class OracleLanes:
    """Four lanes whose verdicts are the CPU oracle's, each distinct set
    judged once (`warm` judges them before the pool runs, so that the
    lanes do not judge the same set side by side). A launch ends only
    when all four lanes hold one: the pool must have placed the window's
    four packages on four lanes, side by side."""

    def __init__(self):
        self.memo: dict = {}
        self.together = threading.Barrier(LANES, timeout=120)
        self.launches: list[tuple[int, str, list[bool]]] = []  # (lane, entry, a verdict a job)
        self.mesh = VerifierMesh([
            MeshLane(
                i, self._verify(i), verify_prepared_fn=self._prepared(i), verify_grouped_fn=self._grouped(i),
                staged_prep_host_only=True,
            )
            for i in range(LANES)
        ])

    def judge(self, s: SignatureSet) -> bool:
        key = (s.pubkey, s.message, s.signature)
        if key not in self.memo:
            self.memo[key] = verify_signature_sets([s])
        return self.memo[key]

    def warm(self, payloads) -> None:
        for sets in payloads:
            for s in sets:
                self.judge(s)

    def _answer(self, lane: int, entry: str, jobs) -> list[bool]:
        self.together.wait()
        verdicts = [all(self.judge(s) for s in job) for job in jobs]
        self.launches.append((lane, entry, verdicts))
        return verdicts

    def _verify(self, lane: int):
        return lambda sets: self._answer(lane, "flat", [sets])[0]

    def _grouped(self, lane: int):
        return lambda jobs: self._answer(lane, "grouped", jobs)

    def _prepared(self, lane: int):
        def verify_prepared(inputs):
            assert isinstance(inputs, bv.GroupedLaunchInputs)  # the staged host parse of a multi-job unit
            assert inputs.riding == list(range(len(inputs.jobs)))
            return self._answer(lane, "prepared", inputs.jobs)

        return verify_prepared


class PoolSystem:
    """The two seams of `perfbench/entries/node.py` the `verify` kind
    drives, over a pool."""

    def __init__(self, pool):
        self.pool = pool

    def expect_verifier(self, want: str) -> None:
        assert want == "device"

    def verify_payload(self, triples):
        return [SignatureSet(pubkey=pk, message=m, signature=s) for pk, m, s in triples]

    def verify_options(self, batchable: bool, priority: str):
        return VerifySignatureOpts(batchable=batchable, priority=PriorityClass[priority])

    async def verify(self, payload, options) -> bool:
        return await self.pool.verify_signature_sets(payload, options)


@pytest.fixture
def ledger():
    telemetry.reset_launch_telemetry()
    telemetry.configure_launch_telemetry("on", ledger_size=1024)
    yield telemetry.launch_ledger
    telemetry.reset_launch_telemetry()


def the_cells_traffic_cut_to(calls: int):
    cell = manifest.load_cell(CELL)
    traffic = copy.deepcopy(cell.traffic)
    traffic["wave_calls"] = traffic["replay_calls"] = calls
    replay = generator.build_replay(traffic, SEED)
    workload = cell.kind().Workload(cell.config, traffic, cell.spec, replay, SEED)
    workload.prepare()
    return workload, traffic


def test_a_window_over_four_lanes_is_fed_the_way_one_lane_is_fed(ledger):
    workload, traffic = the_cells_traffic_cut_to(CALLS)
    assert traffic["call"] == {"sets": 131, "batchable": False, "priority": "RANGE_SYNC"}
    assert sorted(e.fault for e in workload.replay if e.fault) == sorted(traffic["faults"])
    lanes = OracleLanes()

    async def go():
        pool = BlsDeviceVerifierPool(mesh=lanes.mesh)
        assert pool._staging and not pool.mesh.sharding_available() and pool.mesh.grouping_available()
        workload.attach(PoolSystem(pool))
        lanes.warm(workload.payloads)
        try:
            records, _, _ = await generator.drive(workload.call, traffic, calls=CALLS)
            return records, dict(pool.metrics), [lane.launches for lane in pool.mesh.lanes]
        finally:
            await pool.close()

    records, metrics, per_lane = asyncio.run(go())

    # every verdict is the plain reference's; the faults lie where the traffic says
    compared = {c["name"]: c for c in workload.check([], records)}
    assert all(c["holds"] for c in compared.values()), compared
    assert compared["verdict_mismatches"]["of"] == CALLS
    assert [r.answer for r in records].count(False) == 4 and all(r.error is None for r in records)

    # four launches of four jobs, one a lane, side by side; a fault fails its own job only
    assert per_lane == [1, 1, 1, 1]
    assert sorted(lane for lane, _, _ in lanes.launches) == [0, 1, 2, 3]
    assert all(len(verdicts) == 4 for _, _, verdicts in lanes.launches)
    assert sum(verdicts.count(False) for _, _, verdicts in lanes.launches) == 4
    assert metrics["jobs_started"] == 16 and metrics["sig_sets_started"] == CALLS * 131
    assert metrics["sharded_launches"] == metrics["sharded_fallbacks"] == metrics["errors"] == 0

    # the first package found the mesh idle and parsed inline; the others were staged, and the
    # dispatcher waited for each parse with a lane free
    assert sorted(entry for _, entry, _ in lanes.launches) == ["grouped", "prepared", "prepared", "prepared"]
    launches = [e for e in ledger() if e["program"] == "bls_lane_verify"]
    assert sorted(e["lane"] for e in launches) == ["dev0", "dev1", "dev2", "dev3"]
    assert all(e["compile"] and e["size_class"] == 288 for e in launches)  # each lane's first call, (288, 4)
    waited = [e["phases"].get("bls.parse_wait", 0.0) for e in launches]
    assert sum(1 for w in waited if w > 0) == 3
    assert all("bls.parse" in e["phases"] for e in launches if "bls.parse_wait" in e["phases"])
    assert metrics["parse_wait_ns"] == pytest.approx(1e9 * sum(waited), rel=1e-6)
    assert metrics["parse_ns"] > 0


def test_a_lone_block_on_an_idle_four_lane_mesh_keeps_the_inline_road(ledger):
    """One call, every lane free: nothing to hide a parse behind, so it
    is not staged and nobody waits (the one-lane rule, read lane by lane)."""
    sets = bv.make_synthetic_sets(3)
    served = []
    mesh = VerifierMesh([
        MeshLane(i, lambda s, i=i: served.append(i) or True, verify_prepared_fn=lambda inputs: True,
                 staged_prep_host_only=True)
        for i in range(LANES)
    ])

    async def go():
        pool = BlsDeviceVerifierPool(mesh=mesh)
        try:
            ok = await pool.verify_signature_sets(sets, VerifySignatureOpts(batchable=False))
            return ok, dict(pool.metrics), pool._staged_packages
        finally:
            await pool.close()

    ok, metrics, staged = asyncio.run(go())
    assert ok and len(served) == 1 and staged == 0
    assert metrics["parse_wait_ns"] == 0 and metrics["parse_ns"] == 0


def test_a_launch_placed_by_its_inputs_is_traced_once_for_all_lanes():
    """Four lanes' first calls at once: one trace (the others wait for
    it), each launch on its own device. Under `jax.default_device`, the
    placement the lanes had, the same program is traced a device."""
    devices = jax.devices()[:LANES]
    assert len(devices) == LANES
    traces: list[int] = []

    @jax.jit
    def program(a, b):
        traces.append(threading.get_ident())
        return (a * b).sum() > 0, (a >= 0).all()

    a, b = np.arange(1, 9, dtype=np.int32), np.ones(8, dtype=np.int32)
    start = threading.Barrier(LANES, timeout=60)
    out: dict = {}

    def lane(i: int) -> None:
        start.wait()
        out[i] = bv._dispatch_launch(program, "probe", (), a, b, device=devices[i])

    threads = [threading.Thread(target=lane, args=(i,)) for i in range(LANES)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert sorted(out) == list(range(LANES))
    assert all(bool(v) and bool(ok) for v, ok in out.values())
    assert len(traces) == 1

    placed = [program(*jax.device_put((a, b), d))[0].devices() for d in devices]
    assert placed == [{d} for d in devices] and len(traces) == 1
    with jax.default_device(devices[1]):
        program(a, b)
    assert len(traces) == 2  # what each lane paid before: the default device is part of the trace key


def test_a_lane_without_a_device_keeps_the_one_chip_call():
    """`device=None` (one visible chip: `build_device_mesh` builds that
    lane from the plain entries): no placement, no trace bookkeeping."""
    calls = []

    def program(a):
        calls.append(type(a))
        return np.bool_(True), np.bool_(True)

    before = set(bv._traced_launches)
    assert bv._dispatch_launch(program, "probe", (), np.zeros(8, dtype=np.int32)) == (np.bool_(True), np.bool_(True))
    assert calls == [np.ndarray] and bv._traced_launches == before
