"""Mesh-backed verifier pool: per-device lanes, least-occupied
placement, sharded bulk, per-chip wedge degradation — and the
single-device regression (one lane behaves exactly like the pre-mesh
pool). Runs on fake lane backends (`testing/mesh.FakeLaneRig`), so the
invariants hold without hardware; the forced-8-device host platform is
exercised separately for the production construction seam."""

from __future__ import annotations

import asyncio
import subprocess
import sys
import time
from types import SimpleNamespace

import pytest

from lodestar_tpu.chain.bls import BlsDeviceVerifierPool, VerifySignatureOpts
from lodestar_tpu.chain.bls.mesh import VerifierMesh, single_lane_mesh
from lodestar_tpu.crypto.bls.api import SignatureSet
from lodestar_tpu.scheduler import PriorityClass
from lodestar_tpu.testing.mesh import FakeLaneRig, mesh_env, virtual_device_count


def _sets(n: int, tag: int = 0) -> list[SignatureSet]:
    return [
        SignatureSet(
            pubkey=bytes([1, tag, i % 256]) + bytes(45),
            message=bytes([2, tag, i % 256]) * 8 + bytes(8),
            signature=bytes([3, tag, i % 256]) + bytes(93),
        )
        for i in range(n)
    ]


def _run(coro):
    return asyncio.run(coro)


# -- single-device regression --------------------------------------------------


def test_single_lane_launches_stay_serialized_and_in_queue_order():
    """With one lane the dispatcher must behave exactly like the
    pre-mesh pool: one launch in flight at a time, dequeue order
    preserved (a later-queued urgent job still overtakes bulk in the
    queue, but launches never overlap)."""
    windows: list[tuple[float, float, int]] = []

    def backend(sets):
        t0 = time.monotonic()
        time.sleep(0.01)
        windows.append((t0, time.monotonic(), sets[0].pubkey[1]))
        return True

    async def go():
        pool = BlsDeviceVerifierPool(backend, scheduler_enabled=True)
        assert len(pool.mesh) == 1  # explicit verify_fn pins a single lane
        jobs = [
            pool.verify_signature_sets(
                _sets(1, tag=i), VerifySignatureOpts(priority=PriorityClass.BACKFILL)
            )
            for i in range(4)
        ]
        ok = await asyncio.gather(*jobs)
        await pool.close()
        return ok

    assert all(_run(go()))
    assert len(windows) == 4
    for (s1, e1, _), (s2, e2, _) in zip(windows, windows[1:]):
        assert e1 <= s2 + 1e-4, "single-lane launches must not overlap"


def test_single_lane_pool_exposes_premesh_surface():
    pool = BlsDeviceVerifierPool(lambda sets: True)
    # the pre-mesh attributes tests and the degradation chain rely on
    assert pool.device_breaker is pool.mesh.lanes[0].breaker
    assert not pool.is_down()
    assert pool.occupancy.occupancy_permille() == 0


# -- placement -----------------------------------------------------------------


def test_latency_work_spreads_to_idle_lanes():
    """Latency-class jobs arriving while launches are in flight land on
    distinct idle chips (jobs arriving together still package into one
    launch — that amortization is the pre-mesh contract and stays)."""
    rig = FakeLaneRig(4, call_s=0.05)

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh, scheduler_enabled=True)
        jobs = []
        for i in range(4):
            jobs.append(
                asyncio.ensure_future(
                    pool.verify_signature_sets(
                        _sets(1, tag=i),
                        VerifySignatureOpts(priority=PriorityClass.GOSSIP_ATTESTATION),
                    )
                )
            )
            # stagger arrivals so each job lands while the previous
            # launch is still occupying its lane
            await asyncio.sleep(0.01)
        ok = await asyncio.gather(*jobs)
        await pool.close()
        return ok

    assert all(_run(go()))
    lanes_used = {i for i, _ in rig.calls}
    assert len(lanes_used) >= 3, f"work did not spread: {rig.calls}"


def test_pick_placement_prefers_least_occupied_lane():
    rig = FakeLaneRig(3)
    pool = BlsDeviceVerifierPool(mesh=rig.mesh, scheduler_enabled=True)
    # seed occupancy: lane0 hot, lane1 warm, lane2 idle
    for lane, busy_s in zip(rig.mesh.lanes, (0.2, 0.05, 0.0)):
        if busy_s:
            lane.occupancy.begin()
            time.sleep(busy_s)
            lane.occupancy.end()
    package = [SimpleNamespace(sets=_sets(1))]
    mode, lanes = pool._pick_placement(
        PriorityClass.GOSSIP_BLOCK, package, pool._free_lanes()
    )
    assert mode == "single"
    assert lanes[0] is rig.mesh.lanes[2]


def test_bulk_shards_across_idle_lanes():
    """A big bulk batch goes data-parallel across >=2 idle chips."""
    rig = FakeLaneRig(4)

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh, scheduler_enabled=True)
        ok = await pool.verify_signature_sets(
            _sets(64), VerifySignatureOpts(priority=PriorityClass.RANGE_SYNC)
        )
        await pool.close()
        return ok

    assert _run(go())
    assert rig.sharded_calls, "bulk batch should use the collective path"
    assert len(rig.sharded_calls[0]) >= 2
    assert not rig.calls, "sharded launch should not fall back to single lanes"


def test_small_bulk_batch_stays_on_one_lane():
    """A bulk batch too small to amortize a collective (under
    2*SHARD_MIN_SETS_PER_LANE sets) runs a plain single-lane launch."""
    rig = FakeLaneRig(4)

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh, scheduler_enabled=True)
        ok = await pool.verify_signature_sets(
            _sets(8), VerifySignatureOpts(priority=PriorityClass.BACKFILL)
        )
        await pool.close()
        return ok

    assert _run(go())
    assert not rig.sharded_calls
    assert len({i for i, _ in rig.calls}) == 1


# -- degradation ---------------------------------------------------------------


def test_lane_kill_degrades_to_remaining_chips_with_verdicts_unchanged():
    """Killing one lane: its wedge breaker trips (counted), verdicts
    keep resolving True via the sibling lanes, and the pool stays up."""
    rig = FakeLaneRig(3, wedge_threshold=2)
    rig.kill(0)

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh, scheduler_enabled=True)
        results = []
        # drive until the sick lane's breaker trips (which dispatch hits
        # the dead chip depends on occupancy micro-ordering; the wedge
        # itself, and the verdicts, must not)
        for i in range(50):
            results.append(
                await pool.verify_signature_sets(
                    _sets(1, tag=i), VerifySignatureOpts(priority=PriorityClass.API)
                )
            )
            if rig.mesh.lanes[0].wedged:
                break
        at_wedge = rig.served_by(0)
        for i in range(5):
            results.append(
                await pool.verify_signature_sets(
                    _sets(1, tag=100 + i),
                    VerifySignatureOpts(priority=PriorityClass.API),
                )
            )
        state = {
            "results": results,
            "is_down": pool.is_down(),
            "available": len(pool.mesh.available()),
            "trips": rig.mesh.lanes[0].wedge_trips,
            "at_wedge": at_wedge,
        }
        await pool.close()
        return state

    state = _run(go())
    # verdicts unchanged: every job resolved True through healthy lanes
    assert state["results"] == [True] * len(state["results"])
    assert state["trips"] == 1, "the sick chip's breaker must trip exactly once"
    assert state["available"] == 2, "pool degrades to the (N-1)-chip mesh"
    assert not state["is_down"]
    # after the wedge, the sick lane stops attracting dispatches
    assert rig.served_by(0) == state["at_wedge"]


def test_all_lanes_wedged_fails_closed_and_reports_down():
    rig = FakeLaneRig(2, wedge_threshold=1)
    rig.kill(0)
    rig.kill(1)

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh, scheduler_enabled=True)
        with pytest.raises(RuntimeError):
            await pool.verify_signature_sets(_sets(1))
        down = pool.is_down()
        await pool.close()
        return down

    assert _run(go())


def test_sharded_error_degrades_to_single_lane_path_verdict_unchanged():
    """A collective failure cannot name the sick chip: the package
    degrades to the attributable single-lane path (verdict unchanged)
    and repeated collective failures park the sharded program while
    single launches keep serving."""
    rig = FakeLaneRig(4, wedge_threshold=3)
    rig.kill(1)  # poisons any collective that includes lane 1

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh, scheduler_enabled=True)
        oks = []
        for i in range(4):
            oks.append(
                await pool.verify_signature_sets(
                    _sets(64, tag=i),
                    VerifySignatureOpts(priority=PriorityClass.RANGE_SYNC),
                )
            )
        stats = dict(pool.metrics)
        await pool.close()
        return oks, stats

    oks, stats = _run(go())
    assert oks == [True] * 4
    assert stats["sharded_fallbacks"] >= 1
    assert rig.sharded_calls, "collective was attempted"
    assert rig.calls, "fallback used single lanes"
    # after SHARD_DISABLE_THRESHOLD consecutive failures the mesh parks
    # the collective: later bulk goes straight to single lanes
    assert rig.mesh.sharded_breaker.is_open or len(rig.sharded_calls) < 4


def test_invalid_sharded_verdict_retries_per_job_not_poisoning_package():
    """ok=False from the collective takes the batch-retry road: the
    package re-verifies on the single-lane path, where per-job verdicts
    are final — an imprecise (or lying) collective can never be weaker
    than the single-device policy."""
    rig = FakeLaneRig(4)
    record = rig.mesh.sharded_fn

    def lying_collective(sets, device_indices):
        record(sets, device_indices)  # keep the rig's call accounting
        return False

    rig.mesh.sharded_fn = lying_collective

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh, scheduler_enabled=True)
        ok = await pool.verify_signature_sets(
            _sets(64), VerifySignatureOpts(priority=PriorityClass.RANGE_SYNC)
        )
        await pool.close()
        return ok

    # the collective says invalid; the per-job single-lane retry passes
    # -> the job resolves True (exactly the RLC batch-then-retry
    # semantics)
    assert _run(go())
    assert rig.sharded_calls and rig.calls
    # lane accounting balanced after the fallback's early release of
    # the unused chips (review regression: no double-decrement, no
    # lane left pinned)
    assert [lane.inflight for lane in rig.mesh.lanes] == [0, 0, 0, 0]


# -- production construction seam ---------------------------------------------


def test_forced_host_platform_exposes_virtual_mesh():
    """tests/conftest.py forces 8 virtual CPU devices — the tier-1
    substrate every mesh invariant above relies on."""
    assert virtual_device_count() >= 8


def test_build_device_mesh_modes_on_forced_platform(monkeypatch):
    from lodestar_tpu.chain.bls.mesh import build_device_mesh
    from lodestar_tpu.models import batch_verify as bv

    # off: single lane, no collective
    off = build_device_mesh("off", fallback_verify_fn=lambda s: True)
    assert len(off) == 1 and off.sharded_fn is None
    # auto on a CPU container: Pallas is not live -> single lane (the
    # default pool stays bit-identical to the pre-mesh pool in tier-1)
    auto = build_device_mesh("auto", fallback_verify_fn=lambda s: True)
    assert len(auto) == 1
    # on: one lane per visible device + the sharded collective
    forced = build_device_mesh("on")
    assert len(forced) == virtual_device_count()
    assert forced.sharded_fn is not None
    labels = [lane.label for lane in forced.lanes]
    assert len(set(labels)) == len(labels)
    # the split schedule's lanes (this CPU backend): no grouped entry,
    # staged prep is device work, a bulk job may take the collective
    assert forced.sharding_available() and not forced.grouping_available()
    assert not forced.staged_prep_is_host_only()
    # where the lanes carry the single launch (a TPU backend; asked of
    # the models layer once, here) the mesh is built WITHOUT the
    # collective: the bulk road is the one-lane road, a lane at a time
    monkeypatch.setattr(bv, "single_launch_active", lambda: True)
    tpu = build_device_mesh("on")
    assert len(tpu) == virtual_device_count() and tpu.sharded_fn is None
    assert not tpu.sharding_available()
    assert tpu.grouping_available() and tpu.staged_prep_is_host_only()
    assert [lane.label for lane in tpu.lanes] == labels
    pool = BlsDeviceVerifierPool(mesh=tpu)
    assert pool._staging
    jobs = [SimpleNamespace(sets=_sets(66), batchable=False) for _ in range(6)]
    # a bulk package is four jobs of the 128 class: one launch, (288, 4) for a block's halves
    assert pool._package_extent(PriorityClass.RANGE_SYNC, jobs) == (4, True)
    assert pool._pick_placement(PriorityClass.RANGE_SYNC, jobs[:4], tpu.lanes)[0] == "single"
    split_pool = BlsDeviceVerifierPool(mesh=forced)
    assert split_pool._package_extent(PriorityClass.RANGE_SYNC, jobs) == (1, True)
    assert split_pool._pick_placement(PriorityClass.RANGE_SYNC, jobs[:1], forced.lanes)[0] == "sharded"


@pytest.mark.slow
def test_mesh_env_subprocess_sees_forced_devices():
    """Belt-and-braces satellite check: the documented XLA_FLAGS env
    alone (no test harness) exposes the virtual mesh in a subprocess."""
    code = "import jax; print(len(jax.devices()))"
    res = subprocess.run(
        [sys.executable, "-c", code],
        env=mesh_env(8),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert res.returncode == 0, res.stderr
    assert int(res.stdout.strip()) == 8


def test_injected_mesh_with_verifier_mesh_of_one_matches_single_lane():
    """A 1-lane injected mesh and the implicit single-lane construction
    serve the same schedule (the regression contract stated in the
    issue: 1 visible device == today's behavior)."""
    calls_a, calls_b = [], []

    def mk(backend_calls):
        def backend(sets):
            backend_calls.append(tuple(s.pubkey[1] for s in sets))
            return True

        return backend

    async def drive(pool):
        jobs = []
        for i, pr in enumerate(
            [PriorityClass.BACKFILL, PriorityClass.GOSSIP_BLOCK, PriorityClass.API]
        ):
            jobs.append(
                pool.verify_signature_sets(
                    _sets(2, tag=i), VerifySignatureOpts(priority=pr)
                )
            )
        ok = await asyncio.gather(*jobs)
        await pool.close()
        return ok

    async def go():
        a = BlsDeviceVerifierPool(mk(calls_a), scheduler_enabled=True)
        b = BlsDeviceVerifierPool(
            mesh=VerifierMesh(single_lane_mesh(mk(calls_b)).lanes),
            scheduler_enabled=True,
        )
        return await drive(a), await drive(b)

    ra, rb = _run(go())
    assert all(ra) and all(rb)
    assert calls_a == calls_b


def test_dispatcher_waits_for_healthy_lane_instead_of_using_wedged_idle_one():
    """Review regression: with a wedged-but-idle chip and a busy
    healthy chip, the dispatcher must WAIT for the healthy lane — not
    feed a launch storm into the hung driver the breaker just
    isolated. Only an all-wedged mesh fails fast through a sick chip."""
    rig = FakeLaneRig(2, wedge_threshold=1)
    pool = BlsDeviceVerifierPool(mesh=rig.mesh, scheduler_enabled=True)
    lane0, lane1 = rig.mesh.lanes
    lane0.breaker.record_failure()  # wedge lane0 (threshold 1)
    assert lane0.wedged
    lane1.inflight = 1  # healthy lane busy
    assert pool._free_lanes() == [], "must wait, not dispatch to the sick chip"
    lane1.inflight = 0
    assert pool._free_lanes() == [lane1]
    # all-wedged: fail fast through a sick chip (pre-mesh behavior)
    lane1.breaker.record_failure()
    assert pool._free_lanes() == [lane0, lane1]


def test_mesh_launch_shared_core_wedges_and_routes_around_sick_chip():
    """`mesh_launch` (the standalone offload host's backend core) keeps
    the per-chip wedge accounting: errors trip the sick lane's breaker,
    the verdict is unchanged via siblings, and once wedged the lane
    stops being picked."""
    from lodestar_tpu.chain.bls.mesh import mesh_launch

    rig = FakeLaneRig(2, wedge_threshold=2)
    rig.kill(0)
    wedges = []
    for i in range(6):
        # prefer the sick lane so every round deterministically attempts
        # it until its breaker trips — the default least-occupied pick is
        # wall-clock EWMA and under a loaded container can route around
        # the sick lane WITHOUT wedging it, which is healthy routing but
        # not the accounting this test pins
        ok, lane = mesh_launch(
            rig.mesh,
            _sets(1, tag=i),
            prefer=rig.mesh.lanes[0],
            on_wedge=lambda l: wedges.append(l.index),
        )
        assert ok and lane.index == 1
        if rig.mesh.lanes[0].wedged:
            break
    assert rig.mesh.lanes[0].wedged and wedges == [0]
    at_wedge = rig.served_by(0)
    for i in range(4):
        ok, lane = mesh_launch(rig.mesh, _sets(1, tag=50 + i))
        assert ok and lane.index == 1
    assert rig.served_by(0) == at_wedge


def test_dispatcher_survives_lane_wedging_between_capacity_check_and_placement():
    """Review regression: a free lane can wedge (cross-lane retries
    record failures from executor threads) between the dispatcher's
    capacity check and placement. The dispatcher must re-wait for a
    healthy lane — not die on an empty placement (which would strand
    the dequeued package's futures forever)."""
    rig = FakeLaneRig(2, wedge_threshold=1, call_s=0.05)

    async def go():
        pool = BlsDeviceVerifierPool(mesh=rig.mesh, scheduler_enabled=True)
        lane0, lane1 = rig.mesh.lanes
        # occupy lane1 with a real launch, then wedge idle lane0 while
        # the dispatcher is parked waiting to place the next job
        first = asyncio.ensure_future(
            pool.verify_signature_sets(
                _sets(1, tag=1), VerifySignatureOpts(priority=PriorityClass.API)
            )
        )
        await asyncio.sleep(0.01)  # first launch in flight on some lane
        busy = lane0 if lane0.inflight else lane1
        idle = lane1 if busy is lane0 else lane0
        idle.breaker.record_failure()  # wedge the idle lane (threshold 1)
        assert idle.wedged
        second = asyncio.ensure_future(
            pool.verify_signature_sets(
                _sets(1, tag=2), VerifySignatureOpts(priority=PriorityClass.API)
            )
        )
        ok = await asyncio.gather(first, second)
        await pool.close()
        return ok

    assert _run(go()) == [True, True]


def test_sharded_lane_subset_is_index_ordered():
    """Review regression: the sharded executable memoizes on device
    ORDER; the dispatcher picks the subset by occupancy but must hand
    it over in canonical index order."""
    rig = FakeLaneRig(4)
    pool = BlsDeviceVerifierPool(mesh=rig.mesh, scheduler_enabled=True)
    # make occupancy rank 3 < 1 < 0 < 2
    for lane, busy_s in zip(rig.mesh.lanes, (0.04, 0.02, 0.08, 0.0)):
        if busy_s:
            lane.occupancy.begin()
            time.sleep(busy_s)
            lane.occupancy.end()
    package = [SimpleNamespace(sets=_sets(48))]
    mode, lanes = pool._pick_placement(
        PriorityClass.RANGE_SYNC, package, pool._free_lanes()
    )
    assert mode == "sharded"
    idx = [l.index for l in lanes]
    assert idx == sorted(idx)
    assert 2 not in idx  # the hottest lane was dropped by the subset pick


def test_build_device_mesh_does_not_hide_a_missing_device(monkeypatch):
    """A caller of build_device_mesh asked for a device verifier: a
    backend that cannot initialise (a chip another process holds) or a
    device model that cannot be imported must raise, never come back as
    a one-lane CPU oracle serving unseen."""
    import builtins

    from lodestar_tpu.chain.bls.mesh import build_device_mesh
    from lodestar_tpu.models import batch_verify as bv

    def busy():
        raise RuntimeError("Unable to initialize backend 'tpu': already in use")

    monkeypatch.setattr(bv, "mesh_device_count", busy)
    with pytest.raises(RuntimeError, match="already in use"):
        build_device_mesh("on")
    monkeypatch.undo()

    real_import = builtins.__import__

    def blocked(name, *a, **kw):
        if name == "lodestar_tpu.models" or name.endswith("batch_verify"):
            raise ImportError("no jax on this host")
        return real_import(name, *a, **kw)

    monkeypatch.setattr(builtins, "__import__", blocked)
    with pytest.raises(ImportError):
        build_device_mesh("auto")


def test_mesh_launch_reroutes_when_preferred_lane_already_wedged():
    """Review regression: chunk N trips the breaker mid-package; chunk
    N+1 (same dispatch lane preference) must start on a healthy lane
    instead of feeding another launch into the hung driver."""
    from lodestar_tpu.chain.bls.mesh import mesh_launch

    rig = FakeLaneRig(2, wedge_threshold=1)
    rig.kill(0)
    lane0 = rig.mesh.lanes[0]
    lane0.breaker.record_failure()  # wedged before this launch
    assert lane0.wedged
    before = rig.served_by(0)
    ok, served = mesh_launch(rig.mesh, _sets(1), prefer=lane0)
    assert ok and served.index == 1
    assert rig.served_by(0) == before, "wedged preferred lane must not be dialed"
