"""One verify schedule, decided once where the lanes are built.

Which schedule a lane runs is what the backend is; the models layer
answers (`batch_verify.single_launch_active`), `mesh.build_device_mesh`
asks once and builds the lanes with the answer, and the pool reads the
lanes only:

* the schedule table: backend × what the lanes take × how many lanes →
  does the pool group jobs, does it stage, is staged prep host-only;
* the pool built with no arguments has the lane `build_device_mesh("off")`
  makes, on either backend;
* a pool over injected lanes never touches the models layer.

The backend is forced where the lanes are built, by patching the
resolver around the factory call: a test's business, not an option.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

from lodestar_tpu.chain.bls import BlsDeviceVerifierPool
from lodestar_tpu.chain.bls.mesh import MeshLane, VerifierMesh, build_device_mesh
from lodestar_tpu.models import batch_verify as bv


def _lane_facts(lane: MeshLane) -> tuple:
    return (
        lane.verify_fn,
        lane.verify_prepared_fn,
        lane.verify_grouped_fn,
        lane.staged_prep_host_only,
    )


@pytest.mark.parametrize(
    "accelerator, staged_inputs, n_lanes, groups, stages, host_only",
    [
        (True, True, 1, True, True, True),
        (True, True, 2, True, True, True),
        (True, False, 1, False, False, False),
        (True, False, 2, False, False, False),
        (False, True, 1, False, False, False),
        (False, True, 2, False, True, False),
        (False, False, 1, False, False, False),
        (False, False, 2, False, False, False),
    ],
)
def test_the_schedule_table(monkeypatch, accelerator, staged_inputs, n_lanes, groups, stages, host_only):
    """Lanes that take staged inputs are the models layer's, from the
    factory; lanes that do not are plain callables (an injected backend:
    `fallback_verify_fn`, or a mesh of mocks). On an accelerator the
    factory's lanes carry the single launch: they group, and their
    staged prep is the host parse, so even one lane stages. On the CPU
    backend they carry the split schedule: no grouped entry, and staged
    prep is device work that only a sibling lane can hide."""
    monkeypatch.setattr(bv, "single_launch_active", lambda: accelerator)
    monkeypatch.setattr(bv, "mesh_device_count", lambda: n_lanes)
    plain = lambda sets: True  # noqa: E731
    if staged_inputs:
        mesh = build_device_mesh("on")
    elif n_lanes == 1:
        mesh = build_device_mesh("off", fallback_verify_fn=plain)
    else:
        mesh = VerifierMesh([MeshLane(i, plain) for i in range(n_lanes)])
    assert len(mesh) == n_lanes
    pool = BlsDeviceVerifierPool(mesh=mesh)
    assert (
        mesh.grouping_available(), pool._staging, mesh.staged_prep_is_host_only()
    ) == (groups, stages, host_only)


@pytest.mark.parametrize("accelerator", [True, False], ids=["accelerator", "cpu"])
def test_the_default_pools_lane_is_the_factorys(monkeypatch, accelerator):
    """`BlsDeviceVerifierPool()` holds no second copy of the lane
    construction: callable for callable, fact for fact, its lane is
    `build_device_mesh("off")`'s."""
    monkeypatch.setattr(bv, "single_launch_active", lambda: accelerator)
    pool_lanes = BlsDeviceVerifierPool().mesh.lanes
    factory_lanes = build_device_mesh("off").lanes
    assert [_lane_facts(l) for l in pool_lanes] == [_lane_facts(l) for l in factory_lanes]
    assert _lane_facts(pool_lanes[0]) == (
        bv.verify_signature_sets_device,
        bv.verify_prepared,
        bv.verify_sets_grouped_launch if accelerator else None,
        accelerator,
    )


def test_a_pool_over_injected_lanes_never_imports_the_models_layer():
    """A block (131 sets, two jobs of the 128 class) through a pool over
    a fake lane with a grouped entry is ONE multi-job launch, and the
    process never imports `lodestar_tpu.models.batch_verify`: the pool
    and the mesh read the lanes, nobody reaches round them."""
    script = textwrap.dedent(
        """
        import asyncio, sys
        from lodestar_tpu.chain.bls import BlsDeviceVerifierPool, VerifySignatureOpts
        from lodestar_tpu.chain.bls.mesh import MeshLane, VerifierMesh
        from lodestar_tpu.crypto.bls.api import SignatureSet
        from lodestar_tpu.scheduler import PriorityClass

        launches = []

        def grouped(jobs):
            launches.append([len(j) for j in jobs])
            return [True] * len(jobs)

        sets = [
            SignatureSet(pubkey=bytes([1, i]) + bytes(46), message=bytes(32), signature=bytes(96))
            for i in range(131)
        ]

        async def go():
            mesh = VerifierMesh([MeshLane(0, lambda s: True, verify_grouped_fn=grouped)])
            pool = BlsDeviceVerifierPool(mesh=mesh)
            ok = await pool.verify_signature_sets(
                sets, VerifySignatureOpts(priority=PriorityClass.GOSSIP_BLOCK)
            )
            await pool.close()
            return ok

        assert asyncio.run(go()) is True
        assert launches == [[66, 65]], launches
        loaded = sorted(m for m in sys.modules if m.startswith("lodestar_tpu.models"))
        assert not loaded, loaded
        print("ok")
        """
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, cwd=repo, env=env, timeout=120
    )
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr[-2000:]
