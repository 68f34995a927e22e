"""What a collector flush says of where its seconds went
(`DirtyCollector.steps`, `stats["steps"]`): the five step names on the
device road and on the CPU road, their sum against the flush's wall at
4,096 dirty leaves, nothing while launch telemetry is inactive, and the
roots the same either way."""

from __future__ import annotations

import statistics
import time

import numpy as np
import pytest

from lodestar_tpu import telemetry
from lodestar_tpu.ssz import device_htr as dh
from lodestar_tpu.ssz.hash import hash_nodes_cpu

DEPTH = 16
DIRTY = 4096


@pytest.fixture
def tel():
    telemetry.reset_launch_telemetry()
    telemetry.configure_launch_telemetry(mode="on")
    yield telemetry
    telemetry.reset_launch_telemetry()


@pytest.fixture(params=["device", "cpu"])
def road(request):
    """The collector's two roads at the production per-level threshold:
    on the device road the 2,048-pair levels launch and the small ones
    are hashed on the host, so every step has work."""
    prev = dh.configure_device_htr(mode="on" if request.param == "device" else "off")
    yield request.param
    dh.configure_device_htr(mode=prev)


def build_stack(seed: int = 3) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    levels = [rng.integers(0, 256, size=(1 << DEPTH, 32), dtype=np.uint8)]
    while levels[-1].shape[0] > 1:
        levels.append(hash_nodes_cpu(levels[-1]).copy())
    return levels


def rewrite_and_flush(levels, rng) -> tuple[dict, float]:
    dirty = np.sort(rng.choice(1 << DEPTH, size=DIRTY, replace=False))
    levels[0][dirty, 0] ^= 0x5A
    collector = dh.DirtyCollector()
    t0 = time.monotonic()
    collector.add_stack_job(levels, dirty)
    fed = time.monotonic() - t0
    return collector.flush(), fed


def test_a_flush_names_its_five_steps_and_they_add_up(tel, road):
    levels = build_stack()
    rng = np.random.default_rng(5)
    rewrite_and_flush(levels, rng)  # the first device flush compiles
    shares = []
    for _ in range(5):
        stats, fed = rewrite_and_flush(levels, rng)
        assert stats["backend"] == road
        steps = stats["steps"]
        assert set(steps) == set(dh.FLUSH_STEPS)
        assert all(s >= 0.0 for s in steps.values())
        if road == "device":
            assert stats["launches"] >= 1 and steps["htr.device"] > 0.0
        else:
            assert steps["htr.device"] == 0.0
        assert min(steps["htr.index"], steps["htr.gather"], steps["htr.scatter"],
                   steps["htr.host_hash"]) > 0.0
        total = stats["seconds"] + fed  # `htr.index` starts in add_stack_job
        assert sum(steps.values()) <= total
        shares.append(sum(steps.values()) / total)
    assert statistics.median(shares) >= 0.95, shares
    # the roots are the ones plain hashing gives
    want = [levels[0]]
    while want[-1].shape[0] > 1:
        want.append(hash_nodes_cpu(want[-1]))
    assert all(np.array_equal(ours, ref) for ours, ref in zip(levels, want))


def test_inactive_telemetry_leaves_steps_empty(road):
    telemetry.reset_launch_telemetry()  # auto, no metrics sink: inactive
    levels = build_stack()
    stats, _ = rewrite_and_flush(levels, np.random.default_rng(6))
    assert stats["steps"] == {} and stats["backend"] == road
    assert np.array_equal(levels[1], hash_nodes_cpu(levels[0]))


def test_a_level_outside_a_collector_puts_its_steps_on_the_ledger_entry(tel):
    data = np.random.default_rng(7).integers(0, 256, size=(2 * 12, 32), dtype=np.uint8)
    roots = dh._device_level(data)
    assert np.array_equal(roots, hash_nodes_cpu(data))
    entry = tel.launch_ledger()[-1]
    assert (entry["program"], entry["size_class"], entry["parent"]) == ("merkle_level", 16, None)
    # the launch keeps its extent: padding before it and the unpacking after it are outside
    assert set(entry["phases"]) == {"htr.gather", "htr.device"}
    assert sum(entry["phases"].values()) <= entry["seconds"]
