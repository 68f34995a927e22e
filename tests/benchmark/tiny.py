"""Cells shrunk to a size a test run can hold, and systems broken on
purpose. The shapes of the traffic stay: waves, replay, faults."""

from __future__ import annotations

import asyncio
import copy

from perfbench import manifest, run
from perfbench.entries import reference
from perfbench.reference import parallel

verify_kind = manifest.load_module("kinds", "verify")

# The tier-1 run has six test workers on eight cores, and other files' tests
# hold network timeouts of seconds: a rehearsal takes two reference workers,
# not the eight a run on the chip's host takes.
parallel.MAX_WORKERS = 2

HOST = {"platform": "host", "kind": "reference", "count": 0}


TINY_JOB = 3  # 5 sets a call -> jobs of 3 and 2, as 131 -> 66 and 65 at the stated 128


def tiny_cell(name: str, full_calls: bool = False):
    """`full_calls` keeps a verify cell's calls and jobs at the stated
    size (131 sets, 66 + 65) and shrinks only the replay and the waves."""
    cell = manifest.load_cell(name)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.config = copy.deepcopy(cell.config)
    cell.spec = copy.deepcopy(cell.spec)
    cell.spec["warm_calls"] = 2
    if cell.config["kind"] == "verify":
        if not full_calls:
            cell.traffic["call"]["sets"] = 5
            cell.config["pool"]["MAX_SIGNATURE_SETS_PER_JOB"] = TINY_JOB
        cell.traffic["wave_calls"] = min(cell.traffic["wave_calls"], 4)
        cell.traffic["replay_calls"] = 6  # the four faulty calls and two honest ones
        cell.spec["warm_calls"] = 6  # the reference judges a set once: the window's calls are quick
    else:
        cell.config["tree_depth"] = 8
        cell.traffic["call"]["dirty_leaves"] = 40
        cell.traffic["replay_calls"] = 3
    return cell


def run_tiny(name: str, system_factory=None, control: str | None = None, seed: int = 2**31 + 11,
             seconds: float = 0.3, traced: bool = False, full_calls: bool = False) -> dict:
    cell = tiny_cell(name, full_calls)
    per_job = cell.config.get("pool", {}).get("MAX_SIGNATURE_SETS_PER_JOB")

    async def boot():
        system = reference.ReferenceSystem(control, workers=2)
        return system_factory(system, per_job) if system_factory else system

    return asyncio.run(run.run_cell(cell, seed, seconds, traced, boot, dict(HOST)))


class Wrapped:
    """A system with one seam replaced; the rest is the inner system's."""

    def __init__(self, inner, per_job: int | None = None):
        self.inner = inner
        self.per_job = per_job

    def __getattr__(self, name):
        return getattr(self.inner, name)


class AlwaysTrue(Wrapped):
    """A verifier whose answer is altered where it is produced."""

    async def verify(self, payload, options) -> bool:
        return True


class HalfLeftOut(Wrapped):
    """Judges only the first half of a call's sets."""

    async def verify(self, payload, options) -> bool:
        return await self.inner.verify(payload[: len(payload) // 2], options)


class LastJobDropped(Wrapped):
    """A pool that never awaits the verdict of a call's last job."""

    async def verify(self, payload, options) -> bool:
        spans = verify_kind.job_spans(len(payload), self.per_job)
        return await self.inner.verify(payload[: spans[-1].start or len(payload)], options)


class FirstJobDropped(Wrapped):
    """A pool that loses the verdict of a call's first job."""

    async def verify(self, payload, options) -> bool:
        spans = verify_kind.job_spans(len(payload), self.per_job)
        return await self.inner.verify(payload[spans[0].stop if len(spans) > 1 else 0 :], options)


class StateUnchanged(Wrapped):
    """A flush that returns its state as it found it."""

    def flush(self, levels, dirty) -> dict:
        return {"backend": "reference", "levels": len(levels) - 1, "launches": 0,
                "dirty_chunks": len(dirty), "seconds": 0.0}


class RootAltered(Wrapped):
    """A flush whose root is altered after it is produced."""

    def flush(self, levels, dirty) -> dict:
        stats = self.inner.flush(levels, dirty)
        levels[-1][0, 0] ^= 1
        return stats


class HalfDirtyLeftOut(Wrapped):
    """A flush that re-hashes only the first half of the dirty leaves."""

    def flush(self, levels, dirty) -> dict:
        return self.inner.flush(levels, dirty[: len(dirty) // 2])
