"""The plain reference put in the program's place: the same two seams as
`entries/node.py`, answered by `perfbench/reference/` on the host. With
`control` set it leaves one stated guarantee out, and a run then has to
come out as not correct. It needs no chip and imports nothing of the
program, so the tests' rehearsals and the controls run through it.

Verify controls: see `reference.bls.CONTROLS`. Root control
`stale_repeat`: a flush whose set of dirty leaves was flushed before is
answered from what was hashed then, which a fixed replay would reward.
"""

from __future__ import annotations

import hashlib

import numpy as np

from perfbench.reference import bls
from perfbench.reference.parallel import judge_many

NEEDS_CHIP = False
ROOT_CONTROLS = ("stale_repeat",)


class ReferenceSystem:
    def __init__(self, control: str | None = None, workers: int | None = None):
        if control is not None and control not in bls.CONTROLS + ROOT_CONTROLS:
            raise ValueError(f"unknown control {control!r}")
        self.control = control
        self.workers = workers
        self.runtime = {"platform": "host", "device_kind": "reference", "count": 0,
                        "verifier": "reference", "hasher": "reference"}
        self._judged: dict[tuple, dict] = {}
        self._flushed: set[bytes] = set()
        self._launches: list[dict] = []

    # -- verify seam -----------------------------------------------------------

    def verify_payload(self, triples):
        return [tuple(t) for t in triples]

    def verify_options(self, batchable: bool, priority: str):
        return None

    def judge(self, triples) -> list[dict]:
        new = sorted({t for t in triples if t not in self._judged})
        self._judged.update(zip(new, judge_many(new, self.workers)))
        return [self._judged[t] for t in triples]

    async def verify(self, payload, options) -> bool:
        judged = self.judge(payload)
        if self.control in bls.CONTROLS:
            return bls.control_verdict(judged, self.control)
        return bls.reference_verdict(judged)

    def expect_verifier(self, want: str) -> None:
        pass

    # -- state-root seam -------------------------------------------------------

    def build_stack(self, leaves):
        levels = [leaves]
        while levels[-1].shape[0] > 1:
            levels.append(_hash_pairs(levels[-1]))
        return levels

    def flush(self, levels, dirty) -> dict:
        dirty = np.unique(np.asarray(dirty, dtype=np.int64))
        key = hashlib.sha256(dirty.tobytes()).digest()
        stale = self.control == "stale_repeat" and key in self._flushed
        self._flushed.add(key)
        if not stale:
            frontier = dirty
            for lvl in range(1, len(levels)):
                frontier = np.unique(frontier >> 1)
                pairs = np.empty(2 * frontier.size, dtype=np.int64)
                pairs[0::2] = 2 * frontier
                pairs[1::2] = 2 * frontier + 1
                levels[lvl][frontier] = _hash_pairs(levels[lvl - 1][pairs])
        return {"backend": "reference", "levels": len(levels) - 1, "launches": 0,
                "dirty_chunks": int(dirty.size), "seconds": 0.0}

    def expect_hasher(self, want: str) -> None:
        pass

    # -- counters --------------------------------------------------------------

    def launch_ledger(self) -> list[dict]:
        return []

    def counters(self) -> dict[str, float]:
        return {}

    def fallbacks(self, counters) -> float:
        return 0.0

    async def close(self) -> None:
        pass


def _hash_pairs(rows: np.ndarray) -> np.ndarray:
    buf = rows.tobytes()
    sha = hashlib.sha256
    out = b"".join(sha(buf[i : i + 64]).digest() for i in range(0, len(buf), 64))
    return np.frombuffer(out, dtype=np.uint8).reshape(-1, 32).copy()


async def boot(config: dict, control: str | None = None) -> ReferenceSystem:
    return ReferenceSystem(control)
