"""Kind `root`: a retained level stack of a binary SHA-256 tree behind
the dirty collector. A call rewrites the call's dirty leaves (from a
counter, so that no flush is a no-op), hands the stack and the dirty
indices to the collector and flushes; the answer is the root. `correct`
compares roots, and at the end every level of the stack, with hashlib
over the same leaves (`perfbench/reference/merkle.py`).

`call` parameters of a traffic file of this kind:

    dirty_leaves  leaves rewritten before each flush; every replay entry
                  has its own set of that many indices, drawn from the seed
"""

from __future__ import annotations

import random

import numpy as np

from perfbench.reference import merkle

FAULTS = ()
SPAN = "bench:flush"


class Workload:
    def __init__(self, config: dict, traffic: dict, spec: dict, replay: list, seed: int):
        self.config, self.traffic, self.spec = config, traffic, spec
        self.replay, self.seed = replay, seed
        if traffic.get("faults"):
            raise ValueError("kind root knows no faults")
        self.n_leaves = 1 << config["tree_depth"]
        self.dirty_sets: list[np.ndarray] = []
        self.system = None
        self.system_backend = ""
        self.levels: list[np.ndarray] = []

    # -- set-up ----------------------------------------------------------------

    def _initial_leaves(self) -> np.ndarray:
        return np.random.default_rng(self.seed).integers(
            0, 256, size=(self.n_leaves, 32), dtype=np.uint8
        )

    def prepare(self) -> None:
        count = self.traffic["call"]["dirty_leaves"]
        for entry in self.replay:
            rng = np.random.default_rng(entry.seed)
            self.dirty_sets.append(np.sort(rng.choice(self.n_leaves, size=count, replace=False)))

    def attach(self, system) -> None:
        system.expect_hasher(self.config["resolves"]["hasher"])
        self.system = system
        self.system_backend = system.runtime["hasher"]
        self.levels = system.build_stack(self._initial_leaves())

    @staticmethod
    def _rewrite(leaves: np.ndarray, dirty: np.ndarray, call: int) -> None:
        """What the state transition would do before asking for a root:
        new leaf values, here the call's running number."""
        leaves.view(np.uint64)[dirty, 0] = call + 1

    async def call(self, rec):
        dirty = self.dirty_sets[rec.entry]
        self._rewrite(self.levels[0], dirty, rec.call)
        rec.detail = self.system.flush(self.levels, dirty)
        return self.levels[-1][0].tobytes()

    def release(self) -> None:
        self.system = None

    def failed(self, records: list) -> int:
        """Flushes that left the path the system resolved at boot (a
        device flush that fell back to the host hasher)."""
        return sum(
            1 for r in records if r.error is None and r.detail.get("backend") != self.system_backend
        )

    # -- metrics ---------------------------------------------------------------

    def end_to_end(self, records: list, start: float, end: float) -> dict[str, float]:
        """The whole window over the flushes answered in it: a stall
        shows, also one that runs into the window's end."""
        done = [r for r in records if r.error is None and r.done <= end]
        if not done:
            return {}
        return {"root_flush_ms": 1000.0 * (end - start) / len(done)}

    def flush_series_ms(self, records: list, end: float) -> list[float]:
        return [1000.0 * r.seconds for r in records if r.error is None and r.done <= end]

    # -- correct ---------------------------------------------------------------

    def check(self, warm: list, records: list) -> list[dict]:
        """Replays every rewrite of the run on a copy of the leaves made
        from the seed, and compares a sample of roots (drawn from the
        seed, the last answered flush always in it) and at the end every
        level of the stack."""
        calls = warm + records
        answered = [r for r in calls if r.error is None]
        if [r.call for r in calls] != list(range(len(calls))):
            raise RuntimeError("calls are not numbered 0..n-1 in order")
        rng = random.Random(self.seed)
        k = min(self.spec["correct"]["roots_sampled"], len(answered))
        pool = [r.call for r in answered[:-1] if r.call >= len(warm)] or [r.call for r in answered[:-1]]
        sample = set(rng.sample(pool, min(k - 1, len(pool)))) if k > 1 else set()
        last_answered = answered[-1].call if answered else -1
        sample.add(last_answered)
        leaves = self._initial_leaves()
        mismatches = compared = 0
        levels_differing = len(self.levels)
        for r in calls:
            self._rewrite(leaves, self.dirty_sets[r.entry], r.call)
            if r.call in sample and r.error is None:
                compared += 1
                ref_levels = merkle.levels_from_leaves(leaves.tobytes())
                mismatches += r.answer != ref_levels[-1]
                if r.call == last_answered and r is calls[-1]:
                    levels_differing = sum(
                        1 for ours, ref in zip(self.levels, ref_levels) if ours.tobytes() != ref
                    )
        return [
            {"name": "root_mismatches", "value": mismatches, "limit": 0, "holds": mismatches == 0,
             "of": compared},
            {"name": "stack_levels_differing", "value": levels_differing, "limit": 0,
             "holds": levels_differing == 0, "of": len(self.levels)},
            {"name": "unanswered_calls", "value": len(calls) - len(answered), "limit": 0,
             "holds": len(answered) == len(calls)},
            {"name": "flushes_in_second_round", "value": len(calls) - len(self.replay),
             "limit": 1, "at_least": True, "holds": len(calls) - len(self.replay) >= 1},
        ]
