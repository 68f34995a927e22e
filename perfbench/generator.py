"""The one traffic generator. A traffic mix is a JSON file of parameters
under `perfbench/traffic/`; this module turns it, with `--seed`, into a
fixed replay and drives it as a closed loop.

Parameters (all required unless marked):

    loop          "closed": a caller sends its next call when the last answer
                  is in. (No open loop yet: the gossip cell brings it.)
    wave_calls    calls in flight together; the next wave starts when the last
                  answer of this one is in. 1 = one serial caller.
    replay_calls  length of the fixed replay. Call i of the run is replay
                  entry i mod replay_calls, so every seed does the same work
                  in another order and no call depends on the clock.
    faults        optional {fault name: calls per replay that carry it}. Which
                  entries carry one is drawn from the seed; what a fault is,
                  the kind module says (`perfbench/kinds/`).
    call          parameters of one call, read by the kind module.

A cell that only needs other numbers adds a data file and nothing else.
"""

from __future__ import annotations

import asyncio
import contextlib
import random
import time
from dataclasses import dataclass, field

REQUIRED = ("loop", "wave_calls", "replay_calls", "call")
SEED_LIMIT = 2**63


@dataclass(frozen=True)
class ReplayEntry:
    index: int
    fault: str | None
    seed: int  # this entry's own seed, for the kind module's draws


@dataclass
class Record:
    """One call as the caller saw it."""

    call: int  # running number over the whole run
    entry: int  # index into the replay
    issued: float  # caller's clock, seconds
    done: float = 0.0
    answer: object = None
    error: str | None = None
    detail: dict = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.done - self.issued


def validate(traffic: dict) -> None:
    missing = [k for k in REQUIRED if k not in traffic]
    if missing:
        raise ValueError(f"traffic file lacks {missing}")
    if traffic["loop"] != "closed":
        raise ValueError(f"loop {traffic['loop']!r}: only 'closed' is generated so far")
    if traffic["wave_calls"] < 1 or traffic["replay_calls"] < 1:
        raise ValueError("wave_calls and replay_calls must be at least 1")
    n_faulty = sum(traffic.get("faults", {}).values())
    if n_faulty > traffic["replay_calls"]:
        raise ValueError("more faulty calls than replay entries")


def build_replay(traffic: dict, seed: int) -> list[ReplayEntry]:
    """The fixed replay for this seed: which entries carry which fault,
    and a seed of its own for each entry."""
    validate(traffic)
    rng = random.Random(seed)
    n = traffic["replay_calls"]
    faults: list[str | None] = [None] * n
    names = [name for name, count in sorted(traffic.get("faults", {}).items()) for _ in range(count)]
    for slot, name in zip(rng.sample(range(n), len(names)), names):
        faults[slot] = name
    return [ReplayEntry(i, faults[i], rng.randrange(SEED_LIMIT)) for i in range(n)]


async def drive(call_fn, traffic: dict, *, seconds: float | None = None, calls: int | None = None,
                first_call: int = 0, clock=time.perf_counter,
                span=contextlib.nullcontext) -> tuple[list[Record], float, float]:
    """Closed loop of waves: until `seconds` have passed (no new wave is
    started after that; the one in flight is waited for), or for exactly
    `calls` calls. `call_fn(record)` is awaited once per call and returns
    the answer, inside `span()` (the harness's own trace annotation).
    Returns (records, start, end) on `clock`; `end` is
    start + seconds for a timed run, the last answer's time otherwise."""
    wave, n_replay = traffic["wave_calls"], traffic["replay_calls"]
    records: list[Record] = []
    start = clock()
    deadline = start + seconds if seconds is not None else None

    async def one(rec: Record) -> None:
        try:
            with span():
                rec.answer = await call_fn(rec)
        except Exception as e:  # a failed call is counted, not fatal
            rec.error = f"{type(e).__name__}: {e}"[:200]
        rec.done = clock()

    issued = first_call
    while True:
        if deadline is not None and clock() >= deadline:
            break
        if calls is not None and issued - first_call >= calls:
            break
        size = wave if calls is None else min(wave, calls - (issued - first_call))
        batch = []
        for _ in range(size):
            rec = Record(call=issued, entry=issued % n_replay, issued=clock())
            issued += 1
            records.append(rec)
            batch.append(one(rec))
        await asyncio.gather(*batch)
    end = deadline if deadline is not None else clock()
    return records, start, end
