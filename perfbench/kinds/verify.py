"""Kind `verify`: calls are lists of BLS signature sets, answers are
verdicts. Payloads come from the seed; `correct` compares every verdict
with the plain reference's (`perfbench/reference/bls.py`).

`call` parameters of a traffic file of this kind:

    sets       signature sets in one call (a mainnet block: 128 attestation
               sets + proposer + randao + sync aggregate = 131)
    batchable  the `VerifySignatureOpts.batchable` the caller passes
    priority   name of the scheduler's `PriorityClass`

Faults a traffic file may ask for (calls per replay). The pool splits a
call into jobs (`job_spans`, from the configuration's
`MAX_SIGNATURE_SETS_PER_JOB`) and answers with the `all()` of their
verdicts, so every fault comes in two variants: `.first` puts it inside
the call's first job, `.last` inside its last, and everything else of the
call is honest. A job whose verdict is dropped or never awaited then shows
on every seed.

    tampered_pair  two signatures of one job shifted by +D and -D: both
                   well formed, both wrong, and their plain sum unchanged,
                   so a batch whose blinding coefficients are left out
                   passes them.
    off_subgroup   one pubkey moved by a point of the cofactor torsion:
                   its pairing equation still holds, only the subgroup
                   check rejects it.

All distinct sets of a run are one call's worth (`sets`), signed once
(pure Python: ~30 ms a set) and reordered from the seed for each replay
entry; a launch draws fresh blinding every time, so reuse cancels nothing.
"""

from __future__ import annotations

import random

from perfbench import stats
from perfbench.reference import bls
from perfbench.reference.parallel import judge_many, parallel_map

FAULTS = ("tampered_pair.first", "tampered_pair.last", "off_subgroup.first", "off_subgroup.last")
SPAN = "bench:verify"


def job_spans(n_sets: int, max_per_job: int) -> list[range]:
    """The positions of a call's sets that land in each job: the fewest
    jobs of at most `max_per_job`, sizes as equal as possible, the larger
    first (the reference's `chunkifyMaximizeChunkSize`; 131 -> 66 + 65)."""
    n_jobs = -(-n_sets // max_per_job)
    base, extra = divmod(n_sets, n_jobs)
    spans, pos = [], 0
    for i in range(n_jobs):
        size = base + (1 if i < extra else 0)
        spans.append(range(pos, pos + size))
        pos += size
    return spans


class Workload:
    def __init__(self, config: dict, traffic: dict, spec: dict, replay: list, seed: int):
        self.config, self.traffic, self.spec = config, traffic, spec
        self.replay, self.seed = replay, seed
        self.call_params = traffic["call"]
        self.jobs = job_spans(self.call_params["sets"], config["pool"]["MAX_SIGNATURE_SETS_PER_JOB"])
        unknown = set(traffic.get("faults", {})) - set(FAULTS)
        if unknown:
            raise ValueError(f"kind verify knows no fault {sorted(unknown)}")
        self.entries: list[list[tuple[bytes, bytes, bytes]]] = []
        self.system = None
        self.payloads: list = []
        self.options = None

    # -- set-up ----------------------------------------------------------------

    def prepare(self) -> None:
        """Every replay entry's sets, from the seed, on the host."""
        n = self.call_params["sets"]
        rng = random.Random(self.seed)
        order_r = bls.F.R
        scalars = [rng.randrange(1, order_r) for _ in range(n)]
        messages = [rng.randbytes(32) for _ in range(n)]
        pubkeys = [bytes.fromhex(h) for h in parallel_map("keys", scalars)]
        signatures = [
            bytes.fromhex(h)
            for h in parallel_map("sign", [[s, m.hex()] for s, m in zip(scalars, messages)])
        ]
        base = list(zip(pubkeys, messages, signatures))
        for entry in self.replay:
            erng = random.Random(entry.seed)
            sets = list(base)
            erng.shuffle(sets)
            what, _, where = (entry.fault or "").partition(".")
            job = self.jobs[0 if where == "first" else -1]
            if what == "tampered_pair":
                a, b = erng.sample(job, 2)
                # the shift is a third set's signature: sig_b - sig_b would be the identity
                shift = sets[erng.choice([i for i in range(n) if i not in (a, b)])][2]
                sets[a] = (sets[a][0], sets[a][1], bls.shift_signature(sets[a][2], shift, False))
                sets[b] = (sets[b][0], sets[b][1], bls.shift_signature(sets[b][2], shift, True))
            elif what == "off_subgroup":
                k = erng.choice(job)
                moved = bls.shift_pubkey_off_subgroup(sets[k][0], entry.seed)
                sets[k] = (moved, sets[k][1], sets[k][2])
            self.entries.append(sets)

    def attach(self, system) -> None:
        system.expect_verifier(self.config["resolves"]["verifier"])
        self.system = system
        self.payloads = [system.verify_payload(sets) for sets in self.entries]
        self.options = system.verify_options(
            self.call_params["batchable"], self.call_params["priority"]
        )

    async def call(self, rec):
        return bool(await self.system.verify(self.payloads[rec.entry], self.options))

    def release(self) -> None:
        self.payloads = []
        self.system = None

    def failed(self, records: list) -> int:
        return 0

    # -- metrics ---------------------------------------------------------------

    def end_to_end(self, records: list, start: float, end: float) -> dict[str, float]:
        """Over all the calls that returned inside the window; the rate
        over the whole window. One call is 131 sets, 0.67% of a 30 s
        window's ~149, so the rate reads one of two or three values."""
        done = [r for r in records if r.error is None and r.done <= end]
        out: dict[str, float] = {}
        if done:
            ms = [1000.0 * r.seconds for r in done]
            out["sigs_per_s"] = len(done) * self.call_params["sets"] / (end - start)
            out["verdict_p50_ms"] = stats.percentile(ms, 50)
            out["verdict_p90_ms"] = stats.percentile(ms, 90)
        return out

    # -- correct ---------------------------------------------------------------

    def check(self, warm: list, records: list) -> list[dict]:
        """Every verdict the run got, warm-up included, against the
        reference's: each distinct set judged once, on its own. A run
        proves something only if it judged faulty calls whose faults lie
        in the first job alone, and others in the last job alone."""
        answered = [r for r in warm + records if r.error is None]
        used = sorted({r.entry for r in answered})
        distinct = sorted({t for e in used for t in self.entries[e]})
        judged = dict(zip(distinct, judge_many(distinct)))
        want = {e: bls.reference_verdict([judged[t] for t in self.entries[e]]) for e in used}
        faulty_jobs = {
            e: {j for j, span in enumerate(self.jobs)
                if not all(bls.is_valid(judged[self.entries[e][k]]) for k in span)}
            for e in used
        }
        mismatches = sum(1 for r in answered if r.answer != want[r.entry])
        in_first = sum(1 for r in answered if faulty_jobs[r.entry] == {0})
        in_last = sum(1 for r in answered if faulty_jobs[r.entry] == {len(self.jobs) - 1})
        least = self.spec["correct"]["min_faulty_calls_per_job"]
        return [
            {"name": "verdict_mismatches", "value": mismatches, "limit": 0, "holds": mismatches == 0,
             "of": len(answered)},
            {"name": "unanswered_calls", "value": len(warm + records) - len(answered), "limit": 0,
             "holds": len(answered) == len(warm + records)},
            {"name": "faulty_first_job_calls", "value": in_first, "limit": least, "at_least": True,
             "holds": in_first >= least},
            {"name": "faulty_last_job_calls", "value": in_last, "limit": least, "at_least": True,
             "holds": in_last >= least},
        ]
