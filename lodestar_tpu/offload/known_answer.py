"""Known-answer warm start of an offload host (ROADMAP D2 and M6): before
the port is bound, every verify program that a non-batchable half of a
block can ride answers two known batches through the pool, one
valid and one with a tampered set, and each verdict is held against the
CPU oracle's. That is the first call of each program (the trace and the
compile, or the load from the persistent cache: 85–200 s each on a v5e),
so no RPC pays it inside a 2 s `GOSSIP_BLOCK` budget, and a program that
disagrees with the oracle stops the boot instead of serving.

The programs are the pool's launch units (`chain/bls/pool._launch_units`)
for jobs of `KNOWN_JOB_SETS` sets, a block's half: one job alone rides
the flat 128-row program; two to four jobs of one package ride
`_grouped_launch_verify` at the slot the slot rule gives such jobs
(`telemetry.group_slot_rows`: 72 rows), (144 rows, 2 slots) or (288, 4).
Three first calls, the programs a fleet's blocks ride.
Where the mesh cannot group (its lanes have no grouped entry: a backend
on the split schedule, a faked lane) every job rides the flat program of
whatever schedule serves, and that alone is warmed.
What stays cold, and compiles at its first use: the multi-job launch's
128-row rung, (256, 2) and (512, 4), which jobs of 73 to 128 sets ride
(no block is cut that way; warming it too would be two more first calls,
+110 to 170 s of boot), and every smaller size class (a job of 64 sets
or fewer; ROADMAP S4 (a), M6).
"""

from __future__ import annotations

import asyncio
import time

from lodestar_tpu import telemetry
from lodestar_tpu.crypto.bls.api import SignatureSet

__all__ = ["KnownAnswerError", "KNOWN_JOB_SETS", "known_sets", "check_known_answers"]

#: sets in a known job: the larger half of a 131-set block, so that the
#: job is of the 128 size class as a block's jobs are
KNOWN_JOB_SETS = 66

_SECRETS = (0x6C6F6465, 0x73746172, 0x2D747075, 0x6F66666C)  # any four fixed scalars
_LANE_LAUNCH = "bls_lane_verify"


class KnownAnswerError(RuntimeError):
    """A verify program answered a known batch otherwise than the CPU
    oracle, or another program than the one asked answered it."""


def known_sets() -> tuple[list[SignatureSet], SignatureSet]:
    """Four valid sets signed from fixed keys, and a tampered one: the
    first set's key and message under the second set's signature (a
    point of the right subgroup, so only the pairing can refuse it)."""
    from lodestar_tpu.crypto import bls

    valid = []
    for i, scalar in enumerate(_SECRETS):
        sk = bls.SecretKey.from_bytes(scalar.to_bytes(32, "big"))
        message = bytes([0xA0 + i]) * 32
        valid.append(
            SignatureSet(pubkey=bls.sk_to_pk(sk), message=message, signature=bls.sign(sk, message))
        )
    tampered = SignatureSet(
        pubkey=valid[0].pubkey, message=valid[0].message, signature=valid[1].signature
    )
    return valid, tampered


def _job(valid: list[SignatureSet], tampered: SignatureSet | None) -> list[SignatureSet]:
    """A known job: the valid sets in turn (a launch draws fresh blinding
    for every row, so a repeated set cancels nothing), the tampered set
    in its last row where there is one."""
    sets = [valid[i % len(valid)] for i in range(KNOWN_JOB_SETS)]
    if tampered is not None:
        sets[-1] = tampered
    return sets


def programs_of(pool) -> list[tuple[int, int]]:
    """(rows, jobs) of every program a non-batchable job of
    `KNOWN_JOB_SETS` sets can ride in this pool."""
    from lodestar_tpu.chain.bls.pool import MAX_GROUP_JOBS

    programs = [(telemetry.size_class_of(KNOWN_JOB_SETS), 1)]
    slot = telemetry.group_slot_rows([KNOWN_JOB_SETS])
    slots = 2
    while pool.mesh.grouping_available() and slots <= MAX_GROUP_JOBS:
        programs.append((slots * slot, slots))
        slots *= 2
    return programs


async def check_known_answers(pool, oracle=None, log=None) -> list[dict]:
    """Run on the pool's loop. Returns one record a program; raises
    `KnownAnswerError` on the first disagreement."""
    from lodestar_tpu.chain.bls import VerifySignatureOpts

    if oracle is None:
        from lodestar_tpu.crypto.bls.api import verify_signature_sets as oracle

    valid, tampered = known_sets()
    # the oracle judges each distinct set once, on its own
    if not all(oracle([s]) for s in valid) or oracle([tampered]):
        raise KnownAnswerError("the CPU oracle does not hold the known sets to their verdicts")
    opts = VerifySignatureOpts(batchable=False)
    answered = []
    for rows, n_jobs in programs_of(pool):
        t0 = time.monotonic()
        walls = []
        for bad in (None, n_jobs - 1):
            jobs = [_job(valid, tampered if k == bad else None) for k in range(n_jobs)]
            want = [k != bad for k in range(n_jobs)]
            seen = {e["seq"] for e in telemetry.launch_ledger()}
            # gathered on the loop thread: every job is in the queue before
            # the former wakes, so the jobs are one package and one unit
            got = await asyncio.gather(*(pool.verify_signature_sets(j, opts) for j in jobs))
            if list(got) != want:
                raise KnownAnswerError(
                    f"the {rows}-row verify program answered {list(got)} to a known batch of "
                    f"{n_jobs} job(s), the CPU oracle {want}"
                )
            if telemetry.launch_telemetry_active():
                launches = [
                    e for e in telemetry.launch_ledger()
                    if e["seq"] not in seen and e["program"] == _LANE_LAUNCH
                ]
                if [e["size_class"] for e in launches] != [rows]:
                    raise KnownAnswerError(
                        f"a known batch for the {rows}-row program was answered by launches of "
                        f"{[e['size_class'] for e in launches]} rows"
                    )
                walls.append(launches[0]["seconds"])
        record = {"rows": rows, "jobs": n_jobs, "seconds": round(time.monotonic() - t0, 3)}
        if walls:
            record["first_launch_s"], record["second_launch_s"] = (round(w, 4) for w in walls)
        answered.append(record)
        if log is not None:
            log.info("offload verify program answered its known batches", record)
    return answered
