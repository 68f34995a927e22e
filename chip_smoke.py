"""chip_smoke.py — the quickest proof that lodestar-tpu still starts on the chip.

Drives the served verify path and the state-root path once, through the
entry points a deployment uses and at the sizes the reference runs, on
whatever accelerator JAX finds, and checks every answer against a plain
CPU reference (the pure-Python BLS oracle, hashlib):

1. a beacon node booted with default options (`BeaconNode.init`) must
   resolve a device verifier and a device hasher;
2. every verify program that can serve a verdict by default (the single
   launch, and the staged three-jit schedule over fused device prep)
   must agree with the oracle on a valid, a tampered and a structurally
   invalid batch at both size classes the run dispatches (128, 512);
3. the offload host as `offload.server.main()` builds it
   (`offload.server.boot_host`: the verifier pool behind the wire, the
   port bound only after the flat 128-row program and the multi-job
   programs at (144, 2) and (288, 4), the shapes a block's halves ride,
   have each answered a valid and a tampered known batch), behind real
   gRPC on localhost, answers ten concurrent jobs, each a half of a
   131-set block (eight valid, one with a cancelling pair, one
   malformed), through `BlsOffloadClient`: the jobs a fleet sends, which
   ride the warmed programs (a job of 73 to 128 sets that shares a
   launch rides 128-row slots, and that program's first call is at its
   first use: more than an RPC's deadline);
4. the node's own `BlsDeviceVerifierPool` takes 1,024 sets at
   gossip-attestation priority, so packages reach the 512-set cap;
   the multi-job launch at (2 slots, 72 rows) gives each job the
   oracle's verdict with a cancelling pair in the first job only and
   an off-subgroup key in the last only, and so it does at (2 slots,
   128 rows), the rung a job of more than 72 sets lifts its launch to;
   the pool answers a 131-set block with ONE launch of 144 rows;
5. the node's pool, its pubkey table grown to 64 entries (the genesis
   validators' keys from node init, the rest appended as deposits are),
   takes a 131-set block whose sets name their signers by registry
   index: its halves ride one (144, 2) launch that gathers and sums
   their signers on the chip (`bls.aggregate`) and agree with the
   oracle; a swapped signer in the first job and an index outside the
   table in the last each fail their own job only;
6. `merkle_root_device` at 2^20 chunks (the 1M-validator shape) and
   `DirtyCollector` flushes of a 2^20-leaf stack with 512 and 2^17 dirty
   leaves must give hashlib's roots with `backend == "device"`;
7. no fallback, degradation or wedge counter may have moved
   (`lodestar_bls_aggregate_fallback_total` among them), and the launch
   ledger must name the programs and size classes that ran.

On a host with several chips the smoke checks nothing chip by chip:
that every lane serves, each on its own chip, is what the benchmark's
`backfill-window-four-lanes` cell measures (`lane_launch_share.min`,
`chip_busy_share.min`).

One process holds the chip (the server runs on threads). Any failed
check or any exception exits non-zero; so does a host where JAX finds no
accelerator. The last line of standard output is
`{"ok": true, "device": {"platform", "kind", "count"}}`.

    python3 chip_smoke.py [--seed N]
"""

from __future__ import annotations

import argparse
import asyncio
import concurrent.futures
import faulthandler
import hashlib
import json
import os
import random
import sys
import time

SIZE_CLASSES = (128, 512)  # MAX_SIGNATURE_SETS_PER_JOB and the pool's package cap
TREE_DEPTH = 20  # 2^20 chunks: BASELINE config 4, the 1M-validator shape
DIRTY_COUNTS = (512, 1 << 17)
ZERO_COUNTERS = (
    "lodestar_bls_prep_fallback_total",
    "lodestar_bls_single_launch_fallback_total",
    "lodestar_bls_aggregate_fallback_total",
    "lodestar_ssz_htr_fallback_total",
    "lodestar_kzg_device_fallback_total",
    "lodestar_resilience_fallback_total",
    "lodestar_resilience_fallback_skipped_total",
    "lodestar_sched_lane_wedge_trips_total",
)


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


_T0 = time.monotonic()


# --- data, made from the seed -------------------------------------------------


def make_batches(seed: int) -> dict:
    """The valid / tampered / structurally invalid batch per size class,
    plus the served path's malformed job. Signing is pure Python, so 128
    distinct sets are made once and reused: the 512 batch is four copies
    (every launch draws fresh blinding, so copies do not cancel)."""
    from lodestar_tpu.crypto.bls import curve, fields, serdes
    from lodestar_tpu.crypto.bls.api import SignatureSet
    from lodestar_tpu.models.batch_verify import make_synthetic_sets

    base = make_synthetic_sets(SIZE_CLASSES[0], seed=seed)
    rng = random.Random(seed)
    # a pubkey on the curve but outside the r-subgroup: right length,
    # right flags, so only the subgroup check (on the device) rejects it
    while True:
        x = rng.randrange(fields.P)
        y = fields.fp_sqrt((x * x * x + 4) % fields.P)
        if y is not None and not curve.g1_in_subgroup((x, y)):
            off_subgroup_pk = serdes.g1_to_bytes((x, y))
            break
    batches = {}
    for size in SIZE_CLASSES:
        valid = base * (size // len(base))
        k = rng.randrange(size)
        tampered = list(valid)
        tampered[k] = SignatureSet(
            pubkey=valid[k].pubkey,
            message=valid[k].message,
            signature=valid[(k + 1) % len(base)].signature,
        )
        j = rng.randrange(size)
        invalid = list(valid)
        invalid[j] = SignatureSet(
            pubkey=off_subgroup_pk, message=valid[j].message, signature=valid[j].signature
        )
        batches[size] = {"valid": valid, "tampered": tampered, "invalid": invalid}
    # the two jobs of a 131-set block (66 and 65 sets), and each with a
    # fault of its own: a pair of signatures shifted by +D and -D, which
    # only distinct blinding coefficients catch, and the off-subgroup key
    first, last = base[:66], base[63:128]
    d = curve.g2_mul(curve.G2_GEN, rng.randrange(1, fields.R))
    cancelling = list(first)
    for i, shift in zip(rng.sample(range(len(first)), 2), (d, curve.g2_neg(d))):
        sig = curve.g2_add(serdes.g2_from_bytes(first[i].signature), shift)
        cancelling[i] = SignatureSet(
            pubkey=first[i].pubkey, message=first[i].message, signature=serdes.g2_to_bytes(sig)
        )
    off_key = list(last)
    i = rng.randrange(len(last))
    off_key[i] = SignatureSet(
        pubkey=off_subgroup_pk, message=last[i].message, signature=last[i].signature
    )
    # a job of more than 72 sets beside them: the launch's slots are 128 rows then
    batches["jobs"] = {"first": first, "last": last, "cancelling": cancelling, "off_key": off_key,
                       "long": base[:100]}
    malformed = list(first)
    malformed[rng.randrange(len(first))] = SignatureSet(
        pubkey=b"\x00" * 48, message=base[0].message, signature=b"\xff" * 96
    )
    batches["malformed"] = malformed
    return batches


def oracle_verdicts(batches: dict) -> dict:
    """The CPU oracle's verdict for every batch the run submits."""
    from lodestar_tpu.crypto.bls.api import verify_signature_sets

    out = {"malformed": verify_signature_sets(batches["malformed"])}
    for name, sets in batches["jobs"].items():
        out[("job", name)] = verify_signature_sets(sets)
    for size in SIZE_CLASSES:
        for kind, sets in batches[size].items():
            out[(size, kind)] = verify_signature_sets(sets)
    return out


def hashlib_root(leaves: bytes) -> bytes:
    level = leaves
    while len(level) > 32:
        level = b"".join(
            hashlib.sha256(level[i : i + 64]).digest() for i in range(0, len(level), 64)
        )
    return level


# --- phases -------------------------------------------------------------------


async def boot_node():
    """A node with default options, as `python -m lodestar_tpu beacon`
    builds it (REST off, manual clock: nothing here needs either)."""
    from lodestar_tpu import params
    from lodestar_tpu.config import minimal_chain_config
    from lodestar_tpu.node import BeaconNode, BeaconNodeOptions
    from lodestar_tpu.state_transition.genesis import create_interop_genesis_state

    params.set_active_preset("minimal")
    p = params.active_preset()
    far = 2**64 - 1
    cc = minimal_chain_config().replace(
        ALTAIR_FORK_EPOCH=far, BELLATRIX_FORK_EPOCH=far,
        CAPELLA_FORK_EPOCH=far, DENEB_FORK_EPOCH=far,
    )
    genesis = create_interop_genesis_state(8, p=p, genesis_fork_version=cc.GENESIS_FORK_VERSION)
    return await BeaconNode.init(
        anchor_state=genesis,
        chain_config=cc,
        opts=BeaconNodeOptions(rest_enabled=False, manual_clock=True),
        p=p,
        time_fn=lambda: 0.0,
    )


def phase_programs(batches: dict, oracle) -> dict:
    """Every default verify program against the oracle (ROADMAP S1 gate)."""
    from lodestar_tpu.models import batch_verify as bv

    check(bv.single_launch_active(), "this backend does not run the single-launch schedule")
    programs = {
        "single_launch": bv.verify_sets_single_launch,
        "staged_fused_prep": bv._verify_sets_split,
    }
    out = {}
    for size in SIZE_CLASSES:
        for name, fn in programs.items():
            for kind, sets in batches[size].items():
                t0 = time.monotonic()
                got = bool(fn(sets))
                secs = time.monotonic() - t0
                want = oracle()[(size, kind)]
                log(f"program {name}/{size}/{kind}: {got} (oracle {want}) {secs:.1f}s")
                check(got == want, f"{name} at {size} on the {kind} batch: {got}, oracle {want}")
                out[f"{name}/{size}/{kind}"] = got
    return out


async def phase_served(host, batches: dict, oracle) -> dict:
    """The offload host as the command builds it, behind real gRPC."""
    from lodestar_tpu.offload.client import BlsOffloadClient

    backend = host.backend
    check(backend.description["verifier"] == "device",
          f"offload server resolved {backend.description}")
    check(host.pool is not None, "the offload host serves no verifier pool")
    check([(w["rows"], w["jobs"]) for w in host.warmed] == [(128, 1), (144, 2), (288, 4)],
          f"warm start answered {host.warmed}")
    client = BlsOffloadClient(f"127.0.0.1:{host.port}")
    # a block's halves: every launch they form is one the warm start made the first call of
    halves = batches["jobs"]
    jobs = [("first", halves["first"]), ("last", halves["last"])] * 4 + [
        ("cancelling", halves["cancelling"]),
        ("malformed", batches["malformed"]),
    ]
    try:
        got = await asyncio.gather(*(client.verify_signature_sets(s) for _, s in jobs))
    finally:
        await client.close()
    for (kind, _), verdict in zip(jobs, got):
        want = oracle()["malformed" if kind == "malformed" else ("job", kind)]
        check(verdict == want, f"served {kind} job: {verdict}, oracle {want}")
    log(f"served path: {len(jobs)} jobs, verdicts {[bool(v) for v in got]}")
    return {"description": backend.description, "verdicts": [bool(v) for v in got],
            "warmed": host.warmed, "lanes": backend.mesh.lane_states()}


async def phase_node_pool(node, batches: dict, oracle) -> dict:
    """1,024 sets in flight on the node's own pool."""
    from lodestar_tpu.chain.bls import BlsDeviceVerifierPool, VerifySignatureOpts
    from lodestar_tpu.scheduler import PriorityClass

    pool = node.bls
    check(isinstance(pool, BlsDeviceVerifierPool), f"node verifier is {type(pool).__name__}")
    cap = SIZE_CLASSES[1]
    # eight 128-set jobs; the pool packages them 512 at a time, and each
    # package is exactly the valid 512 batch the oracle judged
    sets = batches[cap]["valid"] * 2
    got = await pool.verify_signature_sets(
        sets, VerifySignatureOpts(batchable=True, priority=PriorityClass.GOSSIP_ATTESTATION)
    )
    want = oracle()[(cap, "valid")]
    check(got == want, f"node pool on {len(sets)} sets: {got}, oracle {want}")
    m = dict(pool.metrics)
    check(m["sig_sets_started"] == len(sets), f"pool started {m['sig_sets_started']} sets")
    check(m["batch_sigs_success"] == len(sets), f"pool batch path served {m}")
    for key in ("errors", "batch_retries", "sharded_fallbacks"):
        check(m[key] == 0, f"pool {key} = {m[key]}")
    log(f"node pool: {len(sets)} sets -> {got}; {m}")
    return {"verdict": bool(got), "metrics": m, "lanes": pool.mesh.lane_states()}


GROUPED_CASES = (("first", "last"), ("cancelling", "last"), ("first", "off_key"),
                 ("cancelling", "long"), ("long", "off_key"))


async def phase_grouped(node, batches: dict, oracle) -> dict:
    """The multi-job launch at (144, 2) and, where a job is longer than
    72 sets, at (256, 2): a verdict a job, each the oracle's for that
    job alone; then the pool's road to it."""
    from lodestar_tpu.chain.bls import VerifySignatureOpts
    from lodestar_tpu.models import batch_verify as bv
    from lodestar_tpu.scheduler import PriorityClass

    jobs = batches["jobs"]
    out = {}
    for names in GROUPED_CASES:
        t0 = time.monotonic()
        got = bv.verify_sets_grouped_launch([jobs[n] for n in names])
        want = [oracle()[("job", n)] for n in names]
        log(f"grouped launch {names}: {got} (oracle {want}) {time.monotonic() - t0:.1f}s")
        check(got == want, f"grouped launch on {names}: {got}, oracle {want}")
        out["/".join(names)] = got
    check(out["cancelling/last"] == out["cancelling/long"] == [False, True]
          and out["first/off_key"] == out["long/off_key"] == [True, False],
          f"the planted faults did not fail their own jobs only: {out}")
    # a 131-set block through the pool: jobs of 66 and 65, one launch
    pool = node.bls
    block = jobs["first"] + jobs["last"]
    check(len(block) == 131, f"block of {len(block)} sets")
    lane_launches = lambda: sum(lane.launches for lane in pool.mesh.lanes)  # noqa: E731
    before, launches = dict(pool.metrics), lane_launches()
    got = await pool.verify_signature_sets(
        block, VerifySignatureOpts(priority=PriorityClass.GOSSIP_BLOCK)
    )
    check(got is True, f"pool on an honest 131-set block: {got}")
    check(pool.metrics["jobs_started"] - before["jobs_started"] == 2, f"pool jobs {pool.metrics}")
    took = lane_launches() - launches
    check(took == 1, f"a block took {took} launches")
    tampered = jobs["cancelling"] + jobs["last"]
    check(await pool.verify_signature_sets(
        tampered, VerifySignatureOpts(priority=PriorityClass.GOSSIP_BLOCK)) is False,
        "pool passed a block with a cancelling pair")
    log(f"pool: 131-set block in {took} launch")
    out["pool_block_launches"] = took
    return out


TABLE_ENTRIES = 64


async def phase_indexed(node, seed: int) -> dict:
    """A block whose sets name their signers by registry index, through
    the node's pool and its pubkey table: the launch sums each row's
    signers on the chip. Interop keys, so a set of several signers is
    signed once, by the sum of their secret keys."""
    from lodestar_tpu.chain.bls import VerifySignatureOpts
    from lodestar_tpu.crypto.bls.api import IndexedSignatureSet, SecretKey, sign, verify_signature_sets
    from lodestar_tpu.crypto.bls.fields import R
    from lodestar_tpu.models import batch_verify as bv
    from lodestar_tpu.scheduler import PriorityClass
    from lodestar_tpu.state_transition.genesis import interop_secret_keys

    pool = node.bls
    table = pool.pubkey_table
    check(pool.takes_indexed_sets, "the node's pool holds no pubkey table on its lanes")
    sks = interop_secret_keys(TABLE_ENTRIES)
    check([table.pubkey_at(i) for i in range(len(table))] == [sk.to_pubkey() for sk in sks[: len(table)]],
          "node init did not load the anchor state's registry into the table")
    table.extend([sk.to_pubkey() for sk in sks[len(table):]])  # deposits: checked
    check(table.lanes() == {lane.label: TABLE_ENTRIES for lane in pool.mesh.lanes}, f"table {table.lanes()}")

    rng = random.Random(seed)
    block = []
    for _ in range(131):
        signers = [rng.randrange(TABLE_ENTRIES) for _ in range(rng.randint(1, 6))]  # repeats are data
        message = rng.randbytes(32)
        scalar = sum(sks[i].scalar for i in signers) % R
        block.append(IndexedSignatureSet(tuple(signers), message, sign(SecretKey(scalar), message)))
    first, last = block[:66], block[66:]
    s = first[5]
    swapped = list(first)
    swapped[5] = IndexedSignatureSet(((s.indices[0] + 1) % TABLE_ENTRIES,) + s.indices[1:], s.message, s.signature)
    beyond = list(last)
    beyond[7] = IndexedSignatureSet(last[7].indices + (TABLE_ENTRIES,), last[7].message, last[7].signature)
    out = {}
    for name, jobs in (("honest", [first, last]), ("swapped", [swapped, last]), ("beyond", [first, beyond])):
        t0 = time.monotonic()
        got = bv.verify_sets_grouped_launch(jobs, None, table)
        want = [verify_signature_sets(job, table.pubkey_at) for job in jobs]
        log(f"indexed launch {name}: {got} (oracle {want}) {time.monotonic() - t0:.1f}s")
        check(got == want, f"indexed launch {name}: {got}, oracle {want}")
        out[name] = got
    check(out == {"honest": [True, True], "swapped": [False, True], "beyond": [True, False]},
          f"the planted faults did not fail their own jobs only: {out}")
    before = dict(pool.metrics)
    opts = VerifySignatureOpts(priority=PriorityClass.GOSSIP_BLOCK)
    check(await pool.verify_signature_sets(block, opts) is True, "pool on an honest indexed block")
    check(await pool.verify_signature_sets(swapped + last, opts) is False, "pool passed a swapped signer")
    check(pool.metrics["indexed_rows_started"] - before["indexed_rows_started"] == 262, f"pool {pool.metrics}")
    out["aggregate_points_started"] = pool.metrics["aggregate_points_started"] - before["aggregate_points_started"]
    log(f"pool: two indexed blocks, {out['aggregate_points_started']} signers named")
    return out


def phase_state_root(seed: int) -> dict:
    import jax
    import numpy as np

    from lodestar_tpu.ops import sha256 as S
    from lodestar_tpu.ssz.device_htr import DirtyCollector
    from lodestar_tpu.ssz.hash import hash_nodes_cpu

    n = 1 << TREE_DEPTH
    rng = np.random.default_rng(seed)
    leaves = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
    want = hashlib_root(leaves.tobytes())
    t0 = time.monotonic()
    got = S.bytes_from_words(np.asarray(S.merkle_root_device(
        jax.device_put(S.words_from_bytes(leaves.tobytes()))
    )))
    check(got == want, f"merkle_root_device at 2^{TREE_DEPTH} chunks differs from hashlib")
    out = {"merkle_root_device_s": round(time.monotonic() - t0, 2), "flushes": []}
    log(f"merkle_root_device 2^{TREE_DEPTH}: equal to hashlib")

    # the retained level stack a state tracker holds, built on the CPU path
    levels = [leaves]
    while levels[-1].shape[0] > 1:
        levels.append(hash_nodes_cpu(levels[-1]).copy())  # the collector writes into it
    check(levels[-1][0].tobytes() == want, "CPU level stack root differs from hashlib")
    for count in DIRTY_COUNTS:
        dirty = rng.choice(n, size=count, replace=False)
        leaves[dirty] = rng.integers(0, 256, size=(count, 32), dtype=np.uint8)
        want = hashlib_root(leaves.tobytes())
        coll = DirtyCollector()
        coll.add_stack_job(levels, dirty)
        stats = coll.flush()
        check(stats["backend"] == "device", f"collector flush backend {stats['backend']}")
        check(levels[-1][0].tobytes() == want,
              f"collector flush with {count} dirty leaves differs from hashlib")
        stats["seconds"] = round(stats["seconds"], 3)
        out["flushes"].append(stats)
        log(f"collector flush, {count} dirty leaves: {stats}")
    # the sparse flush stays on the host by size (every level under the
    # device threshold); the wide one must really have launched
    check(out["flushes"][-1]["launches"] > 0, "the 2^17-leaf flush launched nothing")
    return out


def ledger_summary() -> dict:
    """Programs and size classes that ran: first call (set-up: trace +
    compile or cache load) against later calls, from the launch ledger."""
    from lodestar_tpu import telemetry

    out: dict = {}
    for e in telemetry.launch_ledger():
        row = out.setdefault(
            f"{e['program']}/{e['size_class']}",
            {"first_call_s": 0.0, "first_calls": 0, "cached_calls": 0},
        )
        if e["compile"]:
            row["first_calls"] += 1
            row["first_call_s"] = round(row["first_call_s"] + e["seconds"], 2)
        else:
            row["cached_calls"] += 1
    return out


def counter_totals(*registries) -> dict:
    """Each watched counter summed over its label sets and the registries
    (the node's, and the offload host's: the process-global device seams
    report to whichever of the two was booted last)."""
    totals = dict.fromkeys(ZERO_COUNTERS, 0.0)
    for registry in registries:
        for family in registry.collect():
            for sample in family.samples:
                if sample.name in totals:
                    totals[sample.name] += sample.value
    return totals


# --- main ---------------------------------------------------------------------


async def run(seed: int, device: dict, compile_stats: dict) -> dict:
    from lodestar_tpu import native, telemetry
    from lodestar_tpu.native import bls as native_bls
    from lodestar_tpu.offload.server import boot_host
    from lodestar_tpu.ops import fp_pallas

    check("LODESTAR_FP_PALLAS" not in os.environ, "LODESTAR_FP_PALLAS is set")
    check(fp_pallas.use_pallas(), "use_pallas() is false on this backend")
    report: dict = {
        "native": {"bls": native_bls.available(), "sha256": native.sha256_backend()},
    }

    node = await boot_node()
    try:
        # room for every dispatch of the run (the default bounds a slot)
        telemetry.configure_launch_telemetry(ledger_size=4096)
        report["node"] = node.device_runtime
        check(node.device_runtime["verifier"] == "device", f"node: {node.device_runtime}")
        check(node.device_runtime["hasher"] == "device", f"node: {node.device_runtime}")
        check(
            (node.device_runtime["platform"], node.device_runtime["count"])
            == (device["platform"], device["count"]),
            f"node saw {node.device_runtime}, smoke saw {device}",
        )

        batches = make_batches(seed)
        log("signature sets made")
        # the oracle is pure Python: it works on a thread while the
        # device programs compile, and every comparison waits for it
        with concurrent.futures.ThreadPoolExecutor(1) as ex:
            oracle = ex.submit(oracle_verdicts, batches).result
            report["programs"] = phase_programs(batches, oracle)
            # default flags, as server.main() does; a port of the system's choosing
            host = await asyncio.get_event_loop().run_in_executor(None, lambda: boot_host(port=0))
            try:
                report["served"] = await phase_served(host, batches, oracle)
            finally:
                host.stop()
            report["node_pool"] = await phase_node_pool(node, batches, oracle)
            report["grouped"] = await phase_grouped(node, batches, oracle)
            report["indexed"] = await phase_indexed(node, seed)
        report["oracle"] = {str(k): v for k, v in oracle().items()}
        report["state_root"] = phase_state_root(seed)
        report["counters"] = counter_totals(node.metrics.creator.registry, host.creator.registry)
        for name, value in report["counters"].items():
            check(value == 0, f"{name} = {value}")
        for lanes in (report["served"]["lanes"], report["node_pool"]["lanes"]):
            for lane in lanes:
                check(lane["wedge_trips"] == 0 and not lane["wedged"], f"lane {lane}")
    finally:
        await node.close()

    report["ledger"] = ledger_summary()
    ran = set(report["ledger"])
    for size in SIZE_CLASSES:
        for program in ("_single_launch_verify", "_prep_field_stage", "_prep_subgroup_stage",
                        "hash_finish", "batch_verify_staged", "bls_lane_verify"):
            check(f"{program}/{size}" in ran, f"ledger has no {program}/{size}: {sorted(ran)}")
    # a block's launch is 144 rows, the program's own entry and the lane's; the longer job's, 256
    for launch in ("_grouped_launch_verify/144", "bls_lane_verify/144", "_grouped_launch_verify/256"):
        check(launch in ran, f"ledger has no {launch}: {sorted(ran)}")
    check(f"_merkle_root_fixed/{1 << TREE_DEPTH}" in ran, f"ledger has no merkle root: {sorted(ran)}")
    check(any(k.startswith("merkle_level/") for k in ran), "ledger has no merkle_level launch")
    report["compile"] = {k: round(v, 1) for k, v in compile_stats.items()}
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    args = ap.parse_args(argv)
    # a hung chip must not outlive the 1200 s the contract allows
    faulthandler.dump_traceback_later(1170, exit=True)

    import jax

    from lodestar_tpu.utils import enable_compile_cache, probe_accelerator

    cache_dir = enable_compile_cache()
    # persistent-cache traffic and time inside backend compilation, as
    # JAX itself reports them
    stats = {"requests": 0, "persistent_hits": 0, "persistent_writes": 0, "backend_compile_s": 0.0}
    events = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "persistent_hits",
        "/jax/compilation_cache/cache_misses": "persistent_writes",
    }

    def on_event(event, **_):
        if event in events:
            stats[events[event]] += 1

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            stats["backend_compile_s"] += duration

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    accel = probe_accelerator()
    device = {"platform": accel["platform"], "kind": accel["device_kind"], "count": accel["count"]}
    if device["platform"] != "tpu":
        print(
            f"chip_smoke: no chip found: JAX's default backend is {device['platform']!r} "
            f"({device['kind']}); this check only means something on the accelerator",
            file=sys.stderr,
        )
        return 2
    log(f"device {device}, jax {jax.__version__}, compile cache {cache_dir}")

    report = asyncio.run(run(args.seed, device, stats))
    report.update(
        jax=jax.__version__, seed=args.seed, cache_dir=cache_dir,
        wall_s=round(time.monotonic() - _T0, 1),
    )
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke_report.json"), "a") as f:
        f.write(json.dumps(report) + "\n")
    summary = {
        "jax": report["jax"],
        "device": device,
        "wall_s": report["wall_s"],
        "compile": report["compile"],
        # program/size class: [set-up seconds of first calls, cached calls]
        "ledger": {k: [v["first_call_s"], v["cached_calls"]] for k, v in sorted(report["ledger"].items())},
        "node": report["node"],
        "server": report["served"]["description"],
        "counters_zero": sorted(report["counters"]),
        "native": report["native"],
    }
    print(json.dumps(summary))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
