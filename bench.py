"""Benchmark entrypoint (driver-run on real TPU hardware).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Headline: the NORTH STAR (BASELINE.md config 1) — random-linear-combination
BLS batch verification throughput on a 128-set batch, the workload the
reference routes to its blst thread pool
(`packages/beacon-node/src/chain/bls/multithread/worker.ts:30`,
`verifyMultipleAggregateSignatures`). The device pipeline is
`lodestar_tpu.models.batch_verify`: blinded G1/G2 scalar muls, 129 Miller
loops in lockstep, one shared final exponentiation.

vs_baseline: the reference envelope is ~45 ms for ~100 single-core blst
signature verifications (`verifyBlocksSignatures.ts:41-43`) ≈ 2,200 sigs/s
per core. vs_baseline = device_sigs_per_sec / 2200 — i.e. "how many blst
cores does one TPU chip replace"; ≥10 meets the north-star target.

A secondary line for the SHA-256 merkle kernel is retained in
`bench_merkle()` (BASELINE config 4) for comparison runs but the driver
reads only the first printed line.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

REFERENCE_SIGS_PER_SEC_PER_CORE = 2200.0  # blst envelope, see module docstring
BATCH = 128  # sets per gossip job (the north-star workload unit)
# buffered jobs merged into one RLC device batch. Swept on the real
# v5e-1 with the r5 Pallas core: 16 -> 6781 sigs/s, 32 -> 6586,
# 64 -> 5353 (PERF.md) — the r4 knee of 32 moved to 16 with the faster
# program. Overridable for batch-width sweeps.
MERGE_JOBS = int(os.environ.get("LODESTAR_BENCH_MERGE_JOBS", "16"))
ITERS = 3


def _make_sets(n: int):
    from lodestar_tpu.models.batch_verify import make_synthetic_sets

    return make_synthetic_sets(n, seed=17)


def bench_batch_verify() -> dict:
    """Sustained verification throughput of 128-set gossip jobs.

    The verifier pool buffers batchable jobs and merges them into one
    random-linear-combination batch (the reference merges buffered gossip
    sets the same way, `maybeBatch.ts:18`; we merge MERGE_JOBS x 128 =
    1024 sets per launch). The program is latency-bound, so widening the
    merged batch multiplies throughput at near-constant wall time (the
    merge-width sweep is in PERF.md, Findings, earlier installation).
    """
    from lodestar_tpu.models import batch_verify as bv

    sets = _make_sets(BATCH)
    inputs = bv.build_device_inputs(sets)
    assert inputs is not None
    pk, h, sig, bits, mask = inputs

    # merge MERGE_JOBS buffered jobs into one device batch: tile the
    # prepared arrays (distinct jobs in production; identical content is
    # fine for throughput — each copy gets fresh blinding)
    def tile1(a):
        return np.concatenate([a] * MERGE_JOBS, axis=0)

    merged = BATCH * MERGE_JOBS
    pk_m = (tile1(pk[0]), tile1(pk[1]))
    h_m = (tile1(h[0]), tile1(h[1]))
    sig_m = (tile1(sig[0]), tile1(sig[1]))
    mask_m = np.ones(merged, dtype=bool)

    def fresh_bits():
        coeffs = bv._random_coeffs(merged)
        return bv._bits_msb(coeffs, bv.COEFF_BITS)

    # warmup + compile; correctness gate on the first run
    ok = bool(np.asarray(bv.device_batch_verify(pk_m, h_m, sig_m, fresh_bits(), mask_m)))
    assert ok, "warmup merged batch failed to verify"

    # steady state: fresh blinding per launch, same compiled program;
    # dispatch all launches then drain (the 1-byte verdict transfer to
    # the host is the sync point)
    jobs = [fresh_bits() for _ in range(ITERS)]
    t0 = time.perf_counter()
    results = [bv.device_batch_verify(pk_m, h_m, sig_m, b, mask_m) for b in jobs]
    oks = [bool(np.asarray(r)) for r in results]
    dt = (time.perf_counter() - t0) / ITERS
    assert all(oks)

    sigs_per_sec = merged / dt
    return {
        "metric": "bls_batch_verify_sigs_per_sec",
        "value": round(sigs_per_sec, 1),
        "unit": "sigs/s",
        "vs_baseline": round(sigs_per_sec / REFERENCE_SIGS_PER_SEC_PER_CORE, 2),
    }


def bench_merkle(depth: int = 20) -> dict:
    """Secondary: batched SHA-256 merkleization (BASELINE config 4)."""
    import hashlib

    import jax

    from lodestar_tpu.ops import sha256 as S

    n = 1 << depth
    rng = np.random.default_rng(0)
    chunks_np = rng.integers(0, 2**32, size=(n, 8), dtype=np.uint32)
    chunks = jax.device_put(chunks_np)
    np.asarray(S.merkle_root_device(chunks))

    iters = 5
    t0 = time.perf_counter()
    roots = [S.merkle_root_device(chunks) for _ in range(iters)]
    for r in roots:
        np.asarray(r)
    dt = (time.perf_counter() - t0) / iters
    device_rate = (n - 1) / dt

    sample = 1 << 14
    data = chunks_np[: 2 * sample].astype(">u4").tobytes()
    t0 = time.perf_counter()
    for i in range(sample):
        hashlib.sha256(data[i * 64 : (i + 1) * 64]).digest()
    cpu_rate = sample / (time.perf_counter() - t0)

    return {
        "metric": "merkle_sha256_pair_hashes_per_sec",
        "value": round(device_rate),
        "unit": "hashes/s",
        "vs_baseline": round(device_rate / cpu_rate, 2),
    }


def main() -> None:
    from lodestar_tpu.utils import enable_compile_cache

    enable_compile_cache()
    print(json.dumps(bench_batch_verify()))


if __name__ == "__main__":
    main()
