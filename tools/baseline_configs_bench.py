"""BASELINE.md configs 2-5 on real hardware (VERDICT r4 next-step #10).

Prints one JSON line per config (same schema as bench.py) so the perf
notes record the SYSTEM, not just the config-1 headline:

  2. gossip replay: a per-slot ~4k-signature attestation batch pushed
     through the BlsDeviceVerifierPool (buffering, merge, RLC, retry
     policy — the production path), bls.impl = device.
  3. sync-committee aggregate: 512-pubkey fast-aggregate-verify per
     slot — device G1 tree fold + one pairing check, many slots batched.
  4. hashTreeRoot at 1M validators: the device SHA-256 merkle kernel
     over 2^20 chunks (bench.py bench_merkle, depth 20).
  5. checkpoint-backfill window: 32 slots x ~100 sigs of concurrent
     block+attestation verification as one RLC batch (single chip;
     BASELINE names v5e-4 DP — multiply by chips for the slice number,
     the sharded path is exercised by dryrun_multichip).

Also prints the HOST PREP line (native decompress+subgroup+hash-to-G2
sets/s on this container's single core) — the honest feed-rate bound
the VERDICT asks to record next to the device numbers.

Run: python tools/baseline_configs_bench.py [--quick]
"""

import json
import sys
import time

sys.path.insert(0, ".")

import numpy as np

from lodestar_tpu.utils import enable_compile_cache

enable_compile_cache()

QUICK = "--quick" in sys.argv
REFERENCE_SIGS_PER_SEC_PER_CORE = 2200.0  # blst envelope (bench.py)


def _line(metric, value, unit, vs, digits=1):
    print(json.dumps({
        "metric": metric, "value": round(value, digits), "unit": unit,
        "vs_baseline": round(vs, 2),
    }), flush=True)


def config2_gossip_replay():
    """Per-slot gossip attestation load through the production pool, at
    the verify schedule the backend runs (models/batch_verify
    `single_launch_active`: the single launch on an accelerator, host
    prep and the monolithic program on the CPU)."""
    import asyncio

    from lodestar_tpu.chain.bls.interface import VerifySignatureOpts
    from lodestar_tpu.chain.bls.pool import BlsDeviceVerifierPool
    from lodestar_tpu.models.batch_verify import make_synthetic_sets

    n = 1024 if QUICK else 4096
    sets = make_synthetic_sets(n, seed=31)
    opts = VerifySignatureOpts(batchable=True)

    async def run():
        pool = BlsDeviceVerifierPool()
        # warm the compiled program with one full-size merge
        jobs = [sets[i : i + 32] for i in range(0, n, 32)]
        await asyncio.gather(*[
            pool.verify_signature_sets(j, opts) for j in jobs
        ])
        t0 = time.perf_counter()
        oks = await asyncio.gather(*[
            pool.verify_signature_sets(j, opts) for j in jobs
        ])
        dt = time.perf_counter() - t0
        if not all(oks):
            raise RuntimeError("gossip replay batch failed")
        await pool.close()
        return n / dt

    rate = asyncio.run(run())
    _line("gossip_replay_sigs_per_sec", rate, "sigs/s",
          rate / REFERENCE_SIGS_PER_SEC_PER_CORE)


def config3_sync_committee_aggregate():
    """512-pubkey fast-aggregate-verify per slot, slots batched."""
    import jax.numpy as jnp

    from lodestar_tpu.crypto.bls import api as bls
    from lodestar_tpu.crypto.bls.hash_to_curve import hash_to_g2
    from lodestar_tpu.ops import curve as cv, fp, pairing as prg
    from lodestar_tpu.ops import tower as tw
    from lodestar_tpu.state_transition.genesis import interop_secret_keys

    n_pk = 512
    slots = 2 if QUICK else 8
    # 512 DISTINCT keys: duplicate pubkey points would hit the P == Q
    # exceptional case in the fast (exact=False) tree fold
    sks = interop_secret_keys(n_pk)
    msg = b"\x5a" * 32
    h = hash_to_g2(msg)
    # one aggregate signature over the same message per slot
    sigs = [bls.sign(sks[i], msg) for i in range(n_pk)]
    agg_sig = bls.aggregate_signatures(sigs)
    pk_pts = [sks[i].to_pubkey_point() for i in range(n_pk)]

    # device inputs: (slots*n_pk) pubkey points -> per-slot tree fold
    pk_x = np.stack([fp.mont_limbs_from_int(p[0]) for p in pk_pts] * slots)
    pk_y = np.stack([fp.mont_limbs_from_int(p[1]) for p in pk_pts] * slots)
    h_dev = tw.fp2_from_ints([h[0]] * slots), tw.fp2_from_ints([h[1]] * slots)
    from lodestar_tpu.crypto.bls.serdes import g2_from_bytes
    sp = g2_from_bytes(agg_sig)
    sig_dev = tw.fp2_from_ints([sp[0]] * slots), tw.fp2_from_ints([sp[1]] * slots)

    import jax

    # fold per slot: vectorized tree over the pk axis
    def fold_pk_axis(X, Y, Z):
        pt = (X, Y, Z)
        while pt[0].shape[1] > 1:
            half = pt[0].shape[1] // 2
            a = tuple(c[:, :half] for c in pt)
            b = tuple(c[:, half:] for c in pt)
            pt = cv.jac_add(cv.F1, a, b, exact=False)
        return tuple(c[:, 0] for c in pt)

    @jax.jit
    def program(pk_x, pk_y, hx, hy, sx, sy):
        one1 = fp.one_mont()
        X = pk_x.reshape(slots, n_pk, fp.LIMBS)
        Y = pk_y.reshape(slots, n_pk, fp.LIMBS)
        jac = cv.affine_to_jac(cv.F1, (X, Y), one1)
        agg = fold_pk_axis(*jac)
        agg_aff = cv.jac_to_affine_batch(cv.F1, agg)
        # e(agg_pk, H(m)) * e(-g1, sig) == 1 per slot
        from lodestar_tpu.models.batch_verify import _NEG_G1_X, _NEG_G1_Y

        p_x = jnp.concatenate([agg_aff[0], jnp.broadcast_to(jnp.asarray(_NEG_G1_X), (slots, fp.LIMBS))], axis=0)
        p_y = jnp.concatenate([agg_aff[1], jnp.broadcast_to(jnp.asarray(_NEG_G1_Y), (slots, fp.LIMBS))], axis=0)
        q_x = jnp.concatenate([hx, sx], axis=0)
        q_y = jnp.concatenate([hy, sy], axis=0)
        fs = prg.miller_loop((p_x, p_y), (q_x, q_y))
        # fold pairs per slot: f_i * f_{slots+i}
        f = tw.fp12_mul(fs[:slots], fs[slots:])
        return tw.fp12_eq_one(prg.final_exponentiation(f))

    args = (jnp.asarray(pk_x), jnp.asarray(pk_y),
            jnp.asarray(np.asarray(h_dev[0])), jnp.asarray(np.asarray(h_dev[1])),
            jnp.asarray(np.asarray(sig_dev[0])), jnp.asarray(np.asarray(sig_dev[1])))
    ok = np.asarray(program(*args))
    if not ok.all():
        raise RuntimeError("fast-aggregate-verify rejected a valid aggregate")
    iters = 3
    t0 = time.perf_counter()
    for _ in range(iters):
        np.asarray(program(*args))
    dt = (time.perf_counter() - t0) / iters
    rate = slots / dt
    # reference envelope: one fast-aggregate-verify ~ one sig verify +
    # 511 G1 adds (~0.4ms each on blst) — conservatively ~2 ms/slot CPU
    _line("sync_committee_fast_aggregate_verifies_per_sec", rate, "slots/s", rate / 500.0)


def config4_merkle_1m():
    import bench as b

    out = b.bench_merkle(depth=18 if QUICK else 20)
    # literal metric name (asserted against bench.py's) so the bench
    # trajectory's per-line thresholds are statically checkable against
    # this module's reporting (tools/analysis bench-wiring rule)
    assert out["metric"] == "merkle_sha256_pair_hashes_per_sec", out["metric"]
    _line("merkle_sha256_pair_hashes_per_sec", out["value"], out["unit"], out["vs_baseline"])


def config5_backfill_window():
    """32-slot window: blocks (1 proposer sig each) + attestations."""
    from lodestar_tpu.models import batch_verify as bv

    n = 32 * (8 if QUICK else 100)
    sets = bv.make_synthetic_sets(n, seed=37)
    iters = 3
    # device-only (prepared inputs reused, fresh blinding per launch —
    # the shape a threaded prep host sustains)
    inputs = bv.build_device_inputs(sets)
    pk, h, sig, bits, mask = inputs
    t0 = time.perf_counter()
    for _ in range(iters):
        fresh = bv._bits_msb(bv._random_coeffs(pk[0].shape[0]), bv.COEFF_BITS)
        if not bool(np.asarray(bv.device_batch_verify(pk, h, sig, fresh, mask))):
            raise RuntimeError("device backfill window rejected valid sets")
    dt = (time.perf_counter() - t0) / iters
    _line("backfill_window_device_sigs_per_sec", n / dt, "sigs/s",
          (n / dt) / REFERENCE_SIGS_PER_SEC_PER_CORE)


def host_prep_rate():
    from lodestar_tpu.models.batch_verify import make_synthetic_sets, prepare_sets
    from lodestar_tpu.native import bls as nbls

    n = 256
    sets = make_synthetic_sets(n, seed=41)
    prepare_sets(sets)  # warm native build
    t0 = time.perf_counter()
    out = prepare_sets(sets)
    dt = time.perf_counter() - t0
    if out is None:
        raise RuntimeError("native prep rejected valid sets")
    rate = n / dt
    _line("host_prep_sets_per_sec_single_core", rate, "sets/s",
          rate / REFERENCE_SIGS_PER_SEC_PER_CORE)
    print(json.dumps({
        "note": "container has 1 core; native prep threads scale linearly "
                "on real hosts — cores needed to feed the device at its "
                "bench rate = device_sigs_per_sec / this",
        "native_available": nbls.available(),
    }), flush=True)


def device_prep_rate():
    """On-chip input prep (ops/prep.py staged programs) sets/s — the
    apples-to-apples line next to host_prep_sets_per_sec_single_core:
    same 256-set batch, compressed bytes in, prepared device limbs out."""
    from lodestar_tpu.models.batch_verify import make_synthetic_sets, prepare_sets_device

    n = 256
    sets = make_synthetic_sets(n, seed=41)
    if prepare_sets_device(sets) is None:  # warm the staged compiles
        raise RuntimeError("device prep rejected valid sets")
    iters = 3
    t0 = time.perf_counter()
    for _ in range(iters):
        if prepare_sets_device(sets) is None:
            raise RuntimeError("device prep rejected valid sets")
    dt = (time.perf_counter() - t0) / iters
    rate = n / dt
    _line("device_prep_sets_per_sec", rate, "sets/s",
          rate / REFERENCE_SIGS_PER_SEC_PER_CORE)


def prep_launch_fusion():
    """Launches per set of the fused prep stages, counted at
    ops/prep.py's dispatch seam (the same number
    `lodestar_bls_prep_launches_total` increments), against the
    pre-fusion one-launch-per-leg schedule's `UNFUSED_PREP_LAUNCHES`."""
    from lodestar_tpu.models import batch_verify as bv
    from lodestar_tpu.ops import prep as dp

    n = 32
    sets = bv.make_synthetic_sets(n, seed=47)
    if bv.prepare_sets_device(sets) is None:  # warm compiles
        raise RuntimeError("prep rejected valid sets")
    base = dp.prep_launches_total()
    if bv.prepare_sets_device(sets) is None:
        raise RuntimeError("prep rejected valid sets")
    per_set = (dp.prep_launches_total() - base) / n
    _line(
        "prep_launches_per_set", per_set, "launches/set",
        per_set / (dp.UNFUSED_PREP_LAUNCHES / n), digits=4,
    )


def single_launch_schedule():
    """End-to-end launch count per verified batch: the single-launch
    resident program (ONE counted dispatch)
    vs the split reference (3-launch fused prep + the RLC verify
    dispatch), both counted at the telemetry seam — the dispatch-budget
    invariant the chip run's launch dashboard reads."""
    from lodestar_tpu import telemetry
    from lodestar_tpu.models import batch_verify as bv

    n = 32
    sets = bv.make_synthetic_sets(n, seed=53)
    prev_tel = telemetry.configure_launch_telemetry(mode="on")
    try:
        counts = {}
        for fn, name in (
            (bv.verify_sets_single_launch, "e2e_launches_per_batch"),
            (bv._verify_sets_split, "e2e_launches_per_batch_split"),
        ):
            if not fn(sets):  # warm the compiled program(s)
                raise RuntimeError(f"{name} bench rejected valid sets")
            base = telemetry.launch_totals()["launches"]
            if not fn(sets):
                raise RuntimeError(f"{name} bench rejected valid sets")
            counts[name] = telemetry.launch_totals()["launches"] - base
    finally:
        telemetry.configure_launch_telemetry(mode=prev_tel)
    split = counts["e2e_launches_per_batch_split"]
    _line("e2e_launches_per_batch", counts["e2e_launches_per_batch"],
          "launches/batch", counts["e2e_launches_per_batch"] / split)
    _line("e2e_launches_per_batch_split", split, "launches/batch", 1.0)


def config2_gossip_replay_pipelined():
    """Config-2 gossip replay arriving as a stream, where the pool stages
    prep (an accelerator: the host byte parse of package k+1 behind the
    single launch of package k) — plus the measured fraction of verify
    wall time with a prep stage in flight. Raises where the pool does
    not stage (one lane on the CPU backend)."""
    import asyncio

    from lodestar_tpu.chain.bls.interface import VerifySignatureOpts
    from lodestar_tpu.chain.bls.pool import BlsDeviceVerifierPool
    from lodestar_tpu.models.batch_verify import make_synthetic_sets

    n = 1024 if QUICK else 4096
    sets = make_synthetic_sets(n, seed=31)
    opts = VerifySignatureOpts(batchable=True)

    async def run():
        pool = BlsDeviceVerifierPool()
        jobs = [sets[i : i + 32] for i in range(0, n, 32)]

        async def replay():
            # gossip is a STREAM: jobs arrive over time, so packages
            # form sequentially and prep of package k+1 runs while
            # package k verifies (an all-at-once gather coalesces the
            # whole replay into two giant packages whose preps both
            # finish before the first verify — nothing left to overlap)
            tasks = []
            for j in jobs:
                tasks.append(
                    asyncio.ensure_future(pool.verify_signature_sets(j, opts))
                )
                await asyncio.sleep(0.01)
            return await asyncio.gather(*tasks)

        await replay()  # warm the compiled programs
        base = pool.pipeline_stats()
        t0 = time.perf_counter()
        oks = await replay()
        dt = time.perf_counter() - t0
        if not all(oks):
            raise RuntimeError("pipelined gossip replay batch failed")
        stats = pool.pipeline_stats()
        await pool.close()
        if not stats["pipeline_enabled"] or stats["staged_packages"] == 0:
            raise RuntimeError(
                "pipeline never engaged — refusing to report a pipelined "
                "number for an unpipelined run"
            )
        overlap = stats["overlap_ns"] - base["overlap_ns"]
        verify = stats["verify_ns"] - base["verify_ns"]
        return n / dt, (100.0 * overlap / verify) if verify else 0.0

    rate, overlap_pct = asyncio.run(run())
    _line("pipelined_gossip_replay_sigs_per_sec", rate, "sigs/s",
          rate / REFERENCE_SIGS_PER_SEC_PER_CORE)
    _line("prep_verify_overlap_occupancy_pct", overlap_pct, "pct",
          overlap_pct / 100.0)


def state_htr_rate():
    """Dirty-subtree collector throughput on the config-4 state shape:
    a 2^18-chunk retained level stack (2^16 with --quick) takes a
    4096-chunk dirty set per flush — the epoch-boundary balances sweep
    shape — through one device launch per level. The honest unit is
    dirty chunks *flushed* per second (path re-hash included)."""
    import numpy as np

    from lodestar_tpu.ssz import device_htr as dh

    depth = 16 if QUICK else 18
    n = 1 << depth
    rng = np.random.default_rng(51)
    chunks = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
    levels = [np.zeros((n >> k, 32), dtype=np.uint8) for k in range(depth + 1)]
    levels[0][:] = chunks
    prev = dh.configure_device_htr(mode="on")
    try:
        cold = dh.DirtyCollector()
        cold.add_stack_job(levels, range(n))
        cold.flush()  # warm the per-size-class compiles
        dirty_n = 4096
        iters = 5
        t0 = time.perf_counter()
        for it in range(iters):
            dirty = rng.choice(n, size=dirty_n, replace=False)
            levels[0][dirty] ^= np.uint8(1 + it)
            coll = dh.DirtyCollector()
            coll.add_stack_job(levels, dirty)
            stats = coll.flush()
            if stats["backend"] != "device":
                raise RuntimeError(
                    "device flush silently degraded to CPU — refusing to "
                    "report a CPU number under a device metric name"
                )
            if stats["launches"] > depth:
                raise RuntimeError("launch-count invariant violated in bench")
        dt = (time.perf_counter() - t0) / iters
    finally:
        dh.configure_device_htr(mode=prev)
    rate = dirty_n / dt
    # reference envelope: one host core does ~1M incremental pair
    # hashes/s through hashlib (BASELINE.md config 4 discussion)
    _line("state_htr_chunks_per_sec", rate, "chunks/s", rate / 1_000_000.0)


def epoch_htr_replay():
    """Epoch-boundary hashTreeRoot replay: a minimal-preset state with a
    big registry takes an epoch-shaped mutation batch (every balance
    rewritten, participation swept, a mix/slashings rotation, a handful
    of validator writes), then one state root — device collector vs the
    CPU value path, same JSON-lines shape as the prep-on/off pair."""
    import numpy as np

    from lodestar_tpu import params
    from lodestar_tpu.ssz import device_htr as dh
    from lodestar_tpu.state_transition import state_hash_tree_root
    from lodestar_tpu.types import ssz_types

    prev_preset = params.active_preset()
    params.set_active_preset("minimal")
    p = params.active_preset()
    t = ssz_types(p)
    n = 1024 if QUICK else 16384
    state = t.altair.BeaconState.default()
    vs = []
    for i in range(n):
        v = t.Validator.default()
        v.pubkey = (i.to_bytes(8, "little") * 6)[:48]
        v.effective_balance = 32_000_000_000
        v.exit_epoch = 2**64 - 1
        v.withdrawable_epoch = 2**64 - 1
        vs.append(v)
    state.validators = vs
    state.balances = [32_000_000_000] * n
    state.previous_epoch_participation = [1] * n
    state.current_epoch_participation = [3] * n
    state.inactivity_scores = [0] * n
    rng = np.random.default_rng(52)

    def epoch_mutation(round_):
        state.slot = int(state.slot) + p.SLOTS_PER_EPOCH
        state.balances = [int(x) for x in rng.integers(31_000_000_000, 33_000_000_000, size=n)]
        state.previous_epoch_participation = state.current_epoch_participation
        state.current_epoch_participation = [0] * n
        state.randao_mixes[round_ % len(state.randao_mixes)] = bytes(
            rng.integers(0, 256, size=32, dtype=np.uint8)
        )
        state.slashings[round_ % len(state.slashings)] = int(rng.integers(0, 2**40))
        for i in rng.integers(0, n, size=8):
            state.validators[int(i)].effective_balance = int(rng.integers(0, 2**40))

    # degradation probe: zero launches can be legitimate (the per-level
    # size floor keeps small levels on host digests), but a FALLBACK
    # means the device path errored and the line would silently report
    # a CPU number under a device metric name
    class _Probe:
        def __init__(self):
            self.n = 0

        def labels(self, *a):
            return self

        def inc(self, amount=1):
            self.n += amount

        def observe(self, v):
            pass

    probe = type("M", (), {})()
    for k in ("flushes", "dirty_chunks", "launches", "seconds", "fallbacks"):
        setattr(probe, k, _Probe())

    results = {}
    prev_metrics = dh._htr_metrics
    dh.configure_device_htr(metrics=probe)
    try:
        for mode, metric in (("on", "epoch_htr_ms_device"), ("off", "epoch_htr_ms_cpu")):
            prev = dh.configure_device_htr(mode=mode)
            try:
                epoch_mutation(0)
                state_hash_tree_root(state)  # warm (cold tracker build / compiles)
                iters = 3
                t0 = time.perf_counter()
                for it in range(1, iters + 1):
                    epoch_mutation(it)
                    state_hash_tree_root(state)
                results[metric] = (time.perf_counter() - t0) / iters * 1000.0
                if mode == "on" and probe.fallbacks.n:
                    raise RuntimeError(
                        "device HTR degraded during the epoch replay — "
                        "refusing to report epoch_htr_ms_device"
                    )
            finally:
                dh.configure_device_htr(mode=prev)
    finally:
        dh._htr_metrics = prev_metrics
        params.set_active_preset(prev_preset)
    cpu_ms = results["epoch_htr_ms_cpu"]
    _line("epoch_htr_ms_device", results["epoch_htr_ms_device"], "ms",
          cpu_ms / max(results["epoch_htr_ms_device"], 1e-9))
    _line("epoch_htr_ms_cpu", cpu_ms, "ms", 1.0)


def mesh_scaling():
    """`mesh_sigs_per_sec_{n}dev` for n in 1/2/4/8 ∩ visible devices:
    the same prepared batch (fresh blinding per launch, host prep
    excluded — the scaling of the VERIFY pipeline is the question)
    through the single-device program and the data-parallel sharded
    program over growing sub-meshes. On the production host this is
    the single-vs-mesh headline the PR 8 serving pool banks on; a
    1-device container emits only the 1dev line."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from lodestar_tpu.models import batch_verify as bv

    devices = jax.devices()
    counts = [n for n in (1, 2, 4, 8) if n <= len(devices)]
    n = 256 if QUICK else 1024
    sets = bv.make_synthetic_sets(n, seed=43)
    inputs = bv.build_device_inputs(sets, size=n)
    if inputs is None:
        raise RuntimeError("mesh bench rejected valid sets")
    pk, h, sig, bits, mask = inputs
    iters = 3
    for n_dev in counts:
        if n_dev == 1:
            run = lambda b: bv.device_batch_verify(pk, h, sig, b, mask)
        else:
            mesh = Mesh(np.asarray(devices[:n_dev]), ("data",))
            run = lambda b, m=mesh: bv.device_batch_verify_sharded(
                m, pk, h, sig, b, mask
            )
        if not bool(np.asarray(run(bits))):  # warm the compile
            raise RuntimeError(f"mesh bench rejected valid sets at {n_dev} devices")
        t0 = time.perf_counter()
        for _ in range(iters):
            fresh = bv._bits_msb(bv._random_coeffs(n), bv.COEFF_BITS)
            if not bool(np.asarray(run(fresh))):
                raise RuntimeError(
                    f"mesh bench rejected valid sets at {n_dev} devices"
                )
        dt = (time.perf_counter() - t0) / iters
        _line(f"mesh_sigs_per_sec_{n_dev}dev", n / dt, "sigs/s",
              (n / dt) / REFERENCE_SIGS_PER_SEC_PER_CORE)


def two_tenant_fairness_replay():
    """Saturated two-tenant replay against the offload front-end:
    tenants alice (weight 3) and bob (weight 1) over-admit bulk work
    against one service slot; the line reports the worst deviation of
    served shares from the configured 75/25 split, in percentage
    points (acceptance envelope: 10). The backend is a fixed 2 ms stub
    — service time is a parameter here; the MEASUREMENT is the stride
    scheduler's cross-tenant fairness, which is what the serving host
    runs regardless of die speed."""
    import asyncio
    import threading

    from lodestar_tpu.offload.client import BlsOffloadClient
    from lodestar_tpu.offload.server import BlsOffloadServer

    def backend(sets):
        time.sleep(0.002)
        return True

    server = BlsOffloadServer(
        backend, port=0, max_workers=8,
        tenant_weights={"alice": 3, "bob": 1}, tenant_slots=1,
    )
    server.start()
    target = f"127.0.0.1:{server.port}"
    from lodestar_tpu.models.batch_verify import make_synthetic_sets

    job = make_synthetic_sets(4, seed=44)
    clients = {
        name: BlsOffloadClient(target, probe_interval_s=0.05, tenant=name)
        for name in ("alice", "bob")
    }
    try:
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if all(
                s["tenant_capable"]
                for c in clients.values()
                for s in c.endpoint_states()
            ):
                break
            time.sleep(0.02)

        async def go():
            stop = asyncio.Event()
            from lodestar_tpu.chain.bls.interface import VerifySignatureOpts
            from lodestar_tpu.scheduler import PriorityClass

            bulk = VerifySignatureOpts(priority=PriorityClass.BACKFILL)

            async def pump(client):
                while not stop.is_set():
                    try:
                        await client.verify_signature_sets(job, bulk)
                    except Exception:
                        await asyncio.sleep(0.001)

            pumps = [
                asyncio.ensure_future(pump(c))
                for c in clients.values()
                for _ in range(8)
            ]
            while not all(
                server.tenancy.served.get(t, 0) > 0 for t in ("alice", "bob")
            ):
                await asyncio.sleep(0.01)
            base = {t: server.tenancy.served.get(t, 0) for t in ("alice", "bob")}
            target_grants = 150 if QUICK else 600
            while True:
                window = {
                    t: server.tenancy.served.get(t, 0) - base[t]
                    for t in ("alice", "bob")
                }
                if sum(window.values()) >= target_grants:
                    break
                await asyncio.sleep(0.02)
            stop.set()
            await asyncio.gather(*pumps, return_exceptions=True)
            return window

        window = asyncio.run(go())
        total = sum(window.values())
        err_pct = 100.0 * max(
            abs(window["alice"] / total - 0.75), abs(window["bob"] / total - 0.25)
        )
        # vs_baseline: fraction of the 10-point acceptance envelope used
        _line("two_tenant_fairness_share_error_pct", err_pct, "pct", err_pct / 10.0)
    finally:
        for c in clients.values():
            asyncio.run(c.close())
        server.stop()


def main():
    host_prep_rate()
    device_prep_rate()
    prep_launch_fusion()
    config4_merkle_1m()
    state_htr_rate()
    epoch_htr_replay()
    config5_backfill_window()
    single_launch_schedule()
    config2_gossip_replay()
    config2_gossip_replay_pipelined()
    config3_sync_committee_aggregate()
    mesh_scaling()
    two_tenant_fairness_replay()


if __name__ == "__main__":
    main()
