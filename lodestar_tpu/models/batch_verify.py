"""Device BLS batch signature verification (random-linear-combination).

Checks  e(-g1, sum_i r_i S_i) * prod_i e(r_i PK_i, H(m_i)) == 1  with ONE
shared final exponentiation — exactly the semantics of blst's
`verifyMultipleSignatures` that the reference worker calls
(`packages/beacon-node/src/chain/bls/multithread/worker.ts:52-96`,
`maybeBatch.ts:18`), and bit-identical in outcome to the CPU oracle
`lodestar_tpu.crypto.bls.api.verify_signature_sets`.

Split of labor (SURVEY §7 phase 1):

* **Host**, on a CPU backend (the split schedule): decompression (sqrt),
  KeyValidate/subgroup checks, hash-to-G2 of the 32-byte signing roots.
  On an accelerator (the single launch) the host does byte work only:
  flag and limb parsing, expand_message_xmd, blinding-coefficient
  sampling, and for a set that names its signers by registry index
  (`IndexedSignatureSet`) the bounds check of its index row.
* **Device** (one jitted program per padded batch size): on an
  accelerator the decompression, subgroup checks and hash-to-G2 too,
  and the pubkey aggregation of indexed sets (`bls.aggregate`: a gather
  from the registry table resident on the chip,
  `chain/bls/pubkey_table.py`, and a K-point sum a row — the
  reference's main-thread `getAggregatedPubkey` over
  `EpochContext.index2pubkey`, `state-transition/src/cache/pubkeyCache.ts`);
  then 64-bit blinded scalar multiplications in G1 and G2, the G2 fold
  to the aggregate signature, N+1 Miller loops in lockstep, one product
  fold, one final exponentiation, the ==1 predicate. Only the counted
  fallback (`lodestar_bls_aggregate_fallback_total`: more than K
  signers, or lanes without the table) still sums pubkeys on the host.

The blinding is mandatory: an unrandomized batch is forgeable (defects in
different sets can cancel). Coefficient 0 is resampled; the first
coefficient is 1, as in the oracle.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from lodestar_tpu import telemetry
from lodestar_tpu.crypto.bls import curve as C
from lodestar_tpu.crypto.bls.api import IndexedSignatureSet, SignatureSet, resolve_signature_set
from lodestar_tpu.crypto.bls.curve import G1_GEN
from lodestar_tpu.crypto.bls.hash_to_curve import hash_to_g2
from lodestar_tpu.crypto.bls.serdes import PointDecodeError, g1_from_bytes, g1_to_bytes, g2_from_bytes
from lodestar_tpu.ops import curve as cv
from lodestar_tpu.ops import fp
from lodestar_tpu.ops import pairing as prg
from lodestar_tpu.ops import tower as tw

__all__ = [
    "COEFF_BITS",
    "AGGREGATE_ROW_POINTS",
    "SingleLaunchInputs",
    "GroupedLaunchInputs",
    "configure_device_prep",
    "consume_prep_info",
    "single_launch_active",
    "prepare_sets",
    "prepare_sets_device",
    "prepare_single_launch_inputs",
    "prepare_grouped_launch_inputs",
    "build_device_inputs",
    "device_batch_verify",
    "device_batch_verify_sharded",
    "make_synthetic_sets",
    "verify_signature_sets_device",
    "verify_sets_single_launch",
    "verify_sets_grouped_launch",
    "verify_prepared",
    "prepare_inputs_for_lane",
    "verify_signature_sets_sharded",
    "mesh_device_count",
    "mesh_devices",
    "make_lane_verify_fn",
    "make_lane_verify_prepared_fn",
    "make_lane_verify_grouped_fn",
    "make_mesh_sharded_fn",
]

COEFF_BITS = 64  # blinding scalar width, matches blst's 64-bit rand coeffs
# K, the columns of a launch's index matrix: the most signers of a set
# whose pubkeys the launch sums from the registry table. The mainnet
# preset's SYNC_COMMITTEE_SIZE (512), which is also a beacon committee at
# 2^20 validators (2^20 / 32 slots / 64 committees); a set with more
# signers (MAX_VALIDATORS_PER_COMMITTEE is 2,048, reached above 4 M
# validators) takes the counted host aggregation. TUNING.md has the row.
AGGREGATE_ROW_POINTS = 512

# --- the verify schedule -----------------------------------------------------
# Which schedule a batch runs is a fact of the backend, constant for a
# process, and this module is the one place it is asked
# (`single_launch_active`). On an accelerator (the Pallas backend live)
# the whole chain — field stage (decompression sqrt chains, hash-to-
# field reduction, SSWU candidates), subgroup ladders, hash finish +
# 3-isogeny, RLC aggregation, Miller loop, final exponentiation — is ONE
# resident device program per pow-2 size class, dispatched once through
# ops/prep.py's counted `_dispatch` seam
# (`ops.prep.SINGLE_LAUNCH_BUDGET` == 1). Staged-jit miscompile
# doctrine: the split schedule (3-launch fused device prep + separate
# verify dispatch) is RETAINED as the differential reference, and a
# single-launch device error (or verdict-shape anomaly) degrades that
# batch to it — then to host prep inside build_device_inputs, each
# counted (errors degrade, verdicts — incl. "structurally invalid set"
# — are final). Elsewhere (the CPU backend) a batch is host prep
# (native C++ / python oracle) and the monolithic verify program: a CPU
# XLA prep would just be a slower host prep. The lanes built from this
# answer carry it (chain/bls/mesh.py `build_device_mesh`); nobody above
# asks again, and nobody can set it.
_prep_metrics = None  # guarded by: GIL (set once at node init)
_prep_tls = threading.local()  # per-executor-thread prep span info


def single_launch_active() -> bool:
    """Whether this process's backend runs the single-launch schedule
    (and, on its error road, device prep): the Pallas backend is live."""
    from lodestar_tpu.ops import fp_pallas

    return fp_pallas.use_pallas()


def configure_device_prep(metrics) -> None:
    """Install the lodestar_bls_prep_* metric family (node init)."""
    global _prep_metrics
    _prep_metrics = metrics
    # the launches counter increments at the dispatch site inside
    # ops/prep.py (the only place that actually knows when a device
    # program is launched) — hand it over here, the one config seam
    launches = getattr(metrics, "launches", None)
    if launches is not None:
        from lodestar_tpu.ops import prep as _dp

        _dp.configure_launch_counter(launches)


def consume_prep_info():
    """Pop the calling thread's last prep record (layer/sets/timing) —
    the pool reads this after a verify launch to emit the `bls_prep`
    span without threading a tracer through the model layer."""
    info = getattr(_prep_tls, "info", None)
    _prep_tls.info = None
    return info


def _note_prep(layer: str, n_sets: int, t0_ns: int, rejected: bool = False) -> None:
    end_ns = time.monotonic_ns()
    _prep_tls.info = {
        "layer": layer,
        "sets": n_sets,
        "start_ns": t0_ns,
        "end_ns": end_ns,
        "rejected": rejected,
    }
    m = _prep_metrics
    if m is not None:
        m.sets.labels(layer).inc(n_sets)
        m.seconds.labels(layer).observe((end_ns - t0_ns) / 1e9)
        if rejected:
            m.rejected.inc()


def _note_prep_fallback(err: Exception) -> None:
    m = _prep_metrics
    if m is not None:
        m.fallbacks.inc()
    from lodestar_tpu.logger import get_logger

    get_logger(name="lodestar.bls-prep").warn(
        "device input prep failed, falling back to host prep",
        {"error": str(err)[:120]},
    )


def _note_single_launch_fallback(err: Exception) -> None:
    m = _prep_metrics
    if m is not None:
        m.single_launch_fallbacks.inc()
    from lodestar_tpu.logger import get_logger

    get_logger(name="lodestar.bls-prep").warn(
        "single-launch verify failed, falling back to the split schedule",
        {"error": str(err)[:120]},
    )


def _note_aggregate_fallback(n_sets: int) -> None:
    """Indexed sets whose signers were summed on the host."""
    m = _prep_metrics
    if m is not None:
        m.aggregate_fallbacks.inc(n_sets)


def _host_aggregated(sets: list, table) -> "list[SignatureSet] | None":
    """`sets` with every indexed one as a byte set, its signers' pubkeys
    summed on the host from the table's host side (the counted
    fallback: the roads that take pubkey bytes only); None where one
    has no valid aggregate, a final structural verdict."""
    n_indexed = sum(1 for s in sets if isinstance(s, IndexedSignatureSet))
    if not n_indexed:
        return sets
    _note_aggregate_fallback(n_indexed)
    resolver = table.pubkey_at if table is not None else None
    out = [resolve_signature_set(s, resolver) for s in sets]
    return None if any(s is None for s in out) else out


def _fp_to_mont_host(xs: list[int]) -> np.ndarray:
    """Pure-numpy mont conversion: host prep must never bounce arrays
    through the device (a jitted to_mont plus the pull-back is a device
    round trip per point and serializes the prep pipeline)."""
    return np.stack([fp.mont_limbs_from_int(x) for x in xs])


def _g1_batch_host(pts) -> tuple[np.ndarray, np.ndarray]:
    return (
        _fp_to_mont_host([p[0] for p in pts]),
        _fp_to_mont_host([p[1] for p in pts]),
    )


def _g2_batch_host(pts) -> tuple[np.ndarray, np.ndarray]:
    xs = np.stack([tw._fp2_mont_limbs_host(*p[0]) for p in pts])
    ys = np.stack([tw._fp2_mont_limbs_host(*p[1]) for p in pts])
    return xs, ys


# device-constant: -g1 generator, mont form. Pure numpy — import of this
# module must never touch a JAX backend (the r3 multichip gate
# regression class).
_NEG_G1_X = fp.mont_limbs_from_int(G1_GEN[0])
_NEG_G1_Y = fp.mont_limbs_from_int((-G1_GEN[1]) % C.P)


def _bits_msb(scalars: np.ndarray, width: int) -> np.ndarray:
    """(N,) uint64-ish ints -> (N, width) int32 bit matrix, MSB first."""
    out = np.zeros((len(scalars), width), dtype=np.int32)
    for i, s in enumerate(scalars):
        s = int(s)
        for j in range(width):
            out[i, j] = (s >> (width - 1 - j)) & 1
    return out


def prepare_sets(sets: list[SignatureSet]):
    """Host precompute: decode + validate + hash. Returns device arrays or
    None if any set is structurally invalid (decode failure, non-subgroup
    point, infinity pubkey/signature) — the fail-fast the oracle applies.

    Fast path: the native C++ library (lodestar_tpu/native/bls_host.cpp,
    threaded, differential-tested in tests/native/test_bls_host.py) does
    the whole decode+check+hash pipeline and emits device-layout limbs
    directly. The pure-Python oracle path below is the fallback and the
    correctness anchor.

    Arrays: pk (x, y), h (x, y), sig (x, y).
    """
    if not sets:
        return None
    from lodestar_tpu.native import bls as _nbls

    if all(len(s.message) == 32 for s in sets):
        native = _nbls.prepare_sets_native(
            [bytes(s.pubkey) for s in sets],
            [bytes(s.message) for s in sets],
            [bytes(s.signature) for s in sets],
        )
        if native is not None:
            return native
        if _nbls.available():
            return None  # native path loaded and REJECTED a set: fail fast
    pk_pts, h_pts, sig_pts = [], [], []
    try:
        for s in sets:
            pk = g1_from_bytes(s.pubkey)
            if pk is None or not C.g1_in_subgroup(pk):
                return None
            sig = g2_from_bytes(s.signature)
            if sig is None or not C.g2_in_subgroup(sig):
                return None
            pk_pts.append(pk)
            sig_pts.append(sig)
            h_pts.append(hash_to_g2(s.message))
    except PointDecodeError:
        return None
    return (
        _g1_batch_host(pk_pts),
        _g2_batch_host(h_pts),
        _g2_batch_host(sig_pts),
    )


def _encodings_have_their_lengths(sets: list) -> bool:
    return all(
        len(bytes(s.signature)) == 96
        and (isinstance(s, IndexedSignatureSet) or len(bytes(s.pubkey)) == 48)
        for s in sets
    )


# what an indexed row carries where a byte row carries its pubkey: any
# well-formed key does (the row's pubkey comes from the table)
_FILLER_PUBKEY = g1_to_bytes(G1_GEN)


def _byte_rows(sets: list, size: int, table):
    """`sets` as the rows of a launch: (byte sets, rows_ok, indexed). A
    byte set is its own row. An indexed set whose signers the launch
    can sum (the lanes hold the table, 1 to K signers, every index in
    the table: a gather clamps silently, so the bounds are checked
    here) becomes a row of `indexed` = (idx (size, K) int32 of table
    rows, the identity's in the padded columns; is_indexed (size,)
    bool), None where no set is such, and a filler pubkey stands where
    a byte row has its own. One with more than K signers, or lanes
    without the table, takes the counted host aggregation and rides as
    a byte row. No signer, or an index the registry lacks, makes the
    row structurally invalid (`rows_ok` (size,) bool)."""
    from lodestar_tpu.chain.bls.pubkey_table import IDENTITY_ROW

    rows_ok = np.ones(size, dtype=bool)
    if not any(isinstance(s, IndexedSignatureSet) for s in sets):
        return sets, rows_ok, None
    rows: list[SignatureSet] = []
    idx = is_indexed = None
    fallbacks = 0
    on_device = table is not None and table.on_device
    resolver = table.pubkey_at if table is not None else None
    for row, s in enumerate(sets):
        if not isinstance(s, IndexedSignatureSet):
            rows.append(s)
            continue
        pubkey = _FILLER_PUBKEY
        if on_device and len(s.indices) <= AGGREGATE_ROW_POINTS:
            signers = np.fromiter(s.indices, dtype=np.int64, count=len(s.indices))
            if not signers.size or not table.contains(signers):
                rows_ok[row] = False
            else:
                if idx is None:
                    idx = np.full((size, AGGREGATE_ROW_POINTS), IDENTITY_ROW, dtype=np.int32)
                    is_indexed = np.zeros(size, dtype=bool)
                idx[row, : signers.size] = signers + 1  # registry index i is table row i + 1
                is_indexed[row] = True
        else:
            fallbacks += 1
            resolved = resolve_signature_set(s, resolver)
            rows_ok[row] = resolved is not None
            if resolved is not None:
                pubkey = resolved.pubkey
        rows.append(SignatureSet(pubkey=pubkey, message=s.message, signature=s.signature))
    if fallbacks:
        _note_aggregate_fallback(fallbacks)
    return rows, rows_ok, None if idx is None else (idx, is_indexed)


def _parse_host_arrays(sets: list[SignatureSet], size: int):
    """Host byte stage shared by the split and single-launch schedules:
    wrong-length structural check, compressed-flag/limb parsing on
    size-padded rows, expand_message_xmd reduction halves (padding rows
    repeat row/message 0 and are masked by every consumer). Byte work
    only — zero device dispatches; one source of truth so the two
    schedules can't drift on the parse contract. Returns (pk_limbs,
    pk_sign, pk_struct, sig_limbs, sig_sign, sig_struct, lo, hi), or
    None when a set has a wrong-length encoding (a final structural
    verdict, never a device error)."""
    from lodestar_tpu.ops import prep as dp

    n = len(sets)
    if not _encodings_have_their_lengths(sets):
        return None
    pk_raw = np.frombuffer(
        b"".join(bytes(s.pubkey) for s in sets), dtype=np.uint8
    ).reshape(n, 48)
    sig_raw = np.frombuffer(
        b"".join(bytes(s.signature) for s in sets), dtype=np.uint8
    ).reshape(n, 96)
    msgs = [bytes(s.message) for s in sets]
    pk_limbs, pk_sign, pk_struct = dp.parse_g1_compressed(dp.pad_rows(pk_raw, size))
    sig_limbs, sig_sign, sig_struct = dp.parse_g2_compressed(dp.pad_rows(sig_raw, size))
    lo, hi = dp.hash_to_field_limbs(msgs + [msgs[0]] * (size - n))
    return pk_limbs, pk_sign, pk_struct, sig_limbs, sig_sign, sig_struct, lo, hi


def _parse_launch_rows(sets: list, size: int, table):
    """The single launch's host stage over sets of either form:
    `_byte_rows`, then `_parse_host_arrays` of the rows. Returns
    ((pk_limbs, pk_sign, sig_limbs, sig_sign, lo, hi, struct_ok),
    indexed), or None on a wrong-length encoding."""
    rows, rows_ok, indexed = _byte_rows(sets, size, table)
    parsed = _parse_host_arrays(rows, size)
    if parsed is None:
        return None
    pk_limbs, pk_sign, pk_struct, sig_limbs, sig_sign, sig_struct, lo, hi = parsed
    struct_ok = pk_struct & sig_struct & rows_ok
    return (pk_limbs, pk_sign, sig_limbs, sig_sign, lo, hi, struct_ok), indexed


def _prepare_sets_device_arrays(sets: list[SignatureSet], size: int):
    """Device-resident prep on arrays padded to `size` (one compiled
    program per size class, same bucketing as the verify stages).

    Host work is byte-oriented only (flag parsing, limb unpacking,
    expand_message_xmd); every field op — decompression sqrt, subgroup
    checks, hash-to-field reduction, SSWU/isogeny/cofactor — runs in the
    staged device programs of ops/prep.py: `FUSED_PREP_LAUNCHES` counted
    dispatches per batch. Returns (pk, h, sig, ok) where ok is the
    all-sets-structurally-valid verdict (host bool)."""
    from lodestar_tpu.ops import prep as dp

    n = len(sets)
    parsed = _parse_host_arrays(sets, size)
    if parsed is None:
        # wrong-length encodings are a structural reject, not a device
        # error — don't burn a host-fallback on garbage input
        return None, None, None, False
    pk_limbs, pk_sign, pk_struct, sig_limbs, sig_sign, sig_struct, lo, hi = parsed

    pk, pk_ok, sig, sig_ok, h = dp.prepare_arrays_fused(
        pk_limbs, pk_sign, sig_limbs, sig_sign, lo, hi
    )

    valid = (
        pk_struct[:n]
        & sig_struct[:n]
        & np.asarray(pk_ok)[:n]
        & np.asarray(sig_ok)[:n]
    )
    return pk, h, sig, bool(valid.all())


def prepare_sets_device(sets: list[SignatureSet]):
    """Device-path twin of `prepare_sets`: same contract (device-layout
    arrays or None if any set is structurally invalid), raw compressed
    bytes in, no per-set big-int math on the host. Internally padded to
    the verify size classes so callers share compiled programs:
    `ops.prep.FUSED_PREP_LAUNCHES` dispatches per batch."""
    if not sets:
        return None
    n = len(sets)
    pk, h, sig, ok = _prepare_sets_device_arrays(sets, _pad_pow2(n))
    if not ok:
        return None
    return (
        (pk[0][:n], pk[1][:n]),
        (h[0][:n], h[1][:n]),
        (sig[0][:n], sig[1][:n]),
    )


def _slot_major(a, groups: int):
    """(groups * slot, ...) rows -> (slot, groups, ...): a slot's rows
    down axis 0 and the slots beside each other. The ops' tree folds
    reduce axis 0 and carry every other axis as batch, so on this layout
    they fold all slots at once, each exactly as they fold it alone."""
    return jnp.swapaxes(a.reshape((groups, -1) + a.shape[1:]), 0, 1)


def _fold_sum_slots(F, pts, groups: int):
    """`cv.fold_sum` per slot: (X, Y, Z) each (groups, ...)."""
    return cv.fold_sum(F, tuple(_slot_major(c, groups) for c in pts))


def _fp12_product_fold_slots(fs, mask, groups: int):
    """`prg.fp12_product_fold` per slot, masked rows replaced with one:
    (groups, 2, 3, 2, 33). A slot that is no power of two long (the
    72-row rung, `telemetry.group_slot_rows`) is padded with ones by the
    fold, as `cv.fold_sum` pads it with infinity: it folds as a 128-row
    slot does."""
    with jax.named_scope("bls.fold"):
        ones = tw.fp12_one(fs.shape[:1])
        fs = _slot_major(jnp.where(mask[:, None, None, None, None], fs, ones), groups)
    return prg.fp12_product_fold(fs)


def _blind_and_aggregate_body(pk_x, pk_y, sig_x, sig_y, coeff_bits, mask, groups: int = 1):
    """Blinded scalar muls (r_i*PK_i in G1, r_i*S_i in G2), the masked G2
    fold to each slot's aggregate signature, affine conversions. The
    rows are `groups` slots of equal length, one RLC batch a slot; the
    aggregates come back with the slots down axis 0."""
    with jax.named_scope("bls.blind"):
        one1 = fp.one_mont()
        one2 = tw.fp2_one()
        rpk = cv.scalar_mul_var(cv.F1, (pk_x, pk_y), coeff_bits, one1)
        rsig = cv.scalar_mul_var(cv.F2, (sig_x, sig_y), coeff_bits, one2)
        # padded entries must not contribute to the signature aggregate:
        # force their blinded sig to infinity before the fold
        mcol = mask[:, None, None]
        rsig = (rsig[0], rsig[1], jnp.where(mcol, rsig[2], jnp.zeros_like(rsig[2])))
        s_agg = _fold_sum_slots(cv.F2, rsig, groups)
        rpk_aff = cv.jac_to_affine_batch(cv.F1, rpk)
        s_aff = cv.jac_to_affine_batch(cv.F2, s_agg)
        s_inf = cv.jac_is_inf(cv.F2, s_agg)
    return rpk_aff, s_aff, s_inf


def _assemble_pairs(rpk_aff, s_aff, s_inf, h_x, h_y, mask):
    """Miller batch: N blinded-pubkey/message pairs, then one
    (-g1, S_agg) pair a slot. Padded / infinite entries get the
    generator pair as a placeholder (any valid non-infinity point works;
    the mask drops their Miller value)."""
    with jax.named_scope("bls.assemble"):
        gen_p = (jnp.asarray(_NEG_G1_X), jnp.asarray(_NEG_G1_Y))
        agg_p = [jnp.broadcast_to(c, s_inf.shape + c.shape) for c in gen_p]  # -g1, a row a slot
        p_x = jnp.concatenate([rpk_aff[0], agg_p[0]], axis=0)
        p_y = jnp.concatenate([rpk_aff[1], agg_p[1]], axis=0)
        q_x = jnp.concatenate([h_x, s_aff[0]], axis=0)
        q_y = jnp.concatenate([h_y, s_aff[1]], axis=0)
        pair_mask = jnp.concatenate([mask, ~s_inf], axis=0)
        gen_q_x = jnp.broadcast_to(h_x[0], q_x.shape[1:])
        gen_q_y = jnp.broadcast_to(h_y[0], q_y.shape[1:])
        mm = pair_mask[:, None, None]
        p_x = jnp.where(mm[..., 0], p_x, gen_p[0])
        p_y = jnp.where(mm[..., 0], p_y, gen_p[1])
        q_x = jnp.where(mm, q_x, gen_q_x)
        q_y = jnp.where(mm, q_y, gen_q_y)
    return p_x, p_y, q_x, q_y, pair_mask


def _fold_verdict_body(fs, pair_mask, groups: int = 1):
    """Each slot's Fp12 product (its rows' Miller values times its
    aggregate pair's), one final exponentiation batched over the slots,
    the ==1 predicate: (groups,) bools."""
    rows = fs.shape[0] - groups
    f = _fp12_product_fold_slots(fs[:rows], pair_mask[:rows], groups)
    with jax.named_scope("bls.fold"):
        agg = jnp.where(pair_mask[rows:, None, None, None, None], fs[rows:], tw.fp12_one((groups,)))
        f = tw.fp12_mul(f, agg)
    return tw.fp12_eq_one(prg.final_exponentiation(f))


def _fold_verdict_one(fs, pair_mask):
    """`_fold_verdict_body` of one batch: a scalar bool."""
    return _fold_verdict_body(fs, pair_mask)[0]


@jax.jit
def _device_batch_verify_impl(pk_x, pk_y, h_x, h_y, sig_x, sig_y, coeff_bits, mask):
    """Monolithic composition of the shared stage bodies (one program)."""
    rpk_aff, s_aff, s_inf = _blind_and_aggregate_body(
        pk_x, pk_y, sig_x, sig_y, coeff_bits, mask
    )
    p_x, p_y, q_x, q_y, pair_mask = _assemble_pairs(
        rpk_aff, s_aff, s_inf, h_x, h_y, mask
    )
    fs = prg.miller_loop((p_x, p_y), (q_x, q_y))
    return _fold_verdict_one(fs, pair_mask)


_stage_blind_and_aggregate = jax.jit(_blind_and_aggregate_body)
_stage_miller = jax.jit(lambda p_x, p_y, q_x, q_y: prg.miller_loop((p_x, p_y), (q_x, q_y)))
_stage_fold_verdict = jax.jit(_fold_verdict_one)


def _single_launch_body(
    pk_x_std, pk_sign, sig_x_std, sig_sign, lo, hi, struct_ok, coeff_bits, mask, groups: int,
    indexed: tuple = (),
):
    """The single-launch chain over `groups` slots of equal length, one
    RLC batch a slot: compressed-point limbs + hash-to-field halves in,
    a verdict a slot out.

    Composed by CALLING the fused schedule's three staged legs
    (ops/prep.py `_prep_field_stage` / `_prep_subgroup_stage` /
    `hash_finish` — jitted functions inline inside an outer jit, so the
    single program and the 3-launch reference share one source of truth
    per leg) plus the RLC/pairing bodies of this module; the G2 ladder
    tables and hot curve constants are closed over as jit constants, so
    they stay pinned in device memory across batches. Everything
    row-wise (the prep legs, the blinding ladders, the Miller loop) runs
    flat over all rows; what is per batch (the signature aggregate, its
    (-g1, S_agg) pair, the Fp12 product, the final exponentiation, the
    structural veto) is per slot, so a slot's verdict is what the
    program returns for that slot's rows alone. `indexed`, where the
    launch has rows that name their signers by registry index, is
    (table_x, table_y, idx, is_indexed): such a row's pubkey is the sum
    of the table rows its index row names (`bls.aggregate`:
    `ops/msm.py:aggregate_rows_g1`; an identity sum makes the row
    invalid), the other rows' comes from their bytes as in a launch
    without the four; everything after the pubkey is the same.
    Structurally invalid
    rows (host parse flags in `struct_ok`, on-curve/subgroup flags
    decided here) fold into the verdict on device: any invalid unmasked
    row makes its slot False, exactly the fail-fast the split schedule
    applies before its verify dispatch. Returns (verdict, batch_valid),
    (groups,) bools each — the second distinguishes a structural reject
    from an invalid signature for the prep-rejection metric only (both
    are final False verdicts)."""
    from lodestar_tpu.ops import prep as dp

    # the fused schedule's three legs, one trace: field stage
    # (decompression chains + the shared Fp2 sqrt chain + SSWU +
    # 3-isogeny), subgroup ladders, hash finish (add + Budroni–Pintore
    # clearing + batch affine)
    with jax.named_scope("bls.prep_field"):
        pk_x, pk_y, pk_curve, sig_x, sig_y, sig_curve, q0, q1 = dp._prep_field_stage(
            pk_x_std, pk_sign, sig_x_std, sig_sign, lo, hi
        )
    with jax.named_scope("bls.prep_subgroup"):
        pk_ok, sig_ok = dp._prep_subgroup_stage(
            pk_x, pk_y, pk_curve, sig_x, sig_y, sig_curve
        )
    with jax.named_scope("bls.hash_finish"):
        h_x, h_y = dp.hash_finish(q0, q1)
    if indexed:
        from lodestar_tpu.ops import msm

        table_x, table_y, idx, is_indexed = indexed
        with jax.named_scope("bls.aggregate"):
            agg_x, agg_y, agg_ok = msm.aggregate_rows_g1(table_x, table_y, idx)
            pk_x = jnp.where(is_indexed[:, None], agg_x, pk_x)
            pk_y = jnp.where(is_indexed[:, None], agg_y, pk_y)
            pk_ok = jnp.where(is_indexed, agg_ok, pk_ok)

    # RLC aggregation + Miller loop + final exponentiation. Invalid rows
    # carry in-contract relaxed limbs (the pow-chain outputs), so the
    # group ops below stay well-defined on them; their garbage pairing
    # values are irrelevant because `batch_valid` vetoes the verdict.
    rpk_aff, s_aff, s_inf = _blind_and_aggregate_body(
        pk_x, pk_y, sig_x, sig_y, coeff_bits, mask, groups
    )
    p_x, p_y, q_x, q_y, pair_mask = _assemble_pairs(
        rpk_aff, s_aff, s_inf, h_x, h_y, mask
    )
    fs = prg.miller_loop((p_x, p_y), (q_x, q_y))
    rlc_ok = _fold_verdict_body(fs, pair_mask, groups)

    valid = struct_ok & pk_ok & sig_ok
    batch_valid = jnp.all((valid | ~mask).reshape(groups, -1), axis=1)
    return batch_valid & rlc_ok, batch_valid


@jax.jit
def _single_launch_verify(
    pk_x_std, pk_sign, sig_x_std, sig_sign, lo, hi, struct_ok, coeff_bits, mask, *indexed
):
    """THE single-launch program: one batch, scalar verdict out — one
    resident device program per pow-2 size class
    (`ops.prep.SINGLE_LAUNCH_BUDGET` dispatches per batch, counted at
    ops/prep.py's `_dispatch` seam), and one more per size class for
    batches with indexed rows (`indexed`: the body's four arrays).
    `_single_launch_body` with one slot."""
    verdict, batch_valid = _single_launch_body(
        pk_x_std, pk_sign, sig_x_std, sig_sign, lo, hi, struct_ok, coeff_bits, mask, 1, indexed
    )
    return verdict[0], batch_valid[0]


@functools.partial(jax.jit, static_argnames="groups")
def _grouped_launch_verify(
    pk_x_std, pk_sign, sig_x_std, sig_sign, lo, hi, struct_ok, coeff_bits, mask, *indexed, groups
):
    """The multi-job launch: `groups` jobs ride one program, a slot of
    rows each, and each gets the verdict `_single_launch_verify` gives
    it alone. One resident program per (rows, groups); the pool forms
    (144, 2) and (288, 4) from jobs of 65 to 72 sets, a block's halves,
    and (256, 2) and (512, 4) from longer ones: the same body traced at
    the slot length `telemetry.group_slot_rows` gives, with or without
    the body's four `indexed` arrays."""
    return _single_launch_body(
        pk_x_std, pk_sign, sig_x_std, sig_sign, lo, hi, struct_ok, coeff_bits, mask, groups, indexed
    )


def _device_batch_verify_staged(pk, h, sig, coeff_bits, mask):
    """The batch-verify pipeline as THREE jitted stages instead of one
    monolithic program. Functionally identical to
    `_device_batch_verify_impl`; used on Pallas backends, where the
    monolithic compile has produced wrong verdicts even though every
    stage (and every construct) verifies in isolation — staging sidesteps
    the whole-program miscompile at the cost of two tiny host round
    trips. See tools/pallas_v2_proto.py provenance notes.
    """
    coeff_bits = jnp.asarray(coeff_bits)
    mask = jnp.asarray(mask)
    rpk_aff, s_aff, s_inf = _stage_blind_and_aggregate(
        pk[0], pk[1], sig[0], sig[1], coeff_bits, mask
    )
    p_x, p_y, q_x, q_y, pair_mask = _assemble_pairs(
        rpk_aff, s_aff, s_inf, jnp.asarray(h[0]), jnp.asarray(h[1]), mask
    )
    fs = _stage_miller(p_x, p_y, q_x, q_y)
    return _stage_fold_verdict(fs, pair_mask)


def device_batch_verify(pk, h, sig, coeff_bits, mask) -> jax.Array:
    """Device verification core (see _device_batch_verify_impl /
    _device_batch_verify_staged).

    pk: (x, y) each (N, 33); h/sig: (x, y) each (N, 2, 33); coeff_bits:
    (N, 64) int32 MSB-first; mask: (N,) bool — False entries are padding.
    Returns a scalar bool array.
    """
    from lodestar_tpu.ops import fp_pallas

    staged = fp_pallas.use_pallas()
    # the verify core's jit-cache seam: one record per call (the staged
    # chain is one logical launch unit of 3 dispatches), size class =
    # the padded batch the executable was compiled for
    with telemetry.launch(
        "batch_verify_staged" if staged else "batch_verify", int(pk[0].shape[0])
    ):
        if staged:
            return _device_batch_verify_staged(pk, h, sig, coeff_bits, mask)
        return _device_batch_verify_impl(
            pk[0], pk[1], h[0], h[1], sig[0], sig[1],
            jnp.asarray(coeff_bits), jnp.asarray(mask),
        )


@functools.lru_cache(maxsize=None)
def _sharded_program(mesh):
    """The jitted data-parallel verify program for one mesh (jit
    specializes per batch shape itself). Memoized per mesh: a fresh
    `jax.jit` of a fresh closure would retrace and recompile the
    minutes-long program on every call."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    one1 = fp.one_mont()
    one2 = tw.fp2_one()

    def shard_fn(pk_x, pk_y, h_x, h_y, sig_x, sig_y, bits, mask):
        rpk = cv.scalar_mul_var(cv.F1, (pk_x, pk_y), bits, one1)
        rsig = cv.scalar_mul_var(cv.F2, (sig_x, sig_y), bits, one2)

        # local partial signature aggregate (masked padding -> infinity)
        mcol = mask[:, None, None]
        rsig = (rsig[0], rsig[1], jnp.where(mcol, rsig[2], jnp.zeros_like(rsig[2])))
        local_sig = cv.fold_sum(cv.F2, rsig)

        # local Miller loops on blinded pubkeys vs message hashes
        rpk_aff = cv.jac_to_affine_batch(cv.F1, rpk)
        gen_px = jnp.asarray(_NEG_G1_X)
        gen_py = jnp.asarray(_NEG_G1_Y)
        mm = mask[:, None, None]
        p_x = jnp.where(mm[..., 0], rpk_aff[0], gen_px)
        p_y = jnp.where(mm[..., 0], rpk_aff[1], gen_py)
        q_x = jnp.where(mm, h_x, h_x[0])
        q_y = jnp.where(mm, h_y, h_y[0])
        fs = prg.miller_loop((p_x, p_y), (q_x, q_y))
        local_f = prg.fp12_product_fold(fs, mask=mask)

        # cross-chip: gather tiny partials (one fp12 + one G2 point each)
        all_f = jax.lax.all_gather(local_f, "data")  # (n_dev, 2, 3, 2, 32)
        all_sig = jax.lax.all_gather(local_sig, "data")  # 3x (n_dev, 2, 32)
        f = prg.fp12_product_fold(all_f)
        s_agg = cv.fold_sum(cv.F2, all_sig)

        # final (-g1, S_agg) pair + the one shared final exponentiation
        s_aff = cv.jac_to_affine_batch(cv.F2, tuple(c[None] for c in s_agg))
        s_inf = cv.jac_is_inf(cv.F2, s_agg)
        fin_q_x = jnp.where(s_inf, q_x[0], s_aff[0][0])
        fin_q_y = jnp.where(s_inf, q_y[0], s_aff[1][0])
        f_fin = prg.miller_loop(
            (gen_px[None], gen_py[None]), (fin_q_x[None], fin_q_y[None])
        )
        ones = tw.fp12_one((1,))
        f_fin = jnp.where(s_inf, ones, f_fin)
        f = tw.fp12_mul(f, f_fin[0])
        ok = tw.fp12_eq_one(prg.final_exponentiation(f))
        return ok[None]

    # pk x/y, h x/y, sig x/y, bits, mask: all sharded on the batch axis
    return jax.jit(
        shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(P("data"),) * 8,
            out_specs=P("data"),
            check_vma=False,
        )
    )


def device_batch_verify_sharded(mesh, pk, h, sig, coeff_bits, mask) -> jax.Array:
    """Multi-chip batch verification: the signature-set batch is sharded
    data-parallel over the mesh's 'data' axis (the sharding translation of
    the reference's worker-pool data parallelism, SURVEY §2c: one 128-set
    job split across N workers -> one batch split across N chips).

    Per shard: blinded scalar muls, local Miller loops, local Fp12 partial
    product, local partial G2 fold of the blinded signatures. Cross-chip:
    one all_gather of the (tiny) partial products and partial signature
    points rides the ICI; every chip then finishes the fold + the single
    shared final exponentiation redundantly (SPMD-replicated scalar work).
    """
    # the sharded collective's jit-cache seam: only the first call per
    # (mesh, batch) carries compile
    with telemetry.launch(
        "batch_verify_sharded",
        int(pk[0].shape[0]),
        lane=",".join(str(d.id) for d in mesh.devices.flat),
    ):
        ok = _sharded_program(mesh)(
            pk[0], pk[1], h[0], h[1], sig[0], sig[1],
            jnp.asarray(coeff_bits), jnp.asarray(mask),
        )
    return ok.all()


def _pad_pow2(n: int, floor: int = 8) -> int:
    from lodestar_tpu.ops.prep import pad_pow2

    return pad_pow2(n, floor)


def _random_coeffs(n: int) -> np.ndarray:
    """[1, r_1, ..., r_{n-1}] nonzero 64-bit blinding scalars."""
    out = np.empty(n, dtype=np.uint64)
    out[:1] = 1  # none for an empty slot of a multi-job launch
    for i in range(1, n):
        k = 0
        while k == 0:
            k = int.from_bytes(os.urandom(8), "big")
        out[i] = k
    return out


def _blinding_and_mask(n: int, size: int):
    """Fresh RLC blinding bits + padding mask for a size-padded batch —
    the soundness-critical tail (coeff 0 fixed to 1, the rest nonzero
    64-bit; padding rows zeroed and masked out) shared by BOTH device
    schedules: the split path's `_finish_inputs` and the single-launch
    host stage, so the blinding contract can't drift between them."""
    bits = np.zeros((size, COEFF_BITS), dtype=np.int32)
    bits[:n] = _bits_msb(_random_coeffs(n), COEFF_BITS)
    mask = np.zeros(size, dtype=bool)
    mask[:n] = True
    return bits, mask


def _finish_inputs(pk, h, sig, n: int, size: int):
    """Fresh blinding bits + padding mask over size-padded point arrays."""
    bits, mask = _blinding_and_mask(n, size)
    return pk, h, sig, bits, mask


def build_device_inputs(sets: list, size: int | None = None, table=None):
    """Input prep + padding: decode/validate/hash N sets and pad the
    arrays to `size` (default: next power of two >= 8, the size-class
    bucketing that keeps one compiled program per class — the device
    analogue of the reference's <= 128-sets-per-job chunking,
    `multithread/index.ts:34-39`). Returns (pk, h, sig, bits, mask) device
    inputs with fresh blinding coefficients, or None on invalid input.

    Prep runs on the device where the backend is an accelerator
    (`single_launch_active`), on the host otherwise. A device prep
    ERROR falls back to the verified host pipeline (native C++ → python
    oracle); a structural-invalid verdict is final on whichever layer
    produced it. This road takes pubkey bytes: an indexed set's signers
    are summed on the host first, from `table` (counted).
    """
    if not sets:
        return None
    sets = _host_aggregated(sets, table)
    if sets is None:
        return None
    n = len(sets)
    if size is None:
        size = _pad_pow2(n)
    if size < n:
        raise ValueError("pad size smaller than batch")

    with telemetry.phase("bls.parse"):
        if single_launch_active():
            t0 = time.monotonic_ns()
            try:
                pk, h, sig, ok = _prepare_sets_device_arrays(sets, size)
            except Exception as e:  # degrade to host prep, never resolve here
                _note_prep_fallback(e)
            else:
                _note_prep("device", n, t0, rejected=not ok)
                if not ok:
                    return None
                return _finish_inputs(pk, h, sig, n, size)

        t0 = time.monotonic_ns()
        prepared = prepare_sets(sets)
        _note_prep("host", n, t0, rejected=prepared is None)
        if prepared is None:
            return None
        (pk_x, pk_y), (h_x, h_y), (sig_x, sig_y) = prepared
        from lodestar_tpu.ops.prep import pad_rows

        return _finish_inputs(
            (pad_rows(pk_x, size), pad_rows(pk_y, size)),
            (pad_rows(h_x, size), pad_rows(h_y, size)),
            (pad_rows(sig_x, size), pad_rows(sig_y, size)),
            n,
            size,
        )


def make_synthetic_sets(n: int, seed: int = 1) -> list[SignatureSet]:
    """Deterministic valid signature sets (bench + driver fixtures)."""
    from lodestar_tpu.crypto.bls.api import SecretKey, sign

    sets = []
    for i in range(n):
        sk = SecretKey((seed * 1000003 + i + 1) * 0xDEADBEEF + 13)
        msg = bytes([seed & 0xFF, i & 0xFF]) * 16
        sets.append(SignatureSet(pubkey=sk.to_pubkey(), message=msg, signature=sign(sk, msg)))
    return sets


def verify_signature_sets_device(sets: list, device=None, table=None) -> bool:
    """End-to-end single-device batch verify of N signature sets, on
    `device` (a lane's chip) or, None, wherever JAX puts it. `table`
    (the pool's `PubkeyTable`) is what indexed sets are resolved from.

    On an accelerator the single-launch program (one counted dispatch,
    bytes-in → verdict-out, with its own degradation chain back to the
    split schedule); otherwise the split schedule: host prep followed
    by the RLC verify dispatch."""
    if single_launch_active():
        return verify_sets_single_launch(sets, device, table)
    return _verify_sets_split(sets, device, table)


def _placed_on(device):
    """Placement for the roads that make their own device arrays (the
    split schedule, and every road on a CPU backend): JAX's default
    device. It is part of a jitted program's trace key, so a program
    under it is traced again for every chip; the single launch is
    placed by its inputs instead (`_dispatch_launch`)."""
    return jax.default_device(device) if device is not None else contextlib.nullcontext()


def _verify_sets_split(sets: list, device=None, table=None) -> bool:
    """The split (prep-then-verify) schedule: `build_device_inputs`
    (fused 3-launch device prep, host prep on error or off an
    accelerator) plus
    the separate RLC verify dispatch — the single-launch program's
    differential reference and per-batch fallback."""
    with _placed_on(device):
        inputs = build_device_inputs(sets, table=table)
        if inputs is None:
            return False
        return _verify_split_prepared(inputs)


def _verify_split_prepared(inputs) -> bool:
    """The split schedule's verify dispatch on `build_device_inputs`'
    tuple, under the phase names the single launch uses."""
    pk, h, sig, bits, mask = inputs
    with telemetry.phase("bls.dispatch"):
        out = device_batch_verify(pk, h, sig, bits, mask)
    with telemetry.phase("bls.wait"):
        return bool(np.asarray(out))


class SingleLaunchInputs:
    """Host-staged inputs for one single-launch dispatch: the parsed
    limb/flag/hash arrays, fresh blinding bits, and the padding mask —
    everything `_single_launch_verify` consumes, produced by byte work
    only (no device dispatches). Carries the original sets so the
    verify side can degrade to the split schedule on a device error.
    `table` is what the sets' indices were resolved against; `indexed`
    is None, or (idx, is_indexed) where rows name their signers by
    index: the dispatch takes the table's arrays on the chip it runs on
    (`_indexed_args`)."""

    __slots__ = ("sets", "arrays", "bits", "mask", "n", "table", "indexed")

    def __init__(self, sets, arrays, bits, mask, n, table=None, indexed=None):
        self.sets = sets
        self.arrays = arrays  # (pk_limbs, pk_sign, sig_limbs, sig_sign, lo, hi, struct)
        self.bits = bits
        self.mask = mask
        self.n = n
        self.table = table
        self.indexed = indexed


def _indexed_args(inputs, device) -> tuple:
    """The program's four `indexed` arguments on `device`, none for a
    byte-only launch. The table's copy is taken now: rows are written
    once, so it holds every row the parse checked."""
    if inputs.indexed is None:
        return ()
    return (*inputs.table.arrays_on(device), *inputs.indexed)


def prepare_single_launch_inputs(sets: list, table=None):
    """Host byte stage of the single-launch path: compressed-flag
    parsing, limb unpacking, expand_message_xmd, blinding sampling —
    zero device dispatches. Returns SingleLaunchInputs, or None when a
    set is structurally rejected at parse time (wrong-length encoding:
    a final verdict, never a launch — the pipelined pool stages this
    reject without touching the device)."""
    if not sets:
        return None
    n = len(sets)
    with telemetry.phase("bls.parse"):
        t0 = time.monotonic_ns()
        size = _pad_pow2(n)
        parsed = _parse_launch_rows(sets, size, table)
        if parsed is None:
            _note_prep("single_launch", n, t0, rejected=True)
            return None
        arrays, indexed = parsed
        bits, mask = _blinding_and_mask(n, size)
        _note_prep("single_launch", n, t0)
        return SingleLaunchInputs(list(sets), arrays, bits, mask, n, table, indexed)


_traced_launches: set = set()  # guarded by: _trace_lock (programs, by static arguments and shapes, whose trace JAX holds)
_trace_lock = threading.Lock()


def _trace_once(program, args, static) -> None:
    """One trace of a single-launch program for every lane. A lane's
    launch is placed by its inputs (committed to the lane's chip), which
    are no part of JAX's trace key: the first lane to get here traces,
    the others wait for it instead of tracing the same program beside
    it under one GIL, and then each only lowers and compiles (or loads)
    for its own chip."""
    key = (program, tuple(sorted(static.items())), tuple((a.shape, a.dtype.str) for a in args))
    if key in _traced_launches:
        return
    with _trace_lock:
        if key not in _traced_launches:
            program.trace(*args, **static)
            _traced_launches.add(key)


def _dispatch_launch(program, what: str, shape: tuple, *args, device=None, **static):
    """ONE counted dispatch of a single-launch program and the wait for
    its two outputs, BOTH shape-checked: a miscompile returning a
    malformed batch_valid must raise here, inside the caller's guarded
    region, and degrade like any other anomaly, not reach the
    lane/breaker. Returns (verdict, batch_valid) as numpy bools. With a
    `device` (a lane's chip) the launch runs there, on one trace for all
    lanes (`_trace_once`)."""
    from lodestar_tpu.ops import prep as dp

    with telemetry.phase("bls.dispatch"):  # transfer and enqueue
        if device is not None:
            _trace_once(program, args, static)
            args = jax.device_put(args, device)
        verdict, batch_valid = dp._dispatch(program, *args, **static)
    with telemetry.phase("bls.wait"):  # blocks on the verdict
        v = np.asarray(verdict)
        bvld = np.asarray(batch_valid)
    for name, arr in (("verdict", v), ("batch_valid", bvld)):
        if arr.shape != shape or arr.dtype != np.bool_:
            raise RuntimeError(f"{what} {name} shape anomaly: {arr.shape}/{arr.dtype}")
    return v, bvld


def _verify_single_prepared(si: SingleLaunchInputs, device=None) -> bool:
    """Dispatch ONE single-launch program on host-staged inputs. A
    device error or a verdict-shape anomaly degrades the batch to the
    split schedule (counted + warned) — which itself degrades device
    prep to host prep, the full staged-jit miscompile chain."""
    try:
        v, bvld = _dispatch_launch(
            _single_launch_verify, "single-launch", (), *si.arrays, si.bits, si.mask,
            *_indexed_args(si, device), device=device,
        )
    except Exception as e:  # degrade to the split schedule, never resolve here
        _note_single_launch_fallback(e)
        return _verify_sets_split(si.sets, device, si.table)
    if not bool(bvld):
        m = _prep_metrics
        if m is not None:
            m.rejected.inc()
    return bool(v)


def verify_sets_single_launch(sets: list, device=None, table=None) -> bool:
    """End-to-end single-launch batch verify: compressed bytes in, ONE
    counted device dispatch (`ops.prep.SINGLE_LAUNCH_BUDGET`), verdict
    out — verdicts identical to `verify_signature_sets_device` on the
    same sets. Host-parse rejects cost zero dispatches; device errors
    degrade per-batch to the split schedule."""
    try:
        si = prepare_single_launch_inputs(sets, table)
    except Exception as e:
        # a host-parse ERROR (not a structural reject) degrades to the
        # split schedule like any other single-launch fault — the split
        # path catches the same class inside build_device_inputs and
        # lands on host prep, so a poisoned batch can never raise out
        # of here and charge every lane's breaker in turn
        _note_single_launch_fallback(e)
        return _verify_sets_split(sets, device, table)
    if si is None:
        return False
    return _verify_single_prepared(si, device)


class GroupedLaunchInputs:
    """Host-staged inputs for one multi-job launch: the jobs as they
    came, the parsed arrays of `groups` slots of `slot` rows, blinding
    bits and mask a slot, and `riding`, the indices of the jobs that got
    a slot in slot order (a job with a wrong-length encoding is False at
    parse time and gets none). `table` and `indexed` as
    `SingleLaunchInputs`'."""

    __slots__ = ("jobs", "arrays", "bits", "mask", "groups", "riding", "table", "indexed")

    def __init__(self, jobs, arrays, bits, mask, groups, riding, table=None, indexed=None):
        self.jobs = jobs
        self.arrays = arrays  # as SingleLaunchInputs.arrays, groups * slot rows
        self.bits = bits
        self.mask = mask
        self.groups = groups
        self.riding = riding
        self.table = table
        self.indexed = indexed


def grouped_launch_groups(n_jobs: int) -> int:
    """Slots of the launch that carries `n_jobs` jobs: 2, or 4 for three
    and four (three leave a slot empty, fully masked and ignored)."""
    return 2 if n_jobs <= 2 else 4


def _padding_row(s):
    """A row to fill a slot with: a set's own message and signature, and
    for an indexed set a byte pubkey in its signers' place (a padding
    row is masked: its pubkey is never summed, resolved or counted)."""
    if isinstance(s, IndexedSignatureSet):
        return SignatureSet(pubkey=_FILLER_PUBKEY, message=s.message, signature=s.signature)
    return s


def prepare_grouped_launch_inputs(jobs: list[list], table=None) -> GroupedLaunchInputs:
    """Host byte stage of the multi-job launch: each job parsed into its
    own slot of the launch's rows, with its own blinding and mask — what
    `prepare_single_launch_inputs` makes of the job alone, side by side.
    A slot is as long as `telemetry.group_slot_rows` says for the jobs
    that ride; zero device dispatches."""
    with telemetry.phase("bls.parse"):
        t0 = time.monotonic_ns()
        riding = [i for i, job in enumerate(jobs) if job and _encodings_have_their_lengths(job)]
        n = sum(len(jobs[i]) for i in riding)
        rejected = len(riding) < len(jobs)
        if not riding:
            _note_prep("single_launch", sum(len(j) for j in jobs), t0, rejected=True)
            return GroupedLaunchInputs(jobs, None, None, None, 0, riding, table)
        groups = grouped_launch_groups(len(riding))
        slot = telemetry.group_slot_rows(len(jobs[i]) for i in riding)
        # padding rows repeat a real row and are masked by every
        # consumer, an empty slot's rows too
        filler = _padding_row(jobs[riding[0]][0])
        rows, bits, mask = [], [], []
        for g in range(groups):
            job = jobs[riding[g]] if g < len(riding) else []
            rows += list(job) + [_padding_row(job[0]) if job else filler] * (slot - len(job))
            job_bits, job_mask = _blinding_and_mask(len(job), slot)
            bits.append(job_bits)
            mask.append(job_mask)
        arrays, indexed = _parse_launch_rows(rows, groups * slot, table)
        _note_prep("single_launch", n, t0, rejected=rejected)
        return GroupedLaunchInputs(
            jobs,
            arrays,
            np.concatenate(bits),
            np.concatenate(mask),
            groups,
            riding,
            table,
            indexed,
        )


def _verify_grouped_prepared(gi: GroupedLaunchInputs, device=None) -> list[bool]:
    """Dispatch ONE multi-job program on host-staged inputs: a verdict a
    job. A device error or a shape anomaly of either output degrades the
    unit to one `verify_sets_single_launch` a job (counted + warned)."""
    verdicts = [False] * len(gi.jobs)
    if not gi.riding:
        return verdicts
    try:
        v, bvld = _dispatch_launch(
            _grouped_launch_verify, "grouped-launch", (gi.groups,),
            *gi.arrays, gi.bits, gi.mask, *_indexed_args(gi, device),
            device=device, groups=gi.groups,
        )
    except Exception as e:  # degrade to one launch a job, never resolve here
        _note_single_launch_fallback(e)
        return [verify_sets_single_launch(job, device, gi.table) for job in gi.jobs]
    m = _prep_metrics
    for g, i in enumerate(gi.riding):
        verdicts[i] = bool(v[g])
        if m is not None and not bool(bvld[g]):
            m.rejected.inc()
    return verdicts


def verify_sets_grouped_launch(jobs: list[list], device=None, table=None) -> list[bool]:
    """Up to four jobs, ONE counted device dispatch, a verdict a job —
    each identical to `verify_sets_single_launch` on that job alone. A
    host-parse ERROR degrades to that road, a job at a time."""
    try:
        gi = prepare_grouped_launch_inputs(jobs, table)
    except Exception as e:
        _note_single_launch_fallback(e)
        return [verify_sets_single_launch(job, device, table) for job in jobs]
    return _verify_grouped_prepared(gi, device)


def verify_prepared(inputs, device=None) -> bool | list[bool]:
    """Verify a batch whose inputs were already staged by the pipeline's
    prep stage (chain/bls/pool.py double-buffers prep of batch k+1
    against this call on batch k). Three staged shapes: the split
    schedule's `build_device_inputs` tuple (device arrays; blinding
    sampled at prep time; one RLC verify dispatch here), a
    `SingleLaunchInputs` (host byte-parse only; the ONE single-launch
    program dispatches here, so the whole device chain of batch k
    overlaps the host parse of batch k+1), or a `GroupedLaunchInputs`
    (the same for a multi-job launch: a list of verdicts, one a job).
    Either way a verdict is identical to `verify_signature_sets_device`
    on the same sets, and it is computed on `device` where one is given
    (a lane's chip)."""
    if isinstance(inputs, GroupedLaunchInputs):
        return _verify_grouped_prepared(inputs, device)
    if isinstance(inputs, SingleLaunchInputs):
        return _verify_single_prepared(inputs, device)
    with _placed_on(device):
        return _verify_split_prepared(inputs)


def prepare_inputs_for_lane(sets: list, lane_index: int | None = None, table=None):
    """Pipeline prep stage: `build_device_inputs`, optionally pinned to
    a sibling chip (`jax.default_device`) so staging batch k+1 doesn't
    contend with the lane verifying batch k. A hint that doesn't resolve
    to a device (mock lanes, single-device hosts) preps unpinned —
    placement is an optimization, never a correctness seam.

    Where the backend runs the single launch the prep stage stays on the
    HOST (byte parse + xmd + blinding, zero dispatches): every device
    op of batch k+1 rides its one launch, so the pipeline overlaps the
    host byte-parse/reject of k+1 with the single launch of k. A
    parse-time structural reject stages None — a final verdict, still
    not a launch."""
    if single_launch_active():
        return prepare_single_launch_inputs(sets, table)
    if lane_index is not None:
        try:
            dev = jax.devices()[lane_index]
        except Exception:
            dev = None
        if dev is not None:
            with jax.default_device(dev):
                return build_device_inputs(sets, table=table)
    return build_device_inputs(sets, table=table)


def verify_signature_sets_sharded(sets: list, mesh, table=None) -> bool:
    """End-to-end data-parallel batch verify over a device mesh."""
    n_dev = int(mesh.devices.size)
    n = len(sets)
    size = max(_pad_pow2(n), n_dev)
    if size % n_dev:
        size += n_dev - size % n_dev
    inputs = build_device_inputs(sets, size=size, table=table)
    if inputs is None:
        return False
    pk, h, sig, bits, mask = inputs
    return bool(np.asarray(device_batch_verify_sharded(mesh, pk, h, sig, bits, mask)))


# --- mesh serving helpers (chain/bls/mesh.py construction seam) ---------------


def mesh_device_count() -> int:
    """Visible device count of the default backend — the production
    input to `build_device_mesh`. A backend that cannot initialise
    raises: a busy chip is not a one-lane host."""
    return len(jax.devices())


def mesh_devices() -> list:
    """The default backend's devices in `jax.devices()` order: lane i's chip."""
    return list(jax.devices())


def make_lane_verify_fn(device_index: int):
    """Single-device verify callable pinned to one chip: the per-lane
    backend of the mesh pool. The single launch is placed by its inputs
    (`_dispatch_launch`), so the lanes share the host-side prep and ONE
    trace of each program and every lane lowers and compiles only for
    its own die; the split schedule rides `jax.default_device`."""

    def lane_verify(sets: list, table=None) -> bool:
        return verify_signature_sets_device(sets, jax.devices()[device_index], table)

    lane_verify.__name__ = f"lane_verify_dev{device_index}"
    return lane_verify


def make_lane_verify_prepared_fn(device_index: int):
    """Prepared-inputs twin of `make_lane_verify_fn`: the pipelined
    pool's verify stage, pinned to one chip. Inputs staged on a sibling
    device transfer on first use (jax moves committed arrays); the
    verdict is placement-independent. Handles both staged shapes
    (split-schedule device arrays and host-parsed SingleLaunchInputs —
    see verify_prepared)."""

    def lane_verify_prepared(inputs) -> bool:
        return verify_prepared(inputs, jax.devices()[device_index])

    lane_verify_prepared.__name__ = f"lane_verify_prepared_dev{device_index}"
    return lane_verify_prepared


def make_lane_verify_grouped_fn(device_index: int):
    """Multi-job twin of `make_lane_verify_fn`, pinned to one chip: a
    list of jobs in, one launch, a list of verdicts out."""

    def lane_verify_grouped(jobs: list[list], table=None) -> list[bool]:
        return verify_sets_grouped_launch(jobs, jax.devices()[device_index], table)

    lane_verify_grouped.__name__ = f"lane_verify_grouped_dev{device_index}"
    return lane_verify_grouped


def make_mesh_sharded_fn(table=None):
    """Collective verify callable over a lane subset: builds the jax
    Mesh for the given device indices and runs the data-parallel
    program. One executable is compiled (and memoized, see
    device_batch_verify_sharded) per (device subset, batch size)."""

    def sharded_verify(sets: list, device_indices) -> bool:
        from jax.sharding import Mesh

        devs = jax.devices()
        # canonical device order: the sharded-executable memo keys on
        # the device tuple, and the data-parallel verdict is order-
        # invariant — an occupancy-ordered subset must not recompile
        # the minutes-long program once per permutation
        picked = [devs[i] for i in sorted(device_indices)]
        if len(picked) < 2:
            raise ValueError("sharded verify needs at least two devices")
        mesh = Mesh(np.asarray(picked), ("data",))
        return verify_signature_sets_sharded(sets, mesh, table)

    return sharded_verify
