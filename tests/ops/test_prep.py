"""Differential tests: device input prep (ops/prep.py) vs the CPU oracle.

Pins the acceptance criteria of the device-resident prep pipeline:
bit-exact G1/G2 decompression (including rejection of invalid and
non-subgroup encodings), subgroup checks against both the fast
eigenvalue oracles and the order-R ladders, and the full hash-to-G2 tail
against the CPU reference AND the shared RFC 9380 known-answer vectors
(tests/crypto/rfc9380_vectors.py — the same fixture the CPU tests pin).

Batches are padded to 8 entries throughout so every test shares one
compiled program per stage (the clear-cofactor program is the most
expensive compile in the tree; the persistent cache makes repeat runs
cheap).
"""

import numpy as np
import pytest

from lodestar_tpu.crypto.bls import curve as C
from lodestar_tpu.crypto.bls import fields as F
from lodestar_tpu.crypto.bls import serdes
from lodestar_tpu.crypto.bls.curve import G1_GEN, G2_GEN, g2_rhs
from lodestar_tpu.crypto.bls.hash_to_curve import hash_to_g2
from lodestar_tpu.ops import fp, prep, tower as tw

from tests.crypto.rfc9380_vectors import RFC9380_G2_DST, RFC9380_G2_RO_VECTORS

from .util import rng


def _rand_fp(r):
    while True:
        v = int.from_bytes(r.bytes(48), "little")
        if v < F.P:
            return v


def _g1_noncurve_x(r):
    while True:
        x = _rand_fp(r)
        if F.fp_sqrt((x * x * x + 4) % F.P) is None:
            return x


def _g1_offsubgroup_point(r):
    """Random decompressible x: the point is on E(Fp) but essentially
    never in the r-subgroup (cofactor ~2^125)."""
    while True:
        x = _rand_fp(r)
        y = F.fp_sqrt((x * x * x + 4) % F.P)
        if y is not None:
            pt = (x, y)
            assert not C.g1_in_subgroup_order_check(pt)
            return pt


def _g2_offsubgroup_point(r):
    while True:
        x = (_rand_fp(r), _rand_fp(r))
        y = F.fp2_sqrt(g2_rhs(x))
        if y is not None:
            pt = (x, y)
            assert not C.g2_in_subgroup_order_check(pt)
            return pt


def prepare_sets_unfused(sets):
    """`bv._prepare_sets_device_arrays` over the unfused per-leg
    reference (`prep.prepare_arrays_unfused`): the same host parse, the
    same validity verdict, (pk, h, sig, ok) on size-padded arrays."""
    from lodestar_tpu.models import batch_verify as bv

    n = len(sets)
    pk_limbs, pk_sign, pk_struct, sig_limbs, sig_sign, sig_struct, lo, hi = bv._parse_host_arrays(
        sets, bv._pad_pow2(n)
    )
    pk, pk_ok, sig, sig_ok, h = prep.prepare_arrays_unfused(
        pk_limbs, pk_sign, sig_limbs, sig_sign, lo, hi
    )
    valid = pk_struct & sig_struct & np.asarray(pk_ok) & np.asarray(sig_ok)
    return pk, h, sig, bool(valid[:n].all())


def _g2_nontwist_x(r):
    while True:
        x = (_rand_fp(r), _rand_fp(r))
        if F.fp2_sqrt(g2_rhs(x)) is None:
            return x


class TestG1SubgroupOracle:
    """The new CPU-side phi-eigenvalue check vs the order-R ladder."""

    def test_fast_matches_ladder(self):
        r = rng(11)
        for k in (1, 2, 99, F.R - 1):
            p = C.g1_mul(G1_GEN, k)
            assert C.g1_in_subgroup_fast(p) is True
            assert C.g1_in_subgroup_order_check(p) is True
        for _ in range(8):
            pt = _g1_offsubgroup_point(r)
            assert C.g1_in_subgroup_fast(pt) is False
        assert C.g1_in_subgroup_fast(None) is True


class TestDeviceDecompressG1:
    def test_batch_valid_and_invalid(self):
        r = rng(21)
        pts = [C.g1_mul(G1_GEN, k) for k in (1, 5, 123456789, F.R - 2)]
        bufs = [serdes.g1_to_bytes(p) for p in pts]
        bufs.append(serdes.g1_to_bytes(_g1_offsubgroup_point(r)))  # non-subgroup
        bad = bytearray(_g1_noncurve_x(r).to_bytes(48, "big"))
        bad[0] |= 0x80
        bufs.append(bytes(bad))  # x not on curve
        bufs.append(serdes.g1_to_bytes(None))  # infinity: invalid for prep
        over = bytearray(F.P.to_bytes(48, "big"))
        over[0] |= 0x80
        bufs.append(bytes(over))  # x >= p

        arr = np.stack([np.frombuffer(b, np.uint8) for b in bufs])
        x_std, sign, ok_host = prep.parse_g1_compressed(arr)
        xm, ym, ok_dev = prep.g1_decompress_subgroup(x_std, sign)
        ok = ok_host & np.asarray(ok_dev)
        assert list(ok) == [True] * 4 + [False] * 4

        xs = [fp.int_from_limbs(v) for v in np.asarray(fp.from_mont(xm))[:4]]
        ys = [fp.int_from_limbs(v) for v in np.asarray(fp.from_mont(ym))[:4]]
        for i, p in enumerate(pts):
            assert (xs[i], ys[i]) == p

    def test_uncompressed_flag_rejected(self):
        raw = G1_GEN[0].to_bytes(48, "big")  # no compressed bit set
        arr = np.stack([np.frombuffer(raw, np.uint8)] * 8)
        _, _, ok = prep.parse_g1_compressed(arr)
        assert not ok.any()


class TestDeviceDecompressG2:
    def test_batch_valid_and_invalid(self):
        r = rng(22)
        pts = [C.g2_mul(G2_GEN, k) for k in (1, 7, 987654321)]
        bufs = [serdes.g2_to_bytes(p) for p in pts]
        bufs.append(serdes.g2_to_bytes(_g2_offsubgroup_point(r)))  # non-subgroup
        xx = _g2_nontwist_x(r)
        bad = bytearray(xx[1].to_bytes(48, "big") + xx[0].to_bytes(48, "big"))
        bad[0] |= 0x80
        bufs.append(bytes(bad))  # x not on the twist
        bufs.append(serdes.g2_to_bytes(None))  # infinity: invalid for prep
        over = bytearray(F.P.to_bytes(48, "big") + b"\x00" * 48)
        over[0] |= 0x80
        bufs.append(bytes(over))  # x1 >= p
        raw = bytearray(serdes.g2_to_bytes(pts[0]))
        raw[0] &= 0x7F
        bufs.append(bytes(raw))  # compressed flag cleared

        arr = np.stack([np.frombuffer(b, np.uint8) for b in bufs])
        x_std, sign, ok_host = prep.parse_g2_compressed(arr)
        xm, ym, ok_dev = prep.g2_decompress_subgroup(x_std, sign)
        ok = ok_host & np.asarray(ok_dev)
        assert list(ok) == [True] * 3 + [False] * 5

        gx = tw.fp2_to_ints(np.asarray(xm)[:3])
        gy = tw.fp2_to_ints(np.asarray(ym)[:3])
        for i, p in enumerate(pts):
            assert gx[i] == p[0] and gy[i] == p[1]


class TestFp2Sqrt:
    def test_squares_and_nonresidues(self):
        r = rng(23)
        inputs, expect = [], []
        for _ in range(3):
            a = (_rand_fp(r), _rand_fp(r))
            s = F.fp2_sq(a)
            inputs.append(s)
            expect.append(True)
            inputs.append(F.fp2_mul(s, F._FP2_QNR))
            expect.append(False)
        inputs += [(0, 0), (4, 0)]  # zero and a plain Fp square
        expect += [True, True]
        arr = np.asarray(tw.fp2_from_ints(inputs))
        root, ok = prep.fp2_sqrt_with_flag(arr)
        assert list(np.asarray(ok)) == expect
        roots = tw.fp2_to_ints(np.asarray(root))
        for i, e in enumerate(expect):
            if e:
                assert F.fp2_eq(F.fp2_sq(roots[i]), inputs[i])
                # oracle agreement on squareness only — the chain may find
                # the other root; consumers normalize the sign themselves
                assert F.fp2_sqrt(inputs[i]) is not None


class TestDeviceHashToG2:
    def test_matches_cpu_oracle(self):
        msgs = [b"", b"abc", b"hello world", b"\x5a" * 32, b"lodestar" * 9]
        hx, hy = prep.hash_to_g2_device(msgs)
        gx = tw.fp2_to_ints(np.asarray(hx))
        gy = tw.fp2_to_ints(np.asarray(hy))
        for i, m in enumerate(msgs):
            want = hash_to_g2(m)
            assert gx[i] == want[0], m
            assert gy[i] == want[1], m

    def test_rfc9380_g2_known_answer(self):
        msgs = [v[0] for v in RFC9380_G2_RO_VECTORS]
        hx, hy = prep.hash_to_g2_device(msgs, RFC9380_G2_DST)
        gx = tw.fp2_to_ints(np.asarray(hx))
        gy = tw.fp2_to_ints(np.asarray(hy))
        for i, (_msg, px0, px1, py0, py1) in enumerate(RFC9380_G2_RO_VECTORS):
            assert "%096x" % gx[i][0] == px0
            assert "%096x" % gx[i][1] == px1
            assert "%096x" % gy[i][0] == py0
            assert "%096x" % gy[i][1] == py1


class TestWideReduction:
    def test_mont_from_wide_matches_mod(self):
        r = rng(29)
        wides = [int.from_bytes(r.bytes(64), "big") for _ in range(8)]
        b64 = np.stack([np.frombuffer(v.to_bytes(64, "big"), np.uint8) for v in wides])
        wl = prep.be_bytes_to_limbs(b64, nlimbs=43)
        lo = wl[:, : fp.LIMBS]
        hi = np.zeros((8, fp.LIMBS), np.int32)
        hi[:, : 43 - fp.LIMBS] = wl[:, fp.LIMBS :]
        m = prep.mont_from_wide(lo, hi)
        got = [fp.int_from_limbs(x) for x in np.asarray(fp.from_mont(m))]
        assert got == [v % F.P for v in wides]


class TestFusedPrepSchedule:
    """Round-10 acceptance: the fused dispatch chains. The launch budget
    is asserted against the dispatch-site counter (the same seam the
    `lodestar_bls_prep_launches_total` metric increments), and the fused
    programs are pinned bit-exact against both the pre-fusion per-leg
    schedule and the RFC 9380 known-answer vectors."""

    def _parse_points(self, n=8):
        pk_raw = np.stack(
            [np.frombuffer(serdes.g1_to_bytes(G1_GEN), np.uint8)] * n
        )
        sig_raw = np.stack(
            [np.frombuffer(serdes.g2_to_bytes(G2_GEN), np.uint8)] * n
        )
        pk_limbs, pk_sign, pk_ok = prep.parse_g1_compressed(pk_raw)
        sig_limbs, sig_sign, sig_ok = prep.parse_g2_compressed(sig_raw)
        assert pk_ok.all() and sig_ok.all()
        return pk_limbs, pk_sign, sig_limbs, sig_sign

    def test_launch_budget_independent_of_batch_size(self):
        """`prepare_sets_device` costs exactly FUSED_PREP_LAUNCHES
        dispatches per batch — independent of the number of sets and of
        the chain lengths inside the programs (well under the <= ~12
        acceptance budget; the pre-fusion schedule paid one launch per
        leg and, on dispatch-bound backends, one per squaring)."""
        from lodestar_tpu.models import batch_verify as bv

        assert prep.FUSED_PREP_LAUNCHES <= 12
        for n in (2, 5, 8):
            sets = bv.make_synthetic_sets(n, seed=n)
            base = prep.prep_launches_total()
            assert bv.prepare_sets_device(sets) is not None
            assert prep.prep_launches_total() - base == prep.FUSED_PREP_LAUNCHES

    def test_rejection_batches_stay_on_budget(self):
        """Invalid batches keep the same fixed dispatch budget: a
        non-subgroup point is decided ON DEVICE (full schedule), a
        wrong-length encoding is a host-parse reject (zero dispatches)."""
        from lodestar_tpu.crypto.bls.api import SignatureSet
        from lodestar_tpu.models import batch_verify as bv

        sets = bv.make_synthetic_sets(3, seed=17)
        r = rng(31)
        off = _g1_offsubgroup_point(r)
        bad = list(sets)
        bad[1] = SignatureSet(
            pubkey=serdes.g1_to_bytes(off),
            message=bad[1].message,
            signature=bad[1].signature,
        )
        base = prep.prep_launches_total()
        assert bv.prepare_sets_device(bad) is None
        assert prep.prep_launches_total() - base == prep.FUSED_PREP_LAUNCHES

        short = list(sets)
        short[0] = SignatureSet(
            pubkey=short[0].pubkey, message=short[0].message, signature=b"\x00" * 95
        )
        base = prep.prep_launches_total()
        assert bv.prepare_sets_device(short) is None
        assert prep.prep_launches_total() - base == 0

    def test_fused_matches_unfused_bit_exact(self):
        """The fused stages produce limb-identical outputs to the
        pre-fusion per-leg schedule (both device paths), at
        FUSED_PREP_LAUNCHES vs UNFUSED_PREP_LAUNCHES dispatches."""
        from lodestar_tpu.models import batch_verify as bv

        sets = bv.make_synthetic_sets(5, seed=23)
        base = prep.prep_launches_total()
        fused = bv.prepare_sets_device(sets)
        assert prep.prep_launches_total() - base == prep.FUSED_PREP_LAUNCHES
        base = prep.prep_launches_total()
        *unfused, unfused_ok = prepare_sets_unfused(sets)
        assert prep.prep_launches_total() - base == prep.UNFUSED_PREP_LAUNCHES
        assert fused is not None and unfused_ok
        for leg_f, leg_u in zip(fused, unfused):
            for coord in range(2):
                ff = np.asarray(fp.from_mont(leg_f[coord]))
                uu = np.asarray(fp.from_mont(leg_u[coord][: len(sets)]))
                assert (ff == uu).all()

    def test_rfc9380_g2_known_answer_through_fused_stage(self):
        """RFC 9380 J.10.1 bit-exactness of the FUSED field stage: the
        hash leg of `prepare_arrays_fused` (one shared sqrt chain for
        the G2 root and all SSWU candidates) reproduces the vectors."""
        msgs = [v[0] for v in RFC9380_G2_RO_VECTORS]
        padded = msgs + [msgs[0]] * (8 - len(msgs))
        lo, hi = prep.hash_to_field_limbs(padded, RFC9380_G2_DST)
        pk_limbs, pk_sign, sig_limbs, sig_sign = self._parse_points(8)
        pk, pk_ok, sig, sig_ok, (hx, hy) = prep.prepare_arrays_fused(
            pk_limbs, pk_sign, sig_limbs, sig_sign, lo, hi
        )
        assert np.asarray(pk_ok).all() and np.asarray(sig_ok).all()
        gx = tw.fp2_to_ints(np.asarray(hx))
        gy = tw.fp2_to_ints(np.asarray(hy))
        for i, (_msg, px0, px1, py0, py1) in enumerate(RFC9380_G2_RO_VECTORS):
            assert "%096x" % gx[i][0] == px0
            assert "%096x" % gx[i][1] == px1
            assert "%096x" % gy[i][0] == py0
            assert "%096x" % gy[i][1] == py1

    def test_launch_counter_metric_increments_at_dispatch_site(self):
        """Satellite: `lodestar_bls_prep_launches_total` counts the same
        dispatches the process-local counter does."""
        from lodestar_tpu.metrics import create_metrics
        from lodestar_tpu.models import batch_verify as bv

        metrics = create_metrics()
        bv.configure_device_prep(metrics.bls_prep)
        try:
            sets = bv.make_synthetic_sets(4, seed=29)
            assert bv.prepare_sets_device(sets) is not None
            assert (
                metrics.bls_prep.launches._value.get() == prep.FUSED_PREP_LAUNCHES
            )
        finally:
            prep.configure_launch_counter(None)
            bv._prep_metrics = None
