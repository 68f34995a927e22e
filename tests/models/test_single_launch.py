"""Single-launch verification (an accelerator's schedule): the whole chain —
decompression, subgroup checks, hash-to-G2, RLC aggregation, Miller
loop, final exponentiation — as ONE resident device program.

Pins the round-13 acceptance criteria:

* a verified batch dispatches exactly `ops.prep.SINGLE_LAUNCH_BUDGET`
  (== 1) counted device programs, independent of batch size, asserted
  against the same dispatch-site counter the launch-budget metric
  increments;
* verdicts are identical to the 3-launch fused reference, the 5-launch
  unfused reference, and the CPU oracle — on RFC 9380 J.10.1 message
  batches, seeded replay (valid and invalid), and the rejection batches
  (non-subgroup, infinity, x>=p, uncompressed flag, wrong length);
* host-parse structural rejects cost ZERO dispatches;
* an injected single-launch device fault degrades that batch to the
  split schedule — and with device prep also faulted, to host prep —
  one fallback counter tick per leg;
* the pipelined staging seam: `prepare_inputs_for_lane` stages host
  byte-parse only (no dispatches) and `verify_prepared` runs the one
  launch.

Every batch in this module is <= 8 sets, so all tests share ONE
compiled size-class of the (expensive) single-launch program.

Tests that compile or dispatch the REAL single-launch program are
marked ``slow``: its XLA compile alone is ~40 s on the CPU container
and the tier-1 suite runs at ~825 s of an 870 s budget, so every real
dispatch of the big program rides the slow lane (run with
``pytest -m slow`` / no marker filter). The zero-launch and
injected-fault degradation assertions stay tier-1, and so does the one
about who decides: `verify_signature_sets_device` follows the backend.

The CPU backend runs the other schedule, so the tests force this one at
the call: the single-launch entry points directly, and `single_on`
patches the resolver where a road asks it (the error road's device prep,
`prepare_inputs_for_lane`, the lane-pinned entry).
"""

from __future__ import annotations

import numpy as np
import pytest

from lodestar_tpu.crypto.bls import serdes
from lodestar_tpu.crypto.bls.api import SignatureSet, verify_signature_sets
from lodestar_tpu.models import batch_verify as bv
from lodestar_tpu.ops import prep as dp

from tests.crypto.rfc9380_vectors import RFC9380_G2_RO_VECTORS
from tests.ops.test_prep import (
    _g1_noncurve_x,
    _g1_offsubgroup_point,
    _g2_offsubgroup_point,
    prepare_sets_unfused,
)
from tests.ops.util import rng


@pytest.fixture
def single_on(monkeypatch):
    """The schedule an accelerator runs, on this backend: the resolver
    answers as it does there."""
    monkeypatch.setattr(bv, "single_launch_active", lambda: True)


def _split_verdict(sets, fused: bool) -> bool:
    """The split-schedule reference verdict (3-launch fused prep or the
    5-launch unfused per-leg prep, then the RLC verify dispatch)."""
    n = len(sets)
    size = bv._pad_pow2(n)
    pk, h, sig, ok = (
        bv._prepare_sets_device_arrays(sets, size) if fused else prepare_sets_unfused(sets)
    )
    if not ok:
        return False
    inputs = bv._finish_inputs(pk, h, sig, n, size)
    return bool(np.asarray(bv.device_batch_verify(*inputs)))


def _all_paths_agree(sets, oracle: bool | None = None) -> bool:
    """single == fused-3 == unfused-5 (== CPU oracle when given); returns
    the agreed verdict."""
    single = bv.verify_sets_single_launch(sets)
    fused = _split_verdict(sets, fused=True)
    unfused = _split_verdict(sets, fused=False)
    assert single == fused == unfused, (single, fused, unfused)
    if oracle is not None:
        assert single == oracle
    return single


class TestSingleLaunchBudget:
    @pytest.mark.slow
    def test_one_launch_independent_of_batch_size(self, single_on):
        """Exactly SINGLE_LAUNCH_BUDGET == 1 counted dispatches per
        verified batch, for every batch size in the shared size class."""
        assert dp.SINGLE_LAUNCH_BUDGET == 1
        for n in (2, 5, 8):
            sets = bv.make_synthetic_sets(n, seed=n + 100)
            base = dp.prep_launches_total()
            assert bv.verify_sets_single_launch(sets) is True
            assert dp.prep_launches_total() - base == dp.SINGLE_LAUNCH_BUDGET

    @pytest.mark.slow
    def test_mode_router_serves_single_launch(self, single_on):
        """`verify_signature_sets_device` (the pool/mesh backend) routes
        through the single-launch program where the resolver says so."""
        sets = bv.make_synthetic_sets(3, seed=113)
        base = dp.prep_launches_total()
        assert bv.verify_signature_sets_device(sets) is True
        assert dp.prep_launches_total() - base == 1

    def test_wrong_length_reject_is_zero_launches(self, single_on):
        sets = bv.make_synthetic_sets(3, seed=115)
        bad = list(sets)
        bad[2] = SignatureSet(
            pubkey=bad[2].pubkey, message=bad[2].message, signature=b"\x00" * 95
        )
        base = dp.prep_launches_total()
        assert bv.verify_sets_single_launch(bad) is False
        assert dp.prep_launches_total() - base == 0

    @pytest.mark.slow
    def test_device_decided_rejects_stay_on_budget(self, single_on):
        """Structural invalids decided ON device (non-subgroup, x>=p,
        infinity, uncompressed flag) still cost exactly one launch."""
        r = rng(211)
        sets = bv.make_synthetic_sets(4, seed=117)
        off_pk = serdes.g1_to_bytes(_g1_offsubgroup_point(r))
        over = bytearray((dp.P).to_bytes(48, "big"))
        over[0] |= 0x80
        noncurve = bytearray(_g1_noncurve_x(r).to_bytes(48, "big"))
        noncurve[0] |= 0x80
        for bad_pk in (
            off_pk,
            serdes.g1_to_bytes(None),  # infinity: invalid for verification
            bytes(over),  # x >= p
            bytes(noncurve),  # x not on the curve
        ):
            bad = list(sets)
            bad[1] = SignatureSet(
                pubkey=bytes(bad_pk), message=bad[1].message, signature=bad[1].signature
            )
            base = dp.prep_launches_total()
            assert bv.verify_sets_single_launch(bad) is False
            assert dp.prep_launches_total() - base == 1

        uncompressed = bytearray(sets[0].pubkey)
        uncompressed[0] &= 0x7F  # compressed flag cleared
        bad = list(sets)
        bad[0] = SignatureSet(
            pubkey=bytes(uncompressed), message=bad[0].message, signature=bad[0].signature
        )
        base = dp.prep_launches_total()
        assert bv.verify_sets_single_launch(bad) is False
        assert dp.prep_launches_total() - base == 1


@pytest.mark.slow
class TestSingleLaunchVerdicts:
    def test_rfc9380_messages_verdict_equality(self, single_on):
        """Sets whose messages are the RFC 9380 J.10.1 vector inputs,
        properly signed: the single-launch program (whose hash leg is
        the RFC-pinned fused field stage) agrees with both split
        references and the CPU oracle."""
        from lodestar_tpu.crypto.bls.api import SecretKey, sign

        sets = []
        for i, vec in enumerate(RFC9380_G2_RO_VECTORS):
            sk = SecretKey(0xC0FFEE + i * 7919)
            msg = vec[0]
            sets.append(
                SignatureSet(pubkey=sk.to_pubkey(), message=msg, signature=sign(sk, msg))
            )
        assert _all_paths_agree(sets, oracle=verify_signature_sets(sets)) is True

    def test_seeded_replay_verdict_equality(self, single_on):
        """Seeded replay batches — valid, one-bad-signature, non-subgroup
        signature — agree across single / fused-3 / unfused-5 and the
        CPU oracle on the invalid shapes (cheap: the oracle fails fast)."""
        r = rng(223)
        valid = bv.make_synthetic_sets(4, seed=131)
        assert _all_paths_agree(valid) is True

        swapped = list(valid)
        swapped[1] = SignatureSet(
            pubkey=swapped[1].pubkey,
            message=swapped[1].message,
            signature=valid[0].signature,  # valid point, wrong message
        )
        assert _all_paths_agree(swapped, oracle=verify_signature_sets(swapped)) is False

        offsub = list(valid)
        offsub[2] = SignatureSet(
            pubkey=offsub[2].pubkey,
            message=offsub[2].message,
            signature=serdes.g2_to_bytes(_g2_offsubgroup_point(r)),
        )
        assert _all_paths_agree(offsub, oracle=verify_signature_sets(offsub)) is False


def _cancelling_pair(sets, a: int, b: int):
    """Sets `a` and `b` with their signatures shifted by +D and -D: the
    plain sum of the batch's signatures is unchanged, so only distinct
    blinding coefficients tell the batch from an honest one."""
    from lodestar_tpu.crypto.bls import curve as C

    d = C.g2_mul(C.G2_GEN, 0xD15C0)
    out = list(sets)
    for i, shift in ((a, d), (b, C.g2_neg(d))):
        sig = C.g2_add(serdes.g2_from_bytes(sets[i].signature), shift)
        out[i] = SignatureSet(
            pubkey=sets[i].pubkey, message=sets[i].message, signature=serdes.g2_to_bytes(sig)
        )
    return out


@pytest.mark.slow
class TestGroupedLaunchVerdicts:
    """The multi-job program at (G = 2, slot = 8) against the oracle:
    each slot's verdict is its own job's, whatever rides beside it."""

    def test_a_fault_fails_its_own_job_only(self, single_on):
        r = rng(229)
        first = bv.make_synthetic_sets(5, seed=173)
        last = bv.make_synthetic_sets(4, seed=179)
        cancelling = _cancelling_pair(first, 1, 3)
        off_key = list(last)
        off_key[2] = SignatureSet(
            pubkey=serdes.g1_to_bytes(_g1_offsubgroup_point(r)),
            message=last[2].message,
            signature=last[2].signature,
        )
        for jobs in (
            [first, last],
            [cancelling, last],  # a cancelling pair in slot 0 only
            [first, off_key],  # an off-subgroup key in slot 1 only
            [cancelling, off_key],
        ):
            want = [verify_signature_sets(job) for job in jobs]
            base = dp.prep_launches_total()
            got = bv.verify_sets_grouped_launch(jobs)
            assert dp.prep_launches_total() - base == dp.SINGLE_LAUNCH_BUDGET
            assert got == want
        assert [verify_signature_sets(j) for j in (first, last, cancelling, off_key)] == [
            True, True, False, False
        ]

    def test_three_jobs_and_an_empty_slot(self, single_on):
        """G = 4 at slot 8, the 32-row program: the fourth slot is empty,
        fully masked, and resolves nothing."""
        jobs = [bv.make_synthetic_sets(n, seed=181 + n) for n in (3, 2, 4)]
        jobs[1] = _cancelling_pair(jobs[1], 0, 1)
        assert bv.verify_sets_grouped_launch(jobs) == [True, False, True]


class TestSingleLaunchDegradation:
    def test_device_fault_degrades_to_split_then_host(self, single_on, monkeypatch):
        """Injected single-launch fault → split schedule; with device
        prep ALSO faulted → host prep. One fallback counter tick per
        leg, verdict still True (errors degrade, verdicts are final)."""
        from lodestar_tpu.metrics import create_metrics

        metrics = create_metrics()
        bv.configure_device_prep(metrics.bls_prep)

        def boom(*a, **k):
            raise RuntimeError("injected single-launch device fault")

        monkeypatch.setattr(bv, "_single_launch_verify", boom)
        monkeypatch.setattr(bv, "_prepare_sets_device_arrays", boom)
        sets = bv.make_synthetic_sets(3, seed=137)
        try:
            assert bv.verify_sets_single_launch(sets) is True
        finally:
            dp.configure_launch_counter(None)
            bv._prep_metrics = None
            bv.consume_prep_info()
        assert metrics.bls_prep.single_launch_fallbacks._value.get() == 1
        assert metrics.bls_prep.fallbacks._value.get() == 1
        assert metrics.bls_prep.sets.labels("host")._value.get() == 3

    def test_host_parse_fault_degrades_to_split(self, single_on, monkeypatch):
        """A host-parse ERROR (not a structural reject) must degrade to
        the split schedule instead of raising out of the verify — a
        raise here would charge the serving lane's breaker and
        cross-lane-retry a deterministically poisoned batch into every
        sibling. The split path catches the same class inside
        build_device_inputs and lands on host prep."""
        from lodestar_tpu.metrics import create_metrics

        metrics = create_metrics()
        bv.configure_device_prep(metrics.bls_prep)

        def boom(*a, **k):
            raise RuntimeError("injected host-parse fault")

        monkeypatch.setattr(bv, "_parse_host_arrays", boom)
        sets = bv.make_synthetic_sets(3, seed=151)
        try:
            # the split path's device prep shares _parse_host_arrays, so
            # it degrades host-ward too: single → split → host prep
            assert bv.verify_sets_single_launch(sets) is True
        finally:
            dp.configure_launch_counter(None)
            bv._prep_metrics = None
            bv.consume_prep_info()
        assert metrics.bls_prep.single_launch_fallbacks._value.get() == 1
        assert metrics.bls_prep.fallbacks._value.get() == 1  # split leg ticked too
        assert metrics.bls_prep.sets.labels("host")._value.get() == 3

    @pytest.mark.slow  # runs the real split schedule (~4 s); the full
    # single→split→host chain above stays tier-1
    def test_device_fault_degrades_to_split_device_prep(self, single_on, monkeypatch):
        """With device prep healthy, a single-launch fault lands on the
        3-launch fused schedule (not host prep): exactly the split
        budget in extra dispatches, no prep fallback tick."""
        from lodestar_tpu.metrics import create_metrics

        metrics = create_metrics()
        bv.configure_device_prep(metrics.bls_prep)

        def flaky(*a, **k):
            raise RuntimeError("injected single-launch device fault")

        monkeypatch.setattr(bv, "_single_launch_verify", flaky)
        sets = bv.make_synthetic_sets(3, seed=139)
        try:
            base = dp.prep_launches_total()
            assert bv.verify_sets_single_launch(sets) is True
            # 1 failed single launch + the 3-launch fused prep (the RLC
            # verify dispatch is not on prep's counter)
            assert dp.prep_launches_total() - base == 1 + dp.FUSED_PREP_LAUNCHES
        finally:
            dp.configure_launch_counter(None)
            bv._prep_metrics = None
            bv.consume_prep_info()
        assert metrics.bls_prep.single_launch_fallbacks._value.get() == 1
        assert metrics.bls_prep.fallbacks._value.get() == 0

    @pytest.mark.slow  # runs the real split schedule (~4 s)
    def test_verdict_shape_anomaly_degrades(self, single_on, monkeypatch):
        """A program returning the wrong shape on EITHER output (the
        staged-jit miscompile signature) degrades to the split schedule
        instead of resolving a malformed verdict — a malformed
        batch_valid must not raise past the fallback into the lane."""
        from lodestar_tpu.metrics import create_metrics

        metrics = create_metrics()
        bv.configure_device_prep(metrics.bls_prep)
        sets = bv.make_synthetic_sets(2, seed=149)
        try:
            for anomalous in (
                lambda *a, **k: (np.zeros(3, bool), np.array(True)),  # verdict
                lambda *a, **k: (np.array(True), np.zeros(3, bool)),  # batch_valid
            ):
                monkeypatch.setattr(bv, "_single_launch_verify", anomalous)
                assert bv.verify_sets_single_launch(sets) is True
        finally:
            dp.configure_launch_counter(None)
            bv._prep_metrics = None
            bv.consume_prep_info()
        assert metrics.bls_prep.single_launch_fallbacks._value.get() == 2


class TestSingleLaunchStaging:
    @pytest.mark.slow
    def test_prepare_inputs_for_lane_stages_host_parse_only(self, single_on):
        """The pipelined prep stage under the single launch is byte
        work only (zero dispatches); verify_prepared runs the ONE
        launch — host parse of batch k+1 can overlap the launch of k."""
        sets = bv.make_synthetic_sets(3, seed=151)
        base = dp.prep_launches_total()
        staged = bv.prepare_inputs_for_lane(sets)
        assert isinstance(staged, bv.SingleLaunchInputs)
        assert dp.prep_launches_total() - base == 0
        assert bv.verify_prepared(staged) is True
        assert dp.prep_launches_total() - base == 1

    def test_staged_structural_reject_is_not_a_launch(self, single_on):
        sets = bv.make_synthetic_sets(2, seed=157)
        bad = [
            SignatureSet(pubkey=b"\x00" * 47, message=s.message, signature=s.signature)
            for s in sets
        ]
        base = dp.prep_launches_total()
        assert bv.prepare_inputs_for_lane(bad) is None
        assert dp.prep_launches_total() - base == 0

    @pytest.mark.slow
    def test_lane_pinned_verify_fn_is_the_one_launch_road(self, single_on):
        """`make_lane_verify_fn` serves the one-launch road pinned to a
        device (the mesh lane seam)."""
        fn = bv.make_lane_verify_fn(0)
        sets = bv.make_synthetic_sets(2, seed=163)
        base = dp.prep_launches_total()
        assert fn(sets) is True
        assert dp.prep_launches_total() - base == 1


@pytest.mark.parametrize("accelerator", [True, False], ids=["accelerator", "cpu"])
def test_the_device_entry_follows_the_backend(monkeypatch, accelerator):
    """Which schedule `verify_signature_sets_device` runs is what the
    backend is (the Pallas backend live or not), asked where the verdict
    is made and settable nowhere: the single launch on an accelerator,
    the split schedule otherwise."""
    from lodestar_tpu.ops import fp_pallas

    went = []
    monkeypatch.setattr(fp_pallas, "use_pallas", lambda: accelerator)
    monkeypatch.setattr(bv, "verify_sets_single_launch", lambda sets, device=None, table=None: went.append("single") or True)
    monkeypatch.setattr(bv, "_verify_sets_split", lambda sets, device=None, table=None: went.append("split") or True)
    assert bv.single_launch_active() is accelerator
    assert bv.verify_signature_sets_device(bv.make_synthetic_sets(2, seed=167)) is True
    assert went == ["single" if accelerator else "split"]
