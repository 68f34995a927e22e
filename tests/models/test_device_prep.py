"""Device input prep through the model layer: bytes-in → verdict-out.

Pins the acceptance criteria of the device-resident prep path
(`models/batch_verify.py` + `ops/prep.py`):

* with prep forced on, `verify_signature_sets_device` accepts raw
  compressed bytes and performs NO per-set big-int math in Python or the
  native C++ library (the host oracles are stubbed out to raise),
* the device arrays are canonically identical to the host prep output,
* invalid / non-subgroup encodings reject the batch,
* a device-prep ERROR degrades to the verified host path (same doctrine
  as BLS verify: errors degrade, verdicts are final), and the plain host
  path stays exercised with prep off.
"""

import asyncio

import numpy as np
import pytest

from lodestar_tpu.crypto.bls.api import SecretKey, SignatureSet, sign
from lodestar_tpu.models import batch_verify as bv
from lodestar_tpu.ops import fp


def make_sets(n, seed=0):
    sets = []
    for i in range(n):
        sk = SecretKey(
            int.from_bytes(bytes([seed + 1]) * 31 + bytes([i + 1]), "big") % (2**250) + 1
        )
        msg = bytes([i]) * 32
        sets.append(SignatureSet(pubkey=sk.to_pubkey(), message=msg, signature=sign(sk, msg)))
    return sets


@pytest.fixture(scope="module")
def sets4():
    return make_sets(4)


@pytest.fixture(autouse=True)
def _restore_prep_seams():
    yield
    bv._prep_metrics = None
    bv.consume_prep_info()


@pytest.fixture
def device_prep(monkeypatch):
    """Prep on the device, as an accelerator's split schedule (the single
    launch's error road) runs it: the resolver answers as it does there,
    and the tests call `_verify_sets_split`, that road's entry."""
    monkeypatch.setattr(bv, "single_launch_active", lambda: True)


class TestPrepareSetsDevice:
    def test_matches_host_prep_canonically(self, sets4):
        dev = bv.prepare_sets_device(sets4)
        host = bv.prepare_sets(sets4)
        assert dev is not None and host is not None
        for d, h in zip(dev, host):
            for coord in range(2):
                dd = np.asarray(fp.from_mont(d[coord]))
                hh = np.asarray(fp.from_mont(np.asarray(h[coord])))
                assert (dd == hh).all()

    def test_rejects_structural_garbage(self, sets4):
        bad = list(sets4)
        bad[1] = SignatureSet(
            pubkey=bad[1].pubkey, message=bad[1].message, signature=b"\x00" * 96
        )
        assert bv.prepare_sets_device(bad) is None

    def test_rejects_wrong_length_encoding(self, sets4):
        bad = list(sets4)
        bad[0] = SignatureSet(
            pubkey=bad[0].pubkey, message=bad[0].message, signature=b"\x00" * 95
        )
        assert bv.prepare_sets_device(bad) is None

    def test_rejects_infinity_pubkey(self, sets4):
        from lodestar_tpu.crypto.bls import serdes

        bad = list(sets4)
        bad[0] = SignatureSet(
            pubkey=serdes.g1_to_bytes(None), message=bad[0].message, signature=bad[0].signature
        )
        assert bv.prepare_sets_device(bad) is None


class TestVerifyWithDevicePrep:
    def test_bytes_in_verdict_out(self, sets4, device_prep):
        assert bv._verify_sets_split(sets4) is True
        info = bv.consume_prep_info()
        assert info is not None and info["layer"] == "device"

    def test_tampered_signature_rejects(self, sets4, device_prep):
        bad = list(sets4)
        other = make_sets(1, seed=9)[0]
        bad[2] = SignatureSet(
            pubkey=bad[2].pubkey, message=bad[2].message, signature=other.signature
        )
        assert bv._verify_sets_split(bad) is False

    def test_no_host_bigint_math_on_device_path(self, sets4, device_prep, monkeypatch):
        """The device-prep path must not touch the python big-int
        pipeline (hash_to_g2 / point decompression / subgroup checks) or
        the native C++ prep — stub them all to raise."""
        from lodestar_tpu.native import bls as nbls

        def _boom(*a, **k):
            raise AssertionError("host prep oracle called on the device-prep path")

        monkeypatch.setattr(nbls, "prepare_sets_native", _boom)
        monkeypatch.setattr(bv, "hash_to_g2", _boom)
        monkeypatch.setattr(bv, "g1_from_bytes", _boom)
        monkeypatch.setattr(bv, "g2_from_bytes", _boom)
        assert bv._verify_sets_split(sets4) is True

    def test_device_error_falls_back_to_host(self, sets4, device_prep, monkeypatch):
        from lodestar_tpu.metrics import create_metrics

        metrics = create_metrics()
        bv.configure_device_prep(metrics.bls_prep)

        def _boom(*a, **k):
            raise RuntimeError("injected device prep fault")

        monkeypatch.setattr(bv, "_prepare_sets_device_arrays", _boom)
        assert bv._verify_sets_split(sets4) is True
        info = bv.consume_prep_info()
        assert info is not None and info["layer"] == "host"
        assert metrics.bls_prep.fallbacks._value.get() == 1

    def test_host_path_off_an_accelerator(self, sets4):
        assert bv.verify_signature_sets_device(sets4) is True
        info = bv.consume_prep_info()
        assert info is not None and info["layer"] == "host"


class TestPoolWithDevicePrep:
    def test_pool_verdicts_both_prep_layers(self, sets4, monkeypatch):
        """The default pool on this backend (host prep), and a pool over
        the split schedule's lane with prep on the device."""
        from lodestar_tpu.chain.bls.interface import VerifySignatureOpts
        from lodestar_tpu.chain.bls.mesh import single_lane_mesh
        from lodestar_tpu.chain.bls.pool import BlsDeviceVerifierPool
        from lodestar_tpu.metrics import create_metrics
        from lodestar_tpu.ops import prep as dp

        metrics = create_metrics()
        bv.configure_device_prep(metrics.bls_prep)

        async def run(**pool_kwargs):
            pool = BlsDeviceVerifierPool(**pool_kwargs)
            ok = await pool.verify_signature_sets(
                sets4, VerifySignatureOpts(batchable=False)
            )
            await pool.close()
            return ok

        def prepped(layer):
            return metrics.bls_prep.sets.labels(layer)._value.get()

        try:
            assert asyncio.run(run()) is True
            assert (prepped("host"), prepped("device")) == (4, 0)
            monkeypatch.setattr(bv, "single_launch_active", lambda: True)
            split_lane = single_lane_mesh(
                bv._verify_sets_split, verify_prepared_fn=bv.verify_prepared
            )
            assert asyncio.run(run(mesh=split_lane)) is True
            assert (prepped("host"), prepped("device")) == (4, 4)
        finally:
            dp.configure_launch_counter(None)

    def test_bls_prep_span_recorded(self, sets4):
        """Satellite: the pool stamps a bls_prep span per traced job with
        the serving layer attribute (mirrors verifier_layer)."""
        from lodestar_tpu import tracing
        from lodestar_tpu.chain.bls.interface import VerifySignatureOpts
        from lodestar_tpu.chain.bls.pool import BlsDeviceVerifierPool

        tracer = tracing.reset()
        tracing.configure(enabled=True, slow_slot_ms=1e9)
        try:

            async def run():
                pool = BlsDeviceVerifierPool()
                with tracing.root("block_import", slot=1):
                    ok = await pool.verify_signature_sets(
                        sets4, VerifySignatureOpts(batchable=False)
                    )
                await pool.close()
                return ok

            assert asyncio.run(run()) is True
            trace = list(tracer.ring)[-1]
            prep = [s for s in trace.spans if s.name == "bls_prep"]
            assert prep, [s.name for s in trace.spans]
            attrs = prep[0].attrs or {}
            assert attrs["layer"] == "host" and attrs["sets"] == len(sets4)
        finally:
            tracing.reset()
