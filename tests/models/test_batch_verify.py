"""End-to-end tests: device batch verification vs the CPU oracle.

Mirrors the reference's worker batch-verify semantics tests
(`packages/beacon-node/test/perf/bls/bls.test.ts`,
`multithread/worker.ts:52-96`): valid batches accept, any tampered set
rejects the whole batch, structural garbage fails closed.
"""

import numpy as np
import pytest

from lodestar_tpu.crypto.bls.api import (
    SecretKey,
    SignatureSet,
    sign,
    verify_signature_sets,
)
from lodestar_tpu.models import verify_signature_sets_device


def make_sets(n, seed=0):
    sets = []
    for i in range(n):
        sk = SecretKey(int.from_bytes(bytes([seed + 1]) * 31 + bytes([i + 1]), "big") % (2**250) + 1)
        msg = bytes([i]) * 32
        sets.append(SignatureSet(pubkey=sk.to_pubkey(), message=msg, signature=sign(sk, msg)))
    return sets


@pytest.fixture(scope="module")
def sets4():
    return make_sets(4)


class TestDeviceBatchVerify:
    def test_valid_batch_accepts(self, sets4):
        assert verify_signature_sets_device(sets4) is True
        # oracle agrees
        assert verify_signature_sets(sets4) is True

    def test_tampered_signature_rejects(self, sets4):
        bad = list(sets4)
        other = make_sets(1, seed=7)[0]
        bad[2] = SignatureSet(
            pubkey=bad[2].pubkey, message=bad[2].message, signature=other.signature
        )
        assert verify_signature_sets_device(bad) is False
        assert verify_signature_sets(bad) is False

    def test_swapped_messages_reject(self, sets4):
        bad = list(sets4)
        bad[0] = SignatureSet(
            pubkey=bad[0].pubkey, message=bad[1].message, signature=bad[0].signature
        )
        assert verify_signature_sets_device(bad) is False

    def test_single_set(self):
        sets = make_sets(1, seed=3)
        assert verify_signature_sets_device(sets) is True

    def test_empty_fails(self):
        assert verify_signature_sets_device([]) is False

    def test_garbage_pubkey_fails_closed(self, sets4):
        bad = list(sets4)
        bad[1] = SignatureSet(pubkey=b"\x8a" + b"\x00" * 47, message=bad[1].message,
                              signature=bad[1].signature)
        assert verify_signature_sets_device(bad) is False

    def test_infinity_signature_rejected(self, sets4):
        bad = list(sets4)
        bad[0] = SignatureSet(
            pubkey=bad[0].pubkey,
            message=bad[0].message,
            signature=b"\xc0" + b"\x00" * 95,
        )
        assert verify_signature_sets_device(bad) is False

    def test_nonpow2_batch_padding(self):
        # 5 sets -> padded to 8 internally; must still verify
        sets = make_sets(5, seed=9)
        assert verify_signature_sets_device(sets) is True


class TestShardedBatchVerify:
    """Data-parallel verification over the 8-device virtual CPU mesh —
    the multichip design the driver's dryrun validates (SURVEY §2c/§2d:
    shard the 128-set job, all_gather the pairing partials over ICI)."""

    @pytest.fixture(scope="class")
    def mesh(self):
        import jax
        from jax.sharding import Mesh

        devs = np.asarray(jax.devices("cpu")[:8])
        return Mesh(devs, ("data",))

    def test_sharded_valid_batch(self, mesh, sets4):
        from lodestar_tpu.models import verify_signature_sets_sharded

        sets = sets4 + make_sets(4, seed=21)
        assert verify_signature_sets_sharded(sets, mesh) is True

    def test_sharded_tampered_rejects(self, mesh, sets4):
        from lodestar_tpu.models import verify_signature_sets_sharded

        sets = sets4 + make_sets(4, seed=22)
        other = make_sets(1, seed=23)[0]
        sets[5] = SignatureSet(
            pubkey=sets[5].pubkey, message=sets[5].message, signature=other.signature
        )
        assert verify_signature_sets_sharded(sets, mesh) is False
