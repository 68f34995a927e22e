"""A node whose verifier sums signers from its pubkey table: node init
loads the anchor state's registry into it, block import hands the
verifier sets that name their signers by index and sums no pubkey on
the host, and a block's new validators are appended."""

from __future__ import annotations

import asyncio

import pytest

from lodestar_tpu import params
from lodestar_tpu.chain.bls import BlsSingleThreadVerifier, BlsVerifierMock
from lodestar_tpu.chain.bls.pubkey_table import PubkeyTable
from lodestar_tpu.chain.chain import BeaconChain
from lodestar_tpu.chain.produce_block import produce_block
from lodestar_tpu.crypto.bls.api import IndexedSignatureSet, SignatureSet
from lodestar_tpu.db import MemoryDbController
from lodestar_tpu.node import load_pubkey_table
from lodestar_tpu.state_transition import process_slots
from lodestar_tpu.state_transition import signature_sets as ss
from lodestar_tpu.state_transition.genesis import create_interop_genesis_state
from lodestar_tpu.state_transition.util import get_block_root
from lodestar_tpu.types import ssz_types

N = 32


@pytest.fixture(scope="module", autouse=True)
def minimal_preset():
    prev = params.active_preset()
    params.set_active_preset("minimal")
    yield params.active_preset()
    params.set_active_preset(prev)


class TableVerifier(BlsVerifierMock):
    """What the chain sees of a device pool whose lanes hold the table."""

    takes_indexed_sets = True

    def __init__(self):
        super().__init__(True)
        self.pubkey_table = PubkeyTable()
        self.pubkey_table.place_on([None], ["dev0"])
        self.seen: list = []

    async def verify_signature_sets(self, sets, opts=None):
        self.seen.append(list(sets))
        return True


def _block_with_an_attestation(chain, p, genesis):
    """A slot-2 block that packs one attestation of a whole committee
    (signatures are the mock verifier's to wave through)."""
    t = ssz_types(p)
    work = genesis.copy()
    ctx = process_slots(work, 1, p)
    committee = ctx.get_beacon_committee(1, 0)
    att = t.Attestation.default()
    att.data.slot = 1
    att.data.index = 0
    att.data.beacon_block_root = chain.head_root
    att.data.target.epoch = 0
    att.data.target.root = get_block_root(work, 0, p)
    att.data.source = work.current_justified_checkpoint
    att.aggregation_bits = [True] * len(committee)
    chain.aggregated_attestation_pool.add(att, t.AttestationData.hash_tree_root(att.data))
    signed = t.phase0.SignedBeaconBlock.default()
    signed.message = produce_block(chain, slot=2, randao_reveal=bytes(96))
    assert len(signed.message.body.attestations) == 1
    return signed, sorted(int(i) for i in committee)


def _chain(genesis, verifier):
    return BeaconChain(anchor_state=genesis, bls_verifier=verifier, db=MemoryDbController(), current_slot=2)


def test_node_init_loads_the_anchor_states_registry_where_the_lanes_hold_the_table(minimal_preset):
    genesis = create_interop_genesis_state(N, p=minimal_preset)
    bls = TableVerifier()
    load_pubkey_table(bls, genesis)
    assert len(bls.pubkey_table) == N
    assert bls.pubkey_table.pubkey_at(7) == bytes(genesis.validators[7].pubkey)
    cpu = BlsSingleThreadVerifier()
    load_pubkey_table(cpu, genesis)  # a CPU node has no table and answers as ever
    assert not hasattr(cpu, "pubkey_table")


def test_block_import_names_signers_by_index_and_sums_nothing_on_the_host(minimal_preset, monkeypatch):
    p = minimal_preset
    genesis = create_interop_genesis_state(N, p=p)
    bls = TableVerifier()
    load_pubkey_table(bls, genesis)
    chain = _chain(genesis, bls)
    assert chain.indexed_sets
    signed, committee = _block_with_an_attestation(chain, p, genesis)

    def never(pks):
        raise AssertionError("aggregate_pubkeys on a device node's import path")

    monkeypatch.setattr(ss, "aggregate_pubkeys", never)
    asyncio.run(chain.process_block(signed))
    (sets,) = bls.seen
    assert [type(s) for s in sets] == [IndexedSignatureSet] * 3  # proposer, randao, the attestation
    assert sets[0].indices == sets[1].indices == (int(signed.message.proposer_index),)
    assert sorted(sets[2].indices) == committee and len(committee) > 1


def test_a_node_without_the_table_gets_the_sets_it_got_before(minimal_preset):
    p = minimal_preset
    genesis = create_interop_genesis_state(N, p=p)
    seen = []

    class Recording(BlsVerifierMock):
        async def verify_signature_sets(self, sets, opts=None):
            seen.append(list(sets))
            return True

    chain = _chain(genesis, Recording(True))
    assert not chain.indexed_sets
    signed, _ = _block_with_an_attestation(chain, p, genesis)
    asyncio.run(chain.process_block(signed))
    assert [type(s) for s in seen[0]] == [SignatureSet] * 3


def test_validators_a_block_adds_are_appended_to_the_table(minimal_preset):
    """The table is behind the registry (here: loaded short of it, as
    after a block with deposits): the import appends the rest, checked."""
    p = minimal_preset
    genesis = create_interop_genesis_state(N, p=p)
    bls = TableVerifier()
    bls.pubkey_table.extend([bytes(genesis.validators[i].pubkey) for i in range(N - 3)], trusted=True)
    chain = _chain(genesis, bls)
    signed, _ = _block_with_an_attestation(chain, p, genesis)
    asyncio.run(chain.process_block(signed))
    assert len(bls.pubkey_table) == N
    assert bls.pubkey_table.pubkey_at(N - 1) == bytes(genesis.validators[N - 1].pubkey)


def test_a_sync_committees_members_are_found_by_pubkey_once(minimal_preset):
    genesis = create_interop_genesis_state(N, p=minimal_preset)
    chain = _chain(genesis, TableVerifier())
    pks = [bytes(genesis.validators[i].pubkey) for i in (5, 0, 5, 31)]
    assert chain.registry_indices(genesis, pks) == (5, 0, 5, 31)
    assert len(chain._pubkey2index) == N
