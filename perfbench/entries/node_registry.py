"""The node of `entries/node.py` with a registry of the deployment's size
in its verifier's pubkey table, and calls whose sets name their signers
by registry index: the reference's aggregate form of ISignatureSet
(`state-transition/src/util/signatureSets.ts:10`), as a syncing node's
block import produces them.

The verify kind makes `(pubkey, message, signature)` triples and judges
them by the plain reference; this entry gives each triple a row that
*sums to the triple's pubkey* (`perfbench/reference/registry.py`): the
row names base signers from the registry and one closing entry, appended
to the table as a deposit is, so that the launch's gather and sum
produce exactly the key the reference judges. What the registry holds:

    index < genesis_validators         the booted node's own validators, loaded by node init
    genesis_validators <= i < 2^20     P_i = P_0 + i*D, from the configuration's name
    2^20 + k                           the closing entry of the run's k-th pubkey

Which pubkeys are the run's is decided on first sight, by the
reference's own subgroup check: a key inside G1 gets a row (by sorted
pubkey within the call that shows it first: the run's first two are
single sets, the third the sync aggregate, every other an attestation);
a key outside it (the `off_subgroup` faults) travels as a byte set at
its place in the call, as a Capella block's BLSToExecutionChange sets do.
"""

from __future__ import annotations

import hashlib

import numpy as np

from perfbench.entries import node as node_entry
from perfbench.reference import registry
from perfbench.reference.bls12381 import curve as C
from perfbench.reference.bls12381.serdes import PointDecodeError, g1_from_bytes

NEEDS_CHIP = True

FALLBACK_COUNTERS = node_entry.FALLBACK_COUNTERS + ("lodestar_bls_aggregate_fallback_total",)

SINGLE_SETS = 2  # proposer and randao: one signer each, the closing entry alone


def signer_range(k: int, row_points: int) -> tuple[int, int]:
    """Least and most base signers of the run's k-th pubkey (the
    closing entry comes beside them): none for the two single sets, 481
    to 511 of a row of 512 for the sync aggregate (94% and more of the
    committee), 255 to 511 for an attestation (half its committee and
    more)."""
    if k < SINGLE_SETS:
        return 0, 0
    least = row_points - 31 if k == SINGLE_SETS else row_points // 2 - 1
    return max(1, least), row_points - 1


class RegistrySystem(node_entry.NodeSystem):
    def __init__(self, node, launch_ledger_size: int, config: dict, registry_bytes: bytes):
        super().__init__(node, launch_ledger_size)
        self.stated = config["registry"]
        self.points = registry.CompressedPoints(registry_bytes)
        self.table = node.bls.pubkey_table
        seed = int.from_bytes(hashlib.sha256(config["name"].encode()).digest()[:8], "big")
        self.rng = np.random.default_rng(seed)
        # every set's members come from its own slice of one permutation of the registry
        self.permutation = self.rng.permutation(len(self.points))
        self.rows: dict[bytes, tuple[int, ...]] = {}  # pubkey -> the indices that sum to it
        self.byte_keys: set[bytes] = set()  # pubkeys the reference's subgroup check refuses

    # -- verify seam -----------------------------------------------------------

    def _signers(self, k: int) -> list[int]:
        """The base signers of the run's k-th pubkey."""
        width = self.stated["row_points"]
        lo, hi = signer_range(k, width)
        members = self.permutation[width * k : width * (k + 1)]
        return [int(i) for i in members[: int(self.rng.integers(lo, hi + 1))]]

    def _admit(self, pubkeys: list[bytes]) -> None:
        """Give every pubkey not seen before its row or its place among
        the byte keys, and append the new rows' closing entries."""
        opened = []  # (pubkey, its base signers' keys), for the closing entries
        for pk in sorted(pubkeys):
            try:
                point = g1_from_bytes(pk)
            except PointDecodeError:
                point = None
            if point is None or not C.g1_in_subgroup(point):
                self.byte_keys.add(pk)
                continue
            k = len(self.rows)
            if k >= self.stated["closing_entries"]:
                raise RuntimeError(f"more than {k} distinct pubkeys in a run")
            signers = self._signers(k)
            data = self.points.data
            opened.append((pk, b"".join(data[48 * i : 48 * i + 48] for i in signers)))
            self.rows[pk] = tuple(signers) + (self.stated["validators"] + k,)
        if opened:
            first = self.stated["validators"] + len(self.rows) - len(opened)
            if len(self.table) != first:
                raise RuntimeError(f"the table holds {len(self.table)} entries, not {first}")
            self.table.extend(registry.closing_keys(opened))  # deposits: checked

    def verify_payload(self, triples: list[tuple[bytes, bytes, bytes]]):
        from lodestar_tpu.crypto.bls.api import IndexedSignatureSet, SignatureSet

        self._admit(list({pk for pk, _, _ in triples} - set(self.rows) - self.byte_keys))
        return [
            IndexedSignatureSet(indices=self.rows[pk], message=m, signature=s)
            if pk in self.rows
            else SignatureSet(pubkey=pk, message=m, signature=s)
            for pk, m, s in triples
        ]

    def verify_options(self, batchable: bool, priority: str):
        """Asked once, after every payload is made: what the
        configuration states of the table holds from here on."""
        want = self.stated["validators"] + self.stated["closing_entries"]
        held = self.table.lanes()
        if not held or any(entries != want for entries in held.values()):
            raise RuntimeError(f"the lanes hold {held} table entries, the configuration states {want}")
        return super().verify_options(batchable, priority)

    def fallbacks(self, counters: dict[str, float]) -> float:
        return sum(counters.get(name, 0.0) for name in FALLBACK_COUNTERS)


async def boot(config: dict) -> RegistrySystem:
    """The node as `entries/node.boot` boots it; then the registry, by
    the call node init makes for an anchor state's validators."""
    system = await node_entry.boot(config)
    node = system.node
    table = getattr(node.bls, "pubkey_table", None)
    if table is None or not table.on_device:
        raise RuntimeError("the node's verifier holds no pubkey table on its lanes")
    stated = config["registry"]
    validators = node.chain.get_head_state().validators
    own = b"".join(bytes(validators[i].pubkey) for i in range(len(validators)))
    have = len(own) // 48
    if len(table) != have:
        raise RuntimeError(f"node init loaded {len(table)} keys of the anchor state's {have}")
    p0, d = registry.seed_points(config["name"])
    generated = registry.progression_bytes(p0, d, stated["validators"] - have, start=have)
    keys = [generated[i : i + 48] for i in range(0, len(generated), 48)]
    table.extend(keys, trusted=True)  # as `node.load_pubkey_table` loads a registry
    return RegistrySystem(node, config["boot"]["launch_ledger_size"], config, own + generated)
