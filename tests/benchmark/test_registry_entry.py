"""`mainnet-aggregate-sets-1m` / `range-sync-aggregate-sets`: the plain
reference of an indexed registry, the entry's construction at a
64-entry registry (a row sums to its triple's pubkey, so the kind's
reference judges the row), the configuration's stated sizes, and the
four per-layer readers the cell brings, on contexts made by hand.
(The cell's rehearsals, sound and with a guarantee left out, come from
`test_rehearsal.py`'s parametrisation over the manifest's cells.)"""

from __future__ import annotations

import random
from types import SimpleNamespace

import pytest

from perfbench import manifest, trace
from perfbench.reference import bls as ref
from perfbench.reference import registry
from perfbench.reference.bls12381 import curve as C
from perfbench.reference.bls12381.serdes import g1_from_bytes, g1_to_bytes

from .test_manifest import M, check_a_cell_resolves

CELL = "range-sync-aggregate-sets"
CONFIG = "mainnet-aggregate-sets-1m"
DEV = "/device:TPU:0"
MS = 1_000_000


def reader(name: str):
    return manifest.load_module("metrics", name).read


# -- the plain reference ---------------------------------------------------------------------


def test_a_progression_is_p0_plus_i_times_d():
    p0, d = registry.seed_points("a-name")
    assert (p0, d) == registry.seed_points("a-name") != registry.seed_points("another")
    assert C.g1_in_subgroup(p0) and C.g1_in_subgroup(d)
    points = registry.progression(p0, d, 6, start=3)
    assert points == [C.g1_add(p0, C.g1_mul(d, i)) for i in range(3, 9)]
    assert len(set(points)) == 6


@pytest.mark.parametrize("indices", [[], [2], [0, 1, 2, 3], [4, 4, 1], [5, 0, 5, 0, 5]], ids=str)
def test_a_row_sum_counts_an_index_as_often_as_it_is_named_and_a_closing_point_closes_it(indices):
    p0, d = registry.seed_points("rows")
    points = registry.progression(p0, d, 6)
    want = None
    for i in indices:
        want = C.g1_add(want, points[i])
    assert registry.row_sum(points, indices) == want
    # the closed form of a progression's sum: n*P_0 + (sum of i)*D
    assert want == C.g1_add(C.g1_mul(p0, len(indices)), C.g1_mul(d, sum(indices)))
    pk = C.g1_mul(C.G1_GEN, 77)
    closing = registry.closing_point(pk, points, indices)
    assert C.g1_add(registry.row_sum(points, indices), closing) == pk


def test_a_cancelling_pair_sums_to_the_identity():
    p0, d = registry.seed_points("rows")
    points = registry.progression(p0, d, 2) + [C.g1_neg(p0)]
    assert registry.row_sum(points, [0, 2]) is None
    assert registry.row_sum(points, [0, 1, 2]) == points[1]


def test_the_worker_processes_make_the_progression_the_plain_loop_makes():
    p0, d = registry.seed_points("workers")
    plain = b"".join(g1_to_bytes(p) for p in registry.progression(p0, d, 2100, start=64))
    assert registry.progression_bytes(p0, d, 2100, start=64, workers=2) == plain
    assert registry.progression_bytes(p0, d, 5, start=64) == plain[: 5 * 48]  # too few for a worker
    points = registry.CompressedPoints(plain)
    assert len(points) == 2100 and points[7] == g1_from_bytes(plain[7 * 48 : 8 * 48])
    with pytest.raises(IndexError):
        points[2100]


# -- the entry at a 64-entry registry -----------------------------------------------------------

STATED = {"validators": 64, "closing_entries": 6, "row_points": 8, "bytes_per_point": 264}


@pytest.fixture(scope="module")
def triples():
    rng = random.Random(2**31 + 5)
    scalars = [rng.randrange(1, ref.F.R) for _ in range(6)]
    messages = [rng.randbytes(32) for _ in range(6)]
    return [(ref.pubkey(s), m, ref.sign(s, m)) for s, m in zip(scalars, messages)]


@pytest.fixture
def system():
    from lodestar_tpu.chain.bls.pubkey_table import PubkeyTable

    entry = manifest.load_module("entries", "node_registry")
    table = PubkeyTable()
    table.place_on([None], ["dev0"])
    p0, d = registry.seed_points(CONFIG)
    keys = registry.progression_bytes(p0, d, 64)
    table.extend([keys[i : i + 48] for i in range(0, len(keys), 48)], trusted=True)
    node = SimpleNamespace(bls=SimpleNamespace(pubkey_table=table), device_runtime={"verifier": "device"})
    return entry.RegistrySystem(node, 256, {"name": CONFIG, "registry": STATED}, keys), entry, table


def test_every_row_sums_to_its_triples_pubkey(system, triples):
    from lodestar_tpu.crypto.bls.api import IndexedSignatureSet, aggregate_pubkeys, verify_signature_sets

    system, entry, table = system
    payload = system.verify_payload(triples)
    assert len(table) == 64 + 6 and table.lanes() == {"dev0": 70}
    assert [type(s) for s in payload] == [IndexedSignatureSet] * 6
    by_pubkey = sorted(range(6), key=lambda i: triples[i][0])
    for k, i in enumerate(by_pubkey):
        (pk, m, sig), row = triples[i], payload[i]
        assert (row.message, row.signature) == (m, sig)  # the call's order is kept
        assert row.indices[-1] == 64 + k  # its closing entry, appended as a deposit is
        assert all(0 <= j < 64 for j in row.indices[:-1])
        lo, hi = entry.signer_range(k, STATED["row_points"])
        assert lo <= len(row.indices) - 1 <= hi
        assert aggregate_pubkeys([table.pubkey_at(j) for j in row.indices]) == pk  # the program's own sum
        assert registry.row_sum(system.points, row.indices[:-1]) == C.g1_add(
            g1_from_bytes(pk), C.g1_neg(g1_from_bytes(table.pubkey_at(64 + k)))
        )
    singles = [payload[i] for i in by_pubkey[:2]]
    assert all(len(s.indices) == 1 for s in singles)  # proposer and randao
    members = [set(payload[i].indices[:-1]) for i in by_pubkey[2:]]
    assert all(not a & b for n, a in enumerate(members) for b in members[n + 1 :])  # a slice each
    assert verify_signature_sets(payload, table.pubkey_at)  # and the oracle takes the rows for the triples
    system.verify_options(False, "RANGE_SYNC")  # every lane holds what the configuration states


@pytest.mark.parametrize("k, width, want", [(0, 512, (0, 0)), (1, 512, (0, 0)), (2, 512, (481, 511)),
                                             (3, 512, (255, 511)), (130, 512, (255, 511)), (2, 8, (1, 7)),
                                             (5, 8, (3, 7))])
def test_signers_a_set_are_the_configurations(k, width, want):
    assert manifest.load_module("entries", "node_registry").signer_range(k, width) == want


def test_a_replay_reorders_and_an_off_subgroup_key_travels_as_bytes(system, triples):
    from lodestar_tpu.crypto.bls.api import IndexedSignatureSet, SignatureSet

    system, _, table = system
    first = system.verify_payload(triples)
    moved = ref.shift_pubkey_off_subgroup(triples[3][0], 9)
    replay = [triples[4], (moved, triples[3][1], triples[3][2]), triples[0]]
    again = system.verify_payload(replay)
    assert len(table) == 70  # nothing new to append
    assert again[0] == first[4] and again[2] == first[0]
    assert again[1] == SignatureSet(moved, triples[3][1], triples[3][2])
    assert isinstance(again[0], IndexedSignatureSet)


def test_a_key_first_seen_in_a_later_call_still_gets_its_row(system, triples):
    system, _, table = system
    system.verify_payload(triples[:4])
    assert len(table) == 68
    later = system.verify_payload(triples)
    assert len(table) == 70 and {s.indices[-1] for s in later} == set(range(64, 70))
    with pytest.raises(RuntimeError):
        system.verify_payload([(ref.pubkey(5), b"m" * 32, ref.sign(5, b"m" * 32))])  # a seventh pubkey


def test_the_lanes_are_held_to_the_entries_the_configuration_states(system, triples):
    system, _, _ = system
    system.verify_payload(triples[:3])
    with pytest.raises(RuntimeError, match="table entries"):
        system.verify_options(False, "RANGE_SYNC")


def test_the_aggregate_fallback_is_a_fallback_of_this_entry(system):
    system, entry, _ = system
    node_entry = manifest.load_module("entries", "node")
    assert set(entry.FALLBACK_COUNTERS) == set(node_entry.FALLBACK_COUNTERS) | {"lodestar_bls_aggregate_fallback_total"}
    assert system.fallbacks({"lodestar_bls_aggregate_fallback_total": 2.0, "other": 5.0}) == 2.0
    assert entry.NEEDS_CHIP


# -- the configuration and the cell ------------------------------------------------------------------


def test_the_cell_resolves_and_states_the_deployments_sizes():
    check_a_cell_resolves(M, CELL)
    cell = manifest.load_cell(CELL)
    assert (cell.config["kind"], cell.config["entry"], cell.config["reduced"]) == ("verify", "node_registry", [])
    assert cell.config["registry"] == {"validators": 2**20, "closing_entries": 131, "row_points": 512,
                                       "bytes_per_point": 2 * 33 * 4}
    assert cell.traffic["call"] == {"sets": 131, "batchable": False, "priority": "RANGE_SYNC"}
    assert cell.config["registry"]["closing_entries"] == cell.traffic["call"]["sets"]
    control = manifest.load_cell("node-range-sync")
    assert {k: v for k, v in cell.traffic.items() if k != "why"} == {k: v for k, v in control.traffic.items() if k != "why"}
    assert cell.spec == control.spec and cell.config["pool"] == control.config["pool"]
    assert "registry" in cell.config["boot"]["departs"]
    from lodestar_tpu.models import batch_verify as bv

    assert cell.config["registry"]["row_points"] == bv.AGGREGATE_ROW_POINTS


def test_the_cell_reports_its_controls_rows_and_its_own_four():
    cell, control = manifest.load_cell(CELL), manifest.load_cell("node-range-sync")
    own = {"stage_device_ms.aggregate", "aggregate_points_per_launch", "aggregate_gather_hbm_share",
           "pubkey_table_load_s"}
    names = {m["name"] for m in cell.per_layer}
    assert names == ({m["name"] for m in control.per_layer} - {"launch_row_fill_share"}) | own
    for m in M["per_layer"]:
        if m["name"] in own:
            assert m["workloads"] == [CELL]
    assert {m["name"] for m in cell.end_to_end} == {"sigs_per_s", "setup_s"}


# -- the four readers ----------------------------------------------------------------------------------


def hlo(name: str, result: str, *operands: str) -> str:
    return f"%{name} = {result} fusion({', '.join(f'{o} %p{i}' for i, o in enumerate(operands))}), kind=kLoop"


def launch_by_hand() -> dict:
    """One whole launch: 30 ms of gathers (two, 15 ms each, 288 x 512 rows
    of 33 limbs out of a 2^20-row table), 20 ms of sums under the same
    stage, 50 ms of another stage."""
    table, idx, out = "s32[1064961,33]{1,0}", "s32[512,288]{1,0}", "s32[512,288,33]{2,1,0}"
    gx = hlo("gather_fusion.1", out, table, idx)
    gy = hlo("gather_fusion.2", out, table, idx)
    add = hlo("fusion.3", "s32[256,288,33]{2,1,0}", "s32[256,288,33]{2,1,0}", "s32[256,288,33]{2,1,0}")
    other = hlo("fusion.4", "s32[288,33]{1,0}", "s32[288,33]{1,0}")
    t = 100 * MS
    ops = [[gx, t, 15 * MS], [gy, t + 15 * MS, 15 * MS], [add, t + 30 * MS, 20 * MS], [other, t + 50 * MS, 50 * MS]]
    scopes = {gx: "jit(v)/bls.aggregate/gather/gather:", gy: "jit(v)/bls.aggregate/gather/gather:",
              add: "jit(v)/bls.aggregate/sum/jit(sum_affine_g1)/mul:", other: "jit(v)/bls.miller/mul:"}
    return {"devices": {DEV: {"ops": ops, "modules": [["jit__grouped_launch_verify(7)", t, 100 * MS]],
                              "scopes": scopes}},
            "host": {"executor/2": [["bls_lane_verify", t - 5 * MS, 110 * MS]]}}


def traced_ctx(recorded: dict) -> dict:
    return {"trace": trace.Reduced.from_events(recorded, window_s=0.5), "peaks": {"hbm_bytes_per_s": 819e9}}


def test_the_aggregate_stage_row_reads_the_gather_and_the_sum():
    assert reader("stage_device_ms.aggregate")(traced_ctx(launch_by_hand())) == pytest.approx(50.0)
    assert reader("stage_device_ms.aggregate")({"trace": None}) is None


def test_the_gathers_share_counts_rows_moved_not_the_table():
    read = reader("aggregate_gather_hbm_share")
    rows = 512 * 288 * 33 * 4  # a result; its table operand counts for as much and no more
    want = 100.0 * 2 * (2 * rows + 512 * 288 * 4) / 819e9 / 0.030
    got = read(traced_ctx(launch_by_hand()))
    assert got == pytest.approx(want) and 0 < got <= 100
    assert read({"trace": None}) is None
    no_stage = launch_by_hand()
    no_stage["devices"][DEV]["scopes"] = {k: "jit(v)/bls.miller/mul:" for k in no_stage["devices"][DEV]["scopes"]}
    assert read(traced_ctx(no_stage)) is None  # the parent: no such scope, nothing reported
    module = manifest.load_module("metrics", "aggregate_gather_hbm_share")
    assert module.moved_bytes("%copy.1 = s32[8,33]{1,0} copy(s32[8,33]{1,0} %a)") == 2 * 8 * 33 * 4
    assert module.moved_bytes("%nothing") == 0


def ledger_ctx(**counters) -> dict:
    launch = {"program": "bls_lane_verify", "compile": False, "seconds": 0.16, "size_class": 288}
    load = {"program": "bls_pubkey_table_load", "compile": True, "seconds": 40.0}
    append = {"program": "bls_pubkey_table_load", "compile": False, "seconds": 0.25}
    return {"ledger": [launch] * 4 + [dict(launch, compile=True)], "all_ledger": [load, append, launch],
            "counters_before": {k: 10.0 for k in counters},
            "counters_after": {k: 10.0 + v for k, v in counters.items()}}


def test_points_a_launch_are_the_signers_named_over_the_steady_launches():
    read = reader("aggregate_points_per_launch")
    assert read(ledger_ctx(**{"pool.aggregate_points_started": 4 * 110_000.0})) == pytest.approx(110_000.0)
    assert read(ledger_ctx()) is None  # the parent counts no such thing
    assert read(dict(ledger_ctx(**{"pool.aggregate_points_started": 5.0}), ledger=[])) is None


def test_the_tables_load_is_the_wall_of_every_extend():
    read = reader("pubkey_table_load_s")
    assert read(ledger_ctx()) == pytest.approx(40.25)
    assert read({"all_ledger": [{"program": "bls_lane_verify", "seconds": 1.0}]}) is None


def test_closing_keys_over_workers_are_the_plain_closing_points():
    p0, d = registry.seed_points("closing")
    points = registry.progression(p0, d, 12)
    rows = []
    for k in range(9):
        signers = list(range(k % 4, k % 4 + 1 + k))[:8]
        rows.append((g1_to_bytes(C.g1_mul(C.G1_GEN, 100 + k)), b"".join(g1_to_bytes(points[i]) for i in signers), signers))
    want = [g1_to_bytes(registry.closing_point(g1_from_bytes(pk), points, signers)) for pk, _, signers in rows]
    assert registry.closing_keys([(pk, keys) for pk, keys, _ in rows], workers=2) == want
    assert registry.closing_keys([(pk, keys) for pk, keys, _ in rows[:2]]) == want[:2]  # too few for a worker
    assert registry.closing_keys([]) == []
    assert registry.closing_keys([(rows[0][0], b"")]) == [rows[0][0]]  # no base signer: the key itself


@pytest.mark.parametrize("n, start", [(1, 0), (2, 5), (511, 0), (512, 64), (513, 64), (1300, 7)])
def test_the_progression_in_blocks_is_the_plain_progression(n, start):
    p0, d = registry.seed_points("blocks")
    assert registry.progression_in_blocks(p0, d, n, start) == registry.progression(p0, d, n, start)


def test_adding_to_each_takes_the_plain_addition_where_points_share_an_x():
    p0, d = registry.seed_points("blocks")
    offsets = [d, p0, C.g1_neg(p0), C.g1_add(d, d)]  # p0 + p0 doubles, p0 - p0 is the identity
    assert registry.add_to_each(p0, offsets) == [C.g1_add(p0, o) for o in offsets]
    assert registry.add_to_each(p0, []) == []
