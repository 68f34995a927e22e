"""The registry table (`chain/bls/pubkey_table.py`): keys deserialized
once, compressed bytes on the host, Montgomery limbs on every lane's
device with the identity in row 0, appended in place."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from lodestar_tpu import telemetry
from lodestar_tpu.chain.bls.pubkey_table import IDENTITY_ROW, LOAD_PROGRAM, PubkeyTable
from lodestar_tpu.crypto.bls import curve as C
from lodestar_tpu.crypto.bls.serdes import g1_to_bytes
from lodestar_tpu.ops import fp

POINTS = [C.g1_mul(C.G1_GEN, 11 + 7 * i) for i in range(40)]
KEYS = [g1_to_bytes(p) for p in POINTS]


def rows_match(table, device, upto: int):
    x, y = (np.asarray(a) for a in table.arrays_on(device))
    assert not x[IDENTITY_ROW].any() and not y[IDENTITY_ROW].any()
    for i in range(upto):
        assert (x[i + 1] == fp.mont_limbs_from_int(POINTS[i][0])).all(), i
        assert (y[i + 1] == fp.mont_limbs_from_int(POINTS[i][1])).all(), i
    assert not x[upto + 1 :].any() and not y[upto + 1 :].any()  # nothing behind the length


@pytest.fixture
def table():
    t = PubkeyTable()
    t.place_on([None], ["dev0"])
    return t


def test_a_load_then_appends_land_at_the_next_indices(table):
    assert table.extend(KEYS[:20], trusted=True) == 20
    assert table.extend(KEYS[20:21]) == 21  # a deposit: checked, in place
    capacity = table.arrays_on(None)[0].shape[0]
    assert table.extend(KEYS[21:30]) == 30
    assert table.arrays_on(None)[0].shape[0] == capacity  # an append is no new shape
    rows_match(table, None, 30)
    assert [table.pubkey_at(i) for i in (0, 20, 29)] == [KEYS[0], KEYS[20], KEYS[29]]
    assert table.pubkey_at(30) is None and table.pubkey_at(-1) is None
    assert table.lanes() == {"dev0": 30}


def test_the_capacity_grows_and_keeps_what_it_held(table):
    table.extend(KEYS[:10], trusted=True)
    first = table.arrays_on(None)[0].shape[0]
    for i in range(10, 40, 5):
        table.extend(KEYS[i : i + 5])
    assert table.arrays_on(None)[0].shape[0] > first
    rows_match(table, None, 40)


@pytest.mark.parametrize("indices, held", [
    ([0, 9], True), ([], True), ([10], False), ([-1], False), ([3, 2**31 - 1], False),
], ids=str)
def test_the_parse_asks_contains_because_a_gather_clamps(table, indices, held):
    table.extend(KEYS[:10], trusted=True)
    assert table.contains(np.asarray(indices, dtype=np.int64)) is held


def test_a_key_that_does_not_decode_keeps_its_index_and_is_never_valid(table):
    off_curve = b"\x80" + b"\x00" * 46 + b"\x05"
    assert table.extend([KEYS[0], off_curve, b"\xc0" + bytes(47), KEYS[1], b"short"]) == 5
    assert [table.pubkey_at(i) is not None for i in range(5)] == [True, False, False, True, False]
    assert table.contains(np.asarray([0, 3])) and not table.contains(np.asarray([0, 1]))
    x = np.asarray(table.arrays_on(None)[0])
    assert not x[2].any() and not x[3].any() and x[4].any()


def test_a_key_outside_the_subgroup_is_refused_unless_trusted(table):
    from perfbench.reference import bls as ref

    moved = ref.shift_pubkey_off_subgroup(KEYS[0], 5)
    table.extend([moved])
    assert table.pubkey_at(0) is None
    table.extend([moved], trusted=True)  # an anchor state's registry is not checked again
    assert table.pubkey_at(1) == moved


def test_the_python_decode_is_the_native_one(monkeypatch):
    from lodestar_tpu.chain.bls import pubkey_table as pt
    from lodestar_tpu.native import bls as nbls

    keys = KEYS[:6] + [b"\xc0" + bytes(47)]
    if not nbls.available():  # waits for the build
        pytest.skip("no native library here")
    native = pt._decode(keys, check_subgroup=True)
    monkeypatch.setattr(nbls, "g1_decompress_limbs_native", lambda *a: None)
    plain = pt._decode(keys, check_subgroup=True)
    assert (native[0] == plain[0]).all() and (native[1] == plain[1]).all()
    assert native[1].tolist() == [True] * 6 + [False]


def test_a_handful_of_keys_does_not_wait_for_the_librarys_build(monkeypatch):
    from lodestar_tpu.chain.bls import pubkey_table as pt
    from lodestar_tpu.native import bls as nbls

    asked = []
    monkeypatch.setattr(nbls, "ready", lambda: False)  # still compiling
    monkeypatch.setattr(nbls, "g1_decompress_limbs_native", lambda *a: asked.append(a[1]))
    xy, ok = pt._decode(KEYS[:3], check_subgroup=True)
    assert asked == [] and ok.all() and (xy[0, 0] == fp.mont_limbs_from_int(POINTS[0][0])).all()
    monkeypatch.setattr(pt, "_PYTHON_DECODE_MOST", 2)
    pt._decode(KEYS[:3], check_subgroup=True)  # a registry waits for it
    assert asked == [3]


def test_every_device_asked_for_holds_a_copy_and_a_late_one_is_filled():
    devices = jax.devices()[:3]
    t = PubkeyTable()
    t.place_on(devices[:2], ["dev0", "dev1"])
    t.extend(KEYS[:12], trusted=True)
    t.place_on(devices, ["dev0", "dev1", "dev2"])  # a lane that joins later copies a sibling's
    t.extend(KEYS[12:14])
    assert t.lanes() == {"dev0": 14, "dev1": 14, "dev2": 14}
    for d in devices:
        assert t.arrays_on(d)[0].devices() == {d}
        rows_match(t, d, 14)


def test_a_table_filled_on_the_host_alone_takes_no_lanes_afterwards():
    t = PubkeyTable()
    t.extend(KEYS[:5], trusted=True)  # the split schedule's lanes: bytes for the fallback, no limbs
    assert not t.on_device and len(t) == 5 and t.pubkey_at(4) == KEYS[4]
    with pytest.raises(RuntimeError, match="before it is filled"):
        t.place_on([None], ["dev0"])


def test_every_extend_is_a_ledger_entry_with_its_phases(table):
    telemetry.reset_launch_telemetry()
    telemetry.configure_launch_telemetry(mode="on")
    try:
        table.extend(KEYS[:8], trusted=True)
        entries = [e for e in telemetry.launch_ledger() if e["program"] == LOAD_PROGRAM]
    finally:
        telemetry.reset_launch_telemetry()
    assert len(entries) == 1 and entries[0]["lane"] == "dev0"
    assert {"table.decode", "table.limbs", "table.place"} <= set(entries[0]["phases"])


def test_the_gauge_reads_the_entries_of_each_lane(table):
    class Gauge:
        seen: dict = {}

        def labels(self, lane):
            self.lane = lane
            return self

        def set(self, n):
            self.seen[self.lane] = n

    table.entries_gauge = Gauge()
    table.extend(KEYS[:3], trusted=True)
    assert Gauge.seen == {"dev0": 3}
