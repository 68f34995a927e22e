"""Launch telemetry (lodestar_tpu/telemetry.py): ledger determinism and
bounds, first-call compile detection per (program, size class), mode
semantics, the metric sink, and the counted dispatch seams actually
landing in the histogram — fused prep (3-launch schedule), the
single-launch verification program (exactly one record per batch), HTR
per-level dispatches, and mesh lane launches."""

from __future__ import annotations

import numpy as np
import pytest

from lodestar_tpu import telemetry


@pytest.fixture
def tel():
    telemetry.reset_launch_telemetry()
    telemetry.configure_launch_telemetry(mode="on")
    yield telemetry
    telemetry.reset_launch_telemetry()


class _Probe:
    """DeviceLaunchMetrics shape-twin recording every observation."""

    class _Fam:
        def __init__(self):
            self.events = []

        def labels(self, *a):
            self._labels = a
            return self

        def observe(self, v):
            self.events.append(("observe", self._labels, v))

        def inc(self, amount=1):
            self.events.append(("inc", getattr(self, "_labels", ()), amount))
            self._labels = ()

    def __init__(self):
        self.launch_seconds = self._Fam()
        self.compile_seconds = self._Fam()
        self.compile_hits = self._Fam()
        self.compile_misses = self._Fam()


# -- ledger ---------------------------------------------------------------------


def test_ledger_is_bounded(tel):
    tel.configure_launch_telemetry(ledger_size=16)
    for i in range(100):
        tel.record_launch("prog", 8, 0.001)
    entries = tel.launch_ledger()
    assert len(entries) == 16
    # the ledger keeps the NEWEST entries; cumulative counts keep going
    assert [e["seq"] for e in entries] == list(range(85, 101))
    assert tel.launch_totals()["launches"] == 100


def test_ledger_deterministic_order_and_fields(tel):
    a = tel.record_launch("field_stage", 8, 0.010)
    b = tel.record_launch("field_stage", 8, 0.002, lane="dev1")
    c = tel.record_launch("hash_finish", 16, 0.020)
    d = tel.record_launch("field_stage", 8, 0.002, lane="dev1")
    assert (a["seq"], b["seq"], c["seq"], d["seq"]) == (1, 2, 3, 4)
    entries = tel.launch_ledger()
    assert [e["program"] for e in entries] == ["field_stage", "field_stage", "hash_finish", "field_stage"]
    assert [e["size_class"] for e in entries] == [8, 8, 16, 8]
    assert [e["lane"] for e in entries] == [None, "dev1", None, "dev1"]
    # a lane's first call of a key lowers and compiles (or loads) for its own chip
    assert [e["compile"] for e in entries] == [True, True, True, False]
    # entries are copies: mutating a returned dict can't corrupt the ledger
    entries[0]["program"] = "tampered"
    assert tel.launch_ledger()[0]["program"] == "field_stage"


def test_launch_ledger_count_slicing(tel):
    for i in range(5):
        tel.record_launch("p", 8, 0.001)
    assert [e["seq"] for e in tel.launch_ledger(2)] == [4, 5]
    assert tel.launch_ledger(0) == []


# -- compile detection ----------------------------------------------------------


def test_compile_hit_miss_detection_across_size_classes(tel):
    probe = _Probe()
    tel.configure_launch_telemetry(metrics=probe)
    tel.record_launch("prog", 8, 1.5)  # first (prog, 8): miss
    tel.record_launch("prog", 8, 0.01)  # hit
    tel.record_launch("prog", 16, 2.0)  # new size class: miss again
    tel.record_launch("other", 8, 0.5)  # new program: miss
    tel.record_launch("other", 8, 0.01)  # hit
    misses = [e for e in probe.compile_misses.events]
    hits = [e for e in probe.compile_hits.events]
    assert [m[1] for m in misses] == [("prog",), ("prog",), ("other",)]
    assert [h[1] for h in hits] == [("prog",), ("other",)]
    # compile seconds accumulate ONLY first-call wall time
    assert sum(e[2] for e in probe.compile_seconds.events) == pytest.approx(4.0)
    totals = tel.launch_totals()
    assert totals["compiles"] == 3 and totals["distinct_keys"] == 3


def test_slow_slot_launches_compact_view(tel):
    tel.record_launch("field_stage", 8, 0.0105)
    tel.record_launch("merkle_level", 32, 0.002, lane="dev2")
    view = tel.slow_slot_launches()
    assert view["launches_total"] == 2 and view["compiles_total"] == 2
    assert view["recent"][0] == "field_stage/8 10.5ms [compile]"
    assert view["recent"][1] == "merkle_level/32 2.0ms @dev2 [compile]"


# -- modes ----------------------------------------------------------------------


def test_mode_semantics():
    telemetry.reset_launch_telemetry()
    try:
        # auto without metrics: inactive, record is a no-op
        assert not telemetry.launch_telemetry_active()
        assert telemetry.record_launch("p", 8, 0.1) is None
        # auto + metrics installed: active (the node's shape)
        telemetry.configure_launch_telemetry(metrics=_Probe())
        assert telemetry.launch_telemetry_active()
        assert telemetry.record_launch("p", 8, 0.1) is not None
        # off beats an installed sink
        telemetry.configure_launch_telemetry(mode="off")
        assert not telemetry.launch_telemetry_active()
        assert telemetry.record_launch("p", 8, 0.1) is None
        assert telemetry.launch_totals()["launches"] == 1  # only the auto+metrics one
        with pytest.raises(ValueError):
            telemetry.configure_launch_telemetry(mode="sometimes")
    finally:
        telemetry.reset_launch_telemetry()


def test_size_helpers():
    assert telemetry.size_class_of(1) == 8
    assert telemetry.size_class_of(8) == 8
    assert telemetry.size_class_of(9) == 16
    assert telemetry.size_class_of(100) == 128
    arr = np.zeros((24, 33), dtype=np.int32)
    assert telemetry.launch_size_class((arr,)) == 24
    # tuples-of-arrays (the hash_finish jacobian argument shape)
    assert telemetry.launch_size_class(((arr, arr, arr), arr)) == 24
    assert telemetry.launch_size_class((3, "x")) == 0


# -- the metric sink over a real registry ---------------------------------------


def test_metric_sink_real_registry(tel):
    from lodestar_tpu.metrics import create_metrics

    m = create_metrics()
    tel.configure_launch_telemetry(metrics=m.device_launch)
    tel.record_launch("prog", 8, 0.5)
    tel.record_launch("prog", 8, 0.001)

    def sample(name, labels=None):
        for fam in m.creator.registry.collect():
            for s in fam.samples:
                if s.name == name and (labels is None or all(
                    s.labels.get(k) == v for k, v in labels.items()
                )):
                    return s.value
        return None

    assert sample(
        "lodestar_device_launch_seconds_count",
        {"program": "prog", "size_class": "8"},
    ) == 2
    assert sample("lodestar_device_compile_misses_total", {"program": "prog"}) == 1
    assert sample("lodestar_device_compile_hits_total", {"program": "prog"}) == 1
    assert sample("lodestar_device_compile_seconds_total") == pytest.approx(0.5)


# -- seam: fused prep (3-launch schedule) ---------------------------------------


class TestPrepSeam:
    def test_fused_prep_lands_three_launches(self, tel):
        from lodestar_tpu.models import batch_verify as bv
        from lodestar_tpu.ops import prep

        sets = bv.make_synthetic_sets(2, seed=5)
        base = len(tel.launch_ledger())
        assert bv.prepare_sets_device(sets) is not None
        entries = tel.launch_ledger()[base:]
        assert len(entries) == prep.FUSED_PREP_LAUNCHES == 3
        assert [e["program"] for e in entries] == [
            "_prep_field_stage",
            "_prep_subgroup_stage",
            "hash_finish",
        ]
        # every stage carries the padded size class (2 sets -> 8)
        assert all(e["size_class"] == 8 for e in entries)

    def test_fused_prep_lands_in_the_histogram_with_labels(self, tel):
        """The acceptance wording verbatim: dispatches at the counted
        seam land in lodestar_device_launch_seconds with correct
        program/size_class labels."""
        from lodestar_tpu.metrics import create_metrics
        from lodestar_tpu.models import batch_verify as bv

        m = create_metrics()
        tel.configure_launch_telemetry(metrics=m.device_launch)
        assert bv.prepare_sets_device(bv.make_synthetic_sets(2, seed=5)) is not None

        def count(program):
            for fam in m.creator.registry.collect():
                for s in fam.samples:
                    if (
                        s.name == "lodestar_device_launch_seconds_count"
                        and s.labels.get("program") == program
                        and s.labels.get("size_class") == "8"
                    ):
                        return s.value
            return 0

        for program in ("_prep_field_stage", "_prep_subgroup_stage", "hash_finish"):
            assert count(program) == 1, program

    def test_unfused_prep_lands_five_launches(self, tel):
        from lodestar_tpu.models import batch_verify as bv
        from lodestar_tpu.ops import prep
        from tests.ops.test_prep import prepare_sets_unfused

        sets = bv.make_synthetic_sets(2, seed=5)
        base = len(tel.launch_ledger())
        assert prepare_sets_unfused(sets)[-1]
        entries = tel.launch_ledger()[base:]
        assert len(entries) == prep.UNFUSED_PREP_LAUNCHES == 5
        assert [e["program"] for e in entries] == [
            "g1_decompress_subgroup",
            "g2_decompress_subgroup",
            "mont_from_wide",
            "map_to_g2_jac",
            "hash_finish",
        ]


# -- seam: single-launch verification (one record per batch) --------------------


class TestSingleLaunchSeam:
    @pytest.mark.slow  # compiles the real single-launch program (~40 s
    # XLA compile on the CPU container — over tier-1's remaining budget)
    def test_one_record_per_batch_with_program_and_size_class(self, tel):
        """A batch verified by the single launch lands in the ledger
        as EXACTLY one record carrying the program's own name and the
        pow-2 size class, independent of batch size; compile-miss is
        counted once per (program, size_class); the slow-slot dump
        names it."""
        from lodestar_tpu.models import batch_verify as bv
        from lodestar_tpu.ops import prep

        probe = _Probe()
        tel.configure_launch_telemetry(metrics=probe)
        for n in (2, 3):
            base = len(tel.launch_ledger())
            assert bv.verify_sets_single_launch(
                bv.make_synthetic_sets(n, seed=n + 60)
            )
            entries = tel.launch_ledger()[base:]
            assert len(entries) == prep.SINGLE_LAUNCH_BUDGET == 1
            e = entries[0]
            assert e["program"] == "_single_launch_verify"
            assert e["size_class"] == 8  # both batches share the pow-2 class
        # compile-miss once per (program, size_class): first batch miss,
        # second batch hit — the jit cache holds one executable per key
        misses = [m for m in probe.compile_misses.events if m[1] == ("_single_launch_verify",)]
        hits = [h for h in probe.compile_hits.events if h[1] == ("_single_launch_verify",)]
        assert len(misses) == 1 and len(hits) == 1
        # the launch ledger + slow-slot dumps name the program
        view = tel.slow_slot_launches()
        assert any(r.startswith("_single_launch_verify/8 ") for r in view["recent"])


# -- seam: device HTR per-level dispatches --------------------------------------


class TestHtrSeam:
    def test_per_level_launches_with_size_classes(self, tel):
        from lodestar_tpu.ssz import device_htr as dh

        prev = dh.configure_device_htr(mode="on")
        prev_min = dh.DEVICE_MIN_FLUSH_PAIRS
        dh.DEVICE_MIN_FLUSH_PAIRS = 1
        try:
            depth = 4
            n = 1 << depth
            rng = np.random.default_rng(7)
            levels = [
                np.zeros((n >> k, 32), dtype=np.uint8) for k in range(depth + 1)
            ]
            levels[0][:] = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
            coll = dh.DirtyCollector()
            coll.add_stack_job(levels, range(n))
            base = len(tel.launch_ledger())
            stats = coll.flush()
            assert stats["backend"] == "device"
            entries = tel.launch_ledger()[base:]
            # one telemetry record per DEVICE launch — same count the
            # collector's own per-flush invariant reports
            assert len(entries) == stats["launches"] == depth
            assert all(e["program"] == "merkle_level" for e in entries)
            # per-level size classes: 8 dirty pairs -> 8, then the
            # padded floor for the smaller levels
            assert [e["size_class"] for e in entries] == [
                dh.pad_pow2_pairs((n >> k) // 2) for k in range(depth)
            ]
        finally:
            dh.DEVICE_MIN_FLUSH_PAIRS = prev_min
            dh.configure_device_htr(mode=prev)


# -- seam: mesh lane launches ---------------------------------------------------


class TestMeshSeam:
    def _sets(self, n):
        from lodestar_tpu.crypto.bls.api import SignatureSet

        return [
            SignatureSet(
                pubkey=bytes([1, i]) + bytes(46),
                message=bytes([2, i]) * 16,
                signature=bytes([3, i]) + bytes(94),
            )
            for i in range(n)
        ]

    def test_lane_launch_recorded_with_lane_label(self, tel):
        from lodestar_tpu.chain.bls.mesh import mesh_launch
        from lodestar_tpu.testing.mesh import FakeLaneRig

        rig = FakeLaneRig(2, with_sharded=False)
        ok, served = mesh_launch(rig.mesh, self._sets(3))
        assert ok
        entries = tel.launch_ledger()
        assert len(entries) == 1
        e = entries[0]
        assert e["program"] == "bls_lane_verify"
        assert e["lane"] == served.label
        assert e["size_class"] == 8  # 3 sets -> pow-2 floor

    def test_staged_reject_is_not_a_launch(self, tel):
        """A prep-stage structural reject resolves ok=False WITHOUT a
        backend call — it must not appear in the launch ledger."""
        from lodestar_tpu.chain.bls.mesh import PreparedSets, mesh_launch
        from lodestar_tpu.testing.mesh import FakeLaneRig

        rig = FakeLaneRig(1, with_prepared=True, with_sharded=False)
        ok, _ = mesh_launch(
            rig.mesh, self._sets(2), prepared=PreparedSets(inputs=None)
        )
        assert not ok
        assert tel.launch_ledger() == []

    def test_off_mode_records_nothing(self):
        from lodestar_tpu.chain.bls.mesh import mesh_launch
        from lodestar_tpu.testing.mesh import FakeLaneRig

        telemetry.reset_launch_telemetry()
        telemetry.configure_launch_telemetry(mode="off")
        try:
            rig = FakeLaneRig(1, with_sharded=False)
            ok, _ = mesh_launch(rig.mesh, self._sets(2))
            assert ok
            assert telemetry.launch_ledger() == []
        finally:
            telemetry.reset_launch_telemetry()
