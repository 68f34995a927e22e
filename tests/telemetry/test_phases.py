"""`telemetry.launch` / `telemetry.phase`: what an entry carries (phases,
thread, enclosing launch), where a phase's seconds go, what inactive
telemetry costs, and the compile counter over nested first calls."""

from __future__ import annotations

import subprocess
import sys
import threading

import pytest

from lodestar_tpu import telemetry


@pytest.fixture
def tel():
    telemetry.reset_launch_telemetry()
    telemetry.configure_launch_telemetry(mode="on")
    yield telemetry
    telemetry.reset_launch_telemetry()


@pytest.fixture
def annotations(monkeypatch):
    """Names of the profiler annotations opened, in order."""
    opened: list[str] = []

    def annotate(name):
        opened.append(name)
        return None

    monkeypatch.setattr(telemetry, "_annotate", annotate)
    return opened


def test_inactive_writes_no_entry_and_opens_no_annotation(annotations):
    telemetry.reset_launch_telemetry()
    telemetry.configure_launch_telemetry(mode="off")
    try:
        sink: dict = {}
        with telemetry.launch("prog", 8) as outer:
            with telemetry.phase("bls.parse"):
                pass
            with telemetry.phase("htr.index", into=sink):
                pass
            outer.add_phase("bls.parse", 1.0)
        assert outer.entry is None
        assert telemetry.launch_ledger() == [] and sink == {} and annotations == []
        assert telemetry.launch_totals()["launches"] == 0
    finally:
        telemetry.reset_launch_telemetry()


def test_an_entry_has_phases_thread_and_parent(tel, annotations):
    with tel.launch("prog", 16, lane="dev0") as span:
        with tel.phase("bls.parse"):
            pass
        with tel.phase("bls.dispatch"):
            pass
        with tel.phase("bls.parse"):  # a name opened twice adds up
            pass
    entry = tel.launch_ledger()[-1]
    assert entry == span.entry
    assert set(entry["phases"]) == {"bls.parse", "bls.dispatch"}
    assert all(s >= 0.0 for s in entry["phases"].values())
    assert sum(entry["phases"].values()) <= entry["seconds"]
    assert entry["tid"] == threading.get_ident() and entry["parent"] is None
    assert (entry["program"], entry["size_class"], entry["lane"]) == ("prog", 16, "dev0")
    assert annotations == ["prog", "bls.parse", "bls.dispatch", "bls.parse"]


def test_a_nested_launch_names_the_one_that_holds_it(tel):
    with tel.launch("bls_lane_verify", 128) as outer:
        with tel.phase("bls.dispatch"):
            with tel.launch("_single_launch_verify", 128) as inner:
                with tel.phase("inside"):
                    pass
    assert inner.entry["parent"] == outer.entry["seq"] and outer.entry["parent"] is None
    assert outer.entry["seq"] < inner.entry["seq"]
    # the inner launch is written first; each phase went to the innermost open launch
    assert [e["program"] for e in tel.launch_ledger()] == ["_single_launch_verify", "bls_lane_verify"]
    assert set(inner.entry["phases"]) == {"inside"} and set(outer.entry["phases"]) == {"bls.dispatch"}


def test_a_phase_with_no_open_launch_is_dropped_not_leaked(tel, annotations):
    with tel.phase("bls.resolve"):
        pass
    assert annotations == ["bls.resolve"]  # the profiler still sees it
    with tel.launch("prog", 8) as span:
        pass
    assert span.entry["phases"] == {}
    assert len(tel.launch_ledger()) == 1


def test_into_leaves_the_ledger_alone(tel):
    steps: dict = {"htr.index": 1.0}
    with tel.launch("merkle_level", 8) as span:
        with tel.phase("htr.index", into=steps):
            pass
        with tel.phase("htr.gather", into=steps):
            pass
    assert span.entry["phases"] == {}
    assert set(steps) == {"htr.index", "htr.gather"} and steps["htr.index"] >= 1.0


def test_a_launch_that_raises_writes_no_entry_and_closes(tel):
    with pytest.raises(RuntimeError):
        with tel.launch("prog", 8):
            raise RuntimeError("device fault")
    assert tel.launch_ledger() == []
    with tel.launch("prog", 8) as span:  # the failed one is no parent of this one
        pass
    assert span.entry["parent"] is None


def test_phases_stay_on_their_own_thread(tel):
    inner_entries = []
    start, go = threading.Event(), threading.Event()

    def worker():
        with tel.launch("worker_prog", 8) as span:
            start.set()
            assert go.wait(5)
            with tel.phase("worker.phase"):
                pass
        inner_entries.append(span.entry)

    t = threading.Thread(target=worker)
    with tel.launch("main_prog", 8) as mine:
        t.start()
        assert start.wait(5)
        with tel.phase("main.phase"):
            pass
        go.set()
        t.join(5)
    assert not t.is_alive()
    assert set(mine.entry["phases"]) == {"main.phase"} and mine.entry["parent"] is None
    assert set(inner_entries[0]["phases"]) == {"worker.phase"}
    assert inner_entries[0]["parent"] is None and inner_entries[0]["tid"] != mine.entry["tid"]


def test_compile_seconds_count_top_level_first_calls_only(tel):
    """A first `_single_launch_verify` inside a first `bls_lane_verify`
    adds its seconds once, so the counter can be summed."""
    from lodestar_tpu.metrics import create_metrics

    m = create_metrics()
    tel.configure_launch_telemetry(metrics=m.device_launch)
    for _ in range(2):  # the second round is all hits
        with tel.launch("bls_lane_verify", 128) as outer:
            with tel.launch("_single_launch_verify", 128) as inner:
                pass

    def sample(name, **labels):
        for fam in m.creator.registry.collect():
            for s in fam.samples:
                if s.name == name and all(s.labels.get(k) == v for k, v in labels.items()):
                    return s.value
        return None

    first = [e for e in tel.launch_ledger() if e["compile"]]
    assert [e["program"] for e in first] == ["_single_launch_verify", "bls_lane_verify"]
    top = [e for e in first if e["parent"] is None]
    assert sample("lodestar_device_compile_seconds_total") == pytest.approx(top[0]["seconds"])
    assert sample("lodestar_device_compile_seconds_total") < sum(e["seconds"] for e in first)
    # each program still counts its own miss and hit
    for program in ("bls_lane_verify", "_single_launch_verify"):
        assert sample("lodestar_device_compile_misses_total", program=program) == 1
        assert sample("lodestar_device_compile_hits_total", program=program) == 1
    assert outer.entry["compile"] is False and inner.entry["compile"] is False


def test_totals_and_ledger_show_phases_as_they_are(tel):
    with tel.launch("prog", 8) as span:
        span.add_phase("bls.parse", 0.25)
        span.add_phase("bls.parse", 0.25)
    with tel.launch("prog", 8) as second:
        second.add_phase("bls.wait", 1.0)
    totals = tel.launch_totals()
    assert totals["ledger_phase_seconds"] == {"bls.parse": 0.5, "bls.wait": 1.0}
    copy = tel.launch_ledger()
    copy[0]["phases"]["bls.parse"] = 99.0  # a copy: the ledger keeps its own
    assert tel.launch_ledger()[0]["phases"] == {"bls.parse": 0.5}
    assert tel.record_launch("plain", 8, 0.1)["phases"] == {}


class TestMeshSeamPhases:
    def _sets(self, n):
        from lodestar_tpu.crypto.bls.api import SignatureSet

        return [
            SignatureSet(pubkey=bytes([1, i]) + bytes(46), message=bytes([2, i]) * 16,
                         signature=bytes([3, i]) + bytes(94))
            for i in range(n)
        ]

    def test_staged_prep_seconds_cross_threads_with_the_inputs(self, tel):
        from lodestar_tpu.chain.bls.mesh import PreparedSets, mesh_launch
        from lodestar_tpu.testing.mesh import FakeLaneRig

        rig = FakeLaneRig(1, with_prepared=True, with_sharded=False)
        info = {"layer": "single_launch", "sets": 3, "start_ns": 1_000_000, "end_ns": 4_000_000,
                "rejected": False}
        sets = self._sets(3)
        staged = PreparedSets(inputs=rig.prep_fn(sets, None), info=info)
        ok, _ = mesh_launch(rig.mesh, sets, prepared=staged)
        assert ok
        entry = tel.launch_ledger()[-1]
        assert entry["program"] == "bls_lane_verify"
        assert entry["phases"] == {"bls.parse": pytest.approx(0.003)}

    def test_phases_opened_by_the_lane_land_on_its_launch(self, tel):
        from lodestar_tpu.chain.bls.mesh import mesh_launch, single_lane_mesh

        def verify(sets):
            with tel.phase("bls.parse"):
                pass
            with tel.phase("bls.dispatch"):
                with tel.launch("_single_launch_verify", 8):
                    pass
            with tel.phase("bls.wait"):
                return True

        ok, _ = mesh_launch(single_lane_mesh(verify), self._sets(2))
        assert ok
        inner, outer = tel.launch_ledger()
        assert set(outer["phases"]) == {"bls.parse", "bls.dispatch", "bls.wait"}
        assert inner["parent"] == outer["seq"] and inner["phases"] == {}


def test_the_module_loads_no_jax_and_annotates_once_jax_is_there():
    code = (
        "import sys\n"
        "import lodestar_tpu.telemetry as t\n"
        "assert 'jax' not in sys.modules\n"
        "t.configure_launch_telemetry(mode='on')\n"
        "with t.launch('p', 8):\n"
        "    with t.phase('x'):\n"
        "        pass\n"
        "assert 'jax' not in sys.modules and t._annotate('x') is None\n"
        "import jax\n"
        "note = t._annotate('x')\n"
        "assert isinstance(note, jax.profiler.TraceAnnotation)\n"
        "note.__exit__(None, None, None)\n"
    )
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr
