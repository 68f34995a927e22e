"""The traffic generator and the metric arithmetic."""

from __future__ import annotations

import asyncio

import pytest

from perfbench import generator, stats

TRAFFIC = {"loop": "closed", "wave_calls": 4, "replay_calls": 8,
           "faults": {"tampered_pair": 1, "off_subgroup": 2}, "call": {}}


def test_replay_is_a_function_of_the_seed():
    a = generator.build_replay(TRAFFIC, 2**31 + 7)
    assert a == generator.build_replay(TRAFFIC, 2**31 + 7)
    assert a != generator.build_replay(TRAFFIC, 2**31 + 8)


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 99, 2**33])
def test_every_seed_gets_the_same_work_in_another_order(seed):
    faults = sorted(str(e.fault) for e in generator.build_replay(TRAFFIC, seed))
    assert faults == sorted(["None"] * 5 + ["tampered_pair"] + ["off_subgroup"] * 2)


@pytest.mark.parametrize("broken", [
    {"loop": "open"}, {"wave_calls": 0}, {"replay_calls": 2}, {"call": None, "drop": "call"},
])
def test_a_malformed_traffic_file_is_refused(broken):
    traffic = {**TRAFFIC, **{k: v for k, v in broken.items() if k != "drop"}}
    if "drop" in broken:
        del traffic[broken["drop"]]
    with pytest.raises(ValueError):
        generator.build_replay(traffic, 1)


def test_closed_loop_sends_a_wave_when_the_last_answer_is_in():
    in_flight = peak = 0

    async def call(rec):
        nonlocal in_flight, peak
        in_flight += 1
        peak = max(peak, in_flight)
        await asyncio.sleep(0.001 * (1 + rec.call % 3))
        in_flight -= 1
        return rec.entry

    records, start, end = asyncio.run(generator.drive(call, TRAFFIC, calls=10, first_call=3))
    assert [r.call for r in records] == list(range(3, 13))
    assert [r.entry for r in records] == [c % 8 for c in range(3, 13)]
    assert peak == 4
    waves = [records[0:4], records[4:8], records[8:10]]
    for earlier, later in zip(waves, waves[1:]):
        assert max(r.done for r in earlier) <= min(r.issued for r in later)
    assert all(r.answer == r.entry and r.error is None for r in records)


def test_a_timed_run_stops_issuing_at_the_deadline_and_counts_a_failed_call():
    async def call(rec):
        await asyncio.sleep(0.01)
        if rec.call == 1:
            raise RuntimeError("boom")
        return True

    records, start, end = asyncio.run(generator.drive(call, {**TRAFFIC, "wave_calls": 1}, seconds=0.1))
    assert end == pytest.approx(start + 0.1)
    assert all(r.issued < end for r in records)
    assert records[1].error.startswith("RuntimeError") and records[0].error is None
    assert 5 <= len(records) <= 11


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 2.5), (90, 3.7), (100, 4.0)])
def test_percentile_interpolates(q, want):
    assert stats.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)
