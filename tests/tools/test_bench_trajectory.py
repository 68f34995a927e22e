"""bench_trajectory regression gate: exit codes on synthetic
prior/current round pairs, both round-file shapes, line parsing, and
direction handling."""

from __future__ import annotations

import importlib.util
import json
import pathlib

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]

spec = importlib.util.spec_from_file_location(
    "bench_trajectory", REPO / "tools" / "bench_trajectory.py"
)
bt = importlib.util.module_from_spec(spec)
spec.loader.exec_module(bt)


def _round(path, n, lines):
    doc = {"n": n, "cmd": "synthetic", "rc": 0, "label": "test", "lines": lines}
    path.write_text(json.dumps(doc))
    return str(path)


def _l(metric, value):
    return {"metric": metric, "value": value, "unit": "u", "vs_baseline": 1.0}


def test_compare_exit_zero_when_clean(tmp_path):
    prior = _round(tmp_path / "a.json", 1, [_l("gossip_replay_sigs_per_sec", 100.0)])
    cur = _round(tmp_path / "b.json", 2, [_l("gossip_replay_sigs_per_sec", 95.0)])
    assert bt.main(["--compare", prior, cur]) == 0


def test_compare_exit_nonzero_on_injected_regression(tmp_path):
    # threshold 0.5: a 60% throughput drop must gate
    prior = _round(tmp_path / "a.json", 1, [_l("gossip_replay_sigs_per_sec", 100.0)])
    cur = _round(tmp_path / "b.json", 2, [_l("gossip_replay_sigs_per_sec", 40.0)])
    assert bt.main(["--compare", prior, cur]) == 1


def test_lower_is_better_direction(tmp_path):
    # epoch_htr_ms_device RISING is the regression; falling is fine
    prior = _round(tmp_path / "a.json", 1, [_l("epoch_htr_ms_device", 100.0)])
    worse = _round(tmp_path / "b.json", 2, [_l("epoch_htr_ms_device", 400.0)])
    better = _round(tmp_path / "c.json", 3, [_l("epoch_htr_ms_device", 10.0)])
    assert bt.main(["--compare", prior, worse]) == 1
    assert bt.main(["--compare", prior, better]) == 0


def test_launch_budget_lines_gate_tightly(tmp_path):
    """prep_launches_per_set is a schedule invariant (threshold 0.05):
    a fused schedule quietly growing a fourth launch (3/32 -> 4/32 per
    set at batch 32) MUST gate."""
    prior = _round(tmp_path / "a.json", 1, [_l("prep_launches_per_set", 3 / 32)])
    cur = _round(tmp_path / "b.json", 2, [_l("prep_launches_per_set", 4 / 32)])
    assert bt.main(["--compare", prior, cur]) == 1


def test_zero_prior_lower_is_better_still_gates(tmp_path):
    """A perfect (0.0) lower-is-better prior must not disarm the gate:
    with no denominator, the threshold is read in the metric's own
    units — fairness 0.0 -> 90.0 gates, 0.0 -> 0.5 (inside the 3.0
    allowance) does not."""
    prior = _round(
        tmp_path / "a.json", 1, [_l("two_tenant_fairness_share_error_pct", 0.0)]
    )
    worse = _round(
        tmp_path / "b.json", 2, [_l("two_tenant_fairness_share_error_pct", 90.0)]
    )
    noisy = _round(
        tmp_path / "c.json", 3, [_l("two_tenant_fairness_share_error_pct", 0.5)]
    )
    assert bt.main(["--compare", prior, worse]) == 1
    assert bt.main(["--compare", prior, noisy]) == 0


def test_old_parsed_shape_chains_into_new_lines_shape(tmp_path):
    """r1–r5 files carry one `parsed` metric; the gate diffs the
    intersection, so the old shape feeds the new one."""
    old = tmp_path / "r05.json"
    old.write_text(
        json.dumps(
            {
                "n": 5,
                "cmd": "bench.py",
                "rc": 0,
                "parsed": _l("bls_batch_verify_sigs_per_sec", 5416.0),
            }
        )
    )
    ok = _round(
        tmp_path / "r06.json", 6,
        [_l("bls_batch_verify_sigs_per_sec", 5000.0), _l("new_line", 1.0)],
    )
    bad = _round(
        tmp_path / "r06b.json", 6, [_l("bls_batch_verify_sigs_per_sec", 500.0)]
    )
    assert bt.main(["--compare", str(old), str(ok)]) == 0
    assert bt.main(["--compare", str(old), str(bad)]) == 1


def test_compare_rounds_reports_frames():
    prior = {"m": _l("m", 100.0), "gone": _l("gone", 1.0)}
    current = {"m": _l("m", 10.0), "fresh": _l("fresh", 2.0)}
    regs, notes = bt.compare_rounds(prior, current)
    assert len(regs) == 1
    r = regs[0]
    assert r["metric"] == "m" and r["regression_frac"] == pytest.approx(0.9)
    joined = " ".join(notes)
    assert "gone" in joined and "fresh" in joined


def test_parse_bench_lines_skips_chatter():
    text = "\n".join(
        [
            "WARNING: compiler chatter",
            '{"note": "not a metric"}',
            '{"metric": "x_per_sec", "value": 1.5, "unit": "ops", "vs_baseline": 0.1}',
            "{broken json",
            '{"metric": "y_ms", "value": 2.0, "unit": "ms", "vs_baseline": 1.0}',
        ]
    )
    lines = bt.parse_bench_lines(text)
    assert [l["metric"] for l in lines] == ["x_per_sec", "y_ms"]


def test_real_rounds_load():
    """Every checked-in BENCH_rNN.json parses under the loader (the
    trajectory is resumable from the repo as-is)."""
    rounds = bt.round_files()
    assert rounds
    ns = [n for n, _ in rounds]
    assert ns == sorted(ns)
    by_n = {n: bt.load_round_metrics(path) for n, path in rounds}
    # r01 predates bench.py (parsed: null) — empty is legal there; the
    # rounds the gate actually chains through must carry metrics
    assert len(by_n[6]) >= 15, "r06 must carry the full baseline-bench line set"
    assert "prep_launches_per_set" in by_n[6]
