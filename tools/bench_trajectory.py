"""Resumable bench trajectory with per-line regression gates.

The BENCH_rNN.json trajectory stalled at r5 with no tooling to resume
or gate it: every round was a hand-run of `bench.py` pasted into a
file, and nothing failed when a line regressed. This tool is the
missing loop:

1. Run `tools/baseline_configs_bench.py` (``--quick`` by default on
   this container; pass ``--full`` on a chip host) — or consume an
   existing run's output via ``--from-log`` (the chip run prints the
   lines once; gating must not require a rerun).
2. Write the next ``BENCH_rNN.json`` (N = highest existing + 1) in a
   JSON-lines-carrying shape: ``{"n", "cmd", "rc", "label", "lines"}``.
   The label records WHAT the numbers mean — CPU-container lines
   validate schedule shape, not chip throughput, and must say so.
3. Diff every line against the previous round under the per-line
   thresholds below and **exit nonzero on regression** — the perf CI
   gate. Rounds r1–r5 carry a single ``parsed`` metric
   (``bls_batch_verify_sigs_per_sec``); the diff runs over the metric
   intersection, so the old shape chains into the new one.
4. Regenerate the dashboards (`tools/gen_dashboards.py`) so the
   device-launches dashboard's trajectory panel picks up the round.

``--compare PRIOR CURRENT`` runs ONLY the gate over two existing
round files (exit 0 clean / 1 regression) — the mode CI and the
regression-gate tests drive.

The metric names in ``THRESHOLDS`` are statically checked two-way
against what ``baseline_configs_bench.py`` / ``bench.py`` actually
report by the ``bench-wiring`` analysis rule (tools/analysis): a
renamed bench line without a threshold — or a threshold gating a line
nobody emits — fails the tier-1 gate, not the next chip run.

Run from the repo root: python tools/bench_trajectory.py [--quick|--full]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: metric -> max tolerated fractional regression vs the prior round.
#: Throughput lines carry 0.5 (the CPU container's scheduler noise is
#: real; a chip host can tighten these); the launch-budget lines are
#: near-deterministic schedule invariants and carry 0.05 — a fused
#: schedule quietly growing a fourth launch IS the regression this
#: gate exists for.
THRESHOLDS: dict[str, float] = {
    "host_prep_sets_per_sec_single_core": 0.5,
    "device_prep_sets_per_sec": 0.5,
    "prep_launches_per_set": 0.05,
    # single-launch dispatch budget: 1 program per verified batch vs the
    # 3+verify split reference — a schedule invariant, gated tight (a
    # fused chain quietly growing a second launch IS the regression)
    "e2e_launches_per_batch": 0.05,
    "e2e_launches_per_batch_split": 0.05,
    "merkle_sha256_pair_hashes_per_sec": 0.5,
    "state_htr_chunks_per_sec": 0.5,
    "epoch_htr_ms_device": 0.75,
    "epoch_htr_ms_cpu": 0.75,
    "backfill_window_device_sigs_per_sec": 0.5,
    "gossip_replay_sigs_per_sec": 0.5,
    "pipelined_gossip_replay_sigs_per_sec": 0.5,
    "prep_verify_overlap_occupancy_pct": 0.75,
    "sync_committee_fast_aggregate_verifies_per_sec": 0.5,
    "mesh_sigs_per_sec_1dev": 0.5,
    "mesh_sigs_per_sec_2dev": 0.5,
    "mesh_sigs_per_sec_4dev": 0.5,
    "mesh_sigs_per_sec_8dev": 0.5,
    # lower-better with a tiny, noisy prior (3.2 on a 10-point
    # envelope): tolerate up to 3x before gating
    "two_tenant_fairness_share_error_pct": 3.0,
    # bench.py's config-1 headline — the single metric rounds r1–r5
    # carry, kept so the old trajectory chains into this gate
    "bls_batch_verify_sigs_per_sec": 0.5,
    # chaos harness lines (tools/chaos_experiment.py): worst-case
    # degraded-throughput retention across the scenario matrix, and
    # slots-to-recovery after the last heal. Retention regressing past
    # 25% of prior means a fault class started starving the pipeline;
    # recovery_slots has a 0 prior, so the lower-is-better zero-prior
    # branch gates it absolutely (anything past 2 slots fails).
    "chaos_degraded_throughput_retention_pct": 0.25,
    "chaos_recovery_slots": 2.0,
}

#: metrics where a LARGER value is the regression (latency, error pct,
#: launches-per-set); everything else is higher-is-better throughput
LOWER_IS_BETTER: set = {
    "epoch_htr_ms_device",
    "epoch_htr_ms_cpu",
    "two_tenant_fairness_share_error_pct",
    "prep_launches_per_set",
    "e2e_launches_per_batch",
    "e2e_launches_per_batch_split",
    "chaos_recovery_slots",
}

#: fallback for a metric a newer bench emits before its threshold
#: lands (the bench-wiring rule flags the gap; the gate stays usable
#: on the chip host in the meantime)
DEFAULT_THRESHOLD = 0.5

_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")


def parse_bench_lines(text: str) -> list[dict]:
    """The JSON lines with a "metric" key out of a bench run's stdout
    (warnings, notes, and compiler chatter interleave freely)."""
    lines = []
    for raw in text.splitlines():
        raw = raw.strip()
        if not raw.startswith("{"):
            continue
        try:
            doc = json.loads(raw)
        except ValueError:
            continue
        if isinstance(doc, dict) and "metric" in doc and "value" in doc:
            lines.append(doc)
    return lines


def round_files(repo: str = REPO) -> list[tuple[int, str]]:
    out = []
    for name in os.listdir(repo):
        m = _ROUND_RE.match(name)
        if m:
            out.append((int(m.group(1)), os.path.join(repo, name)))
    return sorted(out)


def load_round_metrics(path: str) -> dict[str, dict]:
    """metric -> line for one round file; understands both the r1–r5
    single-``parsed`` shape and the r6+ ``lines`` shape."""
    with open(path) as f:
        doc = json.load(f)
    out: dict[str, dict] = {}
    for line in doc.get("lines") or []:
        if isinstance(line, dict) and "metric" in line:
            out[line["metric"]] = line
    parsed = doc.get("parsed")
    if isinstance(parsed, dict) and "metric" in parsed:
        out.setdefault(parsed["metric"], parsed)
    return out


def compare_rounds(
    prior: dict[str, dict], current: dict[str, dict]
) -> tuple[list[dict], list[str]]:
    """(regressions, notes) for the metric intersection. A regression
    is a fractional move past the metric's threshold in its bad
    direction; notes record metrics that could not be compared."""
    regressions: list[dict] = []
    notes: list[str] = []
    for metric in sorted(set(prior) & set(current)):
        p = float(prior[metric]["value"])
        c = float(current[metric]["value"])
        threshold = THRESHOLDS.get(metric)
        if threshold is None:
            notes.append(f"{metric}: no threshold (gated at default {DEFAULT_THRESHOLD})")
            threshold = DEFAULT_THRESHOLD
        if p <= 0:
            if metric in LOWER_IS_BETTER and c > threshold:
                # a perfect (0) lower-is-better prior must not disarm the
                # gate: with no denominator to take a fraction of, the
                # threshold is read in the metric's own units (e.g.
                # fairness 0.0 -> anything past 3.0 pct gates)
                regressions.append(
                    {
                        "metric": metric,
                        "prior": p,
                        "current": c,
                        "regression_frac": None,
                        "threshold": threshold,
                        "direction": "lower_is_better (absolute: zero prior)",
                    }
                )
            else:
                notes.append(f"{metric}: prior value {p} not comparable")
            continue
        if metric in LOWER_IS_BETTER:
            frac = (c - p) / p
        else:
            frac = (p - c) / p
        if frac > threshold:
            regressions.append(
                {
                    "metric": metric,
                    "prior": p,
                    "current": c,
                    "regression_frac": round(frac, 4),
                    "threshold": threshold,
                    "direction": "lower_is_better" if metric in LOWER_IS_BETTER else "higher_is_better",
                }
            )
    for metric in sorted(set(prior) - set(current)):
        notes.append(f"{metric}: present in prior round only (not gated)")
    for metric in sorted(set(current) - set(prior)):
        notes.append(f"{metric}: new in this round (baseline recorded)")
    return regressions, notes


def write_round(path: str, n: int, cmd: str, rc: int, label: str, lines: list[dict]) -> None:
    doc = {"n": n, "cmd": cmd, "rc": rc, "label": label, "lines": lines}
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")


def regen_dashboards() -> None:
    """Refresh dashboards/ so the device-launches trajectory panel
    includes the round just written (gen_dashboards reads the
    BENCH_r*.json files at generation time)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import gen_dashboards

    gen_dashboards.main(out=os.path.join(REPO, "dashboards"))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench-trajectory",
        description="run the baseline bench, write the next BENCH_rNN.json, "
        "gate each line against the prior round (exit 1 on regression)",
    )
    ap.add_argument(
        "--compare", nargs=2, metavar=("PRIOR", "CURRENT"), default=None,
        help="gate-only mode: diff two existing round files and exit",
    )
    ap.add_argument(
        "--from-log", default=None, metavar="FILE",
        help="parse bench lines from an existing run's output instead of rerunning",
    )
    ap.add_argument(
        "--full", action="store_true",
        help="run the full bench (default passes --quick; use on chip hosts)",
    )
    ap.add_argument(
        "--label",
        default="cpu-container shape-validation (--quick; schedule shape, not chip throughput)",
        help="what this round's numbers mean — recorded in the round file",
    )
    ap.add_argument(
        "--no-write", action="store_true",
        help="gate against the prior round but do not write a round file",
    )
    ap.add_argument(
        "--no-dashboards", action="store_true",
        help="skip regenerating dashboards/ after writing the round",
    )
    args = ap.parse_args(argv)

    if args.compare is not None:
        prior = load_round_metrics(args.compare[0])
        current = load_round_metrics(args.compare[1])
        regressions, notes = compare_rounds(prior, current)
        for note in notes:
            print(f"note: {note}", file=sys.stderr)
        for r in regressions:
            print(json.dumps({"regression": r}), flush=True)
        if regressions:
            print(
                f"FAIL: {len(regressions)} regression(s) past threshold",
                file=sys.stderr,
            )
            return 1
        print(f"ok: {len(set(prior) & set(current))} line(s) within thresholds")
        return 0

    rounds = round_files()
    if not rounds:
        print("error: no BENCH_rNN.json rounds found (run from the repo root)", file=sys.stderr)
        return 2
    prior_n, prior_path = rounds[-1]
    next_n = prior_n + 1

    if args.from_log is not None:
        with open(args.from_log) as f:
            text = f.read()
        cmd = f"(from log) {args.from_log}"
        rc = 0
    else:
        bench_cmd = [sys.executable, os.path.join(REPO, "tools", "baseline_configs_bench.py")]
        if not args.full:
            bench_cmd.append("--quick")
        cmd = " ".join(bench_cmd)
        print(f"running: {cmd}", flush=True)
        proc = subprocess.run(bench_cmd, cwd=REPO, capture_output=True, text=True)
        text = proc.stdout
        rc = proc.returncode
        if rc != 0:
            sys.stderr.write(proc.stderr[-4000:])
            print(f"error: bench exited {rc}; no round written", file=sys.stderr)
            return 2

    lines = parse_bench_lines(text)
    if not lines:
        print("error: bench output carried no metric lines; no round written", file=sys.stderr)
        return 2

    prior = load_round_metrics(prior_path)
    current = {l["metric"]: l for l in lines}
    regressions, notes = compare_rounds(prior, current)
    for note in notes:
        print(f"note: {note}", file=sys.stderr)

    if not args.no_write:
        out_path = os.path.join(REPO, f"BENCH_r{next_n:02d}.json")
        write_round(out_path, next_n, cmd, rc, args.label, lines)
        print(f"wrote {out_path} ({len(lines)} lines)")
        if not args.no_dashboards:
            regen_dashboards()

    for r in regressions:
        print(json.dumps({"regression": r}), flush=True)
    if regressions:
        print(
            f"FAIL: {len(regressions)} regression(s) vs r{prior_n:02d} past threshold",
            file=sys.stderr,
        )
        return 1
    print(f"ok: r{next_n:02d} within thresholds vs r{prior_n:02d}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
