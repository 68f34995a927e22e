"""Arithmetic for the per-layer metrics that read what the program names
itself: a launch's `phases` in the launch ledger, a flush's `steps` in its
stats (each record's `detail`), and the prep counters. `ctx` is
`perfbench.readers`'. All cover the whole window, need no trace, and
return None where the program records no such thing (a commit before the
phases, the reference entry).
"""

from __future__ import annotations

import statistics

from perfbench.readers import counter_delta, steady_launches

HOST_BEFORE_DEVICE = ("bls.parse", "bls.dispatch")  # what a launch's host side does before the device can start


def launch_host_ms(ctx, program: str = "bls_lane_verify"):
    """Median over the window's steady launches of the host work a launch
    needs before the device can start, on whichever thread it ran."""
    per_launch = [
        1000.0 * sum(e["phases"].get(name, 0.0) for name in HOST_BEFORE_DEVICE)
        for e in steady_launches(ctx, program)
        if e.get("phases")
    ]
    return statistics.median(per_launch) if per_launch else None


def flush_step_ms(ctx, step: str):
    """Mean over the window's flushes of one step of the collector."""
    per_flush = [
        1000.0 * r.detail["steps"][step]
        for r in ctx["records"]
        if r.error is None and step in (r.detail.get("steps") or {})
    ]
    return sum(per_flush) / len(per_flush) if per_flush else None


def host_prep_us_per_set(ctx):
    """Host prep seconds over the sets prepared, both of the window
    (`lodestar_bls_prep_seconds`, `lodestar_bls_prep_sets_total`)."""
    sets = counter_delta(ctx, "lodestar_bls_prep_sets_total")
    seconds = counter_delta(ctx, "lodestar_bls_prep_seconds_sum")
    return 1e6 * seconds / sets if sets and seconds else None
