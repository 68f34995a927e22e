"""Arithmetic the per-layer metric readers share. A reader is a file
`perfbench/metrics/<metric>.py` with `read(ctx)`; `ctx` is what one run
observed (see `run.run_cell`):

    records            the calls of the window
    start, end         the window on the monotonic clock, seconds
    ledger             the program's launch-ledger entries from the window's start to
                       its last answer: the span the counters cover
    all_ledger         the ledger of the whole process
    counters_before/after  the node's counters, summed over labels, at both
                       ends of the window (`pool.<key>`: the pool's own tallies)
    monitor            JAX's compile and persistent-cache events
    trace              `perfbench.trace.Reduced`, or None without a chip
    trace_span         (start, end) of the traced part of the window, seconds
    peaks              this device's row of `perfbench/peaks.json`
    workload           the kind's workload object

A reader that finds nothing to read returns None and the metric is left
out of the line; a share of a peak is never reported as 0.
"""

from __future__ import annotations

import statistics
from perfbench.trace import hlo_io_bytes

FP_KERNELS = ("mul_acc", "sq_acc", "redc", "mont_mul", "mont_sq")
SHA_PAIR_BYTES = 96  # one pair-hash reads two 32-byte nodes and writes one


def steady_launches(ctx, program: str) -> list[dict]:
    return [e for e in ctx["ledger"] if e["program"] == program and not e["compile"]]


def median_launch_wall_ms(ctx, program: str = "bls_lane_verify"):
    walls = [1000.0 * e["seconds"] for e in steady_launches(ctx, program)]
    return statistics.median(walls) if walls else None


def counter_delta(ctx, name: str) -> float:
    return ctx["counters_after"].get(name, 0.0) - ctx["counters_before"].get(name, 0.0)


def first_call_s(ctx):
    """First calls nest (a lane's first launch holds the verify program's
    first call), so the seconds are those of the union of their spans."""
    spans = sorted(
        (e["t_mono_ns"] / 1e9 - e["seconds"], e["t_mono_ns"] / 1e9)
        for e in ctx["all_ledger"] if e["compile"]
    )
    total, reach = 0.0, float("-inf")
    for lo, hi in spans:
        total += max(0.0, hi - max(lo, reach))
        reach = max(reach, hi)
    return total if spans else None


def persistent_cache_hits(ctx):
    return float(ctx["monitor"].count("persistent_cache_hits")) if ctx["monitor"] else None


def fp_kernels_hbm_share(ctx):
    """Bytes the five Pallas Fp kernels' calls have to move, as their
    shapes in the trace say, over the HBM peak, over their device time."""
    if ctx["trace"] is None:
        return None
    calls = ctx["trace"].ops_named(FP_KERNELS)
    seconds = sum(s for _, _, s in calls)
    if not seconds:
        return None
    moved = sum(n * hlo_io_bytes(text) for text, n, _ in calls)
    return 100.0 * moved / ctx["peaks"]["hbm_bytes_per_s"] / seconds
