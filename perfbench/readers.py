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
    trace_span         (start, end) of the traced part of the window, seconds, on
                       the harness's clock (the trace's own events are on the profiler's)
    peaks              this device's row of `perfbench/peaks.json`
    workload           the kind's workload object

A reader that finds nothing to read returns None and the metric is left
out of the line; a share of a peak is never reported as 0.
"""

from __future__ import annotations

import statistics
from perfbench.trace import hlo_io_bytes

FP_KERNELS = ("mul_acc", "sq_acc", "redc", "mont_mul", "mont_sq")
VERIFY_LAUNCH = "bls_lane_verify"  # the launch's ledger entry, and its host span in a trace
STAGE_PREFIX = "bls."  # of the `jax.named_scope` names round the verify program's stages
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


def launch_scopes(ctx):
    """The device time of the verify launches of the traced span by stage
    scope (`Reduced.scope_seconds`), over those whose host span the trace
    holds whole, which leaves out the launch the profiler started or
    stopped in, and whose operations it kept: every launch counted
    brings all of its operations, or a row that is better lower would
    read a dropped event as a gain. An operation counts for the launch
    whose span it starts in. Nothing without a trace, such a launch or a
    scope."""
    if ctx["trace"] is None:
        return None
    found = ctx["trace"].scope_seconds(VERIFY_LAUNCH, STAGE_PREFIX)
    return found if found.spans and any(found.seconds) else None


def stage_device_ms(ctx, stage: str):
    """Device ms of a verify launch under one of the program's stage
    scopes (`bls.miller`); None where no operation carries it. The time
    in which only a container runs (`while`, `conditional`: 3.3% of a
    (256, 2) launch, my chip runs, PR 28) is under no stage:
    `containers_device_ms`."""
    found = launch_scopes(ctx)
    return 1000.0 * found.seconds[stage] / found.spans if found and stage in found.seconds else None


def containers_device_ms(ctx):
    """Device ms of a verify launch in which a container runs and no
    operation inside it: the loops' own time, which no stage row holds.
    With it the rows, the stages without a row and the unscoped
    operations add up to the launch's busy time."""
    found = launch_scopes(ctx)
    return 1000.0 * found.between_s / found.spans if found and found.between_s else None


def stage_scoped_share(ctx):
    """Share of a verify launch's busy device time in which an operation
    under a stage scope runs: a refactoring that drops a scope shows
    here. The containers' own time is busy and under no stage."""
    found = launch_scopes(ctx)
    if not found:
        return None
    scoped = sum(s for scope, s in found.seconds.items() if scope)
    return 100.0 * scoped / (sum(found.seconds.values()) + found.between_s)
