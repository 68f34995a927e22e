"""Verify schedules: share of the launches' rows that carry a set: signature sets started over the summed size class (the launch's rows, padding and empty slots included) of the steady `bls_lane_verify` launches, both of the window."""

from perfbench.readers import VERIFY_LAUNCH, counter_delta, steady_launches


def read(ctx):
    rows = sum(e["size_class"] for e in steady_launches(ctx, VERIFY_LAUNCH))
    sets = counter_delta(ctx, "pool.sig_sets_started")
    return 100.0 * sets / rows if rows and sets else None
