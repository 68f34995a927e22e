"""Verify schedules: signers named by the indexed sets started (padding not counted) over `bls_lane_verify` launches, both of the window: the points a launch's `bls.aggregate` stage has to gather and sum."""

from perfbench.readers import VERIFY_LAUNCH, counter_delta, steady_launches


def read(ctx):
    launches = len(steady_launches(ctx, VERIFY_LAUNCH))
    points = counter_delta(ctx, "pool.aggregate_points_started")
    return points / launches if launches and points else None
