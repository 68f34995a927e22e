"""Pool: jobs started over `bls_lane_verify` launches, both of the window (a count: 2.0 where a block's two jobs ride one launch)."""

from perfbench.readers import VERIFY_LAUNCH, counter_delta, steady_launches


def read(ctx):
    launches = len(steady_launches(ctx, VERIFY_LAUNCH))
    jobs = counter_delta(ctx, "pool.jobs_started")
    return jobs / launches if launches and jobs else None
