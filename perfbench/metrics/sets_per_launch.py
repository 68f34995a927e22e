"""Pool: signature sets started over `bls_lane_verify` launches, both of the window."""

from perfbench.readers import counter_delta, steady_launches


def read(ctx):
    launches = len(steady_launches(ctx, "bls_lane_verify"))
    sets = counter_delta(ctx, "pool.sig_sets_started")
    return sets / launches if launches and sets else None
