"""Pool: mean over the window's steady `bls_lane_verify` launches of `bls.parse_wait`, the dispatcher's wait for a staged parse while a lane was free (a launch's `phases`; nothing from a pool that keeps no `pool.parse_wait_ns`)."""

from perfbench.readers import VERIFY_LAUNCH, steady_launches


def read(ctx):
    launches = steady_launches(ctx, VERIFY_LAUNCH)
    if not launches or "pool.parse_wait_ns" not in ctx["counters_after"]:
        return None
    waited = sum((e.get("phases") or {}).get("bls.parse_wait", 0.0) for e in launches)
    return 1000.0 * waited / len(launches)
