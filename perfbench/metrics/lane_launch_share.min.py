"""Pool: the least-served lane's share of the window's steady `bls_lane_verify` launches, times the lane count (the configuration's `lanes`, so that a lane that served nothing reads 0): 100 is even."""

from collections import Counter

from perfbench.readers import VERIFY_LAUNCH, steady_launches


def read(ctx):
    served = Counter(e.get("lane") for e in steady_launches(ctx, VERIFY_LAUNCH))
    lanes = int(ctx["workload"].config.get("lanes") or len(served))
    total = sum(served.values())
    if not total or not lanes:
        return None
    least = min(served.values()) if len(served) >= lanes else 0
    return 100.0 * lanes * least / total
