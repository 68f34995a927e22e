"""State root: 99th percentile of the window's flushes (the end-to-end metric is their mean)."""

from perfbench import stats


def read(ctx):
    series = ctx["workload"].flush_series_ms(ctx["records"], ctx["end"])
    return stats.percentile(series, 99) if series else None
