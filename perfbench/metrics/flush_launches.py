"""State root: device launches per flush (a count), from the collector's flush stats."""


def read(ctx):
    flushes = [r.detail["launches"] for r in ctx["records"] if r.error is None and "launches" in r.detail]
    return sum(flushes) / len(flushes) if flushes else None
