"""Pool: the idlest chip's busy seconds over the traced window (the result line's `busy_s` is the mean over the chips); a chip of the configuration's `lanes` that the trace does not hold ran nothing and reads 0."""


def read(ctx):
    trace = ctx["trace"]
    busy = getattr(trace, "_busy", None)  # per chip: the merged intervals in which an operation ran, ns
    if not busy or not trace.window_s:
        return None
    per_chip = [sum(b - a for a, b in busy[d]) / 1e9 for d in trace.devices]
    lanes = int(ctx["workload"].config.get("lanes") or len(per_chip))
    least = min(per_chip) if len(per_chip) >= lanes else 0.0
    return 100.0 * least / trace.window_s
