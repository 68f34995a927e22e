"""State root kernels: 96 bytes per pair-hash launched in the traced span, over the HBM peak, over the device time of the `hash_pairs` programs in the trace."""

from perfbench.readers import SHA_PAIR_BYTES


def read(ctx):
    if ctx["trace"] is None:
        return None
    seconds = sum(ctx["trace"].program_runs("hash_pairs"))
    lo, hi = (1e9 * t for t in ctx["trace_span"])
    pairs = sum(
        e["size_class"] for e in ctx["ledger"] if e["program"] == "merkle_level" and lo <= e["t_mono_ns"] <= hi
    )
    if not seconds or not pairs:
        return None
    return 100.0 * pairs * SHA_PAIR_BYTES / ctx["peaks"]["hbm_bytes_per_s"] / seconds
