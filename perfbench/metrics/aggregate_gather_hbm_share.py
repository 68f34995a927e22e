"""field/curve/pairing ops: bytes the operations under `bls.aggregate/gather` have to move, as their shapes in the trace say, over the HBM peak, over their device time. A gather reads the rows it is asked for, not its table, so an operand counts for no more than the result it feeds: result bytes written once, each operand read up to that many."""

import re

from perfbench.trace import CONTAINER_OPS, DTYPE_BYTES, op_short_name

GATHER_SCOPE = re.compile(r"(?:^|/)bls\.aggregate/gather(?=[/:]|$)")
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")


def moved_bytes(hlo_text: str) -> int:
    """The first shape an operation's HLO text names is its result's;
    the others are its operands'."""
    head = hlo_text.split(", custom_call_target")[0].split(", kind=")[0]
    sizes = []
    for dtype, dims in _SHAPE.findall(head):
        n = DTYPE_BYTES[dtype]
        for d in dims.split(","):
            if d:
                n *= int(d)
        sizes.append(n)
    if not sizes:
        return 0
    return sizes[0] + sum(min(size, sizes[0]) for size in sizes[1:])


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    stacks = getattr(trace, "_stacks", {})
    moved = seconds = 0.0
    for text, (calls, spent, _) in trace.op_calls.items():
        if op_short_name(text) in CONTAINER_OPS or not GATHER_SCOPE.search(stacks.get(text) or ""):
            continue
        moved += calls * moved_bytes(text)
        seconds += spent
    if not seconds or not moved:
        return None
    return 100.0 * moved / ctx["peaks"]["hbm_bytes_per_s"] / seconds
