"""One worker of `perfbench.reference.parallel`: reads a JSON job from
standard input, writes the JSON answer. Pure Python, touches no JAX, so
it may run beside the process that holds the chip.

    {"op": "keys", "items": [scalar, ...]}            -> [[pk_hex], ...]
    {"op": "sign", "items": [[scalar, msg_hex], ...]} -> [sig_hex, ...]
    {"op": "judge", "items": [[pk, msg, sig] hex]}    -> [judgement, ...]
"""

from __future__ import annotations

import json
import os
import sys


def main() -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    from perfbench.reference import bls

    job = json.load(sys.stdin)
    op, items = job["op"], job["items"]
    if op == "keys":
        out = [bls.pubkey(s).hex() for s in items]
    elif op == "sign":
        out = [bls.sign(s, bytes.fromhex(m)).hex() for s, m in items]
    elif op == "judge":
        out = [bls.judge(*(bytes.fromhex(h) for h in triple)) for triple in items]
    else:
        raise ValueError(f"unknown op {op!r}")
    json.dump(out, sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
