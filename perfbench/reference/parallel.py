"""Spread pure-Python reference work (signing ~30 ms, judging ~135 ms a
set) over a few worker processes, each started, fed, read and waited
for here. The workers import nothing but `perfbench.reference`."""

from __future__ import annotations

import json
import os
import subprocess
import sys

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bls_worker.py")
MAX_WORKERS = 8


def parallel_map(op: str, items: list, workers: int | None = None) -> list:
    if not items:
        return []
    n = min(len(items), workers or min(MAX_WORKERS, os.cpu_count() or 1))
    slices = [items[i::n] for i in range(n)]
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER], stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True
        )
        for _ in slices
    ]
    try:
        for p, part in zip(procs, slices):
            p.stdin.write(json.dumps({"op": op, "items": part}))
            p.stdin.close()
        answers = []
        for p in procs:
            text = p.stdout.read()
            if p.wait() != 0:
                raise RuntimeError(f"reference worker exited {p.returncode} on {op}")
            answers.append(json.loads(text))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait()
            p.stdout.close()
    out = [None] * len(items)
    for i, part in enumerate(answers):
        out[i::n] = part
    return out


def judge_many(triples: list[tuple[bytes, bytes, bytes]], workers: int | None = None) -> list[dict]:
    """`bls.judge` of every (pubkey, message, signature), in order."""
    return parallel_map("judge", [[b.hex() for b in t] for t in triples], workers)
