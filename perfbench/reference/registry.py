"""The plain reference of a validator registry whose signature sets name
their signers by index: big-integer G1 points, one addition at a time.

A registry of a deployment's size cannot be signed for in a session
(2^20 secret keys), and does not have to be: the verify kind judges
`(pubkey, message, signature)` triples, and a set that names signers
`S` plus one *closing entry* `E = PK - sum(registry[S])` sums to `PK`
exactly, so whoever judges the triple judges the row. The registry is
an arithmetic progression `P_i = P_0 + i*D` of G1 points from a fixed
seed: distinct valid keys at one addition each.

    seed_points(name)              -> (P_0, D) from a name
    progression(p0, d, n, start)   -> [P_start, ..., P_start+n-1]
    progression_in_blocks(...)     -> the same points, one field inversion a block of 512 (what the workers run)
    row_sum(points, indices)       -> the sum of points[i], repeats counted (None: the identity)
    closing_point(pk, points, idx) -> pk - row_sum(points, idx)
    progression_bytes(...)         -> the progression compressed, 48 bytes a key, over worker processes
    closing_keys(rows)             -> closing_point of many rows, compressed, over worker processes

Nothing of the program is imported. Run as a script it is one worker of
`progression_bytes` or `closing_keys`: a JSON job on standard input, raw
keys out.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys

if __name__ == "__main__":  # a worker: find the package from the file
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from perfbench.reference.bls12381 import curve as C  # noqa: E402
from perfbench.reference.bls12381 import fields as F  # noqa: E402
from perfbench.reference.bls12381.serdes import g1_from_bytes, g1_to_bytes  # noqa: E402

MAX_WORKERS = 8


def seed_points(name: str):
    """(P_0, D): two G1 points from a name, by scalars of its SHA-256."""
    scalars = [
        int.from_bytes(hashlib.sha256(f"{name}/{what}".encode()).digest(), "big") % F.R or 1
        for what in ("first", "step")
    ]
    return tuple(C.g1_mul(C.G1_GEN, s) for s in scalars)


def progression(p0, d, n: int, start: int = 0) -> list:
    """[P_0 + start*D, ..., P_0 + (start+n-1)*D], each from the one
    before by one addition."""
    point = C.g1_add(p0, C.g1_mul(d, start)) if start else p0
    out = []
    for _ in range(n):
        out.append(point)
        point = C.g1_add(point, d)
    return out


def add_to_each(base, offsets) -> list:
    """[base + o for o in offsets] with one field inversion for all of
    them (Montgomery's trick: the inverses of the x-differences from
    their running product), where `C.g1_add` pays one an addition. An
    addition whose points share an x (equal or opposite) is left to
    `C.g1_add`."""
    xb, yb = base
    diffs = [(o[0] - xb) % F.P for o in offsets]
    running, acc = [], 1
    for dx in diffs:
        running.append(acc)
        if dx:
            acc = acc * dx % F.P
    inv = F.fp_inv(acc)
    out = [None] * len(offsets)
    for k in range(len(offsets) - 1, -1, -1):
        dx = diffs[k]
        if not dx:
            out[k] = C.g1_add(base, offsets[k])
            continue
        xo, yo = offsets[k]
        lam = (yo - yb) * (inv * running[k] % F.P) % F.P
        inv = inv * dx % F.P
        x3 = (lam * lam - xb - xo) % F.P
        out[k] = (x3, (lam * (xb - x3) - yb) % F.P)
    return out


BLOCK = 512  # points a stretch of `progression_in_blocks`: one inversion a block


def progression_in_blocks(p0, d, n: int, start: int = 0) -> list:
    """`progression(p0, d, n, start)`, point for point, at a few field
    products a point: P_(j*B+k) = P_(j*B) + k*D with the B - 1 multiples
    of D made once and a block's additions sharing one inversion
    (`add_to_each`). What `progression_bytes` runs; the tests hold it to
    the plain loop."""
    multiples = progression(d, d, min(BLOCK, n) - 1)  # D, 2D, ...
    stride = C.g1_add(multiples[-1], d) if multiples else d  # B*D
    base = C.g1_add(p0, C.g1_mul(d, start)) if start else p0
    out = []
    while len(out) < n:
        out.append(base)
        out += add_to_each(base, multiples[: n - len(out)])
        base = C.g1_add(base, stride)
    return out


def row_sum(points, indices):
    """The sum of `points[i]` over `indices`, an index counted as often
    as it is named; None is the identity (no index, or a sum that
    cancels)."""
    acc = None
    for i in indices:
        acc = C.g1_add(acc, points[i])
    return acc


def closing_point(pk, points, indices):
    """E with `row_sum(points, indices) + E == pk`."""
    return C.g1_add(pk, C.g1_neg(row_sum(points, indices)))


class CompressedPoints:
    """`points[i]` over a registry kept as compressed bytes (48 a key):
    a point is decompressed when it is asked for."""

    def __init__(self, data: bytes):
        self.data = data

    def __len__(self) -> int:
        return len(self.data) // 48

    def __getitem__(self, i: int):
        if not 0 <= i < len(self):
            raise IndexError(i)
        return g1_from_bytes(self.data[48 * i : 48 * i + 48])


def _in_workers(jobs: list[dict], sizes: list[int]) -> bytes:
    """Each job to a worker process of its own (this file as a script);
    their raw outputs, `sizes[i]` bytes of job i, joined in order."""
    procs = [
        subprocess.Popen([sys.executable, os.path.abspath(__file__)], stdin=subprocess.PIPE,
                         stdout=subprocess.PIPE)
        for _ in jobs
    ]
    try:
        for p, job in zip(procs, jobs):
            p.stdin.write(json.dumps(job).encode())
            p.stdin.close()
        parts = []
        for p, size in zip(procs, sizes):
            data = p.stdout.read()
            if p.wait() != 0 or len(data) != size:
                raise RuntimeError(f"registry worker exited {p.returncode} with {len(data)} bytes of {size}")
            parts.append(data)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait()
            p.stdout.close()
    return b"".join(parts)


def _workers(asked: int | None, most: int) -> int:
    return max(1, min(asked or min(MAX_WORKERS, os.cpu_count() or 1), most))


def progression_bytes(p0, d, n: int, start: int = 0, workers: int | None = None) -> bytes:
    """`progression(p0, d, n, start)` compressed and joined: 48 * n
    bytes, made by a few worker processes, each a contiguous stretch."""
    if n <= 0:
        return b""
    workers = _workers(workers, n // 1024)
    if workers == 1:
        return b"".join(g1_to_bytes(p) for p in progression_in_blocks(p0, d, n, start))
    base, extra = divmod(n, workers)
    jobs, at = [], start
    for w in range(workers):
        count = base + (1 if w < extra else 0)
        jobs.append({"op": "progression", "p0": list(p0), "d": list(d), "start": at, "n": count})
        at += count
    return _in_workers(jobs, [48 * job["n"] for job in jobs])


def closing_keys(rows: list[tuple[bytes, bytes]], workers: int | None = None) -> list[bytes]:
    """For each (pubkey, its base signers' keys joined), both
    compressed, the compressed closing point `pubkey - sum(signers)`:
    `closing_point` of every row, the rows dealt to worker processes."""
    if not rows:
        return []
    workers = _workers(workers, len(rows) // 4)
    if workers == 1:
        return [_closing_key(pk, signers) for pk, signers in rows]
    dealt = [rows[w::workers] for w in range(workers)]
    jobs = [{"op": "closing", "rows": [[pk.hex(), signers.hex()] for pk, signers in part]} for part in dealt]
    data = _in_workers(jobs, [48 * len(part) for part in dealt])
    out: list = [None] * len(rows)
    at = 0
    for w, part in enumerate(dealt):
        out[w::workers] = [data[at + 48 * i : at + 48 * (i + 1)] for i in range(len(part))]
        at += 48 * len(part)
    return out


def _closing_key(pk: bytes, signers: bytes) -> bytes:
    points = CompressedPoints(signers)
    return g1_to_bytes(closing_point(g1_from_bytes(pk), points, range(len(points))))


def main() -> int:
    job = json.load(sys.stdin)
    out = sys.stdout.buffer
    if job["op"] == "progression":
        for point in progression_in_blocks(tuple(job["p0"]), tuple(job["d"]), job["n"], job["start"]):
            out.write(g1_to_bytes(point))
    elif job["op"] == "closing":
        for pk, signers in job["rows"]:
            out.write(_closing_key(bytes.fromhex(pk), bytes.fromhex(signers)))
    else:
        raise ValueError(f"unknown op {job['op']!r}")
    out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
