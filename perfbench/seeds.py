"""perfbench/seeds.py — `correct` over many seeds in one process.

    python3 perfbench/seeds.py --workloads <cell>[,<cell>] --seeds <n>[,<n>...] --seconds <s>

Where set-up is long (a node cell traces its verify program for 80 s in
every process) a dozen seeds cost a dozen set-ups through `run.py`. This
boots the system once, as the cells' one configuration says, and puts
each seed of each cell through `run.run_cell`: inputs from the seed,
warm-up, a window of `--seconds` at the cell's own call and wave sizes,
the reference pass. One line per run on standard output, the compared
numbers beside their limits in it. It reads `correct` and nothing else:
its times are not a cell's times (`setup_s` counts the runs before it).
The driver does not run it; `--entry reference [--control <name>]`
works as in `run.py`.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import manifest, run  # noqa: E402


class Kept:
    """The system, kept up between runs: `run_cell` closes what it was
    given, and the next seed needs it."""

    def __init__(self, inner):
        self.inner = inner

    def __getattr__(self, name):
        return getattr(self.inner, name)

    async def close(self) -> None:
        pass


async def all_runs(cells, seeds, seconds, entry, boot_args, device) -> bool:
    system = await entry.boot(*boot_args)
    kept = Kept(system)

    async def boot():
        return kept

    every = True
    try:
        for cell in cells:
            for seed in seeds:
                result = await run.run_cell(cell, seed, seconds, False, boot, device)
                every = every and result["correct"]
                print(json.dumps({
                    "workload": cell.name, "seed": seed, "correct": result["correct"],
                    "attempted": result["attempted"], "failed": result["failed"],
                    "window": result["window"], "compared": result["compared"],
                }), flush=True)
    finally:
        await system.close()
    return every


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workloads", required=True, help="cells of one configuration, comma separated")
    ap.add_argument("--seeds", required=True, help="comma separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--entry", default=None)
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)

    cells = [manifest.load_cell(name) for name in args.workloads.split(",")]
    if len({c.workload["config"] for c in cells}) != 1:
        ap.error("the cells have to share one configuration: one boot serves them all")
    entry = cells[0].entry(args.entry)
    if entry.NEEDS_CHIP:
        device = run.find_chip(max(c.chips for c in cells))
        if device is None:
            return run.EXIT_NO_CHIP
    else:
        device = {"platform": "host", "kind": "reference", "count": 0}
    boot_args = (cells[0].config, args.control) if args.control else (cells[0].config,)
    seeds = [int(s) for s in args.seeds.split(",")]
    every = asyncio.run(all_runs(cells, seeds, args.seconds, entry, boot_args, device))
    return 0 if every else 1


if __name__ == "__main__":
    sys.exit(main())
