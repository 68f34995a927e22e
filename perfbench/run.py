"""perfbench/run.py — one run of one cell of `BENCHMARK.json`.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's inputs from the seed, boots the system under test as
its configuration's `entry` says, warms every shape the traffic uses
(set-up), drives the traffic for `--seconds`, then frees the system and
compares what the timed path answered with the plain reference. The last
line of standard output is the result; the numbers compared stand beside
their limits in it, and as the last lines of standard error.

A cell whose entry needs the chip fails, printing no result, where JAX
finds no TPU or fewer chips than the cell asks for. `--entry reference`
(with or without `--control <name>`) puts the plain reference in the
program's place and needs no chip: the controls and the tests use it.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import generator, manifest, trace as trace_mod  # noqa: E402

EXIT_NO_CHIP = 3


def log(msg: str) -> None:
    print(f"[perfbench +{time.monotonic() - T_PROCESS:7.2f}s] {msg}", file=sys.stderr, flush=True)


# --- the chip -----------------------------------------------------------------


def find_chip(chips: int) -> dict | None:
    """The device as JAX reports it, or None where it is no TPU or there
    are fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    found = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if found["platform"] != "tpu" or found["count"] < chips:
        log(f"no chip for this cell: JAX found {found}, the cell asks for {chips} TPU chip(s)")
        return None
    return found


def load_peaks(device_kind: str) -> dict:
    table = manifest.load_json(os.path.join(manifest.BENCH_DIR, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in perfbench/peaks.json")
    return table[device_kind]


class JaxMonitor:
    """Compilations and persistent-cache traffic as JAX itself reports
    them, each with the time it was seen."""

    EVENTS = {
        "/jax/compilation_cache/cache_hits": "persistent_cache_hits",
    }
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seen: list[tuple[str, float]] = []
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_):
        if event in self.EVENTS:
            self.seen.append((self.EVENTS[event], time.monotonic()))

    def _on_duration(self, event, duration, **_):
        if event == self.COMPILE:
            self.seen.append(("backend_compiles", time.monotonic()))

    def count(self, what: str, start: float = float("-inf"), end: float = float("inf")) -> int:
        return sum(1 for name, t in self.seen if name == what and start <= t <= end)


class TraceWindow(threading.Thread):
    """The profiler, on for a short span inside the measured window. The
    session is JAX's own (`jax.profiler.start_trace` wraps the same
    object), stopped with `stop()`: that hands back the serialized trace
    and writes nothing, where `stop_trace` also exports a viewer file,
    which took minutes for the verify path's millions of events. The
    Python tracer is off: it writes an event per Python call (2.5M in
    4 s of the root cell) and slows the host it is meant to watch."""

    def __init__(self, start_after: float, seconds: float):
        super().__init__(name="perfbench-trace", daemon=True)
        self.start_after, self.seconds = start_after, seconds
        self.window_s = self.t0 = self.t1 = 0.0
        self.xspace = b""
        self.error: BaseException | None = None

    def run(self) -> None:
        import jax
        from jax._src.lib import _profiler

        try:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            time.sleep(self.start_after)
            session = _profiler.ProfilerSession(options)
            self.t0 = time.monotonic()
            time.sleep(self.seconds)
            self.t1 = time.monotonic()
            self.window_s = self.t1 - self.t0
            self.xspace = session.stop()
            log(f"trace: {self.window_s:.2f}s traced, {len(self.xspace) / 1e6:.1f} MB, "
                f"stopped in {time.monotonic() - self.t1:.1f}s")
        except BaseException as e:  # surfaced by the run, which joins this thread
            self.error = e

    def reduced(self) -> trace_mod.Reduced:
        self.join()
        if self.error is not None:
            raise RuntimeError(f"profiler failed: {self.error!r}")
        t0 = time.monotonic()
        try:
            return trace_mod.read_xplane(self.xspace, self.window_s)
        finally:
            self.xspace = b""
            log(f"trace: reduced in {time.monotonic() - t0:.1f}s")


# --- one run ------------------------------------------------------------------


async def run_cell(cell, seed: int, seconds: float, traced: bool, boot, device: dict) -> dict:
    """Everything of a run but the look for a chip: `boot()` gives the
    system under test. Returns the result line as a dict."""
    on_chip = device["platform"] == "tpu"
    kind = cell.kind()
    replay = generator.build_replay(cell.traffic, seed)
    workload = kind.Workload(cell.config, cell.traffic, cell.spec, replay, seed)
    workload.prepare()
    log(f"inputs made from seed {seed}: {len(replay)} replay entries")

    monitor = JaxMonitor() if on_chip else None
    system = await boot()
    log(f"system up: {system.runtime}")
    workload.attach(system)

    span = contextlib.nullcontext
    if on_chip:
        import jax

        label = kind.SPAN
        span = lambda: jax.profiler.TraceAnnotation(label)  # noqa: E731

    warm, _, _ = await generator.drive(
        workload.call, cell.traffic, calls=cell.spec["warm_calls"], clock=time.monotonic, span=span
    )
    log(f"warm: {len(warm)} calls, last {1000 * warm[-1].seconds:.1f} ms")
    gc.collect()
    gc.freeze()
    cpu_before = time.process_time()

    tracer = None
    if traced and on_chip:
        tracer = TraceWindow(
            start_after=min(1.0, seconds / 4),
            seconds=min(float(cell.spec["trace_seconds"]), seconds / 2),
        )
    counters_before = system.counters()
    if tracer:
        tracer.start()
    records, start, end = await generator.drive(
        workload.call, cell.traffic, seconds=seconds, first_call=len(warm),
        clock=time.monotonic, span=span,
    )
    counters_after = system.counters()
    cpu_window = time.process_time() - cpu_before
    log(f"window closed: {len(records)} calls issued, all answered by +{time.monotonic() - end:.2f}s")

    memory_peak = None
    if on_chip:
        peaks = [d.memory_stats().get("peak_bytes_in_use", 0) for d in jax.devices()[: cell.chips]]
        memory_peak = max(peaks)
    all_ledger = system.launch_ledger()
    ledger = [e for e in all_ledger if e["t_mono_ns"] >= start * 1e9]
    fallbacks = system.fallbacks(counters_after) - system.fallbacks(counters_before)
    reduced = tracer.reduced() if tracer else None

    metrics = workload.end_to_end(records, start, end)
    metrics["setup_s"] = start - T_PROCESS
    window = {
        "compiles": monitor.count("backend_compiles", start, end) if monitor else 0,
        "cache_loads": monitor.count("persistent_cache_hits", start, end) if monitor else 0,
        "first_calls": sum(1 for e in ledger if e["compile"] and e["t_mono_ns"] <= end * 1e9),
        "calls_answered_in_window": sum(1 for r in records if r.error is None and r.done <= end),
        "process_cpu_s": cpu_window,  # for a run that reads far off: was this process busy or waiting
    }

    workload.release()
    await system.close()
    del system  # the program's state is freed before the reference runs
    gc.unfreeze()
    gc.collect()

    t_ref = time.monotonic()
    compared = workload.check(warm, records)
    log(f"reference pass: {time.monotonic() - t_ref:.2f}s")

    device_out = dict(device)
    device_out["memory_peak_bytes"] = memory_peak
    result: dict = {
        "correct": all(c["holds"] for c in compared),
        "attempted": len(records),
        "failed": int(sum(1 for r in records if r.error is not None) + workload.failed(records) + fallbacks),
        "device": device_out,
        "window": window,
    }
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if traced:
        ctx = {
            "workload": workload, "records": records,
            "start": start, "end": end, "ledger": ledger, "all_ledger": all_ledger,
            "counters_before": counters_before, "counters_after": counters_after,
            "monitor": monitor, "trace": reduced,
            "trace_span": (tracer.t0, tracer.t1) if tracer else None,
            "peaks": load_peaks(device["kind"]) if on_chip else None,
        }
        values = {}
        for m in cell.per_layer:
            value = cell.reader(m["name"])(ctx)
            if value is not None:
                values[m["name"]] = value
        if reduced is not None:
            device_out["busy_s"] = reduced.busy_s
            device_out["window_s"] = reduced.window_s
            result["breakdown"] = reduced.breakdown()
    else:
        values = {m["name"]: metrics[m["name"]] for m in cell.end_to_end if m["name"] in metrics}
    result["metrics"] = {k: {"value": v, "unit": units[k]} for k, v in values.items()}
    result["compared"] = compared  # last, as the contract asks
    return result


def print_compared(compared: list[dict]) -> None:
    for c in compared:
        sign = ">=" if c.get("at_least") else "<="
        of = f" of {c['of']}" if "of" in c else ""
        verdict = "ok" if c["holds"] else "FAILS"
        print(f"compare {c['name']}: {c['value']}{of} (limit {sign} {c['limit']}) {verdict}",
              file=sys.stderr)
    sys.stderr.flush()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--entry", default=None, help="entry other than the configuration's (reference)")
    ap.add_argument("--control", default=None, help="guarantee the reference entry leaves out")
    args = ap.parse_args(argv)

    cell = manifest.load_cell(args.workload)
    entry = cell.entry(args.entry)
    if args.control and entry.NEEDS_CHIP:
        ap.error("--control goes with --entry reference")
    if entry.NEEDS_CHIP:
        device = find_chip(cell.chips)
        if device is None:
            return EXIT_NO_CHIP
        load_peaks(device["kind"])  # an unknown device is an error before any work
    else:
        device = {"platform": "host", "kind": "reference", "count": 0}
    boot_args = (cell.config, args.control) if args.control else (cell.config,)
    result = asyncio.run(
        run_cell(cell, args.seed, args.seconds, bool(args.trace), lambda: entry.boot(*boot_args), device)
    )
    print_compared(result["compared"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
