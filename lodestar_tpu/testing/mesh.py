"""Tier-1 mesh fixtures: drive the multi-chip serving path on CPU.

The production mesh backs onto real accelerator devices; tier-1 runs on
a CPU container. Two tools close the gap:

1. **Forced host platform** — `XLA_FLAGS=--xla_force_host_platform_device_count=N`
   makes the CPU backend expose N virtual devices. `tests/conftest.py`
   forces 8 in-process; `mesh_env(n)` builds the same environment for a
   SUBPROCESS (the belt-and-braces check that the flag alone, without
   the test harness, is sufficient), and `virtual_device_count()` /
   `require_virtual_devices(n)` gate in-process tests so a run on real
   hardware (or without the flag) SKIPS instead of failing.

2. **Fake lane backends** — the real sharded program takes minutes to
   compile on CPU; per-device-lane and sharded-bulk INVARIANTS (who
   served what, how errors degrade) don't need real pairings. `FakeLaneRig`
   builds an N-lane `VerifierMesh` over recording fake backends with
   injectable per-lane latency/errors and a fake collective that records
   which device subset each sharded launch used.
"""

from __future__ import annotations

import os
import threading
import time

from lodestar_tpu.chain.bls.mesh import MeshLane, VerifierMesh

__all__ = [
    "mesh_env",
    "virtual_device_count",
    "require_virtual_devices",
    "FakeLaneRig",
]


def mesh_env(n_devices: int = 8, base_env: dict | None = None) -> dict:
    """Environment for a subprocess that must see `n_devices` virtual
    CPU devices — the satellite check that the mesh path works under
    nothing but the documented flags."""
    env = dict(os.environ if base_env is None else base_env)
    flags = env.get("XLA_FLAGS", "")
    flags = " ".join(
        part
        for part in flags.split()
        if "xla_force_host_platform_device_count" not in part
    )
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    return env


def virtual_device_count() -> int:
    """Devices the in-process jax backend exposes (0 when jax is
    unimportable/uninitializable)."""
    try:
        import jax

        return len(jax.devices())
    except Exception:
        return 0


def require_virtual_devices(n: int):
    """pytest.skip unless the in-process platform exposes >= n devices
    (conftest forces 8 on CPU; a real-chip run without the flag skips
    rather than fails). Returns the device list."""
    import pytest

    count = virtual_device_count()
    if count < n:
        pytest.skip(f"needs {n} visible devices, have {count}")
    import jax

    return jax.devices()[:n]


class FakeLaneRig:
    """N-lane mesh over recording fake backends.

    Each lane's verify_fn sleeps `call_s`, records (device_index, tag)
    per call, and raises while its index is in `failing` — the seam for
    lane-kill tests. The collective `sharded_fn` records the device
    subset per launch and delegates the verdict to `verdict_fn`
    (default: all sets valid). `calls`/`sharded_calls` are appended
    under a lock so executor threads can't tear them. The lanes state
    their own facts, as production lanes do: `with_prepared` gives them
    the staged-inputs seam, `staged_prep_host_only` says that what is
    staged for it touches no device (so a one-lane pool stages too)."""

    def __init__(
        self,
        n_lanes: int,
        *,
        call_s: float = 0.0,
        wedge_threshold: int = 2,
        verdict_fn=None,
        with_sharded: bool = True,
        with_prepared: bool = False,
        staged_prep_host_only: bool = False,
    ) -> None:
        self.call_s = call_s
        self.verdict_fn = verdict_fn or (lambda sets: True)
        self._record_lock = threading.Lock()
        self.calls: list[tuple[int, int]] = []  # guarded by: _record_lock
        self.prepared_calls: list[tuple[int, int]] = []  # guarded by: _record_lock
        self.sharded_calls: list[tuple[int, ...]] = []  # guarded by: _record_lock
        self.failing: set[int] = set()  # guarded by: _record_lock — lanes currently erroring
        lanes = [
            MeshLane(
                i,
                self._make_lane_fn(i),
                wedge_threshold=wedge_threshold,
                verify_prepared_fn=(
                    self._make_prepared_fn(i) if with_prepared else None
                ),
                staged_prep_host_only=staged_prep_host_only,
            )
            for i in range(n_lanes)
        ]
        self.mesh = VerifierMesh(
            lanes, sharded_fn=self._sharded if with_sharded else None
        )

    @staticmethod
    def prep_fn(sets, lane_hint):
        """Pool `prep_fn` seam twin: wraps the sets as staged 'inputs'
        so the prepared lane callables can delegate to `verdict_fn` —
        the pipeline invariants don't need real limb arrays."""
        return ("prepped", list(sets), lane_hint)

    def _make_lane_fn(self, index: int):
        def lane_fn(sets):
            if self.call_s:
                time.sleep(self.call_s)
            with self._record_lock:
                failing = index in self.failing
                self.calls.append((index, len(sets)))
            if failing:
                raise RuntimeError(f"injected device error on dev{index}")
            return self.verdict_fn(sets)

        return lane_fn

    def _make_prepared_fn(self, index: int):
        def lane_prepared_fn(inputs):
            tag, sets, _hint = inputs
            assert tag == "prepped"
            if self.call_s:
                time.sleep(self.call_s)
            with self._record_lock:
                failing = index in self.failing
                self.calls.append((index, len(sets)))
                self.prepared_calls.append((index, len(sets)))
            if failing:
                raise RuntimeError(f"injected device error on dev{index}")
            return self.verdict_fn(sets)

        return lane_prepared_fn

    def _sharded(self, sets, device_indices):
        if self.call_s:
            time.sleep(self.call_s)
        with self._record_lock:
            failing = bool(set(device_indices) & self.failing)
            self.sharded_calls.append(tuple(device_indices))
        if failing:
            raise RuntimeError(f"injected device error in collective {device_indices}")
        return self.verdict_fn(sets)

    def kill(self, index: int) -> None:
        with self._record_lock:
            self.failing.add(index)

    def heal(self, index: int) -> None:
        with self._record_lock:
            self.failing.discard(index)

    def served_by(self, index: int) -> int:
        with self._record_lock:
            return sum(1 for i, _ in self.calls if i == index)
