"""Probe-loop reconnect/backoff path: RECONNECT_BACKOFF_S progression,
the no-reconnect-while-outstanding rule, event-based close wakeup, and
health recovery within one probe interval."""

from __future__ import annotations

import asyncio
import time

import pytest

from lodestar_tpu.offload.client import BlsOffloadClient
from lodestar_tpu.offload.server import BlsOffloadServer
from lodestar_tpu.testing import FaultInjector, FaultKind, FaultRule

# a port with nothing listening (same choice as the existing dead-
# transport test)
DEAD_TARGET = "127.0.0.1:1"


def test_reconnect_backoff_slows_redial_of_dead_endpoint():
    """A dead endpoint is re-dialed on the RECONNECT_BACKOFF_S schedule,
    not once per probe interval: gaps between reconnects grow."""
    times: list[float] = []
    orig_reconnect = BlsOffloadClient._reconnect

    def spy_reconnect(self, ep):
        times.append(time.monotonic())
        orig_reconnect(self, ep)

    BlsOffloadClient._reconnect = spy_reconnect
    try:
        client = BlsOffloadClient(DEAD_TARGET, probe_interval_s=0.05)
        time.sleep(2.2)
        ep = client._endpoints[0]
        assert not ep.healthy
        assert ep.consecutive_failures >= 3
        # backoff (0.5, 1.0, ...) bounds redials: a 0.05s probe interval
        # would have produced ~40 dials without it
        assert 2 <= len(times) <= 5
        gaps = [b - a for a, b in zip(times, times[1:])]
        assert gaps[0] >= 0.3  # first backoff step (0.5s, scheduling slack)
        if len(gaps) >= 2:
            assert gaps[1] > gaps[0]  # progression, not a fixed delay
    finally:
        BlsOffloadClient._reconnect = orig_reconnect
        asyncio.run(client.close())


def test_no_reconnect_while_verifications_outstanding():
    """`offload/client.py` contract: a channel with RPCs in flight is
    never torn down by the probe loop — in-flight work fails or succeeds
    on its own merits."""
    reconnects = []
    orig_reconnect = BlsOffloadClient._reconnect

    def spy_reconnect(self, ep):
        reconnects.append(ep.target)
        orig_reconnect(self, ep)

    orig_probe_one = BlsOffloadClient._probe_one
    in_flight = []

    def probe_with_an_rpc_in_flight(self, ep):
        # on the probe thread, ahead of its first probe: set from the test's
        # thread, the first failed probe's redial could win the race
        if not in_flight:
            in_flight.append(ep)
            with self._lock:
                ep.outstanding = 1  # simulate an in-flight RPC
        return orig_probe_one(self, ep)

    BlsOffloadClient._reconnect = spy_reconnect
    BlsOffloadClient._probe_one = probe_with_an_rpc_in_flight
    try:
        client = BlsOffloadClient(DEAD_TARGET, probe_interval_s=0.05)
        ep = client._endpoints[0]
        # two failed probes are ~0.5 s apart on an idle machine (the first
        # backoff step) and later on a loaded one: wait for them, not for a
        # fixed time
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and ep.consecutive_failures < 2:
            time.sleep(0.02)
        assert ep.consecutive_failures >= 2  # probing continued
        assert reconnects == []  # but no teardown under outstanding work
        with client._lock:
            ep.outstanding = 0
        # the backoff schedule (now at ~2s steps) paces the next redial
        deadline = time.monotonic() + 4.0
        while time.monotonic() < deadline and not reconnects:
            time.sleep(0.05)
        assert len(reconnects) >= 1  # resumed once the work drained
    finally:
        BlsOffloadClient._reconnect = orig_reconnect
        BlsOffloadClient._probe_one = orig_probe_one
        asyncio.run(client.close())


def test_close_wakes_sleeping_probe_and_joins_thread():
    """close() must not leave the probe thread sleeping out a long
    interval (it could re-dial a closed channel); the event wakeup makes
    close prompt and the thread is joined, not orphaned."""
    server = BlsOffloadServer(lambda s: True, port=0)
    server.start()
    client = BlsOffloadClient(f"127.0.0.1:{server.port}", probe_interval_s=30.0)
    try:
        time.sleep(0.3)  # first probe done; thread now asleep for ~30s
        assert client._probe_thread.is_alive()
        t0 = time.monotonic()
        asyncio.run(client.close())
        assert time.monotonic() - t0 < 5.0  # not probe_interval_s
        assert not client._probe_thread.is_alive()
    finally:
        server.stop()


def test_health_recovers_within_one_probe_interval_after_fault_window():
    """Status failures mark the endpoint unhealthy (with backoff-paced
    redials); once the transport heals, the next probe restores health
    and resets the failure counter."""
    server = BlsOffloadServer(lambda s: True, port=0)
    server.start()
    inj = FaultInjector(
        [
            FaultRule(
                FaultKind.UNAVAILABLE,
                methods=frozenset({"status"}),
                first_call=0,
                last_call=1,
            )
        ]
    )
    client = BlsOffloadClient(
        f"127.0.0.1:{server.port}",
        probe_interval_s=0.05,
        transport_wrapper=inj.wrap_transport,
    )
    try:
        deadline = time.monotonic() + 1.0
        while time.monotonic() < deadline and client._endpoints[0].healthy:
            time.sleep(0.02)
        assert not client._endpoints[0].healthy  # fault window observed

        # fault window is 2 probes; backoff schedules the 3rd at ~1.5s
        deadline = time.monotonic() + 4.0
        while time.monotonic() < deadline and not client._endpoints[0].healthy:
            time.sleep(0.05)
        ep = client._endpoints[0]
        assert ep.healthy
        assert ep.consecutive_failures == 0
        assert client.can_accept_work()
    finally:
        asyncio.run(client.close())
        server.stop()
