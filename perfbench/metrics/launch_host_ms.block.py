"""Pool: median host work of a `bls_lane_verify` launch before the device can start (`bls.parse` + `bls.dispatch`), one block at a time."""

from perfbench.phase_readers import launch_host_ms as read  # noqa: F401
