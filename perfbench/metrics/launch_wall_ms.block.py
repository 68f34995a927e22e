"""Verify schedules: median wall of the window's `bls_lane_verify` launches, one block at a time."""

from perfbench.readers import median_launch_wall_ms as read  # noqa: F401
