"""Pool: jobs started over `bls_lane_verify` launches, both of the window (4.0 where two tenants' blocks ride every launch): `jobs_per_launch`'s reading, for the cell whose rate it moves."""

from perfbench.metrics.jobs_per_launch import read  # noqa: F401
