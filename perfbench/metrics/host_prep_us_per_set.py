"""Verify schedules: host prep time per signature set over the window (`lodestar_bls_prep_seconds` over `lodestar_bls_prep_sets_total`)."""

from perfbench.phase_readers import host_prep_us_per_set as read  # noqa: F401
