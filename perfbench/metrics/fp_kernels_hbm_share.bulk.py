"""Pallas Fp kernels: share of the HBM peak their calls reach, bulk traffic."""

from perfbench.readers import fp_kernels_hbm_share as read  # noqa: F401
