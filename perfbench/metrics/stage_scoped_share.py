"""field/curve/pairing ops: share of a `bls_lane_verify` launch's busy device time in which an operation under any stage scope of the program runs (the containers' own time is under none)."""

from perfbench.readers import stage_scoped_share as read  # noqa: F401
