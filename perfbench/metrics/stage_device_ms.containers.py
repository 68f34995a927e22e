"""field/curve/pairing ops: device ms a `bls_lane_verify` launch of the traced span spends in a container (`while`, `conditional`, `call`) while no operation inside it runs: the loops' own time, which the rows by stage leave out."""

from perfbench.readers import containers_device_ms as read  # noqa: F401
