"""Offload wire: mean `offload_rpc` wall less mean `offload_serve` wall, both over the window's entries (gRPC both ways, thread hops, digest check), ms."""

from perfbench.offload_readers import wire_ms as read  # noqa: F401
