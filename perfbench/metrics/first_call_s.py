"""Entry + platform resolution: seconds of every first call (trace + compile or cache load) in the launch ledger."""

from perfbench.readers import first_call_s as read  # noqa: F401
