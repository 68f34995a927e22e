"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's test posture of exercising the full concurrency
topology without real hardware (reference
`packages/beacon-node/test/utils/node/beacon.ts` getDevBeaconNode spins
multi-node topologies in-process). Runs on the chip go through
`chip_smoke.py` (README "Running").
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# tests never take the accelerator (backends initialise lazily, so this
# holds even where something imported jax before this file)
jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the pairing / batch-verify graphs take
# minutes to compile on the CPU backend; caching makes repeat test runs
# (and the driver's round-end run) pay compile once per machine.
from lodestar_tpu.utils import enable_compile_cache  # noqa: E402

enable_compile_cache()


def pytest_configure(config):
    # tier-1 deselects these via `-m 'not slow'` (ROADMAP verify line)
    config.addinivalue_line(
        "markers", "slow: long-running tests (chaos soaks) excluded from tier-1"
    )
