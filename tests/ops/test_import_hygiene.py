"""Import hygiene: no device compute at module import time.

Regression guard for the r3 multichip-gate failure: `ops/tower.py` used to
compute Frobenius constants via jitted JAX at import, initializing the
default accelerator backend before `dryrun_multichip` could pin its CPU
mesh. Every module in `lodestar_tpu` (ops especially) must import cleanly
with the default JAX backend made UNAVAILABLE — proving imports never
trigger backend initialization.

Runs in a subprocess so the parent's already-initialized backend can't
mask the regression.
"""

import subprocess
import sys

_SNIPPET = r"""
import pkgutil, importlib
# The detector is jax's backend registry after importing the whole
# package: it must still be EMPTY (backends initialize lazily, only on
# first device compute).
import lodestar_tpu
failures = []
for m in pkgutil.walk_packages(lodestar_tpu.__path__, "lodestar_tpu."):
    if m.name.endswith("__main__"):
        continue  # CLI entry parses argv
    if m.name.rsplit(".", 1)[-1].startswith("lib"):
        continue  # ctypes shared objects picked up by the walker
    try:
        importlib.import_module(m.name)
    except Exception as e:  # noqa: BLE001
        failures.append(f"{m.name}: {e!r}")
if failures:
    raise SystemExit("import failures:\n" + "\n".join(failures))
from jax._src import xla_bridge
live = list(getattr(xla_bridge, "_backends", {"<unknown>": None}))
if live:
    raise SystemExit(f"import-time device compute: backends initialized = {live}")
print("all-imports-clean")
"""


def test_no_import_time_device_compute():
    proc = subprocess.run(
        [sys.executable, "-c", _SNIPPET],
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, f"stdout={proc.stdout}\nstderr={proc.stderr[-3000:]}"
    assert "all-imports-clean" in proc.stdout
