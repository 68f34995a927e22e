"""Which verifier and hasher the normal entry points resolve, from the
backend they observe — with the observation faked, since tier-1 has no
chip. A TPU with one device must give the device verifier on the offload
server and `BlsDeviceVerifierPool` plus device hashTreeRoot on the node
with default flags; a CPU backend keeps the CPU verifiers; a process
that builds no verifier never initialises a backend; and a chip that
cannot be had stops the process with the ways out.
"""

import asyncio
import subprocess
import sys

import pytest

from lodestar_tpu import telemetry, utils
from lodestar_tpu.chain.bls import BlsDeviceVerifierPool, BlsSingleThreadVerifier
from lodestar_tpu.metrics import create_metrics
from lodestar_tpu.node import BeaconNode, BeaconNodeOptions, configure_device_runtime
from lodestar_tpu.ssz import device_htr

ONE_CHIP = {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1}


@pytest.fixture
def restore_seams():
    """configure_device_runtime writes process-global seams."""
    yield
    device_htr.configure_device_htr(mode="auto", accelerator=False)
    telemetry.reset_launch_telemetry()


@pytest.fixture
def one_chip(monkeypatch, restore_seams):
    monkeypatch.setattr(utils, "probe_accelerator", lambda: dict(ONE_CHIP))


@pytest.fixture
def chip_taken(monkeypatch, restore_seams):
    import jax

    def busy():
        raise RuntimeError(
            "Unable to initialize backend 'tpu': ABORTED: The TPU is already in use by "
            "process with pid 4242."
        )

    monkeypatch.setattr(jax, "devices", busy)


def _boot(opts: BeaconNodeOptions) -> BeaconNode:
    from lodestar_tpu import params
    from lodestar_tpu.config import minimal_chain_config
    from lodestar_tpu.state_transition.genesis import create_interop_genesis_state

    prev = params.active_preset()
    params.set_active_preset("minimal")
    try:
        p = params.active_preset()
        far = 2**64 - 1
        cc = minimal_chain_config().replace(
            ALTAIR_FORK_EPOCH=far, BELLATRIX_FORK_EPOCH=far,
            CAPELLA_FORK_EPOCH=far, DENEB_FORK_EPOCH=far,
        )
        genesis = create_interop_genesis_state(
            4, p=p, genesis_fork_version=cc.GENESIS_FORK_VERSION
        )

        async def run():
            node = await BeaconNode.init(
                anchor_state=genesis, chain_config=cc, opts=opts, p=p, time_fn=lambda: 0.0
            )
            await node.close()
            return node

        return asyncio.run(run())
    finally:
        params.set_active_preset(prev)


def test_node_default_flags_on_one_chip(one_chip):
    node = _boot(BeaconNodeOptions(rest_enabled=False, manual_clock=True))
    assert isinstance(node.bls, BlsDeviceVerifierPool)
    assert node.device_runtime == {**ONE_CHIP, "verifier": "device", "hasher": "device", "lanes": 1}
    assert device_htr.device_htr_active()


def test_node_default_flags_on_cpu(restore_seams):
    node = _boot(BeaconNodeOptions(rest_enabled=False, manual_clock=True))
    assert isinstance(node.bls, BlsSingleThreadVerifier)
    assert node.device_runtime["platform"] == "cpu"
    assert (node.device_runtime["verifier"], node.device_runtime["hasher"]) == ("cpu", "cpu")
    assert not device_htr.device_htr_active()


def test_offload_node_leaves_the_chip_alone(chip_taken):
    """--bls-offload is one of the two ways out on a host whose chip is
    taken: such a node must not initialise a backend at all."""
    got = configure_device_runtime(
        BeaconNodeOptions(offload_endpoints=["127.0.0.1:1"]), create_metrics()
    )
    assert got["platform"] == "none" and got["hasher"] == "cpu"
    assert got["verifier"] == "offload+cpu"
    # a local device fallback does need the chip
    with pytest.raises(utils.AcceleratorUnavailable):
        configure_device_runtime(
            BeaconNodeOptions(offload_endpoints=["127.0.0.1:1"], offload_fallback="device"),
            create_metrics(),
        )


def test_server_backend_on_one_chip(one_chip):
    from lodestar_tpu.models.batch_verify import verify_signature_sets_device
    from lodestar_tpu.offload.server import build_backend

    for mode in ("auto", "off"):  # off = one lane on the device, as on the node
        backend = build_backend(mode)
        assert backend.description == {**ONE_CHIP, "verifier": "device", "lanes": 1}
        assert backend.mesh.lanes[0].verify_fn is verify_signature_sets_device
        assert backend.chip_status_fn == backend.mesh.chip_table


def test_server_backend_on_cpu():
    from lodestar_tpu.crypto.bls.api import verify_signature_sets
    from lodestar_tpu.offload.server import build_backend

    for mode in ("auto", "off"):
        backend = build_backend(mode)
        assert backend.verify is verify_signature_sets
        assert backend.description["verifier"] == "cpu-oracle" and backend.mesh is None


def test_chip_taken_stops_with_the_ways_out(chip_taken, monkeypatch, capsys):
    with pytest.raises(utils.AcceleratorUnavailable) as err:
        utils.probe_accelerator()
    assert "JAX_PLATFORMS=cpu" in str(err.value) and "--bls-offload" in str(err.value)
    assert "already in use" in str(err.value)

    from lodestar_tpu import cli
    from lodestar_tpu.offload import server

    monkeypatch.setattr(sys, "argv", ["offload-server", "--port", "0"])
    assert server.main() == 1
    assert cli.main(["dev", "--validators", "4", "--slots", "1"]) == 1
    assert capsys.readouterr().err.count("JAX_PLATFORMS=cpu") == 2


def test_use_pallas_lets_a_backend_error_through(monkeypatch):
    import jax

    from lodestar_tpu.ops import fp_pallas

    def busy():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", busy)
    fp_pallas.use_pallas.cache_clear()
    try:
        with pytest.raises(RuntimeError):
            fp_pallas.use_pallas()
    finally:
        monkeypatch.undo()
        fp_pallas.use_pallas.cache_clear()
    assert fp_pallas.use_pallas() is False  # the failure was not cached


_HTR_SNIPPET = """
import sys
{preimport}
from lodestar_tpu.ssz import device_htr
assert not device_htr.device_htr_active()          # never configured: host
device_htr.configure_device_htr(mode="auto", accelerator=True)
assert device_htr.device_htr_active()              # what node init observed
device_htr.configure_device_htr(accelerator=False)
assert not device_htr.device_htr_active()
assert ("jax" in sys.modules) == {jax_loaded}
print("resolved")
"""

_NO_BACKEND_SNIPPET = """
import sys
from lodestar_tpu import cli
for argv in (
    ["validator", "--interop-keys", "1", "--beacon-url", "http://127.0.0.1:1"],
    ["lightclient", "--server", "http://127.0.0.1:1"],
):
    try:
        cli.main(argv)
    except Exception as e:  # nothing listens there: each dies at its first request
        print(argv[0], "stopped at", type(e).__name__)
if "jax" in sys.modules:
    from jax._src import xla_bridge
    assert not xla_bridge._backends, list(xla_bridge._backends)
print("no-backend")
"""


def _run(code: str) -> str:
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, f"stdout={proc.stdout}\nstderr={proc.stderr[-3000:]}"
    return proc.stdout


@pytest.mark.parametrize("preimport,jax_loaded", [("", False), ("import jax", True)])
def test_htr_auto_does_not_depend_on_who_imported_jax(preimport, jax_loaded):
    out = _run(_HTR_SNIPPET.format(preimport=preimport, jax_loaded=jax_loaded))
    assert "resolved" in out


def test_validator_and_lightclient_start_without_a_backend():
    out = _run(_NO_BACKEND_SNIPPET)
    assert "validator stopped at" in out and "lightclient stopped at" in out
    assert "no-backend" in out
