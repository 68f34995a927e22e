"""The system under test, built as a deployment builds it: a beacon node
booted as `python -m lodestar_tpu beacon` boots one (`BeaconNode.init`
at the preset, chain config and genesis the configuration's `boot`
states), whose own resolution of the backend decides verifier and
hasher. The benchmark drives two of its seams and reads its counters;
nothing else of the program is imported.

    verifier   `node.bls`: `IBlsVerifier.verify_signature_sets` on the
               `BlsDeviceVerifierPool` the node resolved
    collector  `DirtyCollector` under the `--htr-device auto` placement
               that node init configured
"""

from __future__ import annotations

NEEDS_CHIP = True

# each of these moving means the device path degraded to a fallback
FALLBACK_COUNTERS = (
    "lodestar_bls_prep_fallback_total",
    "lodestar_bls_single_launch_fallback_total",
    "lodestar_ssz_htr_fallback_total",
    "lodestar_resilience_fallback_total",
    "lodestar_sched_lane_wedge_trips_total",
)


class NodeSystem:
    def __init__(self, node, launch_ledger_size: int):
        from lodestar_tpu import telemetry

        self.node = node
        self.runtime = dict(node.device_runtime)
        self.telemetry = telemetry
        telemetry.configure_launch_telemetry(ledger_size=launch_ledger_size)

    # -- verify seam -----------------------------------------------------------

    def verify_payload(self, triples: list[tuple[bytes, bytes, bytes]]):
        from lodestar_tpu.crypto.bls.api import SignatureSet

        return [SignatureSet(pubkey=pk, message=m, signature=s) for pk, m, s in triples]

    def verify_options(self, batchable: bool, priority: str):
        from lodestar_tpu.chain.bls import VerifySignatureOpts
        from lodestar_tpu.scheduler import PriorityClass

        return VerifySignatureOpts(batchable=batchable, priority=PriorityClass[priority])

    async def verify(self, payload, options) -> bool:
        return await self.node.bls.verify_signature_sets(payload, options)

    def expect_verifier(self, want: str) -> None:
        if self.runtime["verifier"] != want:
            raise RuntimeError(f"node resolved {self.runtime}, the configuration states verifier={want}")

    # -- state-root seam -------------------------------------------------------

    def build_stack(self, leaves):
        """The retained level stack a state tracker holds, leaf level
        first, built on the host path as the tracker builds it."""
        from lodestar_tpu.ssz.hash import hash_nodes_cpu

        levels = [leaves]
        while levels[-1].shape[0] > 1:
            levels.append(hash_nodes_cpu(levels[-1]).copy())  # the collector writes into it
        return levels

    def flush(self, levels, dirty) -> dict:
        from lodestar_tpu.ssz.device_htr import DirtyCollector

        collector = DirtyCollector()
        collector.add_stack_job(levels, dirty)
        return collector.flush()

    def expect_hasher(self, want: str) -> None:
        if self.runtime["hasher"] != want:
            raise RuntimeError(f"node resolved {self.runtime}, the configuration states hasher={want}")

    # -- counters --------------------------------------------------------------

    def launch_ledger(self) -> list[dict]:
        return self.telemetry.launch_ledger()

    def counters(self) -> dict[str, float]:
        """Every sample of the node's registry, summed over its labels,
        with the pool's own tallies beside them."""
        out: dict[str, float] = {}
        for family in self.node.metrics.creator.registry.collect():
            for sample in family.samples:
                out[sample.name] = out.get(sample.name, 0.0) + sample.value
        for key, value in dict(getattr(self.node.bls, "metrics", {})).items():
            out[f"pool.{key}"] = float(value)
        return out

    def fallbacks(self, counters: dict[str, float]) -> float:
        return sum(counters.get(name, 0.0) for name in FALLBACK_COUNTERS)

    async def close(self) -> None:
        await self.node.close()


def check_stated_constants(config: dict) -> None:
    """The pool constants the configuration states are the program's."""
    from lodestar_tpu.chain.bls import pool
    from lodestar_tpu.ssz import hash as ssz_hash

    for name, want in config.get("pool", {}).items():
        have = getattr(pool, name)
        if have != want:
            raise RuntimeError(f"configuration states pool.{name}={want}, the program has {have}")
    want = config.get("device_min_pairs")
    if want is not None and ssz_hash.DEVICE_MIN_PAIRS != want:
        raise RuntimeError(
            f"configuration states device_min_pairs={want}, the program has {ssz_hash.DEVICE_MIN_PAIRS}"
        )


async def boot(config: dict) -> NodeSystem:
    """As `cli._run_beacon` builds a node from the command's defaults;
    what departs from them is stated in the configuration's `boot`."""
    from lodestar_tpu import config as chain_configs
    from lodestar_tpu import params
    from lodestar_tpu.node import BeaconNode, BeaconNodeOptions
    from lodestar_tpu.state_transition.genesis import create_interop_genesis_state
    from lodestar_tpu.utils import enable_compile_cache

    stated = config["boot"]
    check_stated_constants(config)
    enable_compile_cache()
    params.set_active_preset(stated["preset"])
    p = params.active_preset()
    cc = getattr(chain_configs, stated["chain_config"] + "_chain_config")()
    genesis = create_interop_genesis_state(
        stated["genesis_validators"], p=p, genesis_fork_version=cc.GENESIS_FORK_VERSION
    )
    node = await BeaconNode.init(
        anchor_state=genesis,
        chain_config=cc,
        opts=BeaconNodeOptions(
            rest_enabled=stated["rest_enabled"], manual_clock=stated["manual_clock"]
        ),
        p=p,
        time_fn=lambda: 0.0,
    )
    return NodeSystem(node, stated["launch_ledger_size"])
