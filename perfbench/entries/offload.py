"""The served fleet, built as a deployment builds it: one offload host
as `python -m lodestar_tpu.offload.server` builds it from the command's
defaults (`offload.server.boot_host`, the one function `main()` calls),
and four tenants, each the verifier `BeaconNode.init` builds for a node
with `--bls-offload` set (`node._offload_verifier`), over real gRPC on
localhost. The benchmark drives the tenants' `IBlsVerifier` seam, call
*i* through tenant *i* mod 4, and reads the host's and the tenants'
counters; nothing else of the program is imported. What departs from
the defaults is stated in the configuration's `boot`.

The import below is at module level on purpose: a commit without the
boot function fails here, at once and before any chip is looked for.
"""

from __future__ import annotations

import asyncio
import itertools
import time

from lodestar_tpu.offload.server import boot_host

from perfbench.entries.node import FALLBACK_COUNTERS as NODE_FALLBACK_COUNTERS
from perfbench.entries.node import check_stated_constants

NEEDS_CHIP = True

# each of these moving means a call was not served by the host's device path:
# the node entry's, and what the wire and the tenancy put in the way
FALLBACK_COUNTERS = NODE_FALLBACK_COUNTERS + (
    "lodestar_resilience_fallback_skipped_total",
    "lodestar_resilience_hedge_total",
    "lodestar_resilience_failover_total",
    "lodestar_resilience_shed_total",
    "lodestar_resilience_breaker_transitions_total",
    "lodestar_offload_tenant_shed_total",
)


class OffloadSystem:
    def __init__(self, host, tenants: list, registries: list, launch_ledger_size: int):
        from lodestar_tpu import telemetry

        self.host = host
        self.tenants = tenants
        self.registries = registries  # the host's first, then one a tenant
        self.runtime = {**host.backend.description, "tenants": len(tenants), "port": host.port,
                        "warmed": host.warmed}
        self.telemetry = telemetry
        telemetry.configure_launch_telemetry(ledger_size=launch_ledger_size)
        self._calls = itertools.count()

    # -- verify seam -----------------------------------------------------------

    def verify_payload(self, triples: list[tuple[bytes, bytes, bytes]]):
        from lodestar_tpu.crypto.bls.api import SignatureSet

        return [SignatureSet(pubkey=pk, message=m, signature=s) for pk, m, s in triples]

    def verify_options(self, batchable: bool, priority: str):
        from lodestar_tpu.chain.bls import VerifySignatureOpts
        from lodestar_tpu.scheduler import PriorityClass

        return VerifySignatureOpts(batchable=batchable, priority=PriorityClass[priority])

    async def verify(self, payload, options) -> bool:
        tenant = self.tenants[next(self._calls) % len(self.tenants)]
        return await tenant.verify_signature_sets(payload, options)

    def expect_verifier(self, want: str) -> None:
        if self.runtime["verifier"] != want:
            raise RuntimeError(f"host resolved {self.runtime}, the configuration states verifier={want}")
        if self.host.pool is None:
            raise RuntimeError("the configuration states the pool as the backend; the host has none")

    # -- counters --------------------------------------------------------------

    def launch_ledger(self) -> list[dict]:
        return self.telemetry.launch_ledger()

    def counters(self) -> dict[str, float]:
        """Every sample of the host's and the tenants' registries, summed
        over registries and labels, with the pool's own tallies."""
        out: dict[str, float] = {}
        for registry in self.registries:
            for family in registry.collect():
                for sample in family.samples:
                    out[sample.name] = out.get(sample.name, 0.0) + sample.value
        for key, value in dict(self.host.pool.metrics).items():
            out[f"pool.{key}"] = float(value)
        return out

    def fallbacks(self, counters: dict[str, float]) -> float:
        return sum(counters.get(name, 0.0) for name in FALLBACK_COUNTERS)

    async def close(self) -> None:
        for tenant in self.tenants:
            await tenant.close()
        await asyncio.get_event_loop().run_in_executor(None, self.host.stop)


async def boot(config: dict) -> OffloadSystem:
    from lodestar_tpu.metrics import create_metrics
    from lodestar_tpu.node import BeaconNodeOptions, _offload_verifier

    stated = config["boot"]
    check_stated_constants(config)
    # the host: every option the command's default, on a port of the system's choosing
    host = await asyncio.get_event_loop().run_in_executor(None, lambda: boot_host(port=stated["port"]))
    # The one thing the harness changes on the booted host, and no operator can
    # (the command has no option for it; `boot.departs` says why): a closed loop
    # holds the lane ~99% busy, the host's admission would say REJECT on that
    # occupancy alone, and the tenants would verify on their CPUs. The veto,
    # the depth grading, SHED_BULK and the tenants' quotas stay as booted.
    host.server.admission.reject_at = stated["admission_reject_at"]
    tenants, registries = [], [host.creator.registry]
    try:
        for i in range(config["tenants"]):
            metrics = create_metrics()
            registries.append(metrics.creator.registry)
            tenants.append(_offload_verifier(
                BeaconNodeOptions(
                    offload_endpoints=[f"127.0.0.1:{host.port}"],
                    offload_tenant=f"node-{i}",
                    offload_audit_rate=stated["offload_audit_rate"],
                ),
                metrics,
            ))
        # a node has probed its host long before its first block: the tenant
        # trailer (identity and class) rides only once Status has advertised it
        deadline = time.monotonic() + 30.0
        clients = [t.layers[0][1] if hasattr(t, "layers") else t for t in tenants]
        while not all(s["tenant_capable"] for c in clients for s in c.endpoint_states()):
            if time.monotonic() > deadline:
                raise RuntimeError("a tenant's Status probe has not seen the host in 30 s")
            await asyncio.sleep(0.05)
    except BaseException:
        for tenant in tenants:
            await tenant.close()
        host.stop()
        raise
    return OffloadSystem(host, tenants, registries, stated["launch_ledger_size"])
