"""Generate the Grafana dashboards under dashboards/ (reference ships 16
under /dashboards; these cover the subsystems this framework actually
exports, wired to the repo's metric names so a Grafana + Prometheus pair
scraping the node renders them unmodified).

Run from the repo root: python tools/gen_dashboards.py
"""

import glob
import json
import os

OUT = "dashboards"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def panel(title, exprs, *, unit="short", x=0, y=0, w=12, h=8, pid=1, kind="timeseries"):
    targets = [
        {"expr": e, "legendFormat": leg, "refId": chr(ord("A") + i)}
        for i, (e, leg) in enumerate(exprs)
    ]
    return {
        "id": pid,
        "title": title,
        "type": kind,
        "datasource": {"type": "prometheus", "uid": "${DS_PROMETHEUS}"},
        "gridPos": {"x": x, "y": y, "w": w, "h": h},
        "fieldConfig": {"defaults": {"unit": unit}, "overrides": []},
        "targets": targets,
    }


def text_panel(title, content, *, x=0, y=0, w=24, h=8, pid=1):
    """Markdown panel (no Prometheus targets — static content baked at
    generation time, e.g. the bench trajectory table)."""
    return {
        "id": pid,
        "title": title,
        "type": "text",
        "gridPos": {"x": x, "y": y, "w": w, "h": h},
        "options": {"mode": "markdown", "content": content},
        "targets": [],
    }


def dashboard(uid, title, panels, tags):
    return {
        "uid": uid,
        "title": title,
        "tags": tags,
        "timezone": "utc",
        "schemaVersion": 39,
        "version": 1,
        "refresh": "10s",
        "time": {"from": "now-1h", "to": "now"},
        "templating": {
            "list": [
                {
                    "name": "DS_PROMETHEUS",
                    "type": "datasource",
                    "query": "prometheus",
                    "current": {},
                }
            ]
        },
        "panels": panels,
    }


def bls_pool():
    ps = [
        panel(
            "Signature throughput (sets/s)",
            [
                ("rate(lodestar_bls_thread_pool_sig_sets_started_total[1m])", "started"),
                ("rate(lodestar_bls_thread_pool_batch_sigs_success_total[1m])", "batch success"),
                # prometheus_client suffixes counters with _total even when
                # the reference name already ends in _count
                ("rate(lodestar_bls_thread_pool_success_jobs_signature_sets_count_total[1m])", "success"),
            ],
            unit="ops", x=0, y=0, pid=1,
        ),
        panel(
            "Jobs started / errors",
            [
                ("rate(lodestar_bls_thread_pool_jobs_started_total[1m])", "jobs"),
                ("rate(lodestar_bls_thread_pool_error_jobs_signature_sets_count_total[1m])", "error sets"),
                ("rate(lodestar_bls_thread_pool_batch_retries_total[1m])", "batch retries"),
            ],
            unit="ops", x=12, y=0, pid=2,
        ),
        panel(
            "Queue wait time",
            [
                (
                    "histogram_quantile(0.5, rate(lodestar_bls_thread_pool_queue_job_wait_time_seconds_bucket[5m]))",
                    "p50",
                ),
                (
                    "histogram_quantile(0.95, rate(lodestar_bls_thread_pool_queue_job_wait_time_seconds_bucket[5m]))",
                    "p95",
                ),
            ],
            unit="s", x=0, y=8, pid=3,
        ),
        panel(
            "Device time per signature set",
            [
                (
                    "histogram_quantile(0.5, rate(lodestar_bls_thread_pool_time_per_sig_set_seconds_bucket[5m]))",
                    "p50",
                ),
                (
                    "histogram_quantile(0.95, rate(lodestar_bls_thread_pool_time_per_sig_set_seconds_bucket[5m]))",
                    "p95",
                ),
            ],
            unit="s", x=12, y=8, pid=4,
        ),
        panel(
            "Input prep throughput by layer (device vs host)",
            [
                ("rate(lodestar_bls_prep_sets_total[1m])", "{{layer}}"),
            ],
            unit="ops", x=0, y=16, pid=5,
        ),
        panel(
            "Input prep time by layer",
            [
                (
                    "histogram_quantile(0.95, sum by (le, layer) (rate(lodestar_bls_prep_seconds_bucket[5m])))",
                    "p95 {{layer}}",
                ),
            ],
            unit="s", x=12, y=16, pid=6,
        ),
        panel(
            "Input prep fallbacks / rejected batches",
            [
                ("rate(lodestar_bls_prep_fallback_total[1m])", "device→host fallbacks"),
                (
                    "rate(lodestar_bls_single_launch_fallback_total[1m])",
                    "single-launch→split fallbacks",
                ),
                ("rate(lodestar_bls_prep_rejected_total[1m])", "rejected batches"),
            ],
            unit="ops", x=0, y=24, pid=7,
        ),
        panel(
            # launches-per-set: the fused schedule costs a fixed launch
            # budget per batch, so this quotient falls with batch size
            # and spikes if a regression re-serializes the chains. The
            # numerator is the plain dispatch counter (it counts per-leg
            # and hash-to-G2 dispatches too); the strict per-batch
            # budget invariant lives in the tests. BOTH operands wrapped
            # in sum(): a labeled-vs-aggregated vector match is empty
            # and renders the panel permanently blank (the PR 7 round-5
            # launches/flush lesson). The plain
            # lodestar_bls_prep_launches_total counter counts EVERY
            # dispatch at the seam (single-launch verifies included
            # since round 13), so the split-schedule numerator
            # subtracts the single-launch program's telemetry count —
            # with the `or vector(0)` guard so the subtraction (and the
            # panel) still renders when telemetry is off or no
            # single-launch traffic exists. Known over-reads, both
            # deliberate: with telemetry off under the single launch the
            # series blends the schedules (no per-program signal to
            # subtract), and during a single-launch fallback storm the
            # FAILED dispatches stay in the numerator (the counter
            # ticks at dispatch, the histogram only on success) — an
            # elevated split series next to a busy fallbacks panel is
            # the storm being visible, not a split-schedule regression.
            # The single-launch
            # series reads the one-program schedule (an accelerator
            # backend's): numerator = the single-launch
            # program's dispatches, denominator the sets staged under
            # the single_launch prep layer — at budget it tracks
            # 1/batch-size while the split series tracks 3/batch-size.
            "Prep launches per set (device layer)",
            [
                (
                    "(sum(rate(lodestar_bls_prep_launches_total[5m])) - "
                    "(sum(rate(lodestar_device_launch_seconds_count{program=\"_single_launch_verify\"}[5m])) or vector(0))) / "
                    "sum(rate(lodestar_bls_prep_sets_total{layer=\"device\"}[5m]))",
                    "split-schedule launches/set",
                ),
                (
                    "sum(rate(lodestar_device_launch_seconds_count{program=\"_single_launch_verify\"}[5m])) / "
                    "sum(rate(lodestar_bls_prep_sets_total{layer=\"single_launch\"}[5m]))",
                    "single-launch launches/set",
                ),
            ],
            unit="ops", x=12, y=24, pid=8,
        ),
        panel(
            # live export of the pool's pipeline_stats(): how much of
            # verify wall time carried a prep stage in flight (the PR 9
            # bench line, now readable during a run) and whether the
            # double buffer engaged at all (0 staged packages = it
            # never did — one lane on the split schedule, or no
            # stageable lanes)
            "Prep→verify pipeline overlap",
            [
                ("lodestar_bls_pipeline_overlap_occupancy_pct", "overlap % of verify time"),
                ("lodestar_bls_pipeline_staged_packages", "staged packages (cum)"),
            ],
            x=0, y=32, pid=9,
        ),
        panel(
            "Pipeline stage busy time (rate of cumulative seconds)",
            [
                ("rate(lodestar_bls_pipeline_prep_seconds_total[5m])", "prep busy s/s"),
                ("rate(lodestar_bls_pipeline_verify_seconds_total[5m])", "verify busy s/s"),
            ],
            x=12, y=32, pid=10,
        ),
        panel(
            # the registry table the verify lanes sum indexed sets'
            # signers from (chain/bls/pubkey_table.py): entries on each
            # lane's chip, and the sets whose signers the host summed
            # instead (more signers than a launch's index matrix has
            # columns, or lanes without the table) — a device node's
            # fallback series should read 0
            "Pubkey table entries / host-aggregation fallbacks",
            [
                ("lodestar_bls_pubkey_table_entries", "entries {{lane}}"),
                ("rate(lodestar_bls_aggregate_fallback_total[1m])", "host-aggregated sets/s"),
            ],
            x=0, y=40, pid=11,
        ),
    ]
    return dashboard("lodestar-bls-pool", "Lodestar TPU - BLS verifier pool", ps, ["lodestar", "bls"])


def block_processor():
    ps = [
        panel(
            "Head / finalized",
            [
                ("beacon_head_slot", "head slot"),
                ("beacon_clock_slot", "clock slot"),
                ("beacon_finalized_epoch * 8", "finalized (slots)"),
            ],
            x=0, y=0, pid=1,
        ),
        panel(
            "Block processing time",
            [
                (
                    "histogram_quantile(0.5, rate(lodestar_stfn_process_block_seconds_bucket[5m]))",
                    "p50",
                ),
                (
                    "histogram_quantile(0.95, rate(lodestar_stfn_process_block_seconds_bucket[5m]))",
                    "p95",
                ),
            ],
            unit="s", x=12, y=0, pid=2,
        ),
        panel(
            "Epoch transition / hashTreeRoot",
            [
                (
                    "histogram_quantile(0.95, rate(lodestar_stfn_epoch_transition_seconds_bucket[5m]))",
                    "epoch p95",
                ),
                (
                    "histogram_quantile(0.95, rate(lodestar_stfn_hash_tree_root_seconds_bucket[5m]))",
                    "htr p95",
                ),
            ],
            unit="s", x=0, y=8, pid=3,
        ),
        panel(
            "Gossip queues",
            [
                ("lodestar_gossip_validation_queue_length", "{{topic}}"),
                ("rate(lodestar_gossip_validation_queue_dropped_jobs_total[1m])", "dropped {{topic}}"),
            ],
            x=12, y=8, pid=4,
        ),
        panel(
            "State caches",
            [
                ("rate(lodestar_state_cache_hits_total[1m])", "state hits"),
                ("rate(lodestar_state_cache_misses_total[1m])", "state misses"),
                ("rate(lodestar_cp_state_cache_hits_total[1m])", "checkpoint hits"),
            ],
            unit="ops", x=0, y=16, pid=5,
        ),
        panel(
            "Fork choice",
            [
                ("rate(lodestar_fork_choice_requests_total[1m])", "findHead"),
                ("rate(lodestar_fork_choice_reorg_events_total[1m])", "reorgs"),
                ("rate(lodestar_fork_choice_errors_total[1m])", "errors"),
            ],
            unit="ops", x=12, y=16, pid=6,
        ),
    ]
    return dashboard(
        "lodestar-block-processor", "Lodestar TPU - Block processor", ps, ["lodestar", "chain"]
    )


def networking():
    ps = [
        panel(
            "Peers",
            [
                ("libp2p_peers", "total"),
                ("lodestar_peers_by_direction_count", "{{direction}}"),
            ],
            x=0, y=0, pid=1,
        ),
        panel(
            "Gossip traffic",
            [
                ("rate(lodestar_gossip_peer_received_messages_total[1m])", "received"),
                ("rate(lodestar_gossipsub_seen_cache_duplicates_total[1m])", "duplicates"),
            ],
            unit="ops", x=12, y=0, pid=2,
        ),
        panel(
            "ReqResp",
            [
                ("rate(beacon_reqresp_outgoing_requests_total[1m])", "out {{protocol}}"),
                ("rate(beacon_reqresp_incoming_requests_total[1m])", "in {{protocol}}"),
                ("rate(beacon_reqresp_incoming_errors_total[1m])", "errors {{protocol}}"),
            ],
            unit="ops", x=0, y=8, pid=3,
        ),
        panel(
            "Sync",
            [
                ("rate(lodestar_sync_range_blocks_total[1m])", "range blocks"),
                ("rate(lodestar_sync_range_errors_total[1m])", "range errors"),
                ("rate(lodestar_backfill_sync_blocks_total[1m])", "backfill blocks"),
            ],
            unit="ops", x=12, y=8, pid=4,
        ),
    ]
    return dashboard(
        "lodestar-networking", "Lodestar TPU - Networking & sync", ps, ["lodestar", "network"]
    )


def validator_monitor():
    ps = [
        panel(
            "Local validators",
            [("validator_monitor_validators_total", "registered")],
            x=0, y=0, w=6, pid=1, kind="stat",
        ),
        panel(
            "Proposals",
            [("rate(validator_monitor_beacon_block_total[10m])", "blocks")],
            unit="ops", x=6, y=0, w=6, pid=2,
        ),
        panel(
            "Attestation hits / misses per epoch",
            [
                ("increase(validator_monitor_prev_epoch_attestations_total[10m])", "attested"),
                (
                    "increase(validator_monitor_prev_epoch_attestations_missed_total[10m])",
                    "missed",
                ),
            ],
            x=12, y=0, pid=3,
        ),
        panel(
            "Inclusion distance",
            [
                (
                    "histogram_quantile(0.5, rate(validator_monitor_prev_epoch_attestation_inclusion_distance_bucket[10m]))",
                    "p50",
                ),
                (
                    "histogram_quantile(0.95, rate(validator_monitor_prev_epoch_attestation_inclusion_distance_bucket[10m]))",
                    "p95",
                ),
            ],
            x=0, y=8, pid=4,
        ),
        panel(
            "Gossip-seen local attestations",
            [("rate(validator_monitor_unaggregated_attestation_total[1m])", "seen")],
            unit="ops", x=12, y=8, pid=5,
        ),
    ]
    return dashboard(
        "lodestar-validator-monitor", "Lodestar TPU - Validator monitor", ps,
        ["lodestar", "validator"],
    )


def mesh_serving_dashboard():
    """Multi-chip serving (chain/bls/mesh.py + offload/tenancy.py):
    per-device occupancy/launch/wedge state for the verifier mesh and
    per-tenant served/shed/in-flight for the multi-tenant offload
    front-end. The "is the fleet healthy and is every tenant getting
    its share" dashboard."""
    ps = [
        panel(
            "Per-chip occupancy (‰)",
            [("lodestar_sched_lane_occupancy_permille", "{{device}}")],
            pid=1,
        ),
        panel(
            "Mesh lanes available (non-wedged)",
            [("lodestar_sched_mesh_lanes_available", "lanes")],
            x=12, pid=2,
        ),
        panel(
            "Launch rate by chip and mode",
            [
                (
                    "sum by (device, mode) (rate(lodestar_sched_lane_launches_total[5m]))",
                    "{{device}} {{mode}}",
                ),
            ],
            unit="ops", y=8, pid=3,
        ),
        panel(
            "Per-chip wedge-breaker trips",
            [
                (
                    "sum by (device) (increase(lodestar_sched_lane_wedge_trips_total[1h]))",
                    "{{device}}",
                ),
            ],
            x=12, y=8, pid=4,
        ),
        panel(
            "Tenant served sets rate",
            [
                (
                    "sum by (tenant) (rate(lodestar_offload_tenant_served_sets_total[5m]))",
                    "{{tenant}}",
                ),
            ],
            unit="ops", y=16, pid=5,
        ),
        panel(
            "Tenant sheds by reason",
            [
                (
                    "sum by (tenant, reason) (rate(lodestar_offload_tenant_shed_total[5m]))",
                    "{{tenant}} {{reason}}",
                ),
            ],
            unit="ops", x=12, y=16, pid=6,
        ),
        panel(
            "Tenant in-flight grants vs quota weight",
            [
                ("lodestar_offload_tenant_inflight", "inflight {{tenant}}"),
                ("lodestar_offload_tenant_quota_weight", "weight {{tenant}}"),
            ],
            y=24, pid=7,
        ),
    ]
    return dashboard(
        "lodestar-mesh-serving",
        "Lodestar TPU - Multi-chip serving",
        ps,
        ["lodestar", "mesh", "tenancy"],
    )


def _bench_trajectory_markdown():
    """Markdown table of the BENCH_rNN.json trajectory, baked at
    generation time (tools/bench_trajectory.py regenerates dashboards
    after writing each round, so this panel tracks the trajectory).
    Handles both the r1–r5 single-``parsed`` shape and the r6+
    ``lines`` shape."""
    rows = []
    for path in sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json"))):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        n = doc.get("n", "?")
        label = doc.get("label", "")
        lines = [l for l in doc.get("lines") or [] if isinstance(l, dict) and "metric" in l]
        parsed = doc.get("parsed")
        if isinstance(parsed, dict) and "metric" in parsed:
            lines.append(parsed)
        for line in lines:
            rows.append(
                "| r{n:02d} | `{metric}` | {value} {unit} | {vs} | {label} |".format(
                    n=int(n) if isinstance(n, int) else 0,
                    metric=line.get("metric", "?"),
                    value=line.get("value", "?"),
                    unit=line.get("unit", ""),
                    vs=line.get("vs_baseline", ""),
                    label=label,
                )
            )
    header = (
        "### Bench trajectory (BENCH_rNN.json)\n\n"
        "Written by `tools/bench_trajectory.py` — each round is gated "
        "line-by-line against the prior round (exit nonzero on "
        "regression). CPU-container rounds validate schedule shape, "
        "not chip throughput; read the label column.\n\n"
        "| round | metric | value | vs baseline | label |\n"
        "|---|---|---|---|---|\n"
    )
    return header + "\n".join(rows) + "\n"


def device_launches_dashboard():
    """Device launch telemetry (lodestar_tpu/telemetry.py): per-program
    dispatch latency and rate at the counted launch seams, the
    compile-vs-dispatch decomposition (first-call detection per
    (program, size class)), and the bench trajectory. The "where did
    the chip run's wall time go" dashboard the hardware measurement
    campaign reads."""
    ps = [
        panel(
            "Launch rate by program",
            [
                (
                    "sum by (program) (rate(lodestar_device_launch_seconds_count[5m]))",
                    "{{program}}",
                ),
            ],
            unit="ops", pid=1,
        ),
        panel(
            "Launch wall time p95 by program",
            [
                (
                    "histogram_quantile(0.95, sum by (program, le) "
                    "(rate(lodestar_device_launch_seconds_bucket[5m])))",
                    "{{program}}",
                ),
            ],
            unit="s", x=12, pid=2,
        ),
        panel(
            "Launch wall time p95 by size class",
            [
                (
                    "histogram_quantile(0.95, sum by (size_class, le) "
                    "(rate(lodestar_device_launch_seconds_bucket[5m])))",
                    "class {{size_class}}",
                ),
            ],
            unit="s", y=8, pid=3,
        ),
        panel(
            # compile vs dispatch: misses are first-call-per-(program,
            # size class) dispatches that paid trace+compile (or the
            # persistent-cache load); a miss spike in steady state means
            # a new shape bucket leaked into the hot path
            "Compile hits / misses by program",
            [
                (
                    "sum by (program) (rate(lodestar_device_compile_hits_total[5m]))",
                    "hit {{program}}",
                ),
                (
                    "sum by (program) (rate(lodestar_device_compile_misses_total[5m]))",
                    "MISS {{program}}",
                ),
            ],
            unit="ops", x=12, y=8, pid=4,
        ),
        panel(
            "Compile wall time (first-call dispatches, s/s)",
            [
                ("rate(lodestar_device_compile_seconds_total[5m])", "compile s/s"),
            ],
            y=16, pid=5,
        ),
        panel(
            "Launch time share by program (sum/s)",
            [
                (
                    "sum by (program) (rate(lodestar_device_launch_seconds_sum[5m]))",
                    "{{program}}",
                ),
            ],
            unit="s", x=12, y=16, pid=6,
        ),
        text_panel(
            "Bench trajectory",
            _bench_trajectory_markdown(),
            y=24, pid=7,
        ),
    ]
    return dashboard(
        "lodestar-device-launches",
        "Lodestar TPU - Device launch telemetry",
        ps,
        ["lodestar", "telemetry"],
    )


def slo_dashboard():
    """Slot-deadline SLO (lodestar_tpu/slo): per-class remaining-slack
    distributions at enqueue/dispatch/verdict, deadline-miss rates, the
    good/total SLI availability ratio and its error-budget burn rate
    (the panels behind alerts/lodestar_alerts.yml), and the offload
    host's per-tenant serving slack. The "are verdicts landing inside
    the slot, and if not where did the budget go" dashboard — the
    per-leg wait decomposition lives at GET /eth/v0/debug/slo."""
    ps = [
        panel(
            # p05, not p50: the SLO question is the worst-case tail —
            # "how close to the cliff are the slowest verdicts"
            "Verdict slack p05 by class (s left at the cutoff)",
            [
                (
                    "histogram_quantile(0.05, sum by (class, le) "
                    '(rate(lodestar_slo_slack_seconds_bucket{stage="verdict"}[5m])))',
                    "{{class}}",
                ),
            ],
            unit="s", pid=1,
        ),
        panel(
            # enqueue vs verdict medians: slack lost BETWEEN the stages
            # is spent inside this process (the wait-budget legs);
            # slack already negative at enqueue is upstream lateness
            "Slack p50 by stage (where the budget goes)",
            [
                (
                    "histogram_quantile(0.5, sum by (stage, le) "
                    "(rate(lodestar_slo_slack_seconds_bucket[5m])))",
                    "{{stage}}",
                ),
            ],
            unit="s", x=12, pid=2,
        ),
        panel(
            "Deadline misses by class",
            [
                (
                    "sum by (class) (rate(lodestar_slo_deadline_miss_total[5m]))",
                    "{{class}}",
                ),
            ],
            unit="ops", y=8, pid=3,
        ),
        panel(
            "SLI availability (good/total) by class",
            [
                (
                    "sum by (class) (rate(lodestar_slo_sli_good_total[5m])) / "
                    "sum by (class) (rate(lodestar_slo_sli_total[5m]))",
                    "{{class}}",
                ),
            ],
            unit="percentunit", x=12, y=8, pid=4,
        ),
        panel(
            # burn rate in budget multiples (1.0 = exactly on target,
            # 14.4 = the fast-burn page threshold): the live view of
            # the alert pair in alerts/lodestar_alerts.yml
            "Error-budget burn rate (x budget, 99.9% target)",
            [
                (
                    "(1 - (sum(rate(lodestar_slo_sli_good_total[5m])) / "
                    "sum(rate(lodestar_slo_sli_total[5m])))) / 0.001",
                    "5m window",
                ),
                (
                    "(1 - (sum(rate(lodestar_slo_sli_good_total[1h])) / "
                    "sum(rate(lodestar_slo_sli_total[1h])))) / 0.001",
                    "1h window",
                ),
            ],
            y=16, pid=5,
        ),
        panel(
            "Offload host: per-tenant serving slack p05",
            [
                (
                    "histogram_quantile(0.05, sum by (tenant, le) "
                    "(rate(lodestar_offload_tenant_slack_seconds_bucket[5m])))",
                    "{{tenant}}",
                ),
            ],
            unit="s", x=12, y=16, pid=6,
        ),
    ]
    return dashboard(
        "lodestar-slo",
        "Lodestar TPU - Slot-deadline SLO",
        ps,
        ["lodestar", "slo"],
    )


def all_dashboards():
    return (
        ("lodestar_bls_verifier_pool.json", bls_pool()),
        ("lodestar_block_processor.json", block_processor()),
        ("lodestar_networking.json", networking()),
        ("lodestar_validator_monitor.json", validator_monitor()),
        ("lodestar_sync.json", sync_dashboard()),
        ("lodestar_reqresp_api.json", reqresp_api_dashboard()),
        ("lodestar_db.json", db_dashboard()),
        ("lodestar_block_pipeline_trace.json", trace_dashboard()),
        ("lodestar_sched_occupancy.json", sched_dashboard()),
        ("lodestar_offload_resilience.json", resilience_dashboard()),
        ("lodestar_offload_audit.json", audit_dashboard()),
        ("lodestar_ssz_htr.json", ssz_htr_dashboard()),
        ("lodestar_node_internals.json", node_internals_dashboard()),
        ("lodestar_mesh_serving.json", mesh_serving_dashboard()),
        ("lodestar_device_launches.json", device_launches_dashboard()),
        ("lodestar_slo.json", slo_dashboard()),
    )


def main(out: str = OUT):
    os.makedirs(out, exist_ok=True)
    for name, dash in all_dashboards():
        path = os.path.join(out, name)
        with open(path, "w") as f:
            # sort_keys keeps the output byte-stable across dict-build
            # order changes, so the static-analysis metrics rule (and
            # the regen-is-noop test) can diff dashboards exactly
            json.dump(dash, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {path}")



def sync_dashboard():
    ps = [
        panel("Sync status", [("lodestar_sync_status", "status (0 stalled/1 syncing/2 synced)")], pid=1),
        panel("Head distance (slots behind)", [("lodestar_sync_head_distance_slots", "behind")], x=12, pid=2),
        panel(
            "Range-sync batches",
            [
                ("rate(lodestar_sync_range_batches_total[5m])", "{{status}}"),
                ("rate(lodestar_sync_range_batches_downloaded_total[5m])", "downloaded"),
                ("rate(lodestar_sync_range_download_retries_total[5m])", "retries"),
            ],
            y=8, pid=3,
        ),
        panel(
            "Blocks imported by sync",
            [
                ("rate(lodestar_sync_range_blocks_total[5m])", "range"),
                ("rate(lodestar_backfill_sync_blocks_total[5m])", "backfill"),
            ],
            x=12, y=8, pid=4,
        ),
        panel(
            "Batch latency p95",
            [
                ("histogram_quantile(0.95, rate(lodestar_sync_range_batch_download_seconds_bucket[5m]))", "download"),
                ("histogram_quantile(0.95, rate(lodestar_sync_range_batch_processing_seconds_bucket[5m]))", "processing"),
            ],
            unit="s", y=16, pid=5,
        ),
        panel(
            "Backfill / unknown-block",
            [
                ("lodestar_backfill_earliest_slot", "backfill earliest slot"),
                ("lodestar_sync_unknown_block_pending_count", "unknown-block pending"),
                ("rate(lodestar_sync_unknown_block_requests_total[5m])", "unknown-block requests"),
            ],
            x=12, y=16, pid=6,
        ),
    ]
    return dashboard("lodestar-sync", "Lodestar TPU - Sync", ps, ["lodestar", "sync"])


def reqresp_api_dashboard():
    ps = [
        panel(
            "Req/resp requests",
            [
                ("sum by (protocol) (rate(beacon_reqresp_incoming_requests_total[5m]))", "in {{protocol}}"),
                ("sum by (protocol) (rate(beacon_reqresp_outgoing_requests_total[5m]))", "out {{protocol}}"),
            ],
            pid=1,
        ),
        panel(
            "Req/resp chunks + errors",
            [
                ("sum by (protocol) (rate(beacon_reqresp_outgoing_response_chunks_total[5m]))", "chunks {{protocol}}"),
                ("sum by (protocol) (rate(beacon_reqresp_incoming_errors_total[5m]))", "errors {{protocol}}"),
                ("sum by (protocol) (rate(beacon_reqresp_rate_limited_total[5m]))", "rate-limited {{protocol}}"),
            ],
            x=12, pid=2,
        ),
        panel(
            "REST API requests",
            [
                ("sum by (method, status) (rate(lodestar_api_rest_requests_total[5m]))", "{{method}} {{status}}"),
                ("rate(lodestar_api_rest_errors_total[5m])", "5xx"),
            ],
            y=8, pid=3,
        ),
        panel(
            "REST response time p95",
            [("histogram_quantile(0.95, rate(lodestar_api_rest_response_time_seconds_bucket[5m]))", "p95")],
            unit="s", x=12, y=8, pid=4,
        ),
        panel(
            "Dial health",
            [
                ("rate(beacon_reqresp_dial_timeouts_total[5m])", "dial timeouts"),
                ("rate(beacon_reqresp_streams_reset_total[5m])", "streams reset"),
            ],
            y=16, pid=5,
        ),
    ]
    return dashboard("lodestar-reqresp-api", "Lodestar TPU - ReqResp and REST API", ps, ["lodestar", "api"])


def db_dashboard():
    ps = [
        panel(
            "DB requests",
            [
                ("sum by (bucket) (rate(lodestar_db_read_req_total[5m]))", "read {{bucket}}"),
                ("sum by (bucket) (rate(lodestar_db_write_req_total[5m]))", "write {{bucket}}"),
            ],
            pid=1,
        ),
        panel(
            "DB items",
            [
                ("sum by (bucket) (rate(lodestar_db_read_items_total[5m]))", "read {{bucket}}"),
                ("sum by (bucket) (rate(lodestar_db_write_items_total[5m]))", "write {{bucket}}"),
            ],
            x=12, pid=2,
        ),
        panel(
            "Size",
            [
                ("lodestar_db_size_bytes", "db"),
                ("lodestar_db_wal_size_bytes", "wal"),
            ],
            unit="bytes", y=8, pid=3,
        ),
        panel(
            "Archive / prune",
            [
                ("rate(lodestar_db_archived_states_total[5m])", "states archived"),
                ("rate(lodestar_db_archived_blocks_total[5m])", "blocks archived"),
                ("rate(lodestar_db_pruned_blocks_total[5m])", "blocks pruned"),
            ],
            x=12, y=8, pid=4,
        ),
        panel(
            "Batch write latency p95",
            [("histogram_quantile(0.95, rate(lodestar_db_batch_write_seconds_bucket[5m]))", "p95")],
            unit="s", y=16, pid=5,
        ),
    ]
    return dashboard("lodestar-db", "Lodestar TPU - Database", ps, ["lodestar", "db"])


def trace_dashboard():
    """Per-slot pipeline tracing (lodestar_tpu/tracing): span-duration
    summaries the tracer derives into the registry, plus the slow-slot
    dump rate. Slot-level detail lives at /eth/v0/debug/traces/{slot}."""
    ps = [
        panel(
            "Block pipeline duration",
            [
                (
                    "histogram_quantile(0.5, rate(lodestar_trace_block_pipeline_seconds_bucket[5m]))",
                    "p50",
                ),
                (
                    "histogram_quantile(0.95, rate(lodestar_trace_block_pipeline_seconds_bucket[5m]))",
                    "p95",
                ),
            ],
            unit="s", pid=1,
        ),
        panel(
            "Span p95 by stage",
            [
                (
                    "histogram_quantile(0.95, sum by (span, le) "
                    "(rate(lodestar_trace_span_duration_seconds_bucket[5m])))",
                    "{{span}}",
                ),
            ],
            unit="s", x=12, pid=2,
        ),
        panel(
            "Span time share (sum/s by stage)",
            [
                (
                    "sum by (span) (rate(lodestar_trace_span_duration_seconds_sum[5m]))",
                    "{{span}}",
                ),
            ],
            unit="s", y=8, pid=3,
        ),
        panel(
            "Traces completed / slow-slot dumps",
            [
                ("rate(lodestar_trace_completed_total[5m])", "completed"),
                ("rate(lodestar_trace_slow_slot_total[5m])", "slow slots"),
            ],
            unit="ops", x=12, y=8, pid=4,
        ),
        panel(
            "Span rate by stage",
            [
                (
                    "sum by (span) (rate(lodestar_trace_span_duration_seconds_count[5m]))",
                    "{{span}}",
                ),
            ],
            unit="ops", y=16, pid=5,
        ),
    ]
    return dashboard(
        "lodestar-block-pipeline-trace",
        "Lodestar TPU - Block pipeline trace",
        ps,
        ["lodestar", "tracing"],
    )


def sched_dashboard():
    """Device work scheduler (lodestar_tpu/scheduler): EWMA occupancy +
    graded admission, per-launch-class queue depth/wait/serve rates, and
    the anti-starvation/shed counters. The "can this host absorb another
    beacon node" dashboard."""
    ps = [
        panel(
            "Device occupancy (busy-ns per wall-ns, ‰)",
            [("lodestar_sched_occupancy_permille", "occupancy ‰")],
            pid=1,
        ),
        panel(
            "Admission state",
            [("lodestar_sched_admission_state", "0 accept / 1 shed-bulk / 2 reject")],
            x=12, pid=2,
        ),
        panel(
            "Launch queue depth by class",
            [("lodestar_sched_queue_depth", "{{class}}")],
            y=8, pid=3,
        ),
        panel(
            "Queue wait p95 by class",
            [
                (
                    "histogram_quantile(0.95, sum by (class, le) "
                    "(rate(lodestar_sched_queue_wait_seconds_bucket[5m])))",
                    "{{class}}",
                ),
            ],
            unit="s", x=12, y=8, pid=4,
        ),
        panel(
            "Dequeue rate by class",
            [
                (
                    "sum by (class) (rate(lodestar_sched_jobs_dequeued_total[5m]))",
                    "{{class}}",
                ),
            ],
            unit="ops", y=16, pid=5,
        ),
        panel(
            "Starvation promotions / shed work",
            [
                ("rate(lodestar_sched_starvation_promotions_total[5m])", "aging promotions"),
                ("sum by (class) (rate(lodestar_sched_shed_total[5m]))", "shed {{class}}"),
            ],
            unit="ops", x=12, y=16, pid=6,
        ),
    ]
    return dashboard(
        "lodestar-sched-occupancy",
        "Lodestar TPU - Device work scheduler",
        ps,
        ["lodestar", "scheduler"],
    )


def resilience_dashboard():
    """Offload resilience (offload/resilience.py + chain/bls/fallback.py):
    per-endpoint routing/failover/hedge rates, circuit-breaker states,
    and the degradation chain's fallback activity. The "is the offload
    leg healthy, and what is absorbing its failures" dashboard."""
    ps = [
        panel(
            "Breaker state by endpoint (0 closed / 1 half-open / 2 open)",
            [("lodestar_resilience_breaker_state", "{{endpoint}}")],
            pid=1,
        ),
        panel(
            "Verify RPCs routed by endpoint",
            [
                ("sum by (endpoint) (rate(lodestar_resilience_routed_total[5m]))", "{{endpoint}}"),
            ],
            unit="ops", x=12, pid=2,
        ),
        panel(
            "Failovers / breaker transitions",
            [
                ("sum by (endpoint) (rate(lodestar_resilience_failover_total[5m]))", "failover {{endpoint}}"),
                (
                    "sum by (endpoint, state) (rate(lodestar_resilience_breaker_transitions_total[5m]))",
                    "{{endpoint}} -> {{state}}",
                ),
            ],
            unit="ops", y=8, pid=3,
        ),
        panel(
            "Hedged retries by class",
            [
                ("sum by (class) (rate(lodestar_resilience_hedge_total[5m]))", "hedged {{class}}"),
                ("sum by (class) (rate(lodestar_resilience_hedge_win_total[5m]))", "won {{class}}"),
            ],
            unit="ops", x=12, y=8, pid=4,
        ),
        panel(
            "Degradation chain activity",
            [
                ("lodestar_resilience_fallback_active", "fallback active"),
                ("sum by (layer) (rate(lodestar_resilience_fallback_total[5m]))", "served {{layer}}"),
                (
                    "sum by (layer) (rate(lodestar_resilience_fallback_skipped_total[5m]))",
                    "skipped {{layer}}",
                ),
            ],
            y=16, pid=5,
        ),
        panel(
            "Admission sheds / outage-unscored rejections",
            [
                ("sum by (reason) (rate(lodestar_resilience_shed_total[5m]))", "shed {{reason}}"),
                (
                    "rate(lodestar_resilience_outage_unscored_total[5m])",
                    "outage rejections (peer spared)",
                ),
            ],
            unit="ops", x=12, y=16, pid=6,
        ),
    ]
    return dashboard(
        "lodestar-offload-resilience",
        "Lodestar TPU - Offload resilience",
        ps,
        ["lodestar", "resilience"],
    )


def audit_dashboard():
    """Byzantine offload auditing (offload/audit.py): sampling and
    re-verification rates, per-endpoint trust EWMA, Byzantine events and
    quarantine state, and the audit worker's CPU spend against its duty-
    cycle budget. The "can I trust my offload helpers" dashboard.
    (prometheus_client suffixes counters with _total — every counter
    expr below carries it.)"""
    ps = [
        panel(
            "Trust score by endpoint (EWMA, 1.0 = never contradicted)",
            [("lodestar_offload_audit_trust_score", "{{endpoint}}")],
            pid=1,
        ),
        panel(
            "Quarantined endpoints / Byzantine events",
            [
                ("lodestar_offload_audit_quarantined", "quarantined {{endpoint}}"),
                (
                    "sum by (endpoint) (increase(lodestar_offload_audit_byzantine_total[1h]))",
                    "byzantine {{endpoint}} (1h)",
                ),
            ],
            x=12, pid=2,
        ),
        panel(
            "Audit sampling rate by class",
            [
                (
                    "sum by (class) (rate(lodestar_offload_audit_sampled_total[5m]))",
                    "sampled {{class}}",
                ),
            ],
            unit="ops", y=8, pid=3,
        ),
        panel(
            "Re-verification outcomes",
            [
                (
                    "sum by (outcome) (rate(lodestar_offload_audit_verified_total[5m]))",
                    "{{outcome}}",
                ),
                (
                    "sum by (reason) (rate(lodestar_offload_audit_dropped_total[5m]))",
                    "dropped {{reason}}",
                ),
            ],
            unit="ops", x=12, y=8, pid=4,
        ),
        panel(
            "Audit queue backlog",
            [("lodestar_offload_audit_queue_depth", "backlog")],
            y=16, pid=5,
        ),
        panel(
            "Audit CPU duty cycle (fraction of one core)",
            [
                (
                    "rate(lodestar_offload_audit_cpu_seconds_total[5m])",
                    "audit cpu s/s",
                ),
            ],
            x=12, y=16, pid=6,
        ),
    ]
    return dashboard(
        "lodestar-offload-audit",
        "Lodestar TPU - Offload Byzantine audit",
        ps,
        ["lodestar", "audit"],
    )


def ssz_htr_dashboard():
    """Device hashTreeRoot (ssz/device_htr.py collector +
    state_transition/htr.py tracker): flush rate per backend, dirty
    chunk volume, device dispatch rate (all hash_pairs launches —
    collector flush levels plus shared-hook batch levels; the strict
    one-per-level-per-flush invariant is asserted by tests, which read
    the per-collector counter), flush latency, and degradations by
    leg. (prometheus_client suffixes counters with _total — every
    counter expr below carries it.)"""
    ps = [
        panel(
            "Collector flushes by backend",
            [
                (
                    "sum by (backend) (rate(lodestar_ssz_htr_flushes_total[5m]))",
                    "{{backend}}",
                ),
            ],
            unit="ops", pid=1,
        ),
        panel(
            "Dirty chunks re-hashed",
            [("rate(lodestar_ssz_htr_dirty_chunks_total[5m])", "chunks/s")],
            unit="ops", x=12, pid=2,
        ),
        panel(
            "Device dispatch rate (flush levels + batch-hook levels)",
            [
                (
                    "sum (rate(lodestar_ssz_htr_launches_total[5m]))",
                    "dispatches/s",
                ),
                (
                    'sum (rate(lodestar_ssz_htr_flushes_total{backend="device"}[5m]))',
                    "device flushes/s",
                ),
            ],
            unit="ops", y=8, pid=3,
        ),
        panel(
            "Flush wall time p95 by backend",
            [
                (
                    "histogram_quantile(0.95, sum by (le, backend) "
                    "(rate(lodestar_ssz_htr_seconds_bucket[5m])))",
                    "p95 {{backend}}",
                ),
            ],
            unit="s", x=12, y=8, pid=4,
        ),
        panel(
            "Degradations by leg (flush = device fault, tracker = logic bug)",
            [
                (
                    "sum by (leg) (rate(lodestar_ssz_htr_fallback_total[5m]))",
                    "{{leg}}",
                ),
            ],
            unit="ops", y=16, pid=5,
        ),
        panel(
            "State hashTreeRoot time (state-transition histogram)",
            [
                (
                    "histogram_quantile(0.95, sum by (le) "
                    "(rate(lodestar_stfn_hash_tree_root_seconds_bucket[5m])))",
                    "p95",
                ),
            ],
            unit="s", x=12, y=16, pid=6,
        ),
    ]
    return dashboard(
        "lodestar-ssz-htr",
        "Lodestar TPU - Device hashTreeRoot",
        ps,
        ["lodestar", "ssz"],
    )


def node_internals_dashboard():
    """Node internals (chain/process/peer detail): the registered
    families that belong on a dashboard but fit none of the
    subsystem-specific ones. Kept two-way-consistent with the registry
    by the static-analysis metrics rule (tools/analysis)."""
    ps = [
        panel(
            "Block import / production p95",
            [
                ("histogram_quantile(0.95, rate(lodestar_block_processor_import_seconds_bucket[5m]))", "import p95"),
                ("histogram_quantile(0.95, rate(lodestar_block_production_seconds_bucket[5m]))", "production p95"),
            ],
            unit="s", pid=1,
        ),
        panel(
            "Import outcomes",
            [
                ("sum by (source) (rate(lodestar_blocks_imported_total[5m]))", "imported {{source}}"),
                ("sum by (reason) (rate(lodestar_blocks_rejected_total[5m]))", "rejected {{reason}}"),
                ("rate(lodestar_attestations_imported_total[5m])", "attestations"),
            ],
            unit="ops", x=12, pid=2,
        ),
        panel(
            "Gossip validation verdicts",
            [
                ("sum by (topic) (rate(lodestar_gossip_validation_accept_total[5m]))", "accept {{topic}}"),
                ("sum by (topic) (rate(lodestar_gossip_validation_reject_total[5m]))", "reject {{topic}}"),
            ],
            unit="ops", y=8, pid=3,
        ),
        panel(
            "Event loop lag",
            [
                ("histogram_quantile(0.5, rate(lodestar_event_loop_lag_seconds_bucket[5m]))", "p50"),
                ("histogram_quantile(0.95, rate(lodestar_event_loop_lag_seconds_bucket[5m]))", "p95"),
            ],
            unit="s", x=12, y=8, pid=4,
        ),
        panel(
            "State caches & regen",
            [
                ("lodestar_state_cache_size", "hot states"),
                ("lodestar_cp_state_cache_size", "checkpoint states"),
                ("lodestar_regen_queue_length", "regen queue"),
                ("histogram_quantile(0.95, rate(lodestar_regen_fn_call_duration_seconds_bucket[5m]))", "regen p95 (s)"),
            ],
            y=16, pid=5,
        ),
        panel(
            "Seen caches",
            [
                ("lodestar_seen_cache_attesters_size", "attesters"),
                ("lodestar_seen_cache_aggregators_size", "aggregators"),
            ],
            x=12, y=16, pid=6,
        ),
        panel(
            "Op pool sizes",
            [
                ("lodestar_op_pool_attestation_pool_size", "attestations"),
                ("lodestar_op_pool_aggregated_attestation_pool_size", "aggregated"),
                ("lodestar_op_pool_voluntary_exit_pool_size", "exits"),
                ("lodestar_op_pool_proposer_slashing_pool_size", "proposer slashings"),
                ("lodestar_op_pool_attester_slashing_pool_size", "attester slashings"),
                ("lodestar_op_pool_sync_committee_message_pool_size", "sync messages"),
            ],
            y=24, pid=7,
        ),
        panel(
            "Peers & dials",
            [
                ("lodestar_peers_count", "peers"),
                ("lodestar_peers_by_client_count", "{{client}}"),
                ("sum by (reason) (rate(lodestar_peer_disconnects_total[5m]))", "disconnects {{reason}}"),
                ("rate(lodestar_peers_dial_attempts_total[5m])", "dials"),
                ("rate(lodestar_peers_dial_success_total[5m])", "dials ok"),
            ],
            x=12, y=24, pid=8,
        ),
        panel(
            "Fork choice findHead p95",
            [("histogram_quantile(0.95, rate(lodestar_fork_choice_find_head_seconds_bucket[5m]))", "p95")],
            unit="s", y=32, pid=9,
        ),
        panel(
            "Offload client (process view)",
            [
                ("lodestar_offload_outstanding_jobs", "outstanding"),
                ("lodestar_offload_healthy", "healthy bit"),
            ],
            x=12, y=32, pid=10,
        ),
    ]
    return dashboard(
        "lodestar-node-internals",
        "Lodestar TPU - Node internals",
        ps,
        ["lodestar", "node"],
    )


if __name__ == "__main__":
    main()
