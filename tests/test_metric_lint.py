"""Metric-name convention lint (scripts/check_metric_names.py) as a fast
tier-1 test, so a PR registering an off-convention instrument fails CI."""

import os
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))

from check_metric_names import (  # noqa: E402
    RegisteredMetric,
    check_name,
    iter_registered_metrics,
    run_check,
)

PACKAGE_ROOT = os.path.join(REPO_ROOT, "dynamo_tpu")


def test_all_registered_metric_names_conform():
    violations = run_check(PACKAGE_ROOT)
    assert not violations, "\n".join(violations)


def test_lint_sees_the_real_instrument_catalog():
    """The AST walk must actually find the known call sites — an empty
    scan would make the conformance test pass vacuously."""
    names = {m.name for m in iter_registered_metrics(PACKAGE_ROOT)}
    expected = {
        "dynamo_http_service_requests_total",
        "dynamo_http_service_time_to_first_token_seconds",
        "dynamo_scheduler_step_duration_seconds",
        "dynamo_scheduler_inter_token_latency_seconds",
        "dynamo_kv_evictions_total",
        "dynamo_kv_block_usage_ratio",
        "dynamo_kv_router_decisions_total",
        "dynamo_kv_router_worker_staleness_seconds",
        "dynamo_disagg_remote_prefill_duration_seconds",
        "dynamo_disagg_remote_prefill_failures_total",
        # streamed remote prefill (disagg/prefill_worker.py)
        "dynamo_prefill_worker_prefills_total",
        "dynamo_prefill_worker_prefill_tokens_total",
        "dynamo_prefill_worker_queue_wait_seconds",
        "dynamo_prefill_worker_prefix_hit_ratio",
        # unified transfer plane (transfer/plane.py): one
        # {plane,backend}-labelled family replaces the per-plane
        # transfer instruments the disagg/fabric planes used to register
        "dynamo_transfer_bytes_total",
        "dynamo_transfer_duration_seconds",
        "dynamo_transfer_exposed_seconds",
        "dynamo_transfer_channels",
        # flight recorder / watchdog / XLA compile observability
        # (telemetry/flight.py, telemetry/watchdog.py)
        "dynamo_engine_xla_compiles_total",
        "dynamo_engine_xla_compile_duration_seconds",
        "dynamo_watchdog_trips_total",
        "dynamo_runtime_event_loop_lag_seconds",
        # closed-loop SLA planner (planner/admission.py, planner/planner.py)
        "dynamo_planner_admissions_total",
        "dynamo_planner_queue_wait_seconds",
        "dynamo_planner_admission_queue_depth_requests",
        "dynamo_planner_inflight_requests",
        "dynamo_planner_admission_limit_requests",
        "dynamo_planner_shedding_info",
        "dynamo_planner_actions_total",
        "dynamo_planner_cycles_total",
        "dynamo_planner_replica_target_replicas",
        "dynamo_planner_shed_level_depth",
        "dynamo_planner_local_prefill_threshold_tokens",
        # staleness-aware KV routing (kv_router/router.py)
        "dynamo_kv_router_stale_worker_skips_total",
        # persistent decode loop: device-resident finish detection
        # (engine/scheduler.py)
        "dynamo_engine_device_finished_rows_total",
        "dynamo_engine_decode_drain_lag_seconds",
        "dynamo_engine_decode_burst_chain_length",
        # self-healing serving (recovery/controller.py,
        # llm/engines/subprocess_host.py, kv_router/router.py)
        "dynamo_recovery_actions_total",
        "dynamo_recovery_migrations_total",
        "dynamo_recovery_drain_duration_seconds",
        "dynamo_engine_restarts_total",
        "dynamo_kv_router_draining_worker_skips_total",
        # request X-ray: device-time/roofline attribution
        # (telemetry/device_time.py), SLO goodput (telemetry/slo.py),
        # bounded trace store (telemetry/tracing.py)
        "dynamo_engine_device_time_seconds",
        "dynamo_engine_device_busy_ratio",
        "dynamo_engine_roofline_fraction",
        "dynamo_slo_attainment_total",
        "dynamo_slo_goodput_tokens_total",
        "dynamo_slo_target_seconds",
        "dynamo_trace_evicted_total",
        "dynamo_trace_store_requests",
        # fleet telemetry hub + incident recorder (telemetry/hub.py,
        # telemetry/incidents.py, engine/scheduler.py drain gauge)
        "dynamo_hub_scrapes_total",
        "dynamo_hub_scrape_duration_seconds",
        "dynamo_hub_fleet_workers_replicas",
        "dynamo_hub_fleet_busy_ratio",
        "dynamo_hub_fleet_kv_usage_ratio",
        "dynamo_hub_history_series_depth",
        "dynamo_incidents_total",
        "dynamo_incidents_suppressed_total",
        "dynamo_scheduler_draining_info",
        # cluster KV fabric: cross-worker prefix pull (kv/fabric.py)
        # + content-addressed cold tier (kv/cold_tier.py)
        "dynamo_kv_fabric_prefix_pull_total",
        "dynamo_kv_fabric_cold_tier_hits_total",
        "dynamo_kv_fabric_cold_tier_misses_total",
        "dynamo_kv_fabric_cold_tier_evictions_total",
        "dynamo_kv_fabric_cold_tier_bytes",
        # multi-model multi-tenant fleet (registry/: registry.py cards
        # view, pools.py scale-to-zero + cold start, tenants.py token
        # buckets; cli/run.py worker model advertisement)
        "dynamo_registry_models_info",
        "dynamo_registry_model_info",
        "dynamo_registry_pool_workers_replicas",
        "dynamo_registry_cold_starts_total",
        "dynamo_registry_scale_to_zero_total",
        "dynamo_registry_cold_start_wait_seconds",
        "dynamo_registry_tenant_sheds_total",
        "dynamo_registry_tenant_fallbacks_total",
        "dynamo_registry_tenant_tokens_total",
        # unrestricted persistent decode (engine/scheduler.py): the
        # sync-path fallback ladder attribution + the in-carry
        # propose-verify acceptance-length histogram
        "dynamo_engine_sync_fallback_total",
        "dynamo_engine_spec_accept_length",
        # attention route attribution (ops/attention.py): which kernel
        # each compiled program's attention resolved to, counted once
        # per trace via the CompileTracker dispatch hook
        "dynamo_engine_attention_route_total",
        # sequence-parallel long-context prefill (engine/scheduler.py;
        # docs/long_context.md)
        "dynamo_engine_prefill_sp_chunks_total",
        "dynamo_engine_prefill_sp_tokens_total",
        "dynamo_engine_prefill_sp_axis_depth",
        "dynamo_engine_prefill_sp_exposed_seconds",
        # trace-driven fleet simulator (sim/metrics.py): run counters
        # and gauges published through the standard /metrics plumbing
        "dynamo_sim_requests_total",
        "dynamo_sim_tokens_total",
        "dynamo_sim_scale_actions_total",
        "dynamo_sim_chaos_injections_total",
        "dynamo_sim_recoveries_total",
        "dynamo_sim_watchdog_trips_total",
        "dynamo_sim_resubmits_total",
        "dynamo_sim_slo_attainment_ratio",
        "dynamo_sim_kv_usage_ratio",
        "dynamo_sim_virtual_time_seconds",
        "dynamo_sim_workers_replicas",
    }
    missing = expected - names
    assert not missing, f"lint no longer sees: {sorted(missing)}"
    assert len(names) >= 115


@pytest.mark.parametrize("name", [
    # the start-up timeline and a first dispatch in its parts (ISSUE 50;
    # telemetry/flight.py)
    "dynamo_engine_startup_seconds",
    "dynamo_engine_startup_mark_monotonic_seconds",
    "dynamo_engine_xla_compile_part_seconds_total",
    "dynamo_engine_compile_cache_total",
])
def test_lint_sees_the_startup_series(name):
    found = [m for m in iter_registered_metrics(PACKAGE_ROOT)
             if m.name == name]
    assert found, name
    assert all(m.file.endswith("flight.py") for m in found), found
    assert not any(check_name(m) for m in found)


def _metric(name, kind):
    return RegisteredMetric(name, kind, "x.py", 1)


def test_rules_reject_bad_names():
    assert check_name(_metric("dynamo_scheduler_preemptions", "counter"))
    assert check_name(_metric("dynamo_BadCase_seconds", "gauge"))
    # NOTE "depth" joined the unit vocabulary with the decode-pipeline
    # depth gauge (structural stage counts); "size" remains a non-unit
    assert check_name(_metric("dynamo_queue_size", "gauge"))
    assert check_name(_metric("dynamo_kv_usage_ratio", "histogram"))
    assert check_name(_metric("dynamo_kv_blocks_total", "gauge"))
    # too few segments: no component between prefix and unit
    assert check_name(_metric("dynamo_total", "counter"))


def test_rules_accept_good_names():
    assert not check_name(_metric("dynamo_scheduler_preemptions_total", "counter"))
    assert not check_name(_metric("dynamo_scheduler_step_duration_seconds", "histogram"))
    assert not check_name(_metric("dynamo_kv_block_usage_ratio", "gauge"))
    assert not check_name(_metric("dynamo_scheduler_active_slots", "gauge"))
    # "fraction" joined the unit vocabulary with the live roofline gauge
    # (achieved-over-physical-bound, vs "ratio"'s part-of-whole share)
    assert not check_name(_metric("dynamo_engine_roofline_fraction", "gauge"))
    # it names a bound comparison, not a base unit a histogram measures
    assert check_name(_metric("dynamo_engine_roofline_fraction", "histogram"))
