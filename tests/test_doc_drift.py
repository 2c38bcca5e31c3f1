"""Doc-drift guard: the README "Observability" section must document
every counter the runtime actually exports.

A counter renamed/added in code without a README row silently rots the
operator docs; this test diffs the real key sets against the text so
the drift fails the suite instead of a pager rotation.
"""

from pathlib import Path

import pytest

import ray_tpu
# Counter registries come through the analyzer's AST parser (ISSUE 13)
# — the same code path `python -m ray_tpu.analysis` lints with, so the
# doc checks and the linter cannot drift from each other. The parsed
# tuples are asserted identical to the importable ones in
# tests/test_static_analysis.py.
from ray_tpu._private.analysis.counter_keys import registry_keys

PIPELINE_STAT_KEYS = registry_keys("node_executor",
                                   "PIPELINE_STAT_KEYS")
DATA_PLANE_STAT_KEYS = registry_keys("node_executor",
                                     "DATA_PLANE_STAT_KEYS")
FAULT_STAT_KEYS = registry_keys("node_executor", "FAULT_STAT_KEYS")

README = Path(__file__).resolve().parent.parent / "README.md"

# Metric families the agent can emit; per-node families never show in
# a local scrape, so they are asserted from this list rather than a
# live body.
EXPORTED_SERIES = (
    "ray_tpu_tasks",
    "ray_tpu_actors",
    "ray_tpu_object_store_memory_bytes",
    "ray_tpu_object_store_num_objects",
    "ray_tpu_spilled_bytes_total",
    "ray_tpu_nodes_alive",
    "ray_tpu_resource_available",
    "ray_tpu_same_host_copy_hits",
    "ray_tpu_export_map_leases",
    "ray_tpu_task_events_dropped_total",
    "ray_tpu_trace_spans_dropped_total",
    "ray_tpu_faults_total",
    "ray_tpu_node_tasks_executed",
    "ray_tpu_node_running_tasks",
    "ray_tpu_node_pipeline",
    "ray_tpu_node_data_plane",
    "ray_tpu_node_faults",
    # Spill tier (ISSUE 10): driver counters as one labeled family
    # (+ the restore-latency gauge) and the per-node heartbeat series.
    "ray_tpu_spill_total",
    "ray_tpu_spill_restore_p50_ms",
    "ray_tpu_node_spill",
    # Always-on performance plane (ISSUE 8): stage-latency histogram
    # triplets per (stage, node), per-function attribution, and the
    # serve router's per-deployment latency histograms (emitted from
    # serve/router.py's collector, same scrape).
    # Scheduler decision plane (ISSUE 9): placement/speculation
    # counters and the per-node load view pick_node scores.
    "ray_tpu_sched_decisions_total",
    "ray_tpu_sched_node_load",
    "ray_tpu_stage_latency",
    "ray_tpu_stage_latency_bucket",
    "ray_tpu_stage_latency_sum",
    "ray_tpu_stage_latency_count",
    "ray_tpu_task_resources",
    "ray_tpu_serve_latency",
    "ray_tpu_serve_latency_bucket",
    "ray_tpu_serve_latency_sum",
    "ray_tpu_serve_latency_count",
    # Durable control plane (ISSUE 12): the head's persistence
    # counters + live incarnation epoch, scraped via the driver's
    # cached gcs_persist_stats() fetch (connected mode only).
    "ray_tpu_gcs_epoch",
    "ray_tpu_gcs_persist_total",
    "ray_tpu_gcs_snapshot_restore_ms",
    # LLM inference engine (ISSUE 14): ENGINE_STAT_KEYS counters per
    # hosting process — driver-local engines under node="driver",
    # daemon-hosted ones via the heartbeat "engine" stats group.
    "ray_tpu_node_engine",
    # Sharded driver dispatch (ISSUE 15): submit-ring/columnar intake
    # and lane-occupancy counters under node="driver"
    # (SUBMIT_STAT_KEYS / DISPATCH_STAT_KEYS in worker.py).
    "ray_tpu_node_submit",
    "ray_tpu_node_dispatch",
    # Sharded GCS hot tables (ISSUE 19): one labeled gauge sample per
    # shard per GCS_SHARD_STAT_KEYS key — only on sharded heads.
    "ray_tpu_gcs_shard",
    # Cluster history plane (ISSUE 20): active watchdog verdicts as a
    # labeled gauge + per-rule fired counter, and the latest
    # per-interval sample per (node, key) from the head's ring store.
    "ray_tpu_health",
    "ray_tpu_health_fired_total",
    "ray_tpu_node_history",
)


@pytest.fixture(scope="module")
def observability_text() -> str:
    text = README.read_text()
    start = text.find("## Observability")
    assert start != -1, "README lost its Observability section"
    end = text.find("\n## ", start + 1)
    return text[start:end if end != -1 else len(text)]


def test_every_executor_stats_counter_documented(observability_text):
    missing = [key for key in (PIPELINE_STAT_KEYS
                               + DATA_PLANE_STAT_KEYS
                               + FAULT_STAT_KEYS)
               if f"`{key}`" not in observability_text]
    assert not missing, (
        f"executor_stats() counter keys missing from the README "
        f"Observability tables: {missing}")


def test_every_driver_stats_counter_documented(observability_text,
                                               ray_start_regular):
    runtime = ray_start_regular
    driver_keys = set(runtime.fault_stats())
    pipeline = runtime.execution_pipeline_stats()
    for group, table in pipeline.items():
        driver_keys.add(group)
        driver_keys.update(table)
    missing = [key for key in sorted(driver_keys)
               if f"`{key}`" not in observability_text]
    assert not missing, (
        f"driver fault_stats()/execution_pipeline_stats() keys missing "
        f"from the README Observability tables: {missing}")


def test_every_exported_series_documented(observability_text):
    missing = [name for name in EXPORTED_SERIES
               if f"`{name}`" not in observability_text]
    assert not missing, (
        f"/metrics series missing from the README metrics table: "
        f"{missing}")


def test_exported_series_list_matches_agent_source():
    """EXPORTED_SERIES itself must not rot: every family name the
    metrics agent writes appears in the list, so a new series forces
    both this list and the README row."""
    import inspect

    from ray_tpu._private import metrics_agent

    source = inspect.getsource(metrics_agent)
    import re

    emitted = set(re.findall(r"(ray_tpu_[a-z0-9_]+)", source))
    # Drop derived suffix forms (e.g. histogram _bucket) — none today.
    missing = sorted(emitted - set(EXPORTED_SERIES))
    assert not missing, (
        f"metrics_agent emits series absent from EXPORTED_SERIES "
        f"(add README rows too): {missing}")


def test_tracing_knobs_documented(observability_text):
    from ray_tpu._private.config import _DEFAULTS

    knobs = [k for k in _DEFAULTS if k.startswith("tracing_")]
    assert knobs, "tracing knobs vanished from config"
    missing = [k for k in knobs if f"`{k}`" not in observability_text]
    assert not missing, (
        f"tracing knobs missing from the README knob table: {missing}")


def test_submit_pipeline_knobs_documented():
    """The submit-ring knobs must keep their README rows (the
    'Pipelined submission' knob table)."""
    from ray_tpu._private.config import _DEFAULTS

    knobs = [k for k in _DEFAULTS if k.startswith("submit_")]
    assert knobs, "submit-pipeline knobs vanished from config"
    text = README.read_text()
    missing = [k for k in knobs if f"`{k}`" not in text]
    assert not missing, (
        f"submit-pipeline knobs missing from the README knob table: "
        f"{missing}")


def test_submit_stage_counter_keys_documented(observability_text):
    """The submit-stage counter keys are asserted statically (the
    dynamic driver-stats test only sees them while the ring is armed):
    dropping one from execution_pipeline_stats()["submit"] or from the
    README must fail here."""
    keys = ("submit", "ring_submits", "flushes", "flush_tasks",
            "ring_full_waits", "buffered_cancels", "arg_cache_hits")
    missing = [k for k in keys if f"`{k}`" not in observability_text]
    assert not missing, (
        f"submit-stage counter keys missing from the README "
        f"Observability tables: {missing}")


def test_sharded_dispatch_knobs_documented():
    """ISSUE 15: the columnar/lane knobs must keep README rows in the
    'Pipelined submission' knob table, and the decision table must
    name the three submit paths."""
    from ray_tpu._private.config import _DEFAULTS

    assert "driver_sharded_dispatch" in _DEFAULTS
    assert "dispatch_lanes" in _DEFAULTS
    text = README.read_text()
    for knob in ("driver_sharded_dispatch", "dispatch_lanes"):
        assert f"`{knob}`" in text, (
            f"sharded-dispatch knob {knob!r} missing from the README "
            f"knob table")
    # Decision-table / semantics phrases the section must keep.
    for phrase in ("columnar records", "dispatch lanes",
                   "classic submit ring", "acquire_batch",
                   "started_many"):
        assert phrase in text, (
            f"'Pipelined submission' section lost the {phrase!r} "
            f"semantics")


def test_sharded_dispatch_counter_registries_documented():
    """Every SUBMIT_STAT_KEYS / DISPATCH_STAT_KEYS registry key (read
    through the analyzer's AST parser, like the other registries) must
    keep a README row, and the registries must match what
    execution_pipeline_stats() actually returns."""
    SUBMIT_KEYS = registry_keys("worker", "SUBMIT_STAT_KEYS")
    DISPATCH_KEYS = registry_keys("worker", "DISPATCH_STAT_KEYS")
    assert SUBMIT_KEYS and DISPATCH_KEYS
    text = README.read_text()
    missing = [k for k in SUBMIT_KEYS + DISPATCH_KEYS
               if f"`{k}`" not in text]
    assert not missing, (
        f"submit/dispatch counter keys missing from the README: "
        f"{missing}")
    from ray_tpu._private.worker import (
        DISPATCH_STAT_KEYS,
        SUBMIT_STAT_KEYS,
    )

    assert tuple(SUBMIT_KEYS) == SUBMIT_STAT_KEYS
    assert tuple(DISPATCH_KEYS) == DISPATCH_STAT_KEYS


def test_overload_knobs_documented():
    """Every overload-control knob (deadlines, admission caps, circuit
    breaker) plus the serve-tier shedding knobs must keep README rows
    (the 'Fault tolerance' knob tables)."""
    from ray_tpu._private.config import _DEFAULTS

    knobs = [k for k in _DEFAULTS
             if k.startswith(("admission_", "rpc_breaker_"))
             or k == "task_default_deadline_s"]
    assert len(knobs) >= 5, f"overload knobs vanished from config: {knobs}"
    text = README.read_text()
    missing = [k for k in knobs if f"`{k}`" not in text]
    assert not missing, (
        f"overload-control knobs missing from the README knob tables: "
        f"{missing}")
    for serve_knob in ("max_queued_requests", "request_timeout_s"):
        assert f"`{serve_knob}`" in text, (
            f"serve shedding knob {serve_knob!r} missing from README")


def test_overload_counters_documented(observability_text):
    """The shed/expiry/breaker counters must be documented next to the
    other fault counters (they ride the same fault_stats() family)."""
    for key in ("task_timeouts", "admission_shed", "breaker_open"):
        assert f"`{key}`" in observability_text, (
            f"overload counter {key!r} missing from the README "
            f"Observability tables")


def test_deadline_stage_table_documented():
    """The 'where a budget can die' semantics table must keep a row per
    stage the runtime actually seals (TaskTimeoutError.stage values)."""
    text = README.read_text()
    for stage in ("submit", "queued", "dispatch", "execute",
                  "admitted", "worker", "actor_queue", "serve_queue",
                  "llm_queue", "llm_decode"):
        assert f"`{stage}`" in text, (
            f"deadline stage {stage!r} missing from the README "
            f"semantics table")


def test_perf_plane_knobs_documented(observability_text):
    """The always-on plane's knobs (master switch + flight-recorder
    sizing) must keep README rows."""
    from ray_tpu._private.config import _DEFAULTS

    knobs = [k for k in _DEFAULTS
             if k == "perf_plane" or k.startswith("flight_recorder_")]
    assert len(knobs) >= 3, f"perf-plane knobs vanished from config: {knobs}"
    missing = [k for k in knobs
               if f"`{k}`" not in observability_text]
    assert not missing, (
        f"perf-plane knobs missing from the README knob table: "
        f"{missing}")


def test_stage_histogram_names_documented(observability_text):
    """Every stage-histogram name the runtime records must be in the
    README's stage table (STAGE_HIST_KEYS is the canonical list)."""
    from ray_tpu._private.node_executor import STAGE_HIST_KEYS

    missing = [s for s in STAGE_HIST_KEYS
               if f"`{s}`" not in observability_text]
    assert not missing, (
        f"perf-plane stage names missing from the README: {missing}")


def test_sched_knobs_documented():
    """Every locality-/speculation-scheduling knob must keep a README
    row (the "Scheduling" knob table)."""
    from ray_tpu._private.config import _DEFAULTS

    knobs = [k for k in _DEFAULTS
             if k.startswith(("locality_", "speculation_"))
             or k == "sched_stats_stale_s"]
    assert len(knobs) >= 8, f"sched knobs vanished from config: {knobs}"
    text = README.read_text()
    missing = [k for k in knobs if f"`{k}`" not in text]
    assert not missing, (
        f"scheduling knobs missing from the README knob table: "
        f"{missing}")


def test_sched_counter_keys_documented(observability_text,
                                       ray_start_regular):
    """The sched decision counters must be documented both in the
    Scheduling section and next to the other driver counter keys
    (they ride execution_pipeline_stats()['sched'])."""
    runtime = ray_start_regular
    keys = set(runtime.execution_pipeline_stats()["sched"])
    assert {"locality_hits", "locality_bytes_saved", "load_spillbacks",
            "stale_stats_skips", "speculations_launched",
            "speculations_won", "speculations_lost"} <= keys, keys
    sched_section = README.read_text()
    start = sched_section.find("## Scheduling")
    assert start != -1, "README lost its Scheduling section"
    end = sched_section.find("\n## ", start + 1)
    sched_section = sched_section[start:end]
    for key in sorted(keys):
        assert f"`{key}`" in observability_text, (
            f"sched counter {key!r} missing from the README "
            f"Observability tables")
        assert f"`{key}`" in sched_section, (
            f"sched counter {key!r} missing from the README "
            f"Scheduling section")


def test_sched_node_load_keys_documented():
    """The per-node load-view keys (the ray_tpu_sched_node_load series
    + the `summary placement` table) must keep README rows."""
    text = README.read_text()
    for key in ("running", "depth", "age_s", "admit_p50_s",
                "exec_p50_s", "admit_p50_ms", "exec_p50_ms",
                "tasks_executed"):
        assert f"`{key}`" in text, (
            f"placement/load key {key!r} missing from the README")
    assert "summary placement" in text, (
        "the `summary placement` CLI lost its README mention")


def test_straggle_chaos_site_documented():
    """The sched.straggle injection site (and its delay env knob) must
    stay documented in the fault-tolerance chaos list."""
    text = README.read_text()
    assert "`sched.straggle`" in text
    assert "RAY_TPU_STRAGGLE_S" in text


def test_summary_and_debug_clis_documented():
    """The summary and debug subcommands (and the timeline one from
    PR 5) must keep their README mentions."""
    text = README.read_text()
    for cmd in ("python -m ray_tpu summary",
                "python -m ray_tpu debug",
                "python -m ray_tpu timeline"):
        assert cmd in text, f"CLI {cmd!r} missing from README"


def test_summarize_tasks_keys_documented(observability_text):
    """The summarize_tasks() per-function views must be documented
    next to the CLI that prints them."""
    for key in ("latency", "resources", "p50_s", "p99_s",
                "cpu_s", "peak_rss_kb"):
        assert f"`{key}`" in observability_text, (
            f"summarize_tasks key {key!r} missing from the README "
            f"Observability section")


def test_readme_stage_list_matches_tracing_stages():
    from ray_tpu.util import tracing

    text = README.read_text()
    chain = " → ".join(tracing.STAGES)
    assert chain in text.replace("\n", " ").replace("  ", " "), (
        f"README stage chain drifted from tracing.STAGES: {chain}")


# -------------------------------------------------------- fused execution


@pytest.fixture(scope="module")
def fused_text() -> str:
    text = README.read_text()
    start = text.find("## Fused execution")
    assert start != -1, "README lost its Fused execution section"
    end = text.find("\n## ", start + 1)
    return text[start:end if end != -1 else len(text)]


def test_fused_knobs_documented(fused_text):
    """Every fused-execution / raw-framing knob must keep a README row
    in the Fused execution knob table."""
    from ray_tpu._private.config import _DEFAULTS

    knobs = [k for k in _DEFAULTS
             if k.startswith("fused_") or k == "raw_framing"]
    assert len(knobs) >= 4, f"fused knobs vanished from config: {knobs}"
    missing = [k for k in knobs if f"`{k}`" not in fused_text]
    assert not missing, (
        f"fused-execution knobs missing from the README knob table: "
        f"{missing}")


def test_fused_decision_table_documented(fused_text):
    """The fused-vs-classic-vs-pipelined decision table must keep a row
    per path, and the counter keys their README mention."""
    for path in ("**fused**", "**pipelined**", "**classic**"):
        assert path in fused_text, (
            f"decision-table row {path} missing from the README Fused "
            f"execution section")
    for key in ("fused_runs", "fused_tasks", "fused_fallbacks",
                "batch_overcommit", "runner_spawns", "runner_reuses"):
        assert f"`{key}`" in fused_text, (
            f"fused counter {key!r} missing from the README Fused "
            f"execution section")


def test_fused_counters_match_driver_stats(ray_start_regular):
    """execution_pipeline_stats()["fused"] must emit exactly the
    documented keys (a new counter forces a README row via the
    Observability-table drift tests)."""
    fused = ray_start_regular.execution_pipeline_stats()["fused"]
    assert set(fused) == {"fused_runs", "fused_tasks",
                          "fused_fallbacks"}, fused
    dispatch = ray_start_regular.execution_pipeline_stats()["dispatch"]
    assert "batch_overcommit" in dispatch, dispatch


# ---------------------------------------------------------- spill tier


@pytest.fixture(scope="module")
def spilling_text() -> str:
    text = README.read_text()
    start = text.find("## Object spilling & tiering")
    assert start != -1, "README lost its spilling section"
    end = text.find("\n## ", start + 1)
    return text[start:end if end != -1 else len(text)]


def test_spill_knobs_documented(spilling_text):
    from ray_tpu._private.config import _DEFAULTS

    knobs = [k for k in _DEFAULTS if k.startswith("spill_")]
    assert len(knobs) >= 6, "spill knobs vanished from config"
    missing = [k for k in knobs if f"`{k}`" not in spilling_text]
    assert not missing, (
        f"spill knobs missing from the README knob table: {missing}")


def test_spill_counter_keys_documented(spilling_text):
    """Every executor_stats()["spill"] / runtime.spill_stats() key
    (SPILL_STAT_KEYS is the canonical source, read through the
    analyzer's AST parser) plus the derived fields must keep README
    rows."""
    SPILL_STAT_KEYS = registry_keys("spill_manager", "SPILL_STAT_KEYS")

    keys = list(SPILL_STAT_KEYS) + ["restore_p50_ms",
                                    "spilled_plan_hits"]
    missing = [k for k in keys if f"`{k}`" not in spilling_text]
    assert not missing, (
        f"spill counter keys missing from the README spilling "
        f"section: {missing}")


def test_spill_chaos_sites_documented(spilling_text):
    """The three spill chaos sites are part of the chaos-spec contract
    — registered in chaos.SITES (the analyzer's chaos-sites pass
    enforces registry ↔ docstring ↔ tests coherence) and documented in
    the README spilling section."""
    from ray_tpu._private.analysis.chaos_sites import registered_sites

    registered = registered_sites()
    for site in ("spill.torn_write", "spill.disk_full",
                 "spill.restore_delay"):
        assert site in registered, (
            f"chaos site {site} missing from chaos.SITES")
        assert f"`{site}`" in spilling_text, (
            f"chaos site {site} missing from the README spilling "
            f"section")


def test_spill_stats_shape_matches_docs():
    """merged_stats() (the spill_stats()/executor_stats shape) must
    emit exactly the documented keys — a new counter forces a README
    row via test_spill_counter_keys_documented."""
    from ray_tpu._private.spill_manager import (
        SPILL_STAT_KEYS,
        merged_stats,
    )

    stats = merged_stats(None)
    assert set(stats) == set(SPILL_STAT_KEYS) | {"restore_p50_ms",
                                                 "backing_off"}


# ------------------------------------------- durable control plane


@pytest.fixture(scope="module")
def fault_tolerance_text() -> str:
    text = README.read_text()
    start = text.find("## Fault tolerance")
    assert start != -1, "README lost its Fault tolerance section"
    end = text.find("\n## ", start + 1)
    return text[start:end if end != -1 else len(text)]


def test_gcs_persistence_knobs_documented(fault_tolerance_text):
    from ray_tpu._private.config import _DEFAULTS

    knobs = [k for k in _DEFAULTS
             if k.startswith(("gcs_persistence", "gcs_snapshot_",
                              "gcs_wal_", "gcs_epoch_"))]
    assert len(knobs) >= 5, "gcs persistence knobs vanished from config"
    missing = [k for k in knobs
               if f"`{k}`" not in fault_tolerance_text]
    assert not missing, (
        f"gcs persistence/epoch knobs missing from the README fault-"
        f"tolerance knob table: {missing}")


def test_head_failure_model_table_documented(fault_tolerance_text):
    """The head-failure-model contract: what survives a head crash,
    what re-syncs, what fences."""
    assert "Durable, fenced control plane" in fault_tolerance_text
    flat = " ".join(fault_tolerance_text.split())
    for phrase in ("node table", "actor registry", "object directory",
                   "placement groups", "re-syncs",
                   "`StaleEpochError`", "`fenced_writes`",
                   "never resurrect a dead actor",
                   "double-register a node"):
        assert phrase in flat, (
            f"head-failure-model text lost {phrase!r}")


def test_gcs_persist_counter_keys_documented(fault_tolerance_text):
    """Every counter persist_stats() serves (minus the live
    epoch/armed/fencing fields) must appear in the fault-tolerance
    section — the keys the ray_tpu_gcs_persist_total family labels."""
    import tempfile

    from ray_tpu._private.gcs_server import GcsServer

    with tempfile.TemporaryDirectory() as tmp:
        server = GcsServer(
            host="127.0.0.1", port=0, log_dir=tmp,
            persist_path=f"{tmp}/snap.pkl")
        stats = server.persist_stats()
        server._shutdown.set()
        server._server.stop()
    counter_keys = set(stats) - {"epoch", "armed", "fencing"}
    missing = [k for k in sorted(counter_keys)
               if f"`{k}`" not in fault_tolerance_text]
    assert not missing, (
        f"gcs persist counters missing from the README fault-"
        f"tolerance section: {missing}")


def test_partition_and_gcs_chaos_sites_documented(fault_tolerance_text):
    from ray_tpu._private.analysis.chaos_sites import registered_sites

    registered = registered_sites()
    for site in ("net.partition", "gcs.torn_snapshot", "gcs.torn_wal"):
        assert site in registered, (
            f"chaos site {site} missing from chaos.SITES")
        assert f"`{site}`" in fault_tolerance_text, (
            f"chaos site {site} missing from the README fault-"
            f"tolerance section")


def test_recovery_envelope_row_documented(fault_tolerance_text):
    """The guarded recovery row and its refresh knob are part of the
    operator contract."""
    assert "`recovery` row" in fault_tolerance_text
    assert "ENVELOPE_RECOVERY_ONLY" in fault_tolerance_text
    assert "time_to_recovered_s" in fault_tolerance_text
    assert "wal_records_replayed > 0" in fault_tolerance_text


# ----------------------------------------------- sharded GCS hot tables


def test_gcs_shard_knobs_documented(fault_tolerance_text):
    """The sharding knobs (ISSUE 19) keep README rows in the fault-
    tolerance knob table."""
    from ray_tpu._private.config import _DEFAULTS

    knobs = [k for k in _DEFAULTS if k.startswith("gcs_shard")]
    assert len(knobs) >= 2, f"gcs shard knobs vanished from config: {knobs}"
    missing = [k for k in knobs
               if f"`{k}`" not in fault_tolerance_text]
    assert not missing, (
        f"gcs shard knobs missing from the README fault-tolerance "
        f"knob table: {missing}")


def test_shard_failure_model_table_documented(fault_tolerance_text):
    """The shard failure-model contract: shard-kill vs head-kill vs
    partition semantics, degraded-read / queued-write rules, typed
    refusals."""
    flat = " ".join(fault_tolerance_text.split())
    for phrase in ("shard-kill", "head-kill",
                   "replaying only its own WAL",
                   "`ReshardError`", "`SystemOverloadedError`",
                   "stale-marked", "queue WAL-first", "`age_s`",
                   "never lose an acked write",
                   "`gcs.shard_restore`", "`gcs.shard_fenced_write`",
                   "`gcs.shard_backoff`"):
        assert phrase in flat, (
            f"shard failure-model text lost {phrase!r}")


def test_gcs_shard_chaos_sites_documented(fault_tolerance_text):
    from ray_tpu._private.analysis.chaos_sites import registered_sites

    registered = registered_sites()
    for site in ("gcs.shard_die", "gcs.shard_stall"):
        assert site in registered, (
            f"chaos site {site} missing from chaos.SITES")
        assert f"`{site}`" in fault_tolerance_text, (
            f"chaos site {site} missing from the README fault-"
            f"tolerance section")
    assert "RAY_TPU_SHARD_STALL_S" in fault_tolerance_text


def test_gcs_shard_metrics_family_documented(fault_tolerance_text):
    """Every GCS_SHARD_STAT_KEYS key (read through the analyzer's AST
    parser, asserted identical to the importable tuple) keeps a README
    row, and the family itself is documented."""
    parsed = registry_keys("gcs_shard", "GCS_SHARD_STAT_KEYS")
    from ray_tpu._private.gcs_shard import GCS_SHARD_STAT_KEYS

    assert tuple(parsed) == tuple(GCS_SHARD_STAT_KEYS)
    assert len(parsed) >= 9
    assert "`ray_tpu_gcs_shard`" in fault_tolerance_text
    missing = [k for k in parsed
               if f"`{k}`" not in fault_tolerance_text]
    assert not missing, (
        f"GCS_SHARD_STAT_KEYS missing from the README fault-"
        f"tolerance section: {missing}")


def test_recovery_shard_envelope_row_documented(fault_tolerance_text):
    """The shard-kill recovery bench row is operator contract like the
    head-kill one."""
    flat = " ".join(fault_tolerance_text.split())
    assert "`recovery_shard` row" in flat
    assert "1 of 4 shards" in flat


# -------------------------------------------- cluster history plane


def test_history_plane_knobs_documented(observability_text):
    """Every history-plane knob (store cadence/retention + the
    watchdog's health_* thresholds) keeps a README row in the 'Cluster
    history plane' knob table."""
    from ray_tpu._private.config import _DEFAULTS

    knobs = [k for k in _DEFAULTS
             if k.startswith("metrics_history")
             or (k.startswith("health_")
                 and not k.startswith("health_check"))]
    assert len(knobs) >= 11, (
        f"history-plane knobs vanished from config: {knobs}")
    missing = [k for k in knobs
               if f"`{k}`" not in observability_text]
    assert not missing, (
        f"history-plane knobs missing from the README knob table: "
        f"{missing}")


def test_health_rules_parsed_match_importable(observability_text):
    """Every watchdog rule name (AST-parsed from the module source,
    asserted identical to the importable HEALTH_RULES tuple) keeps a
    row in the README rule table."""
    import ast
    import inspect

    from ray_tpu._private import metrics_history
    from ray_tpu._private.metrics_history import HEALTH_RULES

    parsed: tuple = ()
    tree = ast.parse(inspect.getsource(metrics_history))
    for node in tree.body:
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "HEALTH_RULES"
                for t in node.targets):
            assert isinstance(node.value, ast.Tuple)
            parsed = tuple(elt.value for elt in node.value.elts
                           if isinstance(elt, ast.Constant))
    assert tuple(parsed) == tuple(HEALTH_RULES)
    assert len(parsed) == 6
    missing = [r for r in parsed
               if f"`{r}`" not in observability_text]
    assert not missing, (
        f"watchdog rules missing from the README rule table: "
        f"{missing}")


def test_history_stat_keys_parsed_match_importable(observability_text):
    """Every HISTORY_STAT_KEYS sample key (the per-interval row the
    ring store serves and ray_tpu_node_history labels) keeps a README
    mention in the Observability section."""
    parsed = registry_keys("metrics_history", "HISTORY_STAT_KEYS")
    from ray_tpu._private.metrics_history import (
        GAUGE_KEYS,
        HISTORY_STAT_KEYS,
    )

    assert tuple(parsed) == tuple(HISTORY_STAT_KEYS)
    assert len(parsed) >= 12
    assert GAUGE_KEYS <= set(parsed)
    missing = [k for k in parsed
               if f"`{k}`" not in observability_text]
    assert not missing, (
        f"history sample keys missing from the README Observability "
        f"section: {missing}")


def test_history_clis_documented(observability_text):
    """The top/doctor subcommands and the health series semantics keep
    their README quickstarts."""
    for cmd in ("python -m ray_tpu top", "python -m ray_tpu doctor"):
        assert cmd in observability_text, (
            f"CLI {cmd!r} missing from the README Observability "
            f"section")
    flat = " ".join(observability_text.split())
    for phrase in ("`ray_tpu_health`", "`cluster_health`",
                   "`metrics_history`", "sparkline",
                   "ENVELOPE_HISTORY_ONLY"):
        assert phrase in flat, (
            f"'Cluster history plane' section lost {phrase!r}")


def test_history_disarm_gate_registered():
    """The metrics_history knob rides the disarm-gate analysis pass
    (one module attribute, HISTORY_ON) like every other plane."""
    from ray_tpu._private.analysis.disarm_gates import KNOB_GATES

    assert KNOB_GATES.get("metrics_history") == (
        "ray_tpu/_private/metrics_history.py", "HISTORY_ON")
    from ray_tpu._private.config import _DEFAULTS

    assert "metrics_history" in _DEFAULTS


# ---------------------------------------- static analysis tooling


@pytest.fixture(scope="module")
def static_analysis_text() -> str:
    text = README.read_text()
    start = text.find("## Static analysis & concurrency tooling")
    assert start != -1, ("README lost its Static analysis & "
                         "concurrency tooling section")
    end = text.find("\n## ", start + 1)
    return text[start:end if end != -1 else len(text)]


def test_lock_witness_knob_documented(static_analysis_text):
    """The lock_witness knob keeps its README row (and stays a real
    config key)."""
    from ray_tpu._private.config import _DEFAULTS

    assert "lock_witness" in _DEFAULTS, (
        "lock_witness knob vanished from config")
    assert "`lock_witness`" in static_analysis_text
    assert "RAY_TPU_LOCK_WITNESS" in static_analysis_text
    assert "LockOrderError" in static_analysis_text


def test_every_linter_pass_documented(static_analysis_text):
    """Every analyzer pass id keeps a row in the README pass table —
    sourced from the same PASS_IDS tuple the CLI serves."""
    from ray_tpu.analysis import PASS_IDS

    missing = [p for p in PASS_IDS
               if f"`{p}`" not in static_analysis_text]
    assert not missing, (
        f"linter passes missing from the README pass table: {missing}")


def test_linter_cli_and_suppression_format_documented(
        static_analysis_text):
    assert "python -m ray_tpu.analysis" in static_analysis_text
    assert "suppressions.txt" in static_analysis_text
    # The suppression grammar is operator-facing contract.
    assert "::" in static_analysis_text
    from ray_tpu.analysis import MAX_SUPPRESSIONS

    assert str(MAX_SUPPRESSIONS) in static_analysis_text, (
        "suppression budget number drifted out of the README")


# ------------------------------------------------------------- LLM serving


@pytest.fixture(scope="module")
def llm_text() -> str:
    text = README.read_text()
    start = text.find("## LLM serving")
    assert start != -1, "README lost its LLM serving section"
    end = text.find("\n## ", start + 1)
    return text[start:end if end != -1 else len(text)]


def test_llm_engine_knobs_documented(llm_text):
    """Every llm_* knob plus the router latency-report cadence keeps a
    README row in the LLM serving knob table."""
    from ray_tpu._private.config import _DEFAULTS

    knobs = [k for k in _DEFAULTS if k.startswith("llm_")]
    knobs.append("serve_latency_report_s")
    assert len(knobs) >= 4, f"llm knobs vanished from config: {knobs}"
    missing = [k for k in knobs if f"`{k}`" not in llm_text]
    assert not missing, (
        f"LLM engine knobs missing from the README knob table: "
        f"{missing}")


def test_engine_stat_keys_documented(llm_text):
    """Every ENGINE_STAT_KEYS counter (read through the analyzer's AST
    parser, asserted identical to the importable tuple) keeps a README
    row in the LLM serving section."""
    parsed = registry_keys("llm_engine", "ENGINE_STAT_KEYS")
    from ray_tpu.serve.llm_engine import ENGINE_STAT_KEYS

    assert tuple(parsed) == tuple(ENGINE_STAT_KEYS)
    assert len(parsed) >= 12
    missing = [k for k in parsed if f"`{k}`" not in llm_text]
    assert not missing, (
        f"ENGINE_STAT_KEYS missing from the README LLM serving "
        f"section: {missing}")


def test_llm_chaos_site_documented(llm_text):
    """llm.slow_step is part of the chaos-spec contract: registered,
    documented in the LLM section, with its delay env knob."""
    from ray_tpu._private.analysis.chaos_sites import registered_sites

    assert "llm.slow_step" in registered_sites()
    assert "`llm.slow_step`" in llm_text
    assert "RAY_TPU_LLM_SLOW_S" in llm_text


def test_llm_scheduler_and_paging_semantics_documented(llm_text):
    """The operator contract: block/page semantics, the scheduler
    policy, preemption, typed shedding and the autoscaler feed."""
    flat = " ".join(llm_text.split())
    for phrase in ("block table", "block 0", "chunked prefill",
                   "lowest-progress", "recompute-on-resume",
                   "`CacheExhaustedError`", "`target_p99_s`",
                   "`engine_depth`", "latency_stats()",
                   "ray_tpu_node_engine"):
        assert phrase in flat, (
            f"LLM serving section lost {phrase!r}")


def test_llm_engine_disarm_gate_registered():
    """Nothing is registered, because nothing selects: the paged engine
    is the one way to serve a language model. No knob or gate names
    another, ``ray_tpu.serve`` holds no second engine module, and the
    model file holds no serving cache."""
    import importlib
    import pkgutil

    from ray_tpu import serve
    from ray_tpu._private.analysis.disarm_gates import KNOB_GATES
    from ray_tpu._private.config import _DEFAULTS
    from ray_tpu.models import llama

    assert {k for k in _DEFAULTS if k.startswith("llm_")} == {
        "llm_block_size", "llm_prefill_chunk", "llm_max_waiting"}
    assert len(KNOB_GATES) == 12
    assert not [home for home, _ in KNOB_GATES.values()
                if home.startswith("ray_tpu/serve/")]
    modules = {m.name for m in pkgutil.iter_modules(serve.__path__)}
    assert "llm_engine" in modules and "llm" not in modules
    with pytest.raises(ImportError):
        importlib.import_module(".llm", serve.__name__)
    assert not [name for name, value in vars(llama).items()
                if callable(value) and "cache" in name]
