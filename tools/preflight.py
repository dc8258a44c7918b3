"""One-stop repo preflight: every committed-artifact and docs-fence
contract in one obvious place.

The repo grew four separate guards — ``tools/check_bench_schema.py``
(the bench output contract), ``tools/check_metrics_docs.py`` (the three
doc-fenced metric tables), ``obs.metrics.lint_prometheus`` (the
/metrics exposition rules), and ``tools/perf_diff.py`` (headline
regression gates over the committed ``BENCH_rNN`` artifacts). Each has
its own CLI and its own tier-1 test, which means a PR that regresses a
committed headline artifact or desyncs a docs fence fails in whichever
corner happens to notice. This module runs ALL of them:

    python tools/preflight.py            # everything; non-zero on any failure
    python tools/preflight.py --list     # enumerate the checks

Checks:

- **bench-schema** — a fully-assembled synthetic bench result (built
  through ``bench.assemble_result``, including the KV-pressure and
  fleet sections) validates against ``tools/bench_schema.json``. The
  committed round artifacts predate newer required sections and are
  deliberately NOT schema-checked; their contract is the perf gate
  below.
- **metrics-docs** — the engine-gauge / router / round-telemetry
  tables in ``docs/observability.md`` match the code surfaces two-way.
- **metrics-lint** — every declared metric surface renders a clean
  Prometheus exposition (HELP lines, family matching, ``_total``
  counters).
- **fleet-obs** — the router's ``GET /debug/fleet`` snapshot and
  ``/debug/requests`` timeline contracts (router/fleet.py schemas)
  validated element-wise over a synthetic-but-real router state built
  through the production table/recorder/window classes.
- **autoscale** — the autoscale controller's decision-record and
  ``GET /debug/autoscale`` contracts (router/autoscale.py schemas):
  a real controller ticks over the synthetic fleet state and every
  decision record + the endpoint payload validate element-wise, with
  the overloaded state required to produce a scale-up decision (an
  all-hold ring would validate while proving nothing).
- **multichip** — the ``BENCH_MESH`` sweep's ``multichip`` section
  contract: schema element-wise plus the semantic invariants (mesh
  labels parse and match ``devices``, every rung carries a positive
  topology-derived round budget, mesh rungs serve the ``fused_tp``
  tail — a ``materialized`` mesh rung is the silent regression this
  PR's tentpole removed).
- **disagg** — the ``BENCH_DISAGG`` scenario's ``disagg`` section
  contract (docs/disaggregation.md): schema element-wise plus the
  semantic invariants (both arms present at EQUAL chip counts, the
  disagg arm's role census actually splits prefill/decode, and its
  handoff accounting shows the two-leg path ran — a disagg arm with
  zero handoffs AND zero fallbacks silently degenerated to unified).
- **alerts** — a REAL ``obs.alerts.AlertEngine`` ticked over a
  synthetic-but-real metric history through a whole episode: the
  watchdog rule must FIRE on climbing stall deltas (``on_fire`` exactly
  once) and must RESOLVE when the breach ages out of the rule window;
  the incident bundle built from the firing validates against the
  ``incident/v1`` contract and renders via ``tools/incident_report.py``.
- **obs-overhead** — the ``BENCH_OBS_OVERHEAD`` scenario's
  ``obs_overhead`` section contract: schema plus the semantic
  invariants (armed arm actually sampling, overhead arithmetic
  consistent with the two arms).
- **perf-gates** — ``tools/perf_diff.py`` over committed artifact
  pairs: each later round must not regress the earlier one's headline
  metrics (the same pairs/thresholds the tier-1 perf_diff test pins).

Tier-1: ``tests/test_preflight.py`` runs ``run_checks`` green, so a
fence desync or artifact regression fails the suite through this one
entry point too.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Callable

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Committed artifact pairs the perf gate enforces, with per-metric
#: threshold overrides (p99 tail percentiles over single-digit samples
#: jitter between runs — same widening the tier-1 perf_diff test uses).
#: Synthetic driver-shaped fixtures: the repo holds no chip record yet
#: (the benchmark PR, ROADMAP S1, replaces them with ledger rows).
_GATE_DIR = "tests/fixtures/perf_gate"
PERF_GATE_PAIRS: list[tuple[str, str, dict[str, float]]] = [
    (f"{_GATE_DIR}/bench_round_b.json", f"{_GATE_DIR}/bench_round_c.json",
     {"engine_p99_ttft_ms": 20.0}),
    (f"{_GATE_DIR}/bench_round_a.json", f"{_GATE_DIR}/bench_round_c.json",
     {"engine_p99_ttft_ms": 20.0}),
]


def check_bench_schema() -> list[str]:
    """Validate a fully-populated synthetic result through the real
    emit path (``bench.assemble_result`` -> ``validate_result``)."""
    sys.path.insert(0, REPO)
    import bench
    from tools.check_bench_schema import BenchSchemaError, validate_result

    kv_pressure = {
        "pool_tokens": 2048, "host_pool_tokens": 8192,
        "ratios": [1, 2], "turns": 3,
        "arms": [
            {"ratio": 1, "tiering": False, "sessions": 2,
             "cold_p50_ttft_ms": 50.0, "warm_p50_ttft_ms": 40.0,
             "kv_restore_hit_rate": 0.0, "kv_tier_offload_pages": 0,
             "kv_tier_restore_pages": 0, "kv_restore_skipped_cost": 0,
             "prefix_hit_rate": 0.1},
            {"ratio": 1, "tiering": True, "sessions": 2,
             "cold_p50_ttft_ms": 50.0, "warm_p50_ttft_ms": 20.0,
             "kv_restore_hit_rate": 0.5, "kv_tier_offload_pages": 8,
             "kv_tier_restore_pages": 6, "kv_restore_skipped_cost": 1,
             "prefix_hit_rate": 0.6},
        ],
    }
    fleet = {
        "replicas": 2, "sessions": 3, "turns_per_session": 3,
        "session_rps": 4.0, "slo_ttft_ms": 2000.0, "num_tokens": 4,
        "policies": [
            {"policy": p, "offered_turns": 9, "completed": 9,
             "errors": 0, "slo_attainment": 1.0, "ttft_p50_ms": 10.0,
             "ttft_p99_ms": 12.0, "cold_ttft_p50_ms": 11.0,
             "warm_ttft_p50_ms": 9.0, "prefix_hit_tokens": 100,
             "prefix_hit_rate": 0.5, "placed": {"r0": 5, "r1": 4},
             "affinity_hit_placements": 3, "retries_connect": 0,
             "kv_transfer": p == "affinity_transfer",
             "kv_transfer_pages": 4 if p == "affinity_transfer" else 0}
            for p in ("round_robin", "affinity", "affinity_transfer")],
        "fleet_obs": {
            "slo_attainment": 1.0, "window_requests": 9,
            "ttft_p50_ms": 10.0, "error_rate": 0.0,
            "headroom_tokens_per_sec": 120.0,
            "capacity_tokens_per_sec": 200.0,
            "replicas": [
                {"name": f"r{i}", "slo_attainment": 1.0,
                 "window_requests": 4 + i,
                 "headroom_tokens_per_sec": 60.0}
                for i in range(2)],
        },
    }
    autoscale = {
        "duration_s": 12.0, "trace": [[0.3, 1.0], [0.3, 6.0], [0.4, 1.0]],
        "slo_ttft_ms": 2000.0, "deadline_ms": None, "num_tokens": 8,
        "min_replicas": 1, "max_replicas": 3, "interval_s": 0.3,
        "policies": [
            {"policy": "autoscaled", "replicas_static": None,
             "offered": 40, "completed": 38, "shed": 2, "errors": 0,
             "slo_attainment": 0.9, "ttft_p50_ms": 120.0,
             "replica_minutes": 0.4, "avg_replicas": 2.0,
             "peak_replicas": 3, "scale_ups": 2, "scale_downs": 1,
             "surge_rejections": 0, "decisions": 40},
            {"policy": "static", "replicas_static": 2,
             "offered": 40, "completed": 35, "shed": 5, "errors": 0,
             "slo_attainment": 0.8, "ttft_p50_ms": 200.0,
             "replica_minutes": 0.4, "avg_replicas": 2.0,
             "peak_replicas": 2, "scale_ups": 0, "scale_downs": 0,
             "surge_rejections": 0, "decisions": 0},
        ],
    }
    result = bench.assemble_result(
        kind="engine", model="preflight", headline=10.0,
        engine_p50=8.0, engine_p99=12.0, tput=100.0,
        achieved_bw=1e9, bw_util=0.1, bw_steady=True,
        chat=None, e2e_p50=None, e2e_dist=None, e2e_breakdown=None,
        e2e_tps_p50=None, pipeline=bench.pipeline_snapshot({}),
        quant="none", kv_quant=None, weights="random-init",
        prompt_len=16, out_len=4, slots=2, steps_per_round=4,
        kv_pool_pages=8, device="cpu", rtt_ms=None, n_devices=1,
        bench_seconds=1.0, fleet=fleet, kv_pressure=kv_pressure,
        autoscale=autoscale, multichip=synthetic_multichip(),
        disagg=synthetic_disagg(), obs_overhead=synthetic_obs_overhead())
    try:
        validate_result(result)
    except BenchSchemaError as exc:
        return [str(exc)]
    return []


def synthetic_multichip() -> dict:
    """A fully-populated ``multichip`` bench section (the BENCH_MESH
    sweep's output shape) — shared by the bench-schema synthetic result
    and the multichip check below; returned fresh so the tier-1 test
    can doctor a copy to prove the check fails."""
    return {
        "mesh_sweep": ["tp=1", "tp=2"],
        "prompt_len": 16, "output_len": 4, "requests_per_rung": 2,
        "slots": 2,
        "rungs": [
            {"mesh": "tp=1", "devices": 1,
             "engine_p50_ttft_ms": 20.0, "engine_p99_ttft_ms": 25.0,
             "decode_tokens_per_sec": 100.0,
             "tokens_per_sec_per_device": 100.0,
             "sched_round_budget_tokens": 256,
             "cost_source": "PROFILE_preflight.json",
             "cost_topology": "tp=1", "tail": "fused",
             "engine_downgrades": 0, "spec": None},
            {"mesh": "tp=2", "devices": 2,
             "engine_p50_ttft_ms": 14.0, "engine_p99_ttft_ms": 18.0,
             "decode_tokens_per_sec": 160.0,
             "tokens_per_sec_per_device": 80.0,
             "sched_round_budget_tokens": 384,
             "cost_source": "PROFILE_preflight.json@tp=2",
             "cost_topology": "tp=2", "tail": "fused_tp",
             "engine_downgrades": 0,
             "spec": {"draft_tokens": 8, "accepted_tokens": 5,
                      "verify_rounds": 3, "acceptance_rate": 0.625,
                      "tokens_per_step": 1.6}},
        ],
    }


def validate_multichip_block(block: dict) -> list[str]:
    """Element-wise + semantic validation of one ``multichip`` section:
    schema per rung, parseable mesh labels whose axis product matches
    ``devices``, a positive topology-derived round budget, and a tail
    mode from the known set (a mesh rung reading ``materialized`` means
    the sharded fused tail silently regressed to the fallback)."""
    import re as _re

    sys.path.insert(0, REPO)
    from tools.check_bench_schema import (BenchSchemaError, load_schema,
                                          validate_result)
    errors: list[str] = []
    try:
        validate_result({"multichip": block},
                        schema={**load_schema(),
                                "top_level": {"multichip": ["obj"]}})
    except BenchSchemaError as exc:
        errors.append(str(exc))
    for i, rung in enumerate(block.get("rungs") or []):
        if not isinstance(rung, dict):
            continue
        mesh = str(rung.get("mesh", ""))
        if not _re.fullmatch(r"[a-z]+=\d+(,[a-z]+=\d+)*", mesh):
            errors.append(f"rungs[{i}]: mesh label {mesh!r} is not "
                          f"axis=N[,axis=N...]")
            continue
        product = 1
        for part in mesh.split(","):
            product *= int(part.split("=")[1])
        if product != rung.get("devices"):
            errors.append(
                f"rungs[{i}]: devices={rung.get('devices')} does not "
                f"match mesh {mesh!r} (axis product {product})")
        if not rung.get("sched_round_budget_tokens", 0) > 0:
            errors.append(f"rungs[{i}]: sched_round_budget_tokens must "
                          f"be > 0 (no topology row produced a budget)")
        if rung.get("tail") not in ("fused_tp", "fused", "materialized"):
            errors.append(f"rungs[{i}]: unknown tail mode "
                          f"{rung.get('tail')!r}")
        if rung.get("devices", 1) > 1 and rung.get("tail") != "fused_tp":
            errors.append(
                f"rungs[{i}]: mesh rung {mesh!r} served with tail="
                f"{rung.get('tail')!r} — the tp-sharded fused sampler "
                f"regressed to a fallback")
    return errors


def synthetic_disagg() -> dict:
    """A fully-populated ``disagg`` bench section (the BENCH_DISAGG
    scenario's output shape) — shared by the bench-schema synthetic
    result and the disagg check below; returned fresh so the tier-1
    test can doctor a copy to prove the check fails."""
    return {
        "replicas": 2, "requests": 24, "rps": 4.0, "long_frac": 0.4,
        "long_chars": 4600, "short_chars": 400, "num_tokens": 16,
        "arms": [
            {"arm": "unified", "roles": {"unified": 2},
             "offered": 24, "completed": 24, "errors": 0,
             "ttft_p50_ms": 120.0, "ttft_p99_ms": 400.0,
             "long_ttft_p50_ms": 300.0, "short_ttft_p50_ms": 90.0,
             "tokens_generated": 384, "decode_goodput": 60.0,
             "handoffs": 0, "fallbacks": 0, "kv_export_pages": 0,
             "kv_export_shed": 0, "kv_transfer_pages": 0},
            {"arm": "disagg", "roles": {"prefill": 1, "decode": 1},
             "offered": 24, "completed": 24, "errors": 0,
             "ttft_p50_ms": 80.0, "ttft_p99_ms": 280.0,
             "long_ttft_p50_ms": 200.0, "short_ttft_p50_ms": 60.0,
             "tokens_generated": 384, "decode_goodput": 90.0,
             "handoffs": 9, "fallbacks": 1, "kv_export_pages": 36,
             "kv_export_shed": 0, "kv_transfer_pages": 4},
        ],
    }


def validate_disagg_block(block: dict) -> list[str]:
    """Element-wise + semantic validation of one ``disagg`` section:
    schema per arm, both arms present at EQUAL chip counts, the disagg
    arm's role census genuinely split (>= 1 prefill AND >= 1 decode,
    summing to ``replicas``), and its handoff accounting non-degenerate
    (a disagg arm with zero handoffs and zero fallbacks means the
    router never conducted the two-leg path — the arm silently measured
    unified twice)."""
    sys.path.insert(0, REPO)
    from tools.check_bench_schema import (BenchSchemaError, load_schema,
                                          validate_result)
    errors: list[str] = []
    try:
        validate_result({"disagg": block},
                        schema={**load_schema(),
                                "top_level": {"disagg": ["obj"]}})
    except BenchSchemaError as exc:
        errors.append(str(exc))
    arms = {a.get("arm"): a for a in (block.get("arms") or [])
            if isinstance(a, dict)}
    for want in ("unified", "disagg"):
        if want not in arms:
            errors.append(f"arms: missing the {want!r} arm — the "
                          f"comparison needs both at equal chips")
    if len(arms) < 2:
        return errors
    replicas = block.get("replicas")
    for name, arm in arms.items():
        roles = arm.get("roles") or {}
        if sum(roles.values()) != replicas:
            errors.append(
                f"arms[{name}]: roles {roles} do not sum to replicas="
                f"{replicas} — the equal-chips comparison is broken")
    droles = arms["disagg"].get("roles") or {}
    if not (droles.get("prefill", 0) >= 1 and droles.get("decode", 0) >= 1):
        errors.append(
            f"arms[disagg]: role census {droles} is not a prefill/decode "
            f"split")
    if set((arms["unified"].get("roles") or {})) != {"unified"}:
        errors.append(
            f"arms[unified]: role census "
            f"{arms['unified'].get('roles')} is not all-unified")
    if not (arms["disagg"].get("handoffs", 0)
            or arms["disagg"].get("fallbacks", 0)):
        errors.append(
            "arms[disagg]: zero handoffs AND zero fallbacks — the "
            "router never conducted the two-leg path; the arm measured "
            "unified twice")
    return errors


def check_disagg() -> list[str]:
    """Validate the disagg scenario contract over the synthetic section
    (schema + equal-chips/role-split/handoff invariants) — the same
    validator bench consumers can run over a real BENCH_DISAGG
    artifact."""
    return validate_disagg_block(synthetic_disagg())


def synthetic_failover() -> dict:
    """A fully-populated ``failover`` bench section (the BENCH_FAILOVER
    scenario's output shape) — shared by the bench-schema synthetic
    result and the failover check below; returned fresh so the tier-1
    test can doctor a copy to prove the check fails."""
    return {
        "replicas": 3, "requests": 16, "rps": 3.0, "num_tokens": 32,
        "arms": [
            {"arm": "resume_on", "resume_attempts": 1,
             "offered": 17, "completed": 17, "errors": 0,
             "error_frames": 0, "completed_no_error_rate": 1.0,
             "killed_replica": "r1", "resumes_ok": 2,
             "resumes_failed": 0, "resume_replay_tokens": 18,
             "resumed_p50_ms": 900.0, "unresumed_p50_ms": 620.0,
             "resumed_added_p50_ms": 280.0, "ttft_p50_ms": 140.0,
             "tokens_generated": 544},
            {"arm": "resume_off", "resume_attempts": 0,
             "offered": 17, "completed": 15, "errors": 2,
             "error_frames": 2, "completed_no_error_rate": 0.8824,
             "killed_replica": "r0", "resumes_ok": 0,
             "resumes_failed": 2, "resume_replay_tokens": 0,
             "resumed_p50_ms": None, "unresumed_p50_ms": 610.0,
             "resumed_added_p50_ms": None, "ttft_p50_ms": 138.0,
             "tokens_generated": 480},
        ],
    }


def validate_failover_block(block: dict) -> list[str]:
    """Element-wise + semantic validation of one ``failover`` section:
    schema per arm, both arms present around the same scripted kill,
    every completion rate an actual rate in [0, 1], the resume-on arm
    having actually resumed something (zero resumes means the kill
    never landed mid-stream — the arm measured nothing), and the
    resume-off arm honoring its off switch."""
    sys.path.insert(0, REPO)
    from tools.check_bench_schema import (BenchSchemaError, load_schema,
                                          validate_result)
    errors: list[str] = []
    try:
        validate_result({"failover": block},
                        schema={**load_schema(),
                                "top_level": {"failover": ["obj"]}})
    except BenchSchemaError as exc:
        errors.append(str(exc))
    arms = {a.get("arm"): a for a in (block.get("arms") or [])
            if isinstance(a, dict)}
    for want in ("resume_on", "resume_off"):
        if want not in arms:
            errors.append(f"arms: missing the {want!r} arm — the "
                          f"comparison needs both around the same kill")
    for name, arm in arms.items():
        rate = arm.get("completed_no_error_rate")
        if isinstance(rate, (int, float)) and not isinstance(rate, bool):
            if not 0.0 <= rate <= 1.0:
                errors.append(
                    f"arms[{name}]: completed_no_error_rate {rate!r} "
                    f"is not a rate in [0, 1]")
        if isinstance(arm.get("completed"), int) and \
                isinstance(arm.get("offered"), int) and \
                arm["completed"] > arm["offered"]:
            errors.append(
                f"arms[{name}]: completed {arm['completed']} exceeds "
                f"offered {arm['offered']}")
    if len(arms) < 2:
        return errors
    on, off = arms.get("resume_on", {}), arms.get("resume_off", {})
    if not on.get("resumes_ok", 0):
        errors.append(
            "arms[resume_on]: zero successful resumes — the scripted "
            "kill never landed mid-stream; the arm measured an "
            "uninterrupted fleet, not failover")
    if off.get("resumes_ok", 0):
        errors.append(
            f"arms[resume_off]: {off['resumes_ok']} resumes with the "
            f"budget at 0 — the off switch is not honored")
    if on.get("resume_attempts", 0) < 1 or off.get("resume_attempts", 1):
        errors.append(
            "arms: resume_attempts must be >= 1 on the resume_on arm "
            "and 0 on the resume_off arm")
    return errors


def check_failover() -> list[str]:
    """Validate the failover scenario contract over the synthetic
    section (schema + both-arms/rate-range/resume-accounting
    invariants) — the same validator bench consumers can run over a
    real BENCH_FAILOVER artifact."""
    return validate_failover_block(synthetic_failover())


def synthetic_obs_overhead() -> dict:
    """A fully-populated ``obs_overhead`` bench section (the
    BENCH_OBS_OVERHEAD scenario's output shape: armed history sampler +
    alert engine vs HISTORY_INTERVAL_S=0 disarmed, decode tok/s each
    way) — shared by the bench-schema synthetic result and the
    obs-overhead check below; returned fresh so the tier-1 test can
    doctor a copy to prove the check fails."""
    return {
        "history_interval_s": 0.05, "history_window_s": 10.0,
        "alert_rules": 5, "rounds_per_arm": 8,
        "armed_tokens_per_sec": 99.2, "disarmed_tokens_per_sec": 100.0,
        "armed_samples": 40, "overhead_pct": 0.8,
    }


def validate_obs_overhead_block(block: dict) -> list[str]:
    """Element-wise + semantic validation of one ``obs_overhead``
    section: schema, both arms measured (positive tok/s), the armed arm
    actually sampling (zero samples means the sampler never ran — the
    arm measured a disarmed stack twice), and ``overhead_pct``
    arithmetically consistent with the two arms."""
    sys.path.insert(0, REPO)
    from tools.check_bench_schema import (BenchSchemaError, load_schema,
                                          validate_result)
    errors: list[str] = []
    try:
        validate_result({"obs_overhead": block},
                        schema={**load_schema(),
                                "top_level": {"obs_overhead": ["obj"]}})
    except BenchSchemaError as exc:
        errors.append(str(exc))
    armed = block.get("armed_tokens_per_sec")
    disarmed = block.get("disarmed_tokens_per_sec")
    for name, v in (("armed_tokens_per_sec", armed),
                    ("disarmed_tokens_per_sec", disarmed)):
        if not (isinstance(v, (int, float)) and not isinstance(v, bool)
                and v > 0):
            errors.append(f"{name} must be a positive rate, got {v!r}")
    if not block.get("history_interval_s", 0) > 0:
        errors.append("history_interval_s must be > 0 — the armed arm "
                      "ran with the layer disarmed")
    if not block.get("armed_samples", 0) > 0:
        errors.append("armed_samples is 0 — the sampler never ran; the "
                      "armed arm measured a disarmed stack")
    if isinstance(armed, (int, float)) and isinstance(disarmed,
                                                      (int, float)) \
            and disarmed > 0:
        expect = (disarmed - armed) / disarmed * 100.0
        got = block.get("overhead_pct")
        if not (isinstance(got, (int, float))
                and abs(got - expect) <= 0.5):
            errors.append(
                f"overhead_pct {got!r} does not match the arms "
                f"((disarmed-armed)/disarmed*100 = {expect:.3f})")
    return errors


def check_obs_overhead() -> list[str]:
    """Validate the obs-overhead scenario contract over the synthetic
    section — the same validator bench consumers can run over a real
    BENCH_OBS_OVERHEAD artifact."""
    return validate_obs_overhead_block(synthetic_obs_overhead())


def synthetic_incident_bundle() -> dict:
    """An incident bundle built through the REAL pipeline: a fresh
    registry + history ring sampled over a breaching metric, a real
    AlertEngine firing the watchdog rule, and ``build_bundle`` joining
    history + alert evidence + a flight timeline. Returned fresh so the
    tier-1 test can doctor a copy to prove the validator fails."""
    sys.path.insert(0, REPO)
    from generativeaiexamples_tpu.obs import alerts as obs_alerts
    from generativeaiexamples_tpu.obs import flight as obs_flight
    from generativeaiexamples_tpu.obs import history as obs_history
    from generativeaiexamples_tpu.obs import incidents as obs_incidents
    from generativeaiexamples_tpu.obs import metrics as obs_metrics

    reg = obs_metrics.Registry()
    stalls = reg.gauge("engine_watchdog_stalls",
                       "cumulative watchdog stall count (mirror)")
    hist = obs_history.MetricHistory(registry=reg, window_s=30.0,
                                     interval_s=0.01)
    rule = obs_alerts.AlertRule(
        "engine_watchdog_stall", "engine_watchdog_stalls", "delta", ">",
        0.0, window_s=30.0, severity="critical",
        summary="engine serve loop stalled (watchdog fired)")
    fired: list[dict] = []
    engine = obs_alerts.AlertEngine(
        hist, rules=(rule,), registry=reg,
        on_fire=lambda r, rec: fired.append(rec))
    for v in (0.0, 1.0, 2.0):
        stalls.set(v)
        hist.sample_once()
        engine.tick()
    record = fired[0] if fired else {"state": None, "evidence": {}}
    flight = obs_flight.FlightRecorder()
    tl = flight.begin("preflight-req-1")
    flight.complete(tl)
    trigger = {"kind": "alert", "rule": rule.name,
               "severity": rule.severity, "summary": rule.summary,
               "state": record.get("state"),
               "evidence": record.get("evidence", {})}
    bundle = obs_incidents.build_bundle(
        server="chain", trigger=trigger, history=hist, alerts=engine,
        flight=flight, rounds=None)
    bundle["id"] = "inc-preflight-1-engine_watchdog_stall"
    return bundle


def validate_incident_bundle(bundle: dict) -> list[str]:
    """Element-wise validation of one incident bundle against the
    ``incident/v1`` contract: the joined sections all present, an
    alert-triggered bundle carrying real evidence, a non-empty history
    window, and the markdown renderer able to tell the story."""
    sys.path.insert(0, REPO)
    from generativeaiexamples_tpu.obs.incidents import BUNDLE_SCHEMA
    from tools.incident_report import render_markdown

    errors: list[str] = []
    if bundle.get("schema") != BUNDLE_SCHEMA:
        errors.append(f"schema is {bundle.get('schema')!r}, expected "
                      f"{BUNDLE_SCHEMA!r}")
    for key in ("server", "ts", "trigger", "alerts", "history", "flight",
                "rounds"):
        if key not in bundle:
            errors.append(f"bundle is missing the {key!r} section")
    trigger = bundle.get("trigger") or {}
    if trigger.get("kind") not in ("alert", "manual"):
        errors.append(f"trigger kind {trigger.get('kind')!r} is not "
                      f"alert|manual")
    if trigger.get("kind") == "alert":
        if not trigger.get("rule"):
            errors.append("alert-triggered bundle names no rule")
        if not (trigger.get("evidence") or {}).get("series"):
            errors.append("alert-triggered bundle carries no evidence "
                          "series — capture ran before evaluation?")
    hist = bundle.get("history") or {}
    if not hist.get("window"):
        errors.append("history window is empty — the bundle froze "
                      "nothing")
    agg = hist.get("aggregates") or {}
    if agg and not agg.get("series"):
        errors.append("history aggregates carry no series")
    if errors:
        return errors
    try:
        rendered = render_markdown(bundle)
    except Exception as exc:  # noqa: BLE001 — the check must report
        return [f"incident_report.render_markdown raised: {exc!r}"]
    if trigger.get("rule") and trigger["rule"] not in rendered:
        errors.append("rendered report does not mention the firing rule")
    if bundle.get("id") and bundle["id"] not in rendered:
        errors.append("rendered report does not carry the incident id")
    return errors


def check_alerts() -> list[str]:
    """Drive a REAL AlertEngine over a synthetic-but-real MetricHistory
    through the whole episode — must-fire (watchdog stalls climb →
    firing, on_fire exactly once), no re-capture while it stays firing,
    must-resolve (the breach ages out of the rule window → resolved) —
    then validate the incident bundle the firing built. Both the fire
    leg and the resolve leg are provable-to-fail: the tier-1 test
    doctors the inputs each way."""
    import time as _time

    sys.path.insert(0, REPO)
    from generativeaiexamples_tpu.obs import alerts as obs_alerts
    from generativeaiexamples_tpu.obs import history as obs_history
    from generativeaiexamples_tpu.obs import metrics as obs_metrics

    errors: list[str] = []
    reg = obs_metrics.Registry()
    stalls = reg.gauge("engine_watchdog_stalls",
                       "cumulative watchdog stall count (mirror)")
    hist = obs_history.MetricHistory(registry=reg, window_s=30.0,
                                     interval_s=0.01)
    # A short rule window so the resolve leg can age the breach out in
    # tens of milliseconds instead of minutes.
    rule = obs_alerts.AlertRule(
        "engine_watchdog_stall", "engine_watchdog_stalls", "delta", ">",
        0.0, window_s=0.05, severity="critical",
        summary="engine serve loop stalled (watchdog fired)")
    fired: list[dict] = []
    engine = obs_alerts.AlertEngine(
        hist, rules=(rule,), registry=reg,
        on_fire=lambda r, rec: fired.append(rec))
    for v in (0.0, 1.0, 2.0):
        stalls.set(v)
        hist.sample_once()
        engine.tick()
    if engine.firing() != [rule.name]:
        errors.append(f"must-fire: watchdog deltas did not fire the "
                      f"rule (firing={engine.firing()!r})")
    if len(fired) != 1:
        errors.append(f"on_fire ran {len(fired)} times during the "
                      f"firing transition; the episode contract is "
                      f"exactly once")
    # Staying firing must not re-fire (the no-re-capture pin).
    hist.sample_once()
    engine.tick()
    if len(fired) > 1:
        errors.append("on_fire re-ran while the rule STAYED firing — "
                      "every sustained alert would re-capture a bundle")
    vals = reg.snapshot()
    if vals.get('alerts_firing{rule="engine_watchdog_stall"}') != 1.0:
        errors.append("alerts_firing gauge is not 1 while firing")
    # Must-resolve: let the breach age past the rule window, then
    # sample flat values — the delta collapses and the rule clears.
    _time.sleep(0.08)
    for _ in range(2):
        hist.sample_once()
        engine.tick()
    if engine.firing():
        errors.append(f"must-resolve: rule still firing after the "
                      f"breach aged out (firing={engine.firing()!r})")
    vals = reg.snapshot()
    if vals.get('alerts_firing{rule="engine_watchdog_stall"}') != 0.0:
        errors.append("alerts_firing gauge did not drop to 0 on "
                      "resolve")
    if vals.get('alerts_total{rule="engine_watchdog_stall",'
                'state="resolved"}') != 1.0:
        errors.append("alerts_total did not count the resolved "
                      "transition")
    errors.extend(validate_incident_bundle(synthetic_incident_bundle()))
    return errors


def check_multichip() -> list[str]:
    """Validate the multichip sweep contract over the synthetic section
    (schema + mesh-label/device/budget/tail invariants) — the same
    validator bench consumers can run over a real BENCH_MESH artifact."""
    return validate_multichip_block(synthetic_multichip())


def check_metrics_docs() -> list[str]:
    sys.path.insert(0, REPO)
    from tools.check_metrics_docs import check
    return check()


def check_metrics_lint() -> list[str]:
    """Render every declared metric surface into a fresh registry via
    the same helpers production uses, then lint the exposition."""
    sys.path.insert(0, REPO)
    from generativeaiexamples_tpu.engine.engine import _STATS_TEMPLATE
    from generativeaiexamples_tpu.obs import metrics as obs_metrics
    from generativeaiexamples_tpu.obs.rounds import (ROUND_METRICS,
                                                     ROUND_TOKEN_BUCKETS)
    from generativeaiexamples_tpu.router.metrics import ROUTER_METRICS

    reg = obs_metrics.Registry()
    stats = dict(_STATS_TEMPLATE)
    stats["harvest_rounds"] = 1
    stats["harvest_wait_ms"] = 1.0
    obs_metrics.record_engine_stats(stats, registry=reg)
    obs_metrics.observe_stage("engine_ttft", 0.1, registry=reg)
    timer = obs_metrics.RequestTimer("chain_generate", registry=reg)
    timer.token(2)
    timer.finish()
    for name, (kind, help_txt) in ROUND_METRICS.items():
        if kind == "counter":
            reg.counter(name, help_txt).inc()
        elif kind == "gauge":
            reg.gauge(name, help_txt).set(1.0)
        else:
            buckets = (ROUND_TOKEN_BUCKETS
                       if name == "engine_round_tokens"
                       else obs_metrics.STAGE_BUCKETS)
            reg.histogram(name, help_txt, buckets=buckets).observe(1.0)
    for name, (kind, labels, help_txt) in ROUTER_METRICS.items():
        if kind == "histogram":
            m = reg.histogram(name, help_txt,
                              buckets=obs_metrics.STAGE_BUCKETS,
                              labelnames=labels)
        else:
            m = (reg.counter if kind == "counter" else reg.gauge)(
                name, help_txt, labelnames=labels)
        leaf = m.labels(*(["r0"] * len(labels))) if labels else m
        if kind == "counter":
            leaf.inc()
        elif kind == "gauge":
            leaf.set(1.0)
        else:
            leaf.observe(0.1)
    reg.counter("shed_total", "requests rejected at admission, by reason",
                labelnames=("reason",)).labels("queue_full").inc()
    reg.gauge("breaker_state",
              "circuit breaker state (0 closed, 1 half-open, 2 open)",
              labelnames=("name",)).labels("retrieval").set(0)
    return obs_metrics.lint_prometheus(reg.render_prometheus())


def synthetic_fleet_state():
    """A small but fully-populated router state (table + SLO window +
    flight recorder) built through the REAL production classes — what
    the fleet-obs check below snapshots and validates. Returning the
    parts lets the tier-1 test doctor copies to prove the check can
    fail."""
    from generativeaiexamples_tpu.router.flight import (
        RouterFlightRecorder, SloWindow)
    from generativeaiexamples_tpu.router.table import ReplicaTable

    table = ReplicaTable()
    table.add("r0", "http://r0:8081")
    table.add("r1", "http://r1:8081")
    table.update_health("r0", ok=True, body={
        "draining": False,
        "load": {"in_flight": 2, "queue_depth": 3, "rejected_total": 1,
                 "prefix_hit_rate": 0.6},
        "rounds": {"rounds_completed": 10, "tokens_per_sec": 400.0,
                   "wall_tokens_per_sec": 120.0, "avg_device_ms": 8.0,
                   "avg_bw_util": 0.4, "avg_drift_ratio": 1.1,
                   "interleaved_share": 0.3},
        "capacity": {"slots": 8, "decode_step_ms": 2.0,
                     "model_source": "PROFILE_r09.json",
                     "capacity_tokens_per_sec": 4000.0},
        "kv_tier": {"host_pages": 5, "offload_pages": 9,
                    "restore_pages": 4, "transfer_pages": 2},
    })
    table.update_health("r1", ok=False)   # a partitioned sibling
    slo = SloWindow(window_s=600.0)
    recorder = RouterFlightRecorder(slo=slo)
    tl = recorder.begin_request(
        {"X-Request-ID": "preflight-1", "X-Deadline-Ms": "5000"},
        "/generate")
    recorder.placement(tl, replica="r0", affinity_blocks=2,
                       candidates=[{"replica": "r0", "score": 3.0,
                                    "affinity_blocks": 2,
                                    "queue_depth": 3, "in_flight": 2}],
                       t_start=tl.t_start)
    recorder.attempt_failed(tl, replica="r1", reason="connect",
                            retried=True)
    recorder.first_byte(tl)
    recorder.complete_request(tl, outcome="ok", replica="r0", status=200)
    slo.record(replica="r1", outcome="midstream_loss", ttft_ms=50.0)
    slo.record(replica="r0", outcome="shed")
    return table, slo, recorder, tl


def check_fleet_obs() -> list[str]:
    """Validate the ``GET /debug/fleet`` snapshot and the router
    ``/debug/requests`` timeline contracts over a synthetic-but-real
    router state, element-wise (router/fleet.py schemas)."""
    sys.path.insert(0, REPO)
    from generativeaiexamples_tpu.router import fleet as router_fleet

    table, slo, _recorder, tl = synthetic_fleet_state()
    snap = router_fleet.build_fleet_snapshot(table, slo, heartbeat_s=2.0)
    errors = router_fleet.validate_fleet_snapshot(snap)
    errors.extend(router_fleet.validate_router_timeline(tl.to_dict()))
    # The synthetic state exercises every outcome class: an all-empty
    # window would validate while proving nothing.
    if snap["fleet"]["window_requests"] < 3:
        errors.append("synthetic fleet state produced an empty SLO "
                      "window — the check is no longer exercising the "
                      "outcome path")
    return errors


def check_autoscale() -> list[str]:
    """Tick a REAL AutoscaleController over the synthetic fleet state
    and validate the decision ring + ``GET /debug/autoscale`` payload
    element-wise (router/autoscale.py schemas). The seeded state is
    overloaded (deep queue, utilization past the trigger), so the check
    also requires a ``scale_up`` decision — proving the control law and
    the contract together."""
    import asyncio

    sys.path.insert(0, REPO)
    from generativeaiexamples_tpu.router import autoscale as rauto
    from generativeaiexamples_tpu.router.server import FleetRouter

    table, slo, recorder, _tl = synthetic_fleet_state()
    # Overload r0: the queue is deep and the wall token rate consumes
    # nearly all of the calibrated capacity.
    table.update_health("r0", ok=True, body={
        "draining": False,
        "load": {"in_flight": 6, "queue_depth": 12, "rejected_total": 1,
                 "prefix_hit_rate": 0.6},
        "rounds": {"rounds_completed": 12, "tokens_per_sec": 4000.0,
                   "wall_tokens_per_sec": 3800.0, "avg_device_ms": 8.0,
                   "avg_bw_util": 0.7, "avg_drift_ratio": 1.0,
                   "interleaved_share": 0.3},
        "capacity": {"slots": 8, "decode_step_ms": 2.0,
                     "model_source": "PROFILE_r09.json",
                     "capacity_tokens_per_sec": 4000.0},
    })
    router = FleetRouter(table, flight=recorder)
    controller = rauto.AutoscaleController(
        router, policy=rauto.AutoscalePolicy(min_replicas=1,
                                             max_replicas=4),
        executor=None, surge=router.surge)
    errors: list[str] = []
    try:
        records = [asyncio.run(controller.tick()) for _ in range(3)]
    except Exception as exc:  # noqa: BLE001 — the check must report
        return [f"controller tick raised: {exc!r}"]
    snap = controller.snapshot()
    errors.extend(rauto.validate_autoscale_snapshot(snap))
    if not any(r["action"] in ("scale_up", "blocked")
               and "utilization" in r["reason"] for r in records):
        errors.append(
            "overloaded synthetic fleet produced no utilization-driven "
            "scale decision — the control law is no longer reading the "
            "leading indicators")
    if snap["decisions"] and snap["decisions"][-1]["evidence"][
            "queue_depth"] != 12:
        errors.append("decision evidence does not reflect the fleet "
                      "snapshot's queue depth (the /debug/fleet join is "
                      "broken)")
    return errors


def check_perf_gates(pairs=None) -> list[str]:
    sys.path.insert(0, REPO)
    from tools.perf_diff import diff_files
    errors: list[str] = []
    for base, cand, thresholds in (pairs or PERF_GATE_PAIRS):
        base_p = base if os.path.isabs(base) else os.path.join(REPO, base)
        cand_p = cand if os.path.isabs(cand) else os.path.join(REPO, cand)
        if not (os.path.exists(base_p) and os.path.exists(cand_p)):
            errors.append(f"{base} -> {cand}: artifact missing")
            continue
        try:
            regressions, _ = diff_files(base_p, cand_p,
                                        per_metric_pct=dict(thresholds))
        except (OSError, ValueError) as exc:
            errors.append(f"{base} -> {cand}: {exc}")
            continue
        errors.extend(f"{base} -> {cand}: {r}" for r in regressions)
    return errors


CHECKS: dict[str, Callable[[], list[str]]] = {
    "bench-schema": check_bench_schema,
    "metrics-docs": check_metrics_docs,
    "metrics-lint": check_metrics_lint,
    "fleet-obs": check_fleet_obs,
    "autoscale": check_autoscale,
    "multichip": check_multichip,
    "disagg": check_disagg,
    "failover": check_failover,
    "alerts": check_alerts,
    "obs-overhead": check_obs_overhead,
    "perf-gates": check_perf_gates,
}


def run_checks(names=None) -> dict[str, list[str]]:
    """Run the named checks (default: all). Returns
    ``{check: [errors]}`` — all-empty values mean a clean tree."""
    return {name: CHECKS[name]() for name in (names or CHECKS)}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Run every repo contract check; non-zero exit on "
                    "any failure.")
    parser.add_argument("checks", nargs="*", choices=[[], *CHECKS],
                        help="subset of checks (default: all)")
    parser.add_argument("--list", action="store_true",
                        help="list available checks and exit")
    args = parser.parse_args(argv)
    if args.list:
        for name in CHECKS:
            print(name)
        return 0
    failed = 0
    for name, errors in run_checks(args.checks or None).items():
        if errors:
            failed += 1
            print(f"FAIL {name}:")
            for e in errors:
                print(f"  - {e}")
        else:
            print(f"ok   {name}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.path.insert(0, REPO)
    sys.exit(main(sys.argv[1:]))
