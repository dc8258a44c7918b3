"""tools/perf_diff.py: the perf regression gate — headline-metric
extraction, threshold semantics, CLI exit codes, and a tier-1 run over
the committed BENCH_rNN artifacts."""

import json
import os

import pytest

from tools.perf_diff import (DEFAULT_THRESHOLD_PCT, compare,
                             extract_metrics, main)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _result(**over):
    base = {
        "decode_tokens_per_sec": 500.0,
        "engine_p50_ttft_ms": 150.0,
        "engine_p99_ttft_ms": 180.0,
        "hbm_bw_util": 0.72,
        "chat": {"warm_p50_ttft_ms": 40.0,
                 "spec": {"tokens_per_step": 1.8}},
        "openloop": {"rates": [
            {"arrival_rps": 2.0, "slo_attainment": 0.95,
             "goodput_tokens_per_sec": 900.0},
            {"arrival_rps": 4.0, "slo_attainment": 0.80,
             "goodput_tokens_per_sec": 1500.0},
        ]},
    }
    base.update(over)
    return base


def test_extract_flattens_headline_metrics():
    m = extract_metrics(_result())
    assert m["decode_tokens_per_sec"] == (500.0, "higher")
    assert m["engine_p50_ttft_ms"] == (150.0, "lower")
    assert m["chat.warm_p50_ttft_ms"] == (40.0, "lower")
    assert m["slo_attainment@2"] == (0.95, "higher")
    assert m["goodput_tokens_per_sec@4"] == (1500.0, "higher")
    assert m["spec.tokens_per_step"] == (1.8, "higher")
    # driver artifact wrapper unwraps
    assert extract_metrics({"parsed": _result()})["hbm_bw_util"][0] == 0.72


def test_extract_fleet_policy_metrics_direction_aware():
    """Fleet arms contribute per-policy headline metrics (ISSUE 12): a
    cross-replica prefix-hit or SLO regression in one arm is gated like
    any single-replica headline, and a warm-TTFT rise is wrong-way."""
    result = _result(fleet={"policies": [
        {"policy": "round_robin", "prefix_hit_rate": 0.05,
         "slo_attainment": 0.90, "ttft_p50_ms": 120.0,
         "kv_transfer_pages": 0},
        {"policy": "affinity_transfer", "prefix_hit_rate": 0.62,
         "slo_attainment": 0.99, "ttft_p50_ms": 45.0,
         "kv_transfer_pages": 12},
    ]})
    m = extract_metrics(result)
    assert m["fleet.prefix_hit_rate@affinity_transfer"] == (0.62, "higher")
    assert m["fleet.slo_attainment@round_robin"] == (0.90, "higher")
    assert m["fleet.ttft_p50_ms@affinity_transfer"] == (45.0, "lower")
    assert m["fleet.kv_transfer_pages@affinity_transfer"] == (12, "higher")
    # direction-aware comparison: a prefix-hit drop regresses, a TTFT
    # drop improves
    worse = extract_metrics(_result(fleet={"policies": [
        {"policy": "affinity_transfer", "prefix_hit_rate": 0.30,
         "slo_attainment": 0.99, "ttft_p50_ms": 30.0,
         "kv_transfer_pages": 12},
    ]}))
    regressions, notes = compare(m, worse)
    assert any("fleet.prefix_hit_rate@affinity_transfer" in r
               for r in regressions)
    assert any(n.startswith("improved fleet.ttft_p50_ms")
               for n in notes)


def test_extract_autoscale_policy_metrics_direction_aware():
    """Autoscale arms contribute per-policy headline gates (ISSUE 13):
    attainment is gated UP and replica_minutes DOWN — an attainment
    'win' bought by quietly spending a fatter fleet is a regression on
    the bill, and the gate must say so."""
    result = _result(autoscale={"policies": [
        {"policy": "autoscaled", "slo_attainment": 0.97,
         "replica_minutes": 0.42, "ttft_p50_ms": 90.0},
        {"policy": "static", "slo_attainment": 0.81,
         "replica_minutes": 0.42, "ttft_p50_ms": 150.0},
    ]})
    m = extract_metrics(result)
    assert m["autoscale.slo_attainment@autoscaled"] == (0.97, "higher")
    assert m["autoscale.replica_minutes@autoscaled"] == (0.42, "lower")
    assert m["autoscale.slo_attainment@static"] == (0.81, "higher")
    worse = extract_metrics(_result(autoscale={"policies": [
        {"policy": "autoscaled", "slo_attainment": 0.80,
         "replica_minutes": 0.80, "ttft_p50_ms": 90.0},
    ]}))
    regressions, _ = compare(m, worse)
    assert any("autoscale.slo_attainment@autoscaled" in r
               for r in regressions)
    # MORE replica-minutes is the wrong direction
    assert any("autoscale.replica_minutes@autoscaled" in r
               for r in regressions)


def test_extract_multichip_rung_metrics_direction_aware():
    """Multichip rungs contribute per-mesh gates (ISSUE 14): tokens/s
    is gated UP and TTFT DOWN per rung, so a tp=2 rung that quietly
    slowed to single-chip speed regresses the gate even when the tp=1
    rung held."""
    result = _result(multichip={"rungs": [
        {"mesh": "tp=1", "decode_tokens_per_sec": 500.0,
         "engine_p50_ttft_ms": 150.0},
        {"mesh": "tp=2", "decode_tokens_per_sec": 900.0,
         "engine_p50_ttft_ms": 95.0},
    ]})
    m = extract_metrics(result)
    assert m["multichip.tokens_per_sec@tp=2"] == (900.0, "higher")
    assert m["multichip.ttft_p50_ms@tp=2"] == (95.0, "lower")
    assert m["multichip.tokens_per_sec@tp=1"] == (500.0, "higher")
    worse = extract_metrics(_result(multichip={"rungs": [
        {"mesh": "tp=2", "decode_tokens_per_sec": 500.0,
         "engine_p50_ttft_ms": 150.0},
    ]}))
    regressions, _ = compare(m, worse)
    assert any("multichip.tokens_per_sec@tp=2" in r for r in regressions)
    assert any("multichip.ttft_p50_ms@tp=2" in r for r in regressions)


def test_extract_disagg_arm_metrics_direction_aware():
    """Disagg arms contribute per-arm gates (docs/disaggregation.md):
    the scenario's claim is the disagg arm wins BOTH p50 TTFT (down)
    and decode goodput (up), so each is gated round-over-round — a
    handoff path that quietly stopped protecting decode rounds
    regresses the gate even when the unified arm held."""
    result = _result(disagg={"arms": [
        {"arm": "unified", "ttft_p50_ms": 120.0,
         "decode_goodput": 60.0},
        {"arm": "disagg", "ttft_p50_ms": 80.0,
         "decode_goodput": 90.0},
    ]})
    m = extract_metrics(result)
    assert m["disagg.ttft_p50_ms@disagg"] == (80.0, "lower")
    assert m["disagg.decode_goodput@disagg"] == (90.0, "higher")
    assert m["disagg.ttft_p50_ms@unified"] == (120.0, "lower")
    assert m["disagg.decode_goodput@unified"] == (60.0, "higher")
    # the disagg arm regressing toward unified trips BOTH gates
    worse = extract_metrics(_result(disagg={"arms": [
        {"arm": "disagg", "ttft_p50_ms": 115.0,
         "decode_goodput": 62.0},
    ]}))
    regressions, _ = compare(m, worse)
    assert any("disagg.ttft_p50_ms@disagg" in r for r in regressions)
    assert any("disagg.decode_goodput@disagg" in r for r in regressions)


def test_extract_tolerates_missing_sections():
    m = extract_metrics({"decode_tokens_per_sec": 100.0, "chat": {}})
    assert set(m) == {"decode_tokens_per_sec"}


def test_compare_direction_aware():
    base = extract_metrics(_result())
    # throughput DOWN 20% -> regression; TTFT DOWN 20% -> improvement
    new = extract_metrics(_result(decode_tokens_per_sec=400.0,
                                  engine_p50_ttft_ms=120.0))
    regressions, notes = compare(base, new)
    assert any("decode_tokens_per_sec" in r for r in regressions)
    assert not any("engine_p50_ttft_ms" in r for r in regressions)
    assert any(n.startswith("improved engine_p50_ttft_ms")
               for n in notes)
    # inside the default threshold: no regression
    small = extract_metrics(_result(
        decode_tokens_per_sec=500.0 * (1 - DEFAULT_THRESHOLD_PCT / 200)))
    assert compare(base, small)[0] == []


def test_compare_per_metric_threshold_and_skips():
    base = extract_metrics(_result())
    new = extract_metrics(_result(decode_tokens_per_sec=460.0))  # -8%
    assert compare(base, new)[0]                       # default 5% trips
    regs, _ = compare(base, new,
                      per_metric_pct={"decode_tokens_per_sec": 10.0})
    assert regs == []                                  # widened: passes
    # a metric absent from one side is skipped with a note, not a fail
    lean = extract_metrics({"decode_tokens_per_sec": 500.0})
    regs, notes = compare(base, lean)
    assert regs == []
    assert any(n.startswith("skip engine_p50_ttft_ms") for n in notes)


def test_cli_exit_codes(tmp_path, capsys):
    base_p = tmp_path / "base.json"
    new_p = tmp_path / "new.json"
    base_p.write_text(json.dumps(_result()))
    new_p.write_text(json.dumps(_result(decode_tokens_per_sec=300.0)))
    assert main([str(base_p), str(base_p)]) == 0
    assert main([str(base_p), str(new_p)]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out and "decode_tokens_per_sec" in out
    # per-metric override rescues it
    assert main([str(base_p), str(new_p),
                 "--threshold", "decode_tokens_per_sec=50"]) == 0
    # unusable artifacts are a usage error, not a crash
    empty = tmp_path / "empty.json"
    empty.write_text("{}")
    assert main([str(base_p), str(empty)]) == 2
    assert main([str(base_p), str(tmp_path / "missing.json")]) == 2


@pytest.mark.parametrize("pair,expect", [
    (("bench_round_b.json", "bench_round_c.json"), 0),   # c did not regress b
    (("bench_round_a.json", "bench_round_c.json"), 0),   # the whole trajectory
])
def test_committed_artifacts_gate(pair, expect):
    """Tier-1 over committed driver-shaped artifacts (synthetic fixtures
    — the repo holds no chip record yet): each later round passes the
    gate against the earlier one (p99 wobble gets a wider threshold —
    single-digit-sample tail percentiles jitter between runs)."""
    base, new = (os.path.join(REPO, "tests", "fixtures", "perf_gate", p)
                 for p in pair)
    rc = main([base, new, "--threshold", "engine_p99_ttft_ms=20"])
    assert rc == expect
