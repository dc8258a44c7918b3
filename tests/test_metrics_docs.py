"""Tier-1 guard: docs/observability.md's engine gauge table stays in
sync with Engine.stats(), and its router metric table with
router.metrics.ROUTER_METRICS (tools/check_metrics_docs.py) — a rename
on either side can't silently orphan the docs, and a new metric can't
ship undocumented."""

import pytest

from tools.check_metrics_docs import (BEGIN, END, PROCESS_BEGIN,
                                      PROCESS_END, ROUNDS_BEGIN, ROUNDS_END,
                                      ROUTER_BEGIN, ROUTER_END, check,
                                      documented_gauges,
                                      documented_round_metrics,
                                      documented_router_metrics)


def test_docs_gauge_table_matches_engine_stats():
    assert check() == []


def test_checker_flags_ghost_and_missing_gauges():
    """Sanity of the checker itself: a documented gauge with no stats key
    is a ghost; dropping a documented row leaves a stats key missing."""
    ghost = (f"{BEGIN}\n| `engine_requests` | x |\n"
             f"| `engine_not_a_real_stat` | x |\n{END}\n"
             f"{ROUTER_BEGIN}{ROUTER_END}"   # other fences: own tests
             f"{ROUNDS_BEGIN}{ROUNDS_END}"
             f"{PROCESS_BEGIN}{PROCESS_END}")
    errors = check(ghost)
    assert any("engine_not_a_real_stat" in e for e in errors)
    assert any("engine_tokens_generated" in e for e in errors)  # missing


def test_checker_requires_markers():
    with pytest.raises(SystemExit):
        documented_gauges("no markers here")


def _with_router_fence(rows: str) -> str:
    """A doc body whose ENGINE fence is intact (read from the real doc)
    but whose router fence is replaced by ``rows`` — isolates the router
    direction of the check."""
    import tools.check_metrics_docs as mod
    with open(mod.DOC_PATH) as f:
        text = f.read()
    start = text.index(ROUTER_BEGIN)
    end = text.index(ROUTER_END) + len(ROUTER_END)
    return text[:start] + f"{ROUTER_BEGIN}\n{rows}\n{ROUTER_END}" \
        + text[end:]


def test_checker_flags_ghost_and_missing_router_metrics():
    errors = check(_with_router_fence(
        "| `router_replicas_healthy` | x |\n"
        "| `router_not_a_real_metric` | x |"))
    assert any("router_not_a_real_metric" in e for e in errors)
    assert any("router_placed_total" in e for e in errors)  # missing


def test_router_docs_names_ignore_label_suffixes():
    """`router_placed_total{replica=}` documents router_placed_total —
    the label hint in the docs is prose, not part of the name."""
    docs = documented_router_metrics(
        f"{ROUTER_BEGIN}\n| `router_placed_total{{replica=}}` | x |\n"
        f"{ROUTER_END}")
    assert docs == {"router_placed_total"}


def test_checker_requires_router_markers():
    with pytest.raises(SystemExit):
        documented_router_metrics(f"{BEGIN} {END} no router fence")


def _with_rounds_fence(rows: str) -> str:
    """The real doc with only the ROUND fence replaced — isolates the
    round-telemetry direction of the check."""
    import tools.check_metrics_docs as mod
    with open(mod.DOC_PATH) as f:
        text = f.read()
    start = text.index(ROUNDS_BEGIN)
    end = text.index(ROUNDS_END) + len(ROUNDS_END)
    return text[:start] + f"{ROUNDS_BEGIN}\n{rows}\n{ROUNDS_END}" \
        + text[end:]


def test_checker_flags_ghost_and_missing_round_metrics():
    errors = check(_with_rounds_fence(
        "| `engine_rounds_total` | x |\n"
        "| `engine_round_not_real` | x |"))
    assert any("engine_round_not_real" in e for e in errors)
    assert any("sched_cost_drift_ratio" in e for e in errors)  # missing


def test_checker_requires_round_markers():
    with pytest.raises(SystemExit):
        documented_round_metrics(f"{BEGIN} {END} no round fence")
