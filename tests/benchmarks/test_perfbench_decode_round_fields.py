"""The reader of round-record fields that only decoding rounds carry."""

import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness import spec as spec_mod  # noqa: E402
from benchmarks.readers import decode_round_fields  # noqa: E402


def rec(**kw):
    return types.SimpleNamespace(**kw)


def ctx(rounds):
    return types.SimpleNamespace(rounds=rounds)


ROUNDS = [
    rec(decode_slots=0, decode_steps=0, experts_touched=0.0,
        kv_pages_skipped=0.0),                       # prefill only
    rec(decode_slots=8, decode_steps=8, experts_touched=30.0,
        kv_pages_skipped=80.0),
    rec(decode_slots=16, decode_steps=4, experts_touched=50.0,
        kv_pages_skipped=100.0),
]


def test_means_over_the_rounds_that_decoded():
    assert decode_round_fields.read(ctx(ROUNDS), "experts_touched") == 40.0
    assert decode_round_fields.read(ctx(ROUNDS), "kv_pages_skipped",
                                    per="step") == 15.0


@pytest.mark.parametrize("rounds", [
    None, [], ROUNDS[:1],
    [rec(decode_slots=4, decode_steps=8)],           # a program without it
], ids=["none", "empty", "prefill_only", "parent"])
def test_nothing_to_read_is_none(rounds):
    assert decode_round_fields.read(ctx(rounds), "experts_touched") is None


def test_unknown_per_is_an_error():
    with pytest.raises(ValueError):
        decode_round_fields.read(ctx(ROUNDS), "experts_touched", per="x")


@pytest.mark.parametrize("name", ["moe_experts_touched",
                                  "window_pages_skipped"])
def test_the_metric_files_name_this_reader_and_a_round_record_field(name):
    from generativeaiexamples_tpu.obs.rounds import RoundRecord
    m = spec_mod.Spec().layer_metric(name)
    assert m["reader"] == "decode_round_fields"
    assert m["args"]["field"] in RoundRecord.__slots__
