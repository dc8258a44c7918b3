"""What decides ``correct`` in set-up: the engine-token chain rule on a
hand-made engine, and the logits check at the toy's size (CPU)."""

import json
import os
import types

import numpy as np
import pytest

from benchmarks.harness import system

HERE = os.path.dirname(os.path.abspath(__file__))


class FakeEngine:
    """Serves each prompt the tokens written down for it."""

    def __init__(self, answers):
        self.answers = answers

    def submit(self, ids, params):
        toks = self.answers[tuple(ids)]
        assert params.max_tokens == len(toks) and params.top_k == 1
        return types.SimpleNamespace(finish_reason="length", token_ids=toks)


def reference(n_prompts, n_tokens=5, vocab=8):
    """Reference logits whose largest entry at step i is token i + 1, the
    next largest (0.02 of the scale under it) token 0; the rest far."""
    prompts = []
    for p in range(n_prompts):
        logits = np.full((n_tokens, vocab), -1.0, np.float32)
        for i in range(n_tokens):
            logits[i, i + 1] = 1.0
            logits[i, 0] = 0.98
        prompts.append({"ids": [10 + p], "ref_logits": logits,
                        "ref_tokens": [i + 1 for i in range(n_tokens - 1)]})
    return {"prompts": prompts}


def config(agree):
    return {"logits_check": {"tolerance": 0.05, "min_token_agreement": agree}}


def test_engine_tokens_follow_the_chain_and_a_near_tie_is_within():
    ref = reference(2)
    eng = FakeEngine({(10,): [1, 2, 3, 4, 5],      # the chain itself
                      (11,): [1, 0, 7, 7, 7]})     # near-tie, then off it
    out = system.engine_tokens_check(eng, ref, config(1.0))
    # prompt 2: token 0 is within tolerance but leaves the chain, so the
    # tokens after it are not held against logits they were not drawn from
    assert out == {"compared": 7, "within_tolerance": 7,
                   "worst_gap": pytest.approx(0.02)}


@pytest.mark.parametrize("agree,passes", [(0.25, True), (0.5, False)])
def test_a_chain_ends_at_its_first_miss_so_a_prompt_costs_one(agree, passes):
    """The 3-of-7 run the chip read: three prompts agree on their first
    token and miss the second, the fourth misses at once."""
    ref = reference(4)
    eng = FakeEngine({(10,): [1, 7, 3, 4, 5], (11,): [1, 7, 3, 4, 5],
                      (12,): [1, 7, 3, 4, 5], (13,): [7, 2, 3, 4, 5]})
    if passes:
        out = system.engine_tokens_check(eng, ref, config(agree))
        assert (out["compared"], out["within_tolerance"]) == (7, 3)
    else:
        with pytest.raises(system.CheckFailed, match="only 3 of 7"):
            system.engine_tokens_check(eng, ref, config(agree))


def test_a_broken_program_agrees_on_nothing_and_fails_any_threshold():
    ref = reference(4)
    eng = FakeEngine({(10 + p,): [7] * 5 for p in range(4)})
    with pytest.raises(system.CheckFailed, match="only 0 of 4"):
        system.engine_tokens_check(eng, ref, config(0.25))


@pytest.fixture(scope="module")
def toy():
    with open(os.path.join(HERE, "rehearsal", "configs",
                           "tiny-dense.json")) as f:
        cfg_file = json.load(f)
    cfg = system.model_config(cfg_file)
    return cfg_file, cfg, system.make_params(cfg, cfg_file["weight_quant"], 5)


def test_logits_check_passes_on_the_toy_and_returns_the_reference(toy):
    cfg_file, cfg, params = toy
    out = system.logits_check(params, cfg, cfg_file, 5)
    lc = cfg_file["logits_check"]
    assert out["share_over_tolerance"] == 0.0
    assert out["max_rel_err"] <= lc["tolerance"]
    assert len(out["prompts"]) == lc["prompts"]
    assert out["prompts"][0]["ref_logits"].shape == (
        1 + lc["decode_steps"], cfg.vocab_size)


def test_a_failed_logits_check_says_why_and_carries_its_readings(toy):
    cfg_file, cfg, params = toy
    tight = dict(cfg_file, logits_check=dict(
        cfg_file["logits_check"], tolerance=1e-6, median_tolerance=1e-6))
    with pytest.raises(system.CheckFailed) as exc:
        system.logits_check(params, cfg, tight, 5)
    msg = str(exc.value)
    assert "median" in msg and "of the compared positions lie over" in msg
    assert exc.value.readings["share_over_tolerance"] > 0.5
    assert "prompts" not in exc.value.readings
