"""What decides ``correct`` in set-up: the engine-token chain rule on a
hand-made engine, the logits check against the plain reference at the
toy's size (CPU), its control (the program one precision step down), and
a whole run with the checked path broken underneath."""

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
    assert out["reference"] == cfg_file["reference"] == "mixtral"
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


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_the_control_fails_int4_weights_where_int8_is_stated(seed):
    """The control: the paged programs over the same seed's weights
    stored one step lower than the configuration states (the package's
    own int4 path), held to the reference over the stated int8. Every
    position moves by a third of the logits' scale where the stated
    program moves by a hundredth."""
    with open(os.path.join(HERE, "rehearsal", "configs",
                           "tiny-dense.json")) as f:
        cfg_file = json.load(f)
    assert cfg_file["weight_quant"] == "int8"
    cfg = system.model_config(cfg_file)
    from benchmarks import check_sensitivity
    record, replay = check_sensitivity.recorded(
        system.load_reference(cfg_file).forward)
    sound = system.logits_check(system.make_params(cfg, "int8", seed), cfg,
                                cfg_file, seed, forward=record)
    assert check_sensitivity.LOWER["int8"] == "int4"
    with pytest.raises(system.CheckFailed) as exc:
        system.logits_check(system.make_params(cfg, "int4", seed), cfg,
                            cfg_file, seed, forward=replay)
    got = exc.value.readings
    for what in ("prefill_median_rel_err", "decode_median_rel_err"):
        assert got[what] > 3 * cfg_file["logits_check"]["median_tolerance"]
        assert got[what] > 10 * sound[what]
    assert got["share_over_tolerance"] == 1.0


def test_an_int8_pool_moves_the_decode_steps_only(toy):
    """The other control, ``check_sensitivity.py``'s: only the decode
    steps read the pool, so only they move (whether they move past the
    tolerance is read on the chip, not here)."""
    cfg_file, cfg, params = toy
    plain = system.logits_check(params, cfg, cfg_file, 5)
    loose = dict(cfg_file, logits_check=dict(
        cfg_file["logits_check"], tolerance=1.0, median_tolerance=1.0))
    fault = system.logits_check(params, cfg, loose, 5, kv_quantized=True)
    assert fault["prefill_median_rel_err"] == plain["prefill_median_rel_err"]
    assert fault["decode_rel_errs"] != plain["decode_rel_errs"]
    assert len(plain["decode_rel_errs"]) == plain["decode_steps"]


# ------------------------------- a whole run, the checked path broken

REHEARSAL = os.path.join(HERE, "rehearsal")


def run_main(monkeypatch, capsys, cell="tiny-dense.tiny-closed"):
    from benchmarks import run
    # the watchdog wants a real stderr; pytest's capture has no fileno
    monkeypatch.setattr(run, "faulthandler", types.SimpleNamespace(
        dump_traceback_later=lambda *a, **k: None,
        cancel_dump_traceback_later=lambda: None))
    rc = run.main(["--benchmark-json",
                   os.path.join(REHEARSAL, "BENCHMARK.json"),
                   "--data", REHEARSAL, "--workload", cell,
                   "--seed", str(2 ** 31 + 9), "--seconds", "1.5",
                   "--trace", "0"])
    cap = capsys.readouterr()
    lines = [json.loads(ln) for ln in cap.out.splitlines()
             if ln.startswith("{")]
    return rc, lines, cap.err


def decode_step_that_forgets_its_token(monkeypatch):
    """Every decode step embeds token 3, whatever it was given."""
    from generativeaiexamples_tpu.models import llama
    real = llama.apply_decode_paged
    monkeypatch.setattr(
        llama, "apply_decode_paged", lambda params, cfg, tokens, *a, **kw:
        real(params, cfg, tokens * 0 + 3, *a, **kw))


def engine_whose_tokens_are_altered(monkeypatch):
    """Every served token altered where the engine hands it out: the
    lengths, finish reasons and ids stay valid."""
    real = system.build_engine

    class Altered:
        def __init__(self, stream):
            self._s = stream

        def __getattr__(self, name):
            return getattr(self._s, name)

        @property
        def token_ids(self):
            return [(t + 1) % 500 + 3 for t in self._s.token_ids]

    def build(*a, **kw):
        engine = real(*a, **kw)
        submit = engine.submit
        engine.submit = lambda *a, **kw: Altered(submit(*a, **kw))
        return engine
    monkeypatch.setattr(system, "build_engine", build)


BROKEN = {"decode step": (decode_step_that_forgets_its_token,
                          "decode logits differ from the reference"),
          "served tokens": (engine_whose_tokens_are_altered,
                            "engine tokens have a reference logit")}


@pytest.mark.parametrize("what", sorted(BROKEN))
def test_a_run_with_the_checked_path_broken_is_not_correct(
        what, monkeypatch, capsys):
    """Drives ``run.py`` whole (the rehearsal configuration skips the
    look for a chip): with a decode step that returns another token's
    logits, or with the engine's tokens altered where they are handed
    out, the run measures as before and ends ``correct: false``, each
    number compared beside its limit at the end of standard error."""
    breaker, says = BROKEN[what]
    breaker(monkeypatch)
    rc, lines, err = run_main(monkeypatch, capsys)
    assert rc == 0
    last, window = lines[-1], lines[-2]
    assert last["correct"] is False and last["failed"] == 0
    assert last["metrics"]["tpot_p50_ms"]["value"] > 0
    assert any(says in p for p in window["problems"])
    tail = err.strip().splitlines()
    assert any(ln.startswith("problem: ") and says in ln for ln in tail[-3:])
    assert any(ln.startswith("logits_check vs references/mixtral.py: "
                             "decode_median_rel_err=") and "limit=0.05" in ln
               for ln in tail)
