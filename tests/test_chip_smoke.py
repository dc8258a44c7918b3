"""chip_smoke.py and the repairs it rests on, as far as a CPU can show:
the script refuses to pass without a TPU, its serve phase drives both
HTTP surfaces for real (here over the golden-tiny checkpoint), the compile
cache is placed from outside or at one fixed path, an unknown device has
no roofline, and a cost artifact timed on another platform is no prior.
"""

import json
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def test_smoke_fails_without_a_tpu():
    """Under JAX_PLATFORMS=cpu the script exits non-zero at the device
    phase, in seconds, and its last line reports the CPU — not ok."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], env=env,
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert not any(json.loads(ln).get("ok") for ln in lines)
    assert "no TPU" in proc.stderr


def test_serve_phase_on_a_tiny_engine(tmp_path):
    """The serve phase's own function, handed a tiny engine: model
    server (/health, 4 concurrent /v1/completions, streaming chat,
    /metrics) then chain server (/uploadDocument, /documentSearch,
    /generate with the knowledge base on)."""
    import chip_smoke
    from generativeaiexamples_tpu.embed.encoder import get_embedder
    from generativeaiexamples_tpu.engine import Engine, EngineConfig
    from generativeaiexamples_tpu.models.configs import get_model_config
    from generativeaiexamples_tpu.models.import_hf import load_checkpoint
    from generativeaiexamples_tpu.models.tokenizer import get_tokenizer

    doc = tmp_path / "notes.md"
    doc.write_text(
        "Tests run on a virtual eight-device CPU mesh with pytest.\n\n"
        "The paged KV cache shares a pool of fixed-size pages between "
        "decode slots, so capacity follows device memory.\n\n"
        "The chip smoke builds the engine from a seed and serves a few "
        "requests over HTTP before it reports the device.\n" * 3)
    # the trained golden-tiny checkpoint: the smoke's 32k-vocabulary
    # tokenizer geometry, and weights whose output decodes to text
    golden = os.path.join(REPO, "tests", "fixtures", "golden_tiny")
    cfg = get_model_config("golden-tiny")
    engine = Engine(
        load_checkpoint(golden, cfg, dtype=jnp.float32), cfg,
        get_tokenizer(golden), EngineConfig(
            max_slots=4, max_input_length=2048, max_output_length=64,
            prefill_buckets=(128, 512, 2048), dtype="float32",
            steps_per_round=4))
    try:
        out = chip_smoke.phase_serve(
            engine, get_embedder("tpu-jax", "encoder-tiny"), "golden-tiny",
            "cpu", str(doc), prompt_tokens=96, out_tokens=8, rag_tokens=8)
    finally:
        engine.stop()
    assert out["ok"] and len(out["completions"]) == 4
    assert all(c["completion_tokens"] >= 1 for c in out["completions"])
    assert out["chat_stream"]["finish_reason"] in ("length", "stop")
    assert out["rag"]["search_hits"] >= 1
    assert out["requests"] >= 6


def test_compile_cache_placed_from_outside_or_fixed(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set -> no directory is set in code;
    unset -> the one fixed in-checkout path (never on a CPU backend)."""
    from generativeaiexamples_tpu.utils import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # wherever a cache is in force its key takes the metadata in: the
    # stage scopes a profile reads must be this checkout's own
    keyed = ("jax_compilation_cache_include_metadata_in_key", True)
    assert compile_cache.enable_compile_cache() == "/placed/outside"
    assert updates == [keyed]

    del updates[:]
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == fixed
    assert updates == [keyed, ("jax_compilation_" + "cache_dir", fixed)]
    assert compile_cache.enable_compile_cache() == fixed   # never moves

    # CPU exclusion: XLA:CPU results encode the build host's features
    del updates[:]
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert compile_cache.enable_compile_cache() == ""
    assert updates == []


def test_compile_cache_dir_has_one_writer():
    """The helper is the only code that sets the cache directory."""
    needle = "jax_compilation_" + "cache_dir"
    writers = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("chiprun_out", "__pycache__")]
        for name in files:
            path = os.path.join(root, name)
            if name.endswith(".py"):
                with open(path, encoding="utf-8") as f:
                    if needle in f.read():
                        writers.append(os.path.relpath(path, REPO))
    assert writers == ["generativeaiexamples_tpu/utils/compile_cache.py"]


def test_peak_bw_raises_on_unknown_device_kind():
    from generativeaiexamples_tpu.utils.hbm import peak_bw

    class Dev:
        device_kind = "TPU v5 lite"
    assert peak_bw(Dev()) == 819e9
    Dev.device_kind = "TPU v99"
    with pytest.raises(ValueError, match="v99"):
        peak_bw(Dev())
    with pytest.raises(ValueError):
        peak_bw(jax.devices()[0])           # a CPU has no HBM roofline


def test_cost_model_refuses_another_platforms_artifact(tmp_path,
                                                       monkeypatch):
    """A ``platform: cpu`` timing must not prime a TPU engine: load()
    skips it and starts from the built-in defaults; the same artifact
    still serves a CPU engine, and one that records no platform (hand
    written) is accepted."""
    import generativeaiexamples_tpu.engine.scheduler as sched
    from generativeaiexamples_tpu.engine.scheduler import StepCostModel

    (tmp_path / "PROFILE_r99.json").write_text(json.dumps({
        "platform": "cpu", "model": "llama-tiny",
        "full_ms_per_step": 14.348, "prefill_ms_per_token": 0.1207,
        "slots": 8}))
    monkeypatch.setattr(sched, "_REPO_ROOT", str(tmp_path))
    monkeypatch.delenv("SCHED_PROFILE_JSON", raising=False)

    on_tpu = StepCostModel.load(topology="tp=1", platform="tpu")
    assert on_tpu == StepCostModel() and on_tpu.source == "default"
    on_cpu = StepCostModel.load(topology="tp=1", platform="cpu")
    assert on_cpu.source == "PROFILE_r99.json"
    assert on_cpu.decode_step_ms == 14.348

    (tmp_path / "PROFILE_r99.json").write_text(json.dumps({
        "full_ms_per_step": 3.0, "prefill_ms_per_token": 0.3,
        "slots": 8}))
    assert StepCostModel.load(platform="tpu").decode_step_ms == 3.0


def test_committed_profiles_never_prime_a_tpu_engine():
    """Every PROFILE_r*.json in the tree is a CPU timing of llama-tiny."""
    from generativeaiexamples_tpu.engine.scheduler import StepCostModel
    assert StepCostModel.load(platform="tpu").source == "default"
