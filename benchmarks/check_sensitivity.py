"""What the logits check reads, and does it catch a precision fault?

    python benchmarks/check_sensitivity.py --config <configuration> \
        --seeds 1,2,3 [--kv-int8-seeds 1,2] [--weights-lower-seeds 3] \
        [--engine]

For each seed: makes the configuration's weights and runs the set-up
logits check on them as every cell runs it (against the configuration's
plain reference, loaded as ``run.py`` loads it). Two controls, each the
PROGRAM's own path one precision step under what the configuration
states, held to the reference over what it states: for the seeds of
``--kv-int8-seeds`` the paged side's KV pool in int8 where bf16 is
stated; for those of ``--weights-lower-seeds`` the paged side over the
same seed's weights stored one step lower (int4 for int8, int8 for
bf16), the reference's logits replayed from the plain run so that the
two trees never share the chip. ``--engine`` builds the engine too and
holds its own greedy tokens to the same reference logits. Prints one JSON line
for each with the readings and whether the check passed, so that the
tolerances are set from readings and what they can and cannot tell
apart is a reading and not a belief; exits 0 if every plain check
passed. Not a cell: the result is quoted in each configuration's
``logits_check.why`` and in PERF.md.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.harness import spec as spec_mod, system  # noqa: E402


LOWER = {"int8": "int4", "": "int8"}   # stated weight_quant -> the control's


def seeds_of(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def recorded(forward) -> tuple:
    """``forward`` remembering what it returned, and a stand-in that
    replays it without reading any weights."""
    import jax.numpy as jnp
    import numpy as np
    memo: dict = {}

    def key(ids, positions):
        return (np.asarray(ids).tobytes(), tuple(int(p) for p in positions))

    def record(params, model, ids, positions):
        out = np.asarray(forward(params, model, ids, positions))
        memo[key(ids, positions)] = out
        return jnp.asarray(out)

    def replay(params, model, ids, positions):
        return jnp.asarray(memo[key(ids, positions)])
    return record, replay


def readings_of(fn, *a, **kw) -> tuple:
    """(readings, passed, why, the check's whole result or None)."""
    try:
        ref = fn(*a, **kw)
        return ({k: v for k, v in ref.items() if k != "prompts"},
                True, None, ref)
    except system.CheckFailed as exc:
        return exc.readings, False, str(exc), None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--kv-int8-seeds", default="")
    ap.add_argument("--weights-lower-seeds", default="")
    ap.add_argument("--engine", action="store_true")
    ap.add_argument("--benchmark-json", default=None,
                    help="another BENCHMARK.json (the tests' rehearsal)")
    args = ap.parse_args(argv)
    spec = spec_mod.Spec(args.benchmark_json)
    entry = next(c for c in spec.doc["configs"] if c["name"] == args.config)
    config = spec_mod.load_json(os.path.join(os.path.dirname(spec.path),
                                             entry["file"]))
    spec_mod.check_config(config, entry["file"])
    device = system.device_info()
    if config["platform"] == "tpu" and device["platform"] != "tpu":
        print(f"{args.config} runs at published widths and needs a TPU; "
              f"JAX reports {device['platform']!r}", file=sys.stderr)
        return 2
    system.setup_jax()
    cfg = system.model_config(config)
    kv_seeds = set(seeds_of(args.kv_int8_seeds))
    lower_seeds = set(seeds_of(args.weights_lower_seeds))
    quant = config["weight_quant"]
    forward = system.load_reference(config).forward
    all_passed = True

    def say(seed, control, out, ok, why, **more):
        print(json.dumps(dict(
            {"config": args.config, "seed": seed, "control": control,
             "passed": ok, "why": why, "readings": out}, **more)),
            flush=True)

    for seed in seeds_of(args.seeds):
        params = system.make_params(cfg, quant, seed)
        record, replay = recorded(forward)
        out, ok, why, ref = readings_of(
            system.logits_check, params, cfg, config, seed, forward=record)
        all_passed &= ok
        more = {}
        if args.engine and ref is not None:
            engine = system.build_engine(params, cfg, config, seed)
            engine.start()
            try:
                more["engine_tokens"] = system.engine_tokens_check(
                    engine, ref, config)
            except system.CheckFailed as exc:
                more["engine_tokens"] = str(exc)
                all_passed = False
            finally:
                engine.stop()
                del engine
        say(seed, None, out, ok, why, **more)
        if seed in kv_seeds:
            say(seed, "kv_int8", *readings_of(
                system.logits_check, params, cfg, config, seed,
                kv_quantized=True, forward=replay)[:3])
        del params, ref
        gc.collect()
        if seed in lower_seeds:
            lower = system.make_params(cfg, LOWER[quant], seed)
            say(seed, "weights_" + LOWER[quant], *readings_of(
                system.logits_check, lower, cfg, config, seed,
                forward=replay)[:3])
            del lower
            gc.collect()
    return 0 if all_passed else 1


if __name__ == "__main__":
    sys.exit(main())
