"""Does the logits check catch a precision fault?

    python benchmarks/check_sensitivity.py --config <configuration> --seed <n>

Makes the configuration's weights from the seed and runs the set-up
logits check twice on them: as every cell runs it, and with the paged
side's KV pool in int8 where the configuration states bf16. Prints one
JSON line for each with the readings and whether the check passed, so
that what the tolerances can and cannot tell apart is a reading and not
a belief; exits 0 if the plain check passed. Not a cell: the result is
quoted in PERF.md beside the tolerances.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.harness import spec as spec_mod, system  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--benchmark-json", default=None,
                    help="another BENCHMARK.json (the tests' rehearsal)")
    args = ap.parse_args(argv)
    spec = spec_mod.Spec(args.benchmark_json)
    entry = next(c for c in spec.doc["configs"] if c["name"] == args.config)
    config = spec_mod.load_json(os.path.join(os.path.dirname(spec.path),
                                             entry["file"]))
    device = system.device_info()
    if config["platform"] == "tpu" and device["platform"] != "tpu":
        print(f"{args.config} runs at published widths and needs a TPU; "
              f"JAX reports {device['platform']!r}", file=sys.stderr)
        return 2
    system.setup_jax()
    cfg = system.model_config(config)
    params = system.make_params(cfg, config["weight_quant"], args.seed)
    passed = []
    for kv_quantized in (False, True):
        try:
            out = system.logits_check(params, cfg, config, args.seed,
                                      kv_quantized=kv_quantized)
            out.pop("prompts")
            ok, why = True, None
        except system.CheckFailed as exc:
            out, ok, why = exc.readings, False, str(exc)
        passed.append(ok)
        print(json.dumps({"config": args.config, "seed": args.seed,
                          "kv_int8_fault": kv_quantized, "passed": ok,
                          "why": why, "readings": out}), flush=True)
    return 0 if passed[0] else 1


if __name__ == "__main__":
    sys.exit(main())
