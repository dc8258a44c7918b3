"""Does the logits check see what a configuration's model adds?

    python benchmarks/check_faults.py --config <configuration> --seed <n> \
        --faults faults.json [--weights-lower] [--kv-int8]

``check_sensitivity.py`` holds the program one PRECISION step under what
the configuration states; this holds it one MECHANISM short. For one
seed: makes the configuration's weights, runs the set-up logits check
as every cell runs it (the sound reading, its reference logits
recorded), then for each entry ``{"name": {field: value, ...}}`` of the
faults file runs the paged side again over the SAME tree with those
``LlamaConfig`` fields replaced in the PROGRAM's configuration — the
reference as it is, its logits replayed — and prints one JSON line a
fault with the readings, whether the check passed and which of its
limits failed it. A fault the check passes is a mechanism the check
does not see under this draw of the weights (PERF.md section 6).
``--weights-lower`` and ``--kv-int8`` add ``check_sensitivity.py``'s two
controls on the same seed. Exits 0 if the sound check passed and every
fault failed. Not a cell: the table is quoted in the configuration's
``logits_check.why`` and in PERF.md.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.check_sensitivity import (LOWER, readings_of,  # noqa: E402
                                          recorded)
from benchmarks.harness import spec as spec_mod, system  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--faults", required=True,
                    help="JSON file: {name: {LlamaConfig field: value}}")
    ap.add_argument("--weights-lower", action="store_true")
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--benchmark-json", default=None)
    args = ap.parse_args(argv)
    spec = spec_mod.Spec(args.benchmark_json)
    entry = next(c for c in spec.doc["configs"] if c["name"] == args.config)
    config = spec_mod.load_json(os.path.join(os.path.dirname(spec.path),
                                             entry["file"]))
    spec_mod.check_config(config, entry["file"])
    faults = spec_mod.load_json(args.faults)
    device = system.device_info()
    if config["platform"] == "tpu" and device["platform"] != "tpu":
        print(f"{args.config} runs at published widths and needs a TPU; "
              f"JAX reports {device['platform']!r}", file=sys.stderr)
        return 2
    system.setup_jax()
    cfg = system.model_config(config)
    quant, seed = config["weight_quant"], args.seed

    def say(fault, out, ok, why):
        print(json.dumps({"config": args.config, "seed": seed,
                          "fault": fault, "passed": ok, "why": why,
                          "readings": out}), flush=True)
        return ok

    params = system.make_params(cfg, quant, seed)
    record, replay = recorded(system.load_reference(config).forward)
    sound = say(None, *readings_of(system.logits_check, params, cfg, config,
                                   seed, forward=record)[:3])
    unseen = []
    for name, fields in faults.items():
        broken = dataclasses.replace(cfg, **fields)
        if say(name, *readings_of(system.logits_check, params, broken,
                                  config, seed, forward=replay)[:3]):
            unseen.append(name)
    if args.kv_int8:
        say("kv_int8", *readings_of(system.logits_check, params, cfg, config,
                                    seed, kv_quantized=True,
                                    forward=replay)[:3])
    del params
    gc.collect()
    if args.weights_lower:
        lower = system.make_params(cfg, LOWER[quant], seed)
        if say("weights_" + LOWER[quant], *readings_of(
                system.logits_check, lower, cfg, config, seed,
                forward=replay)[:3]):
            unseen.append("weights_" + LOWER[quant])
    print(json.dumps({"sound_passed": sound, "unseen": unseen}), flush=True)
    return 0 if sound and not unseen else 1


if __name__ == "__main__":
    sys.exit(main())
