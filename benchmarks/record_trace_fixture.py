"""Record the small trace the reduction is tested on: three executions
of a program named ``decode_round`` and two of ``extend``, 20 ms apart,
on whatever device JAX has (meant for the TPU). Writes
``chiprun_out/trace_fixture/`` and prints what the trace holds."""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import trace

    @jax.jit
    def decode_round(x):
        return jnp.tanh(x @ x).sum()

    @jax.jit
    def extend(x):
        return (x * 2.0 + 1.0).sum()

    x = jnp.ones((256, 256), jnp.bfloat16)
    jax.block_until_ready((decode_round(x), extend(x)))
    out = os.path.join(REPO, "chiprun_out", "trace_fixture")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=opts)
    for fn in (decode_round, extend, decode_round, extend, decode_round):
        fn(x).block_until_ready()
        time.sleep(0.02)
    jax.profiler.stop_trace()
    path = trace.find_xplane(out)
    planes = trace.load(path)
    red = trace.reduce(planes)
    print(json.dumps({
        "fixture": path, "bytes": os.path.getsize(path),
        "device": jax.devices()[0].device_kind,
        "planes": trace.describe(planes),
        "reduction": None if red is None else {
            "busy_s": red.busy_s, "modules": red.devices[0].module_n,
            "breakdown": red.breakdown()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
