"""Record the small SCOPED trace the scope and host-span readers are
tested on: a program named ``decode_round`` whose stages sit under
``jax.named_scope`` (``attn``, ``mlp``, ``tail`` with ``tail_select``
inside, one matmul left unscoped, the layer body in a ``lax.scan``) and
a program named ``extend``, each launched from a thread that wraps the
launch in the engine's host spans (``engine_round`` > ``loop_dispatch``
/ ``loop_admit`` > ``chunk_dispatch``, ``loop_plan`` before them).
Profiler options as ``run.py`` sets them. Meant for the TPU; writes
``chiprun_out/scoped_fixture/`` and prints what the two readers make of
it (the recording kept as ``tests/benchmarks/fixtures/
tpu_v5e_spans_scopes.xplane.pb`` is one such run; the name sorts after
``tpu_v5e_small``, which an older test takes as the first of ``*.pb``)."""

from __future__ import annotations

import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import ProfileData, TraceAnnotation

    from benchmarks.harness import trace
    from benchmarks.readers import device_scope, host_spans

    @jax.jit
    def decode_round(x, ws):
        wa, w1, w2, wo, wh = ws

        def layer(h, _):
            with jax.named_scope("attn"):
                s = (h @ wa).astype(jnp.float32)
                h = h + jax.nn.softmax(s, axis=-1).astype(h.dtype)
            with jax.named_scope("mlp"):
                h = h + jax.nn.relu(h @ w1) @ w2
            return h, None
        h, _ = jax.lax.scan(layer, x, None, length=3)
        h = jnp.tanh(h @ wo)                        # left unscoped
        with jax.named_scope("tail"):
            logits = (h @ wh).astype(jnp.float32)
            with jax.named_scope("tail_select"):
                top = jnp.sort(logits, axis=-1)[:, -8:]
            return top.sum()

    @jax.jit
    def extend(x, ws):
        with jax.named_scope("attn"):
            return jnp.tanh(x @ ws[0]).sum()

    x = jnp.ones((256, 1024), jnp.bfloat16)
    keys = jax.random.split(jax.random.key(0), 5)
    w = tuple(jax.random.normal(k, shape, jnp.bfloat16) * 0.02
              for k, shape in zip(keys, ((1024, 1024), (1024, 2048),
                                         (2048, 1024), (1024, 1024),
                                         (1024, 4096))))
    jax.block_until_ready((decode_round(x, w), extend(x, w)))
    out = os.path.join(REPO, "chiprun_out", "scoped_fixture")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1

    def rounds():
        for rid in range(3):
            with TraceAnnotation("loop_plan"):
                time.sleep(0.0005)
            with TraceAnnotation("engine_round", round_id=rid, kind="mixed",
                                 t_mono_ns=time.monotonic_ns()):
                with TraceAnnotation("loop_dispatch", round_id=rid, steps=3,
                                     rows=256, ba=256):
                    a = decode_round(x, w)
                with TraceAnnotation("loop_admit", round_id=rid):
                    with TraceAnnotation("chunk_dispatch", round_id=rid,
                                         request_id=f"req-{rid}", tokens=200,
                                         padded=256, mode="middle"):
                        b = extend(x, w)
            with TraceAnnotation("engine_harvest_wait", round_id=rid):
                np.asarray(a)
            jax.block_until_ready(b)
            with TraceAnnotation("loop_idle"):
                time.sleep(0.01)

    jax.profiler.start_trace(out, profiler_options=opts)
    t = threading.Thread(target=rounds, name="engine-sched")
    t.start()
    t.join()
    jax.profiler.stop_trace()

    path = trace.find_xplane(out)
    prof = ProfileData.from_file(path)
    scopes = device_scope.scope_map(path)
    spans = host_spans.load_spans(prof)
    print(json.dumps({
        "fixture": path, "bytes": os.path.getsize(path),
        "device": jax.devices()[0].device_kind,
        "planes": trace.describe(trace.planes_of(prof)),
        "scope_map_modules": {m: len(ops) for m, ops in scopes.items()},
        "scopes": device_scope.reduce_scopes(path, "^jit_decode_round$"),
        "host_note": host_spans.summary(path)["note"],
        "host_spans": {n: len(v) for n, v in spans.items()},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
