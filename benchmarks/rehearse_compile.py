"""Rehearsal without the chip: compile a configuration's programs for a
DESCRIBED v5e (``v5e:2x2``, one device) and print what the chip's
compiler says of their memory. Nothing runs; no time comes of it.

    JAX_PLATFORMS=cpu python benchmarks/rehearse_compile.py <config> \
        [--layers N] [--engine-layers M]

Loads the configuration's plain reference as ``run.py`` does (a
configuration without one is refused here as there) and says which it
is; compiles (1) the weights program (init + quantize, ``--layers`` deep,
default the configuration's own) and, on an engine built on the CPU with
zero weights ``--engine-layers`` deep (default 2: the layers are a scan,
so the programs' temporaries do not grow with depth), (2) the 8-step
decode round and (3) one 512-token chunk of the chunked prefill.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {"arguments": m.argument_size_in_bytes,
            "outputs": m.output_size_in_bytes,
            "temporaries": m.temp_size_in_bytes,
            "aliased": m.alias_size_in_bytes,
            "peak_estimate": m.argument_size_in_bytes
            + m.output_size_in_bytes + m.temp_size_in_bytes
            - m.alias_size_in_bytes}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--engine-layers", type=int, default=2)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness import spec, system
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.ops.quant import quantize_params

    jax.config.update("jax_enable_compilation_cache", False)
    config = spec.load_json(os.path.join(spec.HERE, "configs",
                                         args.config + ".json"))
    spec.check_config(config, args.config)
    cfg = system.model_config(config)
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    quant = config.get("weight_quant", "")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype, sharding=dev):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def on(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    def make(key):
        p = llama.init_params(cfg, key, dtype=jnp.bfloat16)
        return quantize_params(p, quant) if quant else p

    key = jax.eval_shape(lambda: jax.random.key(0))
    out = {"config": args.config, "layers": cfg.num_layers,
           "reference": system.load_reference(config).__name__}
    out["weights_program"] = mem(jax.jit(
        make, out_shardings=dev).lower(key).compile())
    print(json.dumps(out), flush=True)

    # the engine's own programs, lowered for the described chip
    jax.default_backend = lambda: "tpu"        # arm the kernel gates
    from generativeaiexamples_tpu.engine import Engine, EngineConfig
    from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer
    ecfg_small = dataclasses.replace(cfg, num_layers=args.engine_layers)
    shapes = jax.eval_shape(lambda k: quantize_params(
        llama.init_params(ecfg_small, k, dtype=jnp.bfloat16), quant)
        if quant else llama.init_params(ecfg_small, k, dtype=jnp.bfloat16),
        jax.random.key(0))
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    e = dict(config["engine"])
    e["kv_pool_tokens"] = 16 * 1024
    eng = Engine(params, ecfg_small, ByteTokenizer(), EngineConfig(**e))
    out["engine"] = {"kernel_path": bool(eng._use_kernel),
                     "fused_tail": bool(eng._fused_tail),
                     "engine_layers": args.engine_layers,
                     "buckets": list(eng._buckets)}
    state = on({k: v for k, v in eng._state.items() if k != "cache"})
    state["cache"] = {
        k: sds(v.shape, v.dtype, Format(
            Layout(major_to_minor=tuple(range(v.ndim))), dev))
        for k, v in eng._state["cache"].items()}
    p_sds = on(eng.params)
    B = eng.cfg.max_slots
    for greedy in (True, False):
        fn = eng._round_fn(eng._pmax, 8, greedy, B)
        c = fn.lower(p_sds, state, key, sds((B,), jnp.int32)).compile()
        out[f"decode_round_{'greedy' if greedy else 'sampled'}"] = dict(
            mem(c), pallas_kernel="tpu_custom_call" in c.as_text())
    window = eng._pmax
    i32 = sds((), jnp.int32)
    c = eng._chunk_extend_fn(window, "accum").lower(
        state, p_sds, sds((1, 512), jnp.int32), i32, i32, i32,
        sds((1, window), jnp.int32)).compile()
    out["chunk_extend_512"] = mem(c)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
