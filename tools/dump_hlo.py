"""See a program's shape before spending chip time: compile a benchmark
configuration's engine programs for a DESCRIBED v5e (``v5e:2x2``, one
device; on-chip-measurement guide §2.3) and write the OPTIMISED HLO the
chip's compiler produces — the very instruction names a device trace and
the ledger's ``breakdown.device_ops`` print (``copy.69``,
``constant_dynamic-slice_fusion.26``, ``paged_attn_decode.6``).

    JAX_PLATFORMS=cpu python tools/dump_hlo.py <config> \
        [--engine-layers N] [--pool-tokens T] [--out DIR]

The programs of ``benchmarks/rehearse_compile.py`` (which prints memory
only) and the rest of the serving path's, built from
``engine/programs.py`` and SHAPES — no engine, no weights —
``--engine-layers`` deep: the 8-step decode round (greedy and sampled),
a sampled verify round, one 512-token chunk of the chunked prefill, the
chunk program of four prompts' rows, a final chunk and the one-shot
admission, lowered for the described chip. Writes
``<out>/<config>.<program>.hlo.txt`` and prints
one JSON line: each program's temporaries, what :func:`weight_report`
finds in its text and, as ``pool_copies``, what :func:`pool_report` does
(instructions that copy a layer's slab of the KV pool, or a whole pool).
Nothing runs; no time comes of it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# the stacked layer matrices of a block, by parameter-tree key
LAYER_WEIGHTS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
# opcodes a value passes through unchanged in identity (layout, memory
# space and view may change: that is what the report is about; the async
# slice is the compiler's own prefetch of one layer into fast memory)
_PASS = ("bitcast", "copy", "copy-start", "copy-done", "slice-start",
         "slice-done")


def _balanced(s: str, i: int) -> int:
    """Index just past the parenthesis that closes the one at ``s[i]``."""
    depth = 0
    for j in range(i, len(s)):
        depth += (s[j] == "(") - (s[j] == ")")
        if depth == 0:
            return j + 1
    return len(s)


def parse_hlo(text: str) -> dict[str, list[dict]]:
    """Optimised HLO text -> {computation: [instruction]}, an instruction
    being ``name``, ``shape`` (result, with layout), ``op``, ``operands``
    (names, in order), ``attrs`` (the text after the operands: ``calls=``,
    ``body=``, ``index=``, metadata) and ``line``."""
    comps: dict[str, list[dict]] = {}
    cur = None
    for line in text.splitlines():
        s = line.strip()
        if s == "}":
            cur = None
            continue
        if s.endswith("{") and " = " not in s.split("(", 1)[0]:
            m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*\(", s)
            if m:
                cur = comps.setdefault(m.group(1), [])
            continue
        if cur is None or " = " not in s:
            continue
        name, rest = s.removeprefix("ROOT ").split(" = ", 1)
        if rest.startswith("("):        # a tuple's shape has spaces
            cut = _balanced(rest, 0)
        else:
            cut = rest.find(" ")
        shape, rest = rest[:cut], rest[cut:].lstrip()
        m = re.match(r"([\w\-]+)\(", rest)
        if m is None:
            continue
        close = _balanced(rest, m.end() - 1)
        cur.append({
            "name": name.lstrip("%"), "shape": shape, "op": m.group(1),
            "operands": re.findall(r"%([\w.\-]+)",
                                   rest[m.end():close - 1]),
            "attrs": rest[close:], "line": s})
    return comps


def _attr(ins: dict, key: str):
    m = re.search(key + r"=%?([\w.\-]+)", ins["attrs"])
    return m.group(1) if m else None


def trace_weights(comps: dict[str, list[dict]]) -> dict[tuple, object]:
    """Which values ARE a stacked int8 layer weight, by name.

    Optimised HLO names a weight only at the entry parameter (``op_name=
    "params['layers']['wq']['q']"``); inside a ``while`` body it is an
    element of the loop's tuple. Follows each such parameter through
    ``tuple`` / ``while`` / ``get-tuple-element`` / ``call`` and through
    the opcodes of ``_PASS``. Returns {(computation, value): key} where
    key is a weight's name, or for a tuple a {index: key} dict."""
    ident: dict[tuple, object] = {}

    def params_of(comp):
        return sorted((i for i in comps.get(comp, ())
                       if i["op"] == "parameter"),
                      key=lambda i: int(re.search(r"parameter\((\d+)\)",
                                                  i["line"]).group(1)))

    changed = True

    def put(comp, name, val):
        nonlocal changed
        if val and ident.get((comp, name)) != val:
            ident[(comp, name)] = val
            changed = True

    while changed:      # to a fixed point: bodies precede their callers
        changed = False
        for comp, instrs in comps.items():
            for ins in instrs:
                ops = [ident.get((comp, o)) for o in ins["operands"]]
                op = ins["op"]
                if op == "parameter" and ins["shape"].startswith("s8["):
                    m = re.search(r"layers\W+(\w+)\W+q\W", ins["attrs"])
                    if m and m.group(1) in LAYER_WEIGHTS:
                        put(comp, ins["name"], m.group(1))
                elif op in _PASS and ops and isinstance(ops[0], str):
                    put(comp, ins["name"], ops[0])
                elif op == "custom-call" and "ConcatBitcast" in ins["attrs"] \
                        and isinstance(ops[0], str) and len(set(ops)) == 1:
                    put(comp, ins["name"], ops[0])  # a prefetch in pieces
                elif op == "tuple":
                    put(comp, ins["name"],
                        {i: v for i, v in enumerate(ops) if v})
                elif op == "get-tuple-element" and isinstance(ops[0], dict):
                    put(comp, ins["name"],
                        ops[0].get(int(_attr(ins, "index"))))
                elif op == "while" and isinstance(ops[0], dict):
                    put(comp, ins["name"], ops[0])   # weights pass through
                    for sub in (_attr(ins, "body"), _attr(ins, "condition")):
                        for par in params_of(sub)[:1]:
                            put(sub, par["name"], ops[0])
                elif op == "call":
                    for par, val in zip(params_of(_attr(ins, "to_apply")),
                                        ops):
                        put(_attr(ins, "to_apply"), par["name"], val)
    return ident


def weight_report(text: str) -> dict:
    """What a program does with its stacked int8 layer weights
    (``params['layers'][<key>]['q']``, followed by name: `trace_weights`).

    - ``stack_copies``: every ``copy`` whose operand is a whole stack — a
      relayout of all layers at once, held as a temporary while the
      program lives (``copy-start``/``copy-done`` prefetches, which keep
      the layout, are not counted);
    - ``slice_results``: every fusion, ``copy`` or ``dynamic-slice``
      outside a matmul fusion whose RESULT is an s8 array of ONE layer's
      size: the slice was copied out before the matmul instead of being
      read by it;
    - ``matmul_fusions``: per weight, the fusions that contain the
      ``convolution``/``dot`` and take as an operand the stack itself (or
      the compiler's async prefetch of it: ``slice-done``).
    """
    comps = parse_hlo(text)
    ident = trace_weights(comps)
    has_matmul = {c: any(i["op"] in ("convolution", "dot") for i in ins)
                  for c, ins in comps.items()}
    fused = {_attr(i, "calls") for ins in comps.values() for i in ins
             if i["op"] == "fusion"}

    def numel(shape):
        m = re.match(r"s8\[([\d,]+)\]", shape)
        return math.prod(int(d) for d in m.group(1).split(",")) if m else 0

    # one layer's elements, from the stacks as the entry receives them
    slice_sizes = {numel(i["shape"]) // int(i["shape"][3:].split(",")[0])
                   for c, ins in comps.items() for i in ins
                   if i["op"] == "parameter"
                   and isinstance(ident.get((c, i["name"])), str)}
    report = {"weights": sorted({k for k in ident.values()
                                 if isinstance(k, str)}),
              "stack_copies": [], "slice_results": [], "matmul_fusions": {}}
    for comp, instrs in comps.items():
        if comp in fused:       # a fusion's inside is judged by its caller
            continue
        for ins in instrs:
            keys = [k for o in ins["operands"]
                    if isinstance(k := ident.get((comp, o)), str)]
            in_matmul = ins["op"] == "fusion" and has_matmul.get(
                _attr(ins, "calls"))
            if ins["op"] == "copy" and keys:
                report["stack_copies"].append(
                    f"{ins['name']} = {ins['shape']} copy({keys[0]})")
            if ins["op"] in ("fusion", "copy", "dynamic-slice") \
                    and not in_matmul \
                    and numel(ins["shape"]) in slice_sizes:
                report["slice_results"].append(
                    f"{ins['name']} = {ins['shape']} {ins['op']}"
                    f"({','.join(keys)})")
            if in_matmul:
                for k in keys:
                    report["matmul_fusions"].setdefault(k, []).append(
                        ins["name"])
    return report


# opcodes whose result is no new buffer: a view of, or a name for, a
# value that exists already
_VIEWS = ("parameter", "bitcast", "get-tuple-element", "tuple", "while",
          "conditional", "call", "optimization-barrier")


def pool_report(text: str, leaves: list[tuple[str, tuple]]) -> list[str]:
    """The instructions of a program whose RESULT is one layer's slab of
    the paged KV pool, or a whole pool: a copy of KV nobody asked for.

    ``leaves``: (dtype name, shape) of each pool array, layer-major —
    ``("bfloat16", (L, N, KV, page, hd))``, and under int8 KV the
    ``(L, N, KV, page)`` scale arrays too. A result matches by element
    type and shape, leading 1s aside: a slab ``(N, ...)``, a pool
    ``(L, N, ...)`` or the pool with its two leading axes flattened.
    Not reported: the opcodes of ``_VIEWS``, and an instruction that
    takes a whole pool and returns one — the in-place update of the
    donated argument (``memory_analysis()`` says whether it stayed in
    place: temporaries then hold no second pool). A fusion's inside is
    judged by its caller, as in :func:`weight_report`."""
    comps = parse_hlo(text)
    fused = {_attr(i, "calls") for ins in comps.values() for i in ins
             if i["op"] == "fusion"}
    hlo_type = {"bfloat16": "bf16", "float32": "f32", "int8": "s8"}

    def typed_dims(shape):
        m = re.match(r"(\w+)\[([\d,]+)\]", shape)
        if m is None:       # a tuple, a token, a scalar
            return None
        dims = [int(d) for d in m.group(2).split(",")]
        while len(dims) > 1 and dims[0] == 1:
            dims.pop(0)
        return m.group(1), tuple(dims)

    pools, slabs = set(), set()
    for dtype, (n_layers, n_pages, *rest) in leaves:
        t = hlo_type[dtype]
        pools |= {(t, (n_layers, n_pages, *rest)),
                  (t, (n_layers * n_pages, *rest))}
        slabs.add((t, (n_pages, *rest)))
    wanted = pools | slabs
    found = []
    for comp, instrs in comps.items():
        if comp in fused:
            continue
        made_by = {i["name"]: typed_dims(i["shape"]) for i in instrs}
        for ins in instrs:
            made = made_by[ins["name"]]
            if ins["op"] in _VIEWS or made not in wanted:
                continue
            if made in pools and ins["op"] != "copy" and any(
                    made_by.get(o) in pools for o in ins["operands"]):
                continue
            found.append(f"{ins['name']} = {ins['shape']} {ins['op']}"
                         f"({','.join(ins['operands'])})")
    return found


def engine_programs(config_name: str, engine_layers: int,
                    pool_tokens: int = 16 * 1024):
    """Yield (program name, compiled, pool leaves) for every program
    kind of ``benchmarks/configs/<config_name>.json`` — the decode rounds
    (greedy and sampled), a sampled verify round, one chunk, the chunk
    of several prompts' rows, a final chunk and the one-shot admission —
    built from ``engine/programs.py`` and shapes alone: no engine, no
    array. The leaves are :func:`pool_report`'s (dtype name, shape)
    pairs."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness import spec, system
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.ops.quant import quantize_params
    from generativeaiexamples_tpu.ops.sampling import mask_words

    jax.config.update("jax_enable_compilation_cache", False)
    config = spec.load_json(os.path.join(spec.HERE, "configs",
                                         config_name + ".json"))
    cfg = dataclasses.replace(system.model_config(config),
                              num_layers=engine_layers)
    quant = config.get("weight_quant", "")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype, sharding=dev):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def on(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    jax.default_backend = lambda: "tpu"        # arm the kernel gates
    from generativeaiexamples_tpu.engine import EngineConfig, programs
    from generativeaiexamples_tpu.engine.spec_decode import SpecConfig
    from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer

    def make(k):
        p = llama.init_params(cfg, k, dtype=jnp.bfloat16)
        return quantize_params(p, quant) if quant else p

    p_sds = on(jax.eval_shape(make, jax.random.key(0)))
    ecfg = EngineConfig(**dict(config["engine"], kv_pool_tokens=pool_tokens))
    B, page = ecfg.max_slots, ecfg.page_size
    pmax = -(-ecfg.max_cache_len // page)
    S = SpecConfig.resolve().max_draft_tokens + 1
    progs = programs.Programs(programs.ProgramSpec.resolve(
        p_sds, cfg, page_size=page, max_slots=B, pmax=pmax,
        dtype=ecfg.dtype, mesh=None, eos_id=int(ByteTokenizer().eos_id),
        spec_S=S))
    n_pages = 1 + min(B * pmax, max(pmax, -(-pool_tokens // page)))
    state = on(jax.eval_shape(
        lambda: programs.slot_state(cfg.vocab_size, B, pmax)))
    # a recurrent state is a slot's, beside the pages; a verify round
    # cannot roll one back (the engine refuses it by name)
    recurrent = cfg.recurrent
    pool = jax.eval_shape(lambda: llama.init_paged_kv_cache(
        cfg, n_pages, page, progs.spec.dtype,
        quantized=bool(ecfg.kv_quant),
        **({"slots": B} if recurrent else {})))
    state["cache"] = {
        k: sds(v.shape, v.dtype, programs.cache_placement(
            dev, v.ndim, progs.spec.use_kernel)) for k, v in pool.items()}
    leaves = [(v.dtype.name, v.shape) for v in pool.values()]
    key = jax.eval_shape(lambda: jax.random.key(0))
    i32, f32 = sds((), jnp.int32), sds((), jnp.float32)
    slots, window = sds((B,), jnp.int32), pmax
    chunk = (state, p_sds, sds((1, 512), jnp.int32), i32, i32, i32)
    # the sampling state an admission program arms a slot with
    arming = (f32, i32, f32, f32,
              sds((mask_words(cfg.vocab_size),), jnp.uint32),
              sds((programs.MAX_BAD_SEQS, programs.MAX_BAD_LEN), jnp.int32),
              sds((programs.MAX_BAD_SEQS,), jnp.int32), key, i32,
              sds((), jnp.bool_))
    walk = [(f"decode_round_{name}", progs.round_fn(pmax, 8, greedy, B),
             (p_sds, state, key, slots))
            for greedy, name in ((True, "greedy"), (False, "sampled"))]
    walk += [
        ("verify_round_sampled", progs.verify_fn(window, False, B),
         (p_sds, state, key, slots, sds((B, S - 1), jnp.int32), slots))
    ] * (not recurrent) + [
        ("chunk_extend_512", progs.chunk_extend_fn(window, "accum"),
         (*chunk, sds((1, window), jnp.int32)))]
    # the chunk program of several prompts, at its largest rung (none
    # under capacity routing)
    for rows in programs.row_ladder(cfg)[:1]:
        vec = sds((rows,), jnp.int32)
        walk.append((f"chunk_extend_rows{rows}", progs.chunk_rows_fn(rows),
                     (state, p_sds, sds((rows, 512), jnp.int32), vec, vec,
                      sds((rows, window), jnp.int32),
                      sds((rows,), jnp.bool_))))
    walk += [
        ("chunk_final_512", progs.chunk_final_fn(window, True, False),
         (*chunk, sds((pmax,), jnp.int32), sds((1, window), jnp.int32),
          *arming)),
        ("prefill_insert_512", progs.prefill_insert,
         (*chunk[:3], i32, i32, sds((pmax,), jnp.int32), *arming, True))]
    for name, fn, args in walk:
        yield name, fn.lower(*args).compile(), leaves


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--engine-layers", type=int, default=2)
    ap.add_argument("--pool-tokens", type=int, default=16 * 1024,
                    help="KV pool size; at full depth give the cell's "
                         "own (nemotron-8b-chat: 71 pages = 9088)")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "hlo"))
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    out = {"config": args.config, "engine_layers": args.engine_layers}
    for name, compiled, leaves in engine_programs(
            args.config, args.engine_layers, args.pool_tokens):
        text = compiled.as_text()
        path = os.path.join(args.out, f"{args.config}.{name}.hlo.txt")
        with open(path, "w") as f:
            f.write(text)
        out[name] = {
            "file": path,
            "temporaries": compiled.memory_analysis().temp_size_in_bytes,
            "pool_copies": pool_report(text, leaves),
            **weight_report(text)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
