"""engine/programs.py: the arrow points one way (it knows the model, the
kernels and the mesh, nothing of the loop), every program kind builds and
lowers from the module alone — no ``Engine`` — the tail is chosen in one
function, and one function cuts a grant into a chunk."""

import ast
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.engine import Engine, EngineConfig, programs
from generativeaiexamples_tpu.engine.engine import _Request
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import LlamaConfig
from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer
from generativeaiexamples_tpu.ops.quant import quantize_tensor
from generativeaiexamples_tpu.ops.sampling import mask_words

CFG = LlamaConfig(vocab_size=512, hidden_size=64, intermediate_size=128,
                  num_layers=1, num_heads=4, num_kv_heads=2, head_dim=16,
                  max_position_embeddings=128, tie_word_embeddings=False)
PAGE, B, PMAX, C = 16, 2, 5, 32


# ------------------------------------------------------------ the arrow


def test_programs_imports_nothing_of_the_loop():
    """No scheduler, no recorder, no thread, no clock: what the module
    imports, wherever in it, is ``models/``, ``ops/``, ``parallel/``,
    jax and the standard library's plain values."""
    tree = ast.parse(open(programs.__file__).read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            base = "." * node.level + (node.module or "")
            names |= {base} | {f"{base}.{a.name}" for a in node.names}
    assert names, "the walk must see the imports"
    banned = ("threading", "time", "queue", "asyncio", "logging",
              "..obs", "..utils", ".engine", ".scheduler", ".kv_tier",
              ".prefix_cache", ".spec_decode", ".rag_fusion", ".resume")
    hits = sorted(n for n in names for b in banned
                  if n == b or n.startswith(b + "."))
    assert not hits, hits
    assert {n.split(".")[2] for n in names if n.startswith("..")} \
        <= {"models", "ops", "parallel"}


# ------------------------------- every program kind, from the module alone


def _shapes(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        tree)


@pytest.fixture(scope="module")
def built():
    """A program spec resolved from parameter SHAPES, and a shape-only
    state: no engine, no array."""
    params = _shapes(jax.eval_shape(
        lambda k: llama.init_params(CFG, k, dtype=jnp.float32),
        jax.random.key(0)))
    spec = programs.ProgramSpec.resolve(
        params, CFG, page_size=PAGE, max_slots=B, pmax=PMAX,
        dtype="float32", mesh=None, eos_id=2, spec_S=3)
    state = jax.eval_shape(
        lambda: programs.slot_state(CFG.vocab_size, B, PMAX))
    state["cache"] = jax.eval_shape(lambda: llama.init_paged_kv_cache(
        CFG, 1 + B * PMAX, PAGE, jnp.float32))
    return programs.Programs(spec), params, _shapes(state)


def _args(kind, params, state):
    sds = jax.ShapeDtypeStruct
    key = jax.eval_shape(lambda: jax.random.key(0))
    i32, f32 = sds((), jnp.int32), sds((), jnp.float32)
    slots = sds((B,), jnp.int32)
    chunk = (state, params, sds((1, C), jnp.int32), i32, i32, i32)
    arming = (f32, i32, f32, f32, sds((mask_words(CFG.vocab_size),),
                                      jnp.uint32),
              sds((programs.MAX_BAD_SEQS, programs.MAX_BAD_LEN), jnp.int32),
              sds((programs.MAX_BAD_SEQS,), jnp.int32), key, i32,
              sds((), jnp.bool_))
    return {
        "round": (params, state, key, slots),
        "verify": (params, state, key, slots, sds((B, 2), jnp.int32), slots),
        "extend": (*chunk, sds((1, PMAX), jnp.int32)),
        "rows": (state, params, sds((4, C), jnp.int32),
                 sds((4,), jnp.int32), sds((4,), jnp.int32),
                 sds((4, PMAX), jnp.int32), sds((4,), jnp.bool_)),
        "final": (*chunk, sds((PMAX,), jnp.int32),
                  sds((1, PMAX), jnp.int32), *arming),
        "prefill_insert": (*chunk[:3], i32, i32, sds((PMAX,), jnp.int32),
                           *arming),
    }[kind]


@pytest.mark.parametrize("name,kind,build", [
    ("decode_round", "round", lambda p: p.round_fn(PMAX, 2, True, B)),
    ("decode_round", "round", lambda p: p.round_fn(PMAX, 2, False, 1)),
    ("verify_round", "verify", lambda p: p.verify_fn(PMAX, False, B)),
    ("extend", "extend", lambda p: p.chunk_extend_fn(PMAX, "accum")),
    ("extend", "rows", lambda p: p.chunk_rows_fn(4)),
    ("final", "final", lambda p: p.chunk_final_fn(PMAX, True, False)),
    ("final", "final", lambda p: p.chunk_final_fn(PMAX, False, False)),
], ids=["round_greedy", "round_sampled", "verify_sampled", "extend",
        "extend_rows", "final_greedy", "final_sampled"])
def test_a_program_builds_and_lowers_without_an_engine(built, name, kind,
                                                       build):
    """The jitted callable keeps the Python name a trace's readers
    select device time by (``^jit_decode_round$``,
    ``^jit_(prefill_insert|extend|final)$``)."""
    progs, params, state = built
    fn = build(progs)
    assert build(progs) is fn               # kept by its key
    text = fn.lower(*_args(kind, params, state)).as_text()
    assert f"module @jit_{name} " in text


@pytest.mark.parametrize("greedy", [True, False], ids=["greedy", "sampled"])
def test_the_one_shot_admission_lowers_without_an_engine(built, greedy):
    progs, params, state = built
    text = progs.prefill_insert.lower(
        *_args("prefill_insert", params, state), greedy).as_text()
    assert "module @jit_prefill_insert " in text
    assert progs.prefill_insert_raw.__name__ == "prefill_insert"


def test_the_caches_are_keyed_as_the_benchmark_reads_them(built):
    progs, _, _ = built
    progs.round_fn(PMAX, 2, True, B)
    progs.verify_fn(PMAX, False, B)
    progs.chunk_extend_fn(PMAX, "accum")
    progs.chunk_rows_fn(4)
    progs.chunk_final_fn(PMAX, True, False)
    assert (PMAX, 2, True, B) in progs.round_fns
    assert (PMAX, False, B) in progs.verify_fns
    assert {("extend", PMAX, "accum"), ("extend_rows", 4),
            ("final", PMAX, True, False)} <= set(progs.chunk_fns)


# ----------------------------------------------- the tail, in one function


def _head_params(head=None):
    params = llama.init_params(CFG, jax.random.key(0), dtype=jnp.float32)
    if head == "int8":
        params["lm_head"] = quantize_tensor(params["lm_head"], bits=8)
    elif head == "int4":
        params["lm_head"] = quantize_tensor(params["lm_head"], bits=4)
    return params


def _tp_mesh(n=2):
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:n]), ("tp",))


@pytest.mark.parametrize("case,kind", [
    ("cpu", "scan"), ("switched_off", "materialised"),
    ("tpu_int8_head", "kernel"), ("tpu_raw_head", "kernel"),
    ("tpu_int4_head", "scan"), ("tp_mesh", "sharded"),
    ("tp_mesh_on_tpu", "sharded"), ("tp_unsplittable", "materialised")])
def test_resolve_tail_chooses_the_kind(monkeypatch, case, kind):
    """One function holds the whole choice: the operator's switch, the
    mesh, whether the vocabulary splits, whether the kernels take the
    head here. Only the unsplittable vocabulary is a downgrade."""
    cfg, mesh, head = CFG, None, None
    if case == "switched_off":
        monkeypatch.setenv("ENGINE_FUSED_SAMPLER", "0")
    if case.startswith("tpu") or case == "tp_mesh_on_tpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        head = {"tpu_int8_head": "int8", "tpu_int4_head": "int4"}.get(case)
    if case.startswith("tp_"):
        mesh = _tp_mesh()
    if case == "tp_unsplittable":
        cfg = dataclasses.replace(CFG, vocab_size=480)     # 15 words / 2
    tail = programs.resolve_tail(_head_params(head), cfg, mesh)
    assert tail.kind == kind
    assert tail.gathers_rows is (kind != "materialised")
    assert tail.kernel is (kind == "kernel")
    assert (tail.downgrade is not None) is (case == "tp_unsplittable")
    if tail.downgrade:
        assert tail.downgrade[:2] == ("fused_sampler", "materialized_tail")
    assert (tail.head_specs is not None) is (kind == "sharded")
    # what the loop asks of it
    assert tail.returns_resort(greedy=False) is tail.gathers_rows
    assert not tail.returns_resort(greedy=True)
    assert tail.first_from_hidden(greedy=True) is tail.kernel
    assert not tail.first_from_hidden(greedy=False)


def test_there_is_no_fifth_tail():
    with pytest.raises(ValueError, match="no such tail"):
        programs.Tail("fused", CFG)


@pytest.mark.parametrize("kind,slots,want", [
    ("scan", 4, {0: 1, 1: 1, 2: 4, 4: 4}), ("kernel", 1, {0: 1, 1: 1}),
    ("materialised", 4, {0: 4, 1: 4, 3: 4}), ("materialised", 1, {1: 1})])
def test_ba_for_sizes_a_round_to_occupancy_where_the_tail_gathers(
        built, kind, slots, want):
    progs, _, _ = built
    spec = dataclasses.replace(progs.spec, max_slots=slots,
                               tail=programs.Tail(kind, CFG))
    sized = programs.Programs(spec)
    assert {n: sized.ba_for(n) for n in want} == want


def test_an_engine_reads_its_tail_off_its_programs(monkeypatch):
    """``Engine`` holds no flag of its own: ``_fused_tail`` forwards,
    read-only, and the stats ask the object."""
    monkeypatch.setenv("ENGINE_FUSED_SAMPLER", "0")
    eng = Engine(_head_params(), CFG, ByteTokenizer(), EngineConfig(
        max_slots=2, max_input_length=64, max_output_length=16,
        prefill_buckets=(32,), max_prefill_bucket=32, page_size=16,
        dtype="float32", max_queue=4))
    try:
        assert eng.programs.tail.kind == "materialised"
        assert eng._fused_tail is False and eng.stats["tail_kernel"] == 0
        assert not eng.downgrades           # the switch is no downgrade
        with pytest.raises(AttributeError):
            eng._fused_tail = True
        assert eng._round_fns is eng.programs.round_fns
        assert eng._chunk_fns is eng.programs.chunk_fns
        assert eng._round_fn(eng._pmax, 2, True, 2) \
            is eng.programs.round_fn(eng._pmax, 2, True, 2)
        assert eng._chunk_extend_fn(eng._pmax, "accum") \
            is eng.programs.chunk_extend_fn(eng._pmax, "accum")
    finally:
        eng.stop()


# ------------------------------------------ one function cuts the chunks


@pytest.fixture(scope="module")
def chunker():
    """An engine with buckets (32, 64) over 16-token pages, never
    started: ``_next_chunk`` is host arithmetic."""
    eng = Engine(_head_params(), CFG, ByteTokenizer(), EngineConfig(
        max_slots=2, max_input_length=200, max_output_length=8,
        prefill_buckets=(32, 64), max_prefill_bucket=64, page_size=16,
        dtype="float32", max_queue=4))
    yield eng
    eng.stop()


def _req(total, pos=0, start_tok=None, seed=None):
    """A request ``pos`` tokens into its prompt; admitted (``pf`` set)
    where ``start_tok`` is given."""
    req = _Request(stream=None, prompt_ids=[5] * total, params=None,
                   detok=None, stop=None)
    req.pf_pos = pos
    if start_tok is not None:
        req.pf = {"start_tok": start_tok, "seed": seed}
    return req


@pytest.mark.parametrize("req,grant,want", [
    # a whole cold prompt inside one grant: the fused admission
    (_req(20), 64, (20, 32, True, "one-shot", "replace", False)),
    (_req(64), 64, (64, 64, True, "one-shot", "replace", False)),
    # a grant short of the prompt: whole pages, not final
    (_req(100), 40, (32, 32, False, "first", "replace", False)),
    (_req(100), 10, (0, 0, False, "first", "replace", False)),
    # a whole largest bucket with more to come may join a program of rows
    (_req(150), 64, (64, 64, False, "first", "replace", True)),
    (_req(150, 64, start_tok=0), 64,
     (64, 64, False, "middle", "accum", True)),
    (_req(150, 64, start_tok=0), 200,
     (64, 64, False, "middle", "accum", True)),
    # ... but not the prompt's last chunk, however large the grant
    (_req(150, 128, start_tok=0), 64,
     (22, 32, True, "final", "accum", False)),
    (_req(128, 64, start_tok=0), 64,
     (64, 64, True, "final", "accum", False)),
    # after a prefix-cache hit the first chunk is seeded, and joins none
    (_req(150, 32, start_tok=32, seed="mask"), 64,
     (64, 64, False, "first", "seed", False)),
    (_req(150, 96, start_tok=32, seed="mask"), 64,
     (54, 64, True, "final", "accum", False)),
    (_req(40, 32, start_tok=32, seed="mask"), 64,
     (8, 32, True, "final", "seed", False)),
], ids=["one_shot", "one_shot_whole_bucket", "short_grant_whole_pages",
        "grant_under_a_page", "whole_bucket_joins_rows", "middle_joins_rows",
        "grant_capped_at_the_bucket", "last_chunk", "last_whole_bucket",
        "seeded_first", "after_the_seeded", "seeded_and_final"])
def test_next_chunk_cuts_a_grant_once(chunker, req, grant, want):
    chunk = chunker._next_chunk(req, grant)
    assert tuple(chunk) == want
    # the span's arguments are its rendering, the rows' program its field
    assert chunker._chunk_shape(req, grant) == {
        "tokens": want[0], "padded": want[1], "mode": want[3], "rows": 1}


def test_no_chunk_joins_rows_under_capacity_routing():
    assert programs.row_ladder(CFG) == (4,)
    sparse = dataclasses.replace(
        CFG, num_experts=4, num_experts_per_tok=2, moe_impl="sparse")
    assert programs.row_ladder(sparse) == ()
