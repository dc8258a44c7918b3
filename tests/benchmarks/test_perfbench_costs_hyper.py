"""``harness/costs_hyper.py``: every term against a hand count at the
published widths of the configuration that uses it; the reader over it
on the recorded scoped trace — the whole decode step, and a scope of the
chunk programs against the tokens of the executions the trace HOLDS,
read from the program shapes the trace keeps —; and the new cell's data
files as the issue names them."""

import json
import os
import types

import pytest

from benchmarks.harness import costs, costs_hyper, costs_latent, trace
from benchmarks.harness.context import Context
from benchmarks.harness.spec import Spec

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
SCOPED = os.path.join(HERE, "fixtures", "tpu_v5e_spans_scopes.xplane.pb")
DECODE = "^jit_decode_round$"
CELL = "xing4.0-29b-a4b.rag-prefill-16"

with open(os.path.join(REPO, "benchmarks", "configs",
                       "xing4.0-29b-a4b.json")) as f:
    XING = json.load(f)
M = XING["model"]
L, n, C = M["num_layers"], 4, 3584


def test_a_layers_values_operations_and_phi():
    assert (L, costs_hyper.streams(M), M["hidden_size"]) == (8, n, C)
    # the carry in and out: what must cross HBM
    assert costs_hyper.layer_values(M) == 2 * n * C == 28672
    # (the issue's count, each side of a sublayer reading the stream from
    # HBM, is (n C + C) + (n C + C + n C) = 50176 a SUBLAYER: no bound)
    # the projection on phi's 24 columns, H_res . X, the two other mixes
    assert costs_hyper.sublayer_flops(M) == 2 * n * C * 24 \
        + 2 * 16 * C + 4 * n * C == 860160
    assert costs_hyper.phi_bytes(M) == 14336 * 24 * 2
    plain = dict(M, hc_mult=0)
    assert costs_hyper.layer_values(plain) == 0
    assert costs_hyper.hc_stage(plain, 2048.0) == {"bytes": 0.0,
                                                   "flops": 0.0}


def test_the_residual_path_of_a_chunk_program():
    # four prompts' 512 tokens, one program: 8 layers' carry in and out,
    # the first stream written and the last read, phi once a sublayer
    tokens = 2048.0
    hc = costs_hyper.hc_stage(M, tokens)
    values = tokens * (8 * 28672 + 2 * n * C)
    assert hc["bytes"] == values * 2 + 16 * 14336 * 24 * 2
    assert hc["flops"] == tokens * (16 * 860160 + n * C)
    # 2 x 59 MB a layer: 0.14 ms at 819 GB/s, where the issue's six
    # passes a layer are 0.43
    a_layer = hc["bytes"] / L
    assert 2 * 58.7e6 < a_layer < 2.5 * 58.7e6
    least = costs.least_seconds(hc, costs.peaks("TPU v5 lite"))
    assert least["bound"] == "bytes"
    assert 0.14e-3 * L < least["seconds"] < 0.19e-3 * L
    # four programs of one prompt read phi four times, nothing else more
    four = costs_hyper.hc_stage(M, tokens, programs=4.0)
    assert four["bytes"] - hc["bytes"] == 3 * 16 * 14336 * 24 * 2
    assert four["flops"] == hc["flops"]


def test_the_decode_step_is_the_latent_step_plus_the_streams():
    rows, kv = 14.0, 14 * 1600.0
    base = costs_latent.decode_step(M, "int8", rows, kv)
    step = costs_hyper.decode_step(M, "int8", rows, kv)
    hc = costs_hyper.hc_stage(M, rows)
    assert step["bytes"] == base["bytes"] + hc["bytes"]
    assert step["flops"] == base["flops"] + hc["flops"]
    assert step["kv_bytes"] == base["kv_bytes"]
    assert step["hc_bytes"] == hc["bytes"]
    # every expert is held: 14 rows of 4 reach 64 (1 - (15/16)^14) = 38.1
    assert costs_latent.held_experts(M) == 64
    assert costs_latent.expected_held_touched(M, rows) == pytest.approx(
        64 * (1 - (15 / 16) ** 14))
    # the streams are a few thousandths of a step's bytes: latency, not
    # bytes, is what they cost a decode step
    assert 0.001 < hc["bytes"] / step["bytes"] < 0.01
    assert 4.5e9 < step["weight_bytes"] < 8e9


# --------------------------------------------------------------- reader


@pytest.fixture
def scoped_ctx(monkeypatch):
    monkeypatch.setattr(trace, "find_xplane", lambda _dir: SCOPED)
    rounds = [types.SimpleNamespace(decode_steps=3, prefill_tokens=200)] * 3
    cell = types.SimpleNamespace(name="some.cell", config={})
    return Context(cell=cell, rows=[], t0=0.0, t_end=1.0, drain_limit_s=1.0,
                   trace=trace.reduce(trace.load(SCOPED)),
                   trace_rounds=rounds, peaks=costs.peaks("TPU v5 lite"))


def test_the_trace_keeps_every_programs_parameter_shapes():
    from benchmarks.readers import hyper_roofline
    params = hyper_roofline.program_params(SCOPED)
    by_name = {trace.module_name(k): v for k, v in params.items()}
    assert set(by_name) == {"jit_extend", "jit_decode_round"}
    bf16 = 16                            # xla_data.proto PrimitiveType
    assert by_name["jit_extend"][:2] == [(bf16, (256, 1024)),
                                         (bf16, (1024, 1024))]
    assert (bf16, (1024, 4096)) in by_name["jit_decode_round"]
    # the fixture's ``extend`` takes no int32 (rows, bucket): nothing to
    # count, and said so
    assert hyper_roofline.program_tokens(SCOPED, "^jit_extend$",
                                         (512,)) is None
    assert hyper_roofline.program_tokens(SCOPED, "^no_such$", (512,)) is None


def test_tokens_are_those_of_the_executions_the_trace_holds(monkeypatch):
    """Each executed program counts rows x bucket of its own shape: one
    of four prompts' rows, two of one."""
    from benchmarks.readers import hyper_roofline
    S32 = hyper_roofline.S32
    state = [(S32, (16, 28)), (S32, (16, 7)), (16, (131072, 3584))]
    shapes = {"jit_extend(1)": state + [(S32, (4, 512)), (S32, (4, 28))],
              "jit_extend(2)": state + [(S32, (1, 512)), (S32, (1, 8))],
              "jit_final(3)": state + [(S32, (1, 512))],
              "jit_decode_round(4)": state}
    monkeypatch.setattr(hyper_roofline, "program_params", lambda _p: shapes)
    ev = lambda name: types.SimpleNamespace(name=name)  # noqa: E731
    lines = [types.SimpleNamespace(name=trace.MODULES_LINE, events=[
        ev("jit_extend(1)"), ev("jit_decode_round(4)"), ev("jit_extend(2)"),
        ev("jit_extend(2)"), ev("jit_final(3)")])]
    profile = types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/device:TPU:0", lines=lines)])
    import jax.profiler
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda _p: profile))
    held = hyper_roofline.program_tokens("x", "^jit_(extend|final)$", (512,))
    assert held == {"tokens": 2048 + 3 * 512, "programs": 4}


def test_hyper_roofline_on_the_recorded_trace(scoped_ctx, monkeypatch):
    """The fixture's program is a toy, so the arithmetic is held: the
    least time of the count over the device time of the module or the
    scope; nothing where there is nothing to read."""
    from benchmarks.harness.loadgen import Row
    from benchmarks.harness.traffic import Request
    from benchmarks.readers import (device_scope, device_trace,
                                    hyper_roofline)
    ctx = scoped_ctx
    # a configuration on the plain residual path: not this reader's
    ctx.cell.config = {"model": dict(M, hc_mult=0), "weight_quant": "int8",
                       "engine": XING["engine"]}
    assert hyper_roofline.read(ctx, DECODE) is None
    ctx.cell.config = {"model": M, "weight_quant": "int8",
                       "engine": XING["engine"]}
    assert hyper_roofline.read(ctx, DECODE) is None            # no rows
    ctx.trace_t0, ctx.trace_t1 = 0.0, 1.0
    stream = types.SimpleNamespace(first_token_time=-1.0, finish_time=2.0,
                                   token_ids=[5] * 30, finish_reason="length")
    ctx.rows = [Row(Request(i, [3] * p, 30, 1), 0.0, 0.0, stream=stream)
                for i, p in enumerate((900, 1500, 2800))]
    rows, kv = ctx.mean_occupancy(sum)
    share = hyper_roofline.read(ctx, DECODE)
    ms = device_trace.read(ctx, "module_ms_per", modules=DECODE, per="step")
    least = costs.least_seconds(
        costs_hyper.decode_step(M, "int8", rows, kv), ctx.peaks)
    assert share == pytest.approx(100 * least["seconds"] * 1e3 / ms)
    note = ctx.notes["hyper_roofline"]["step"]
    assert note["bound"] == least["bound"] and note["mean_rows"] == rows
    # a scope of the chunk programs: the fixture's ``extend`` has the
    # scope ``attn`` and no hc scope, and no (rows, bucket) parameter
    args = Spec().layer_metric("prefill_hc_roofline")["args"]
    assert hyper_roofline.read(ctx, **args) is None
    monkeypatch.setattr(hyper_roofline, "program_tokens",
                        lambda *_: {"tokens": 2048, "programs": 1})
    assert hyper_roofline.read(ctx, **args) is None     # no hc scope there
    share = hyper_roofline.read(ctx, "^jit_extend$", scope="(^|/)attn(/|$)")
    red = device_scope.reduce_scopes(SCOPED, "^jit_extend$")
    attn_s = sum(s for p, s in red["by_path"].items() if "attn" in p)
    least = costs.least_seconds(costs_hyper.hc_stage(M, 2048, 1), ctx.peaks)
    assert share == pytest.approx(100 * least["seconds"] / attn_s)
    assert ctx.notes["hyper_roofline"]["(^|/)attn(/|$)"]["tokens"] == 2048
    assert hyper_roofline.read(ctx, "^no_such_module$") is None
    ctx.trace = None
    assert hyper_roofline.read(ctx, DECODE) is None


@pytest.mark.parametrize("name,reader", [
    ("hyper_decode_step_roofline", "hyper_roofline"),
    ("prefill_hc_roofline", "hyper_roofline"),
    ("prefill_hc_ms_per_ktok", "device_scope"),
    ("decode_hc_ms", "device_scope"),
    ("hc_row_defect", "decode_round_fields")])
def test_new_metric_files_name_their_reader(name, reader):
    spec = Spec()
    entry = next(m for m in spec.doc["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "out_tok_per_s"
    metric = spec.layer_metric(name)
    assert metric["reader"] == reader
    if reader != "decode_round_fields":
        assert "hc_pre|hc_post" in metric["args"].get("scope", "hc_pre|hc_post")


def test_the_row_defect_is_read_from_the_round_records():
    from benchmarks.readers import decode_round_fields
    rec = types.SimpleNamespace
    ctx = types.SimpleNamespace(rounds=[
        rec(decode_slots=0, decode_steps=0, hc_row_defect=0.0),
        rec(decode_slots=13, decode_steps=8, hc_row_defect=1e-6),
        rec(decode_slots=12, decode_steps=8, hc_row_defect=3e-6)])
    args = Spec().layer_metric("hc_row_defect")["args"]
    assert decode_round_fields.read(ctx, **args) == pytest.approx(2e-6)
    # the parent's records lack the field: nothing, and no error
    old = types.SimpleNamespace(rounds=[rec(decode_slots=3, decode_steps=8)])
    assert decode_round_fields.read(old, **args) is None


def test_the_scopes_are_the_programs():
    from generativeaiexamples_tpu.models import llama
    for name in ("prefill_hc_ms_per_ktok", "decode_hc_ms",
                 "prefill_hc_roofline"):
        scope = Spec().layer_metric(name)["args"]["scope"]
        for s in llama.HC_SCOPES:
            assert s in scope


def test_the_cell_and_its_mix_as_the_issue_names_them():
    spec = Spec()
    cell = spec.cell(CELL)
    assert cell.workload["clients"] == 16 and cell.workload["chips"] == 1
    assert cell.workload["drain_limit_s"] == 45.0
    assert cell.workload["trace_seconds"] == 2.5
    assert set(cell.workload["reports"]) == {"out_tok_per_s", "setup_s"}
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "rag-prefill.json")) as f:
        old = json.load(f)
    mix = cell.mix
    assert mix["set_size"] in (512, 1024) and old["set_size"] == 128
    for key in ("loop", "prompt_tokens", "output_tokens", "sampling",
                "prefix_sharing"):
        assert mix[key] == old[key], key
    assert XING["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                               "num_nextn_predict_layers"]
    assert "experts_held" not in M and M["num_experts"] == 64
    assert XING["chips_sharing_a_layer"] == 1
    assert XING["reference"] == "xing4_0" and XING["weight_quant"] == "int8"
    e = XING["engine"]
    assert (e["max_slots"], e["max_input_length"], e["max_output_length"],
            e["max_prefill_bucket"], e["prefill_buckets"],
            e["kv_pool_tokens"], e["sched_round_budget_tokens"]) == (
        16, 3072, 512, 512, [512], "auto", 16 * 512 + 8 * 16)
    lc = XING["logits_check"]
    assert (lc["prompts"], lc["prompt_pages"], lc["positions"],
            lc["decode_steps"]) == (4, 12, 64, 4)
    joined = [m["name"] for m in spec.doc["per_layer"]
              if CELL in m.get("workloads", ())]
    assert "tput.decode_step_roofline" not in joined
    assert not [m for m in joined if m.startswith("latent_decode_")
                or m in ("decode_latent_proj_ms", "moe_local_assignments")]
    glm = [m["name"] for m in spec.doc["per_layer"]
           if "glm-5.2.long-context-mixed-16" in m.get("workloads", ())
           and not m["name"].startswith(("sparse_", "decode_index",
                                         "prefill_index"))]
    assert set(glm) <= set(joined)


def test_every_fault_of_the_faults_file_is_a_configuration_key():
    from generativeaiexamples_tpu.models.configs import LlamaConfig
    import dataclasses
    with open(os.path.join(REPO, "benchmarks", "faults",
                           "xing4.0-29b-a4b.json")) as f:
        faults = json.load(f)
    cfg = LlamaConfig(**M)
    assert {"sinkhorn_1_iteration", "res_clamp_at_1", "hc_eps_1e-1"} \
        <= set(faults)
    for name, fields in faults.items():
        assert dataclasses.replace(cfg, **fields) != cfg, name
