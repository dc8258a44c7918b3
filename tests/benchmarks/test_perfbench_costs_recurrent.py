"""``harness/costs_recurrent.py``: every term against a hand count at the
published widths of the configuration that uses it, and to the byte
against a stored tree at a small size; the reader over it on the
recorded scoped trace."""

import json
import os
import types

import pytest

from benchmarks.harness import costs, costs_recurrent, trace
from benchmarks.harness.context import Context
from benchmarks.harness.spec import Spec

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
SCOPED = os.path.join(HERE, "fixtures", "tpu_v5e_spans_scopes.xplane.pb")
DECODE = "^jit_decode_round$"
CELL = "qwen3-next-80b-a3b-instruct.long-context-decode-32"

with open(os.path.join(REPO, "benchmarks", "configs",
                       "qwen3-next-80b-a3b-instruct.json")) as f:
    QWEN = json.load(f)
M = QWEN["model"]


def test_the_layers_and_what_a_sequence_costs():
    assert costs_recurrent.layer_counts(M) == (3, 9)
    assert costs_recurrent.conv_channels(M) == 8192
    assert costs_recurrent.state_values(M) == 32 * 128 * 128
    # 2.10 MB of state and 49 KB of tail a recurrent layer
    assert costs_recurrent.slot_bytes(M) == 9 * (32 * 128 * 128 * 4
                                                 + 3 * 8192 * 2) == 19316736
    # the state is float32 whatever the activations are kept in
    assert costs_recurrent.slot_bytes(M, act_bytes=4) == 9 * (
        32 * 128 * 128 * 4 + 3 * 8192 * 4)
    # K and V on three layers of twelve: 2 KB a token a layer
    assert costs_recurrent.kv_bytes_per_token(M) == 3 * 2 * 256 * 2 * 2 == 6144


def test_the_mixers_matrices_as_stored():
    q, raw = costs_recurrent.recurrent_matrices(M)
    assert q == [(2048, 12288), (4096, 2048)]
    assert raw == [(2048, 64), (8192, 4)]
    attn = costs_recurrent.attention_matrices(M)
    assert sum(r * c for r, c in attn) == (2048 * 8192 + 2 * 2048 * 512
                                           + 4096 * 2048)
    mix = costs_recurrent.mixer_weights(M, "int8")
    gdn = (2048 * 12288 + 4 * 12288 + 4096 * 2048 + 4 * 2048
           + 2 * (2048 * 64 + 8192 * 4))
    att = (2 * (2048 * 4096 + 4 * 4096) + 2 * (2048 * 512 + 4 * 512)
           + 4096 * 2048 + 4 * 2048)
    assert mix["bytes"] == 9 * gdn + 3 * att
    assert mix["flops"] == 9 * 2 * (2048 * 12288 + 4096 * 2048 + 2048 * 64
                                    + 8192 * 4) + 3 * 2 * (
        2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048)


def test_the_experts_stage_and_the_share():
    rows = 28
    one = 3 * 2048 * 512
    touched = 128 * (1 - (502 / 512) ** rows)
    assert touched == pytest.approx(54.3, abs=0.1)
    shared_b = one + 4 * (512 + 512 + 2048)
    exp = costs_recurrent.experts_stage(M, "int8", rows)
    assert exp["bytes"] == pytest.approx(12 * (
        touched * 2 * one + 2 * 2048 * 512 + 2 * 2048 + shared_b))
    # a row's ten assignments fall on held experts 128 times in 512
    assert exp["flops"] == pytest.approx(rows * 12 * (
        10 * 128 / 512 * 2 * one + 2 * 2048 * 512 + 2 * 2048 + 2 * one))


def test_the_recurrence_a_step_and_a_chunk():
    step = costs_recurrent.state_step(M, 28)
    # read AND written: 4.2 MB a row a layer, 1.08 GB a step
    assert step["bytes"] == 28 * 2 * 19316736
    assert step["flops"] == 28 * 9 * 6 * 32 * 128 * 128
    chunk = costs_recurrent.state_chunks(M, tokens=2048, rows=4)
    token = (8192 + 4096) * 2 + 2 * 32 * 4
    assert chunk["bytes"] == 9 * 2048 * token + 4 * 2 * 19316736
    assert chunk["flops"] == 2048 * 9 * 6 * 32 * 128 * 128
    # bytes bind: 0.19 ms a 512-token row against 0.07 of operations
    one = costs.least_seconds(costs_recurrent.state_chunks(M, 512, 1),
                              costs.peaks("TPU v5 lite"))
    assert one["bound"] == "bytes" and 0.18e-3 < one["seconds"] < 0.2e-3


def test_the_step_is_its_parts():
    rows, kv = 28.0, 28 * 4500.0
    step = costs_recurrent.decode_step(M, "int8", rows, kv)
    mix = costs_recurrent.mixer_weights(M, "int8")
    exp = costs_recurrent.experts_stage(M, "int8", rows)
    tail = costs.decode_stage(M, "int8", "tail", rows, kv)
    assert tail["bytes"] == 2048 * 151936 + 4 * 151936
    assert step["kv_bytes"] == (kv + rows) * 6144
    assert step["state_bytes"] == rows * 2 * 19316736
    assert step["bytes"] == pytest.approx(
        mix["bytes"] + exp["bytes"] + tail["bytes"] + rows * 2 * 2048
        + step["kv_bytes"] + step["state_bytes"])
    assert step["flops"] == pytest.approx(
        rows * mix["flops"] + exp["flops"] + tail["flops"]
        + rows * 9 * 6 * 32 * 128 * 128 + 3 * 4 * 16 * 256 * kv)
    # the issue's reckoning: about 6.7 GB a step, a sixth of it state,
    # 8 ms at the chip's 819 GB/s
    assert 6.5e9 < step["bytes"] < 6.9e9
    assert 0.15 < step["state_bytes"] / step["bytes"] < 0.18
    least = costs.least_seconds(step, costs.peaks("TPU v5 lite"))
    assert least["bound"] == "bytes" and 7.9e-3 < least["seconds"] < 8.5e-3


def test_to_the_byte_against_a_stored_tree():
    """At a small size: the count is the bytes of the tree's leaves as
    ``ops/quant.py`` stores them, and of the pool's."""
    import jax
    import jax.numpy as jnp

    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.models.configs import LlamaConfig
    from generativeaiexamples_tpu.ops.quant import quantize_params
    m = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
             moe_intermediate_size=64, num_layers=4, num_heads=4,
             num_kv_heads=2, head_dim=64, num_experts=8,
             num_experts_per_tok=2, num_shared_experts=1,
             shared_expert_gate=True, experts_held=4, moe_impl="dropless",
             qk_norm=True, attn_gate=True, partial_rotary_factor=0.25,
             full_attention_interval=2, linear_num_key_heads=2,
             linear_num_value_heads=4, linear_key_head_dim=16,
             linear_value_head_dim=16, linear_conv_kernel_dim=4)
    cfg = LlamaConfig(**m)
    tree = jax.eval_shape(lambda k: quantize_params(
        llama.init_params(cfg, k, jnp.bfloat16), "int8"), jax.random.key(0))

    def nbytes(*names):
        return sum(a.size * a.dtype.itemsize for n in names
                   for a in jax.tree.leaves(tree["layers"][n]))

    mix = costs_recurrent.mixer_weights(m, "int8")
    assert mix["bytes"] == nbytes("gdn_wqkvz", "gdn_wout", "gdn_wba",
                                  "gdn_conv", "wq", "wz", "wk", "wv", "wo")
    # every held expert touched (rows -> infinity): the whole stacks
    exp = costs_recurrent.experts_stage(m, "int8", 1e9)
    assert exp["bytes"] == pytest.approx(nbytes(
        "router", "w_gate", "w_up", "w_down", "ws_gate", "ws_up", "ws_down",
        "ws_gate_w"))
    pool = jax.eval_shape(lambda: llama.init_paged_kv_cache(
        cfg, 5, 16, jnp.bfloat16, slots=3))
    assert 3 * costs_recurrent.slot_bytes(m) == sum(
        pool[n].size * pool[n].dtype.itemsize for n in ("s", "conv"))
    assert 5 * 16 * costs_recurrent.kv_bytes_per_token(m) == sum(
        pool[n].size * pool[n].dtype.itemsize for n in ("k", "v"))


# --------------------------------------------------------------- reader


@pytest.fixture
def scoped_ctx(monkeypatch):
    monkeypatch.setattr(trace, "find_xplane", lambda _dir: SCOPED)
    rounds = [types.SimpleNamespace(decode_steps=3, prefill_tokens=200)] * 3
    cell = types.SimpleNamespace(name="some.cell", config={})
    return Context(cell=cell, rows=[], t0=0.0, t_end=1.0, drain_limit_s=1.0,
                   trace=trace.reduce(trace.load(SCOPED)),
                   trace_rounds=rounds)


def test_recurrent_roofline_on_the_recorded_trace(scoped_ctx):
    """The fixture's program is a toy without recurrent layers, so the
    arithmetic is held over the scopes it has: the least time of the
    count over the device time of the module or the scope; nothing where
    there is nothing to read — as on the parent commit, whose programs
    have no ``gdn_*`` scope."""
    from benchmarks.harness.loadgen import Row
    from benchmarks.harness.traffic import Request
    from benchmarks.readers import (device_scope, device_trace,
                                    recurrent_roofline)
    ctx = scoped_ctx
    ctx.peaks = costs.peaks("TPU v5 lite")
    ctx.cell.config = {"model": {"num_layers": 2}, "weight_quant": "int8",
                       "engine": {"prefill_buckets": [512]}}
    assert recurrent_roofline.read(ctx, DECODE) is None   # not this reader's
    ctx.cell.config = {"model": M, "weight_quant": "int8",
                       "engine": {"prefill_buckets": [512]}}
    assert recurrent_roofline.read(ctx, DECODE) is None   # no rows stamped
    ctx.trace_t0, ctx.trace_t1 = 0.0, 1.0
    stream = types.SimpleNamespace(first_token_time=-1.0, finish_time=2.0,
                                   token_ids=[5] * 30, finish_reason="length")
    ctx.rows = [Row(Request(i, [3] * 100, 30, 1), 0.0, 0.0, stream=stream)
                for i in range(4)]
    rows, kv = ctx.mean_occupancy(sum)
    share = recurrent_roofline.read(ctx, DECODE)
    ms = device_trace.read(ctx, "module_ms_per", modules=DECODE, per="step")
    least = costs.least_seconds(
        costs_recurrent.decode_step(M, "int8", rows, kv), ctx.peaks)
    assert share == pytest.approx(100 * least["seconds"] * 1e3 / ms)
    assert ctx.notes["recurrent_roofline"]["step"]["bound"] == least["bound"]
    scope = "(^|/)attn(/|$)"        # a scope the toy has
    share = recurrent_roofline.read(ctx, DECODE, scope=scope, of="step")
    ms = device_scope.read(ctx, scope, DECODE, per="step")
    least = costs.least_seconds(costs_recurrent.state_step(M, rows),
                                ctx.peaks)
    assert share == pytest.approx(100 * least["seconds"] * 1e3 / ms)
    # the program's own scopes: the toy has none, the parent has none
    for of in ("step", "chunks"):
        assert recurrent_roofline.read(
            ctx, DECODE, scope="(^|/)(gdn_step|gdn_conv)(/|$)", of=of) is None
    assert recurrent_roofline.read(ctx, "^no_such_module$") is None
    with pytest.raises(ValueError, match="does not know of"):
        recurrent_roofline.read(ctx, DECODE, scope=scope, of="else")
    ctx.trace = None
    assert recurrent_roofline.read(ctx, DECODE) is None


@pytest.mark.parametrize("name,reader", [
    ("recurrent_decode_step_roofline", "recurrent_roofline"),
    ("decode_gdn_roofline", "recurrent_roofline"),
    ("prefill_gdn_roofline", "recurrent_roofline"),
    ("decode_gdn_ms", "device_scope"),
    ("prefill_gdn_ms_per_ktok", "device_scope"),
    ("moe_held_assignments", "decode_round_fields"),
    ("recurrent_state_bytes", "engine_stats")])
def test_new_metric_files_name_their_reader(name, reader):
    spec = Spec()
    entry = next(m for m in spec.doc["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "out_tok_per_s"
    assert spec.layer_metric(name)["reader"] == reader
    assert spec.layer_metric(name)["layer"] == entry["layer"]


def test_the_cell_reports_what_its_neighbour_under_the_same_traffic_does():
    """Every un-pinned metric kimi-k2-instruct's cell reports, the new
    cell reports too: the two share a traffic file."""
    spec = Spec()
    kimi = "kimi-k2-instruct.long-context-decode-32"
    pinned = {"latent_decode_step_roofline", "latent_decode_attn_roofline",
              "latent_decode_mlp_roofline", "decode_latent_proj_ms",
              "moe_local_assignments"}
    for m in spec.doc["per_layer"]:
        if kimi in m.get("workloads", ()) and m["name"] not in pinned:
            assert CELL in m["workloads"], m["name"]
    cell = spec.cell(CELL)
    assert cell.mix == spec.cell(kimi).mix
    assert {m["name"] for m in cell.end_to_end} == {"out_tok_per_s",
                                                    "setup_s"}
