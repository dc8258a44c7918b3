"""``harness/costs_kda_latent.py``: every term against a hand count at
the published widths of the configuration that uses it, and to the byte
against a stored tree at a small size; the reader over it on the
recorded scoped trace; the cell's entries in BENCHMARK.json."""

import json
import math
import os
import types

import pytest

from benchmarks.harness import costs, costs_kda_latent as ck, trace
from benchmarks.harness.context import Context
from benchmarks.harness.spec import Spec

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
SCOPED = os.path.join(HERE, "fixtures", "tpu_v5e_spans_scopes.xplane.pb")
DECODE = "^jit_decode_round$"
CELL = "ling-3.0-flash.long-context-decode-32"
NEW = ("decode_kda_ms", "decode_kda_roofline", "prefill_kda_ms_per_ktok",
       "prefill_kda_roofline", "kda_latent_decode_step_roofline",
       "route_groups_held_pct")

with open(os.path.join(REPO, "benchmarks", "configs",
                       "ling-3.0-flash.json")) as f:
    LING = json.load(f)
M = LING["model"]
SLOT = 10 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)


def test_the_layers_and_what_a_sequence_costs():
    assert ck.layer_counts(M) == (2, 10)
    assert ck.layer_counts({**M, "num_layers": 8}) == (1, 7)
    assert ck.conv_channels(M) == 12288
    assert ck.state_values(M) == 32 * 128 * 128
    # 2.10 MB of state and 74 KB of tail a recurrent layer
    assert ck.slot_bytes(M) == SLOT == 21708800
    assert ck.slot_bytes(M, act_bytes=4) == 10 * (32 * 128 * 128 * 4
                                                  + 3 * 12288 * 4)
    # a latent row on two layers of twelve: 1152 B a token a layer
    assert ck.kv_bytes_per_token(M) == 2 * 576 * 2 == 2304


def test_the_mixers_matrices_as_stored():
    q, raw = ck.recurrent_matrices(M)
    assert q == [(2560, 12288), (2560, 4096), (2560, 4096), (4096, 2560)]
    assert raw == [(2560, 32), (12288, 4)]
    lq, lraw = ck.latent_matrices(M)
    assert lq == [(2560, 6144), (2560, 576), (512, 4096), (512, 4096),
                  (4096, 2560)]
    assert lraw == [(2560, 32)]
    assert ck.latent_matrices({**M, "attn_gate": False})[1] == []
    kda = sum(r * c + 4 * c for r, c in q) + 2 * (2560 * 32 + 12288 * 4)
    lat = sum(r * c + 4 * c for r, c in lq) + 2 * 2560 * 32
    # 63.2 MB and 31.9 MB a layer, the issue's count
    assert kda == pytest.approx(63.2e6, rel=0.01)
    assert lat == pytest.approx(31.9e6, rel=0.01)
    mix = ck.mixer_weights(M, "int8")
    assert mix["bytes"] == 10 * kda + 2 * lat
    assert mix["flops"] == 10 * 2 * sum(r * c for r, c in q + raw) \
        + 2 * 2 * sum(r * c for r, c in lq + lraw)


def test_the_group_limit():
    # one group of eight held, four kept: half the rows may send
    assert ck.groups_held_share(M) == pytest.approx(0.5)
    assert ck.groups_held_share({**M, "topk_group": 8}) == 1.0
    two = {**M, "experts_held": 128}
    assert ck.groups_held_share(two) == pytest.approx(
        1 - math.comb(6, 4) / math.comb(8, 4))
    assert ck.groups_held_share({**M, "experts_first": 32}) == \
        ck.groups_held_share(two)           # astride two groups
    # an expert's chance stays 8 / 512: 28 rows, 28 assignments, 23 reached
    assert ck.held_assignments(M, 28) == 28
    from benchmarks.harness import costs_latent
    assert costs_latent.expected_held_touched(M, 28) == pytest.approx(
        64 * (1 - (63 / 64) ** 28)) == pytest.approx(22.8, abs=0.1)


def test_the_recurrence_a_step_and_a_chunk():
    step = ck.state_step(M, 28)
    # read AND written: 4.3 MB a row a layer, 1.22 GB a step
    assert step["bytes"] == 28 * 2 * SLOT
    assert step["flops"] == 28 * 10 * 7 * 32 * 128 * 128
    chunk = ck.state_chunks(M, tokens=2048, rows=4)
    token = (12288 + 4096) * 2 + (4096 + 32) * 4
    assert chunk["bytes"] == 10 * 2048 * token + 4 * 2 * SLOT
    assert chunk["flops"] == 2048 * 10 * 7 * 32 * 128 * 128
    one = costs.least_seconds(ck.state_chunks(M, 512, 1),
                              costs.peaks("TPU v5 lite"))
    assert one["bound"] == "bytes" and 0.3e-3 < one["seconds"] < 0.4e-3


def test_the_step_is_its_parts():
    from benchmarks.harness import costs_latent
    rows, kv = 28.0, 28 * 4500.0
    step = ck.decode_step(M, "int8", rows, kv)
    mix = ck.mixer_weights(M, "int8")
    mlp = costs_latent.mlp_stage(M, "int8", rows)
    tail = costs.decode_stage(M, "int8", "tail", rows, kv)
    assert tail["bytes"] == 2560 * 157184 + 4 * 157184
    one = 3 * 2560 * 768
    dense = 3 * 2560 * 6144 + 4 * (6144 + 6144 + 2560)
    touched = 64 * (1 - (63 / 64) ** rows)
    assert mlp["bytes"] == pytest.approx(2 * dense + 10 * (
        touched * 2 * one + 2 * 2560 * 512 + 4 * 512
        + one + 4 * (768 + 768 + 2560)))
    assert step["kv_bytes"] == (kv + rows) * 2304
    assert step["state_bytes"] == rows * 2 * SLOT
    assert step["bytes"] == pytest.approx(
        mix["bytes"] + mlp["bytes"] + tail["bytes"] + rows * 2 * 2560
        + step["kv_bytes"] + step["state_bytes"])
    assert step["flops"] == pytest.approx(
        rows * mix["flops"] + mlp["flops"] + tail["flops"]
        + rows * 10 * 7 * 32 * 128 * 128
        + 2 * 2 * (576 + 512) * 32 * kv)
    # about 5.5 GB a step, a fifth of it state, 6.7 ms at 819 GB/s
    assert 5.2e9 < step["bytes"] < 5.8e9
    assert 0.2 < step["state_bytes"] / step["bytes"] < 0.24
    least = costs.least_seconds(step, costs.peaks("TPU v5 lite"))
    assert least["bound"] == "bytes" and 6.3e-3 < least["seconds"] < 7.1e-3


def test_to_the_byte_against_a_stored_tree():
    """At a small size: the count is the bytes of the tree's leaves as
    ``ops/quant.py`` stores them, and of the pool's."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import costs_latent
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.models.configs import LlamaConfig
    from generativeaiexamples_tpu.ops.quant import quantize_params
    m = dict(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_layers=8, num_dense_layers=2,
        num_heads=4, num_kv_heads=1, head_dim=24, num_experts=16,
        num_experts_per_tok=4, num_shared_experts=1, moe_impl="dropless",
        router_score_func="sigmoid", router_bias="selection", n_group=4,
        topk_group=2, kv_lora_rank=32, q_lora_rank=0, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_interleave=True,
        attn_gate="head", full_attention_interval=3,
        linear_num_key_heads=4, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=16,
        linear_conv_kernel_dim=4, linear_decay="channel",
        linear_decay_floor=-5.0, experts_held=8, experts_first=4)
    cfg = LlamaConfig(**m)
    tree = jax.eval_shape(lambda k: quantize_params(
        llama.init_params(cfg, k, jnp.bfloat16), "int8"), jax.random.key(0))

    def nbytes(stack, *names):
        return sum(a.size * a.dtype.itemsize for n in names
                   for a in jax.tree.leaves(tree[stack].get(n, ())))

    mixers = ("kda_wqkv", "kda_wf", "kda_wg", "kda_wout", "kda_wb",
              "kda_conv", "wq", "wkv_a", "wk_b", "wv_b", "wo", "wz_head")
    assert ck.layer_counts(m) == (2, 6)
    assert ck.mixer_weights(m, "int8")["bytes"] == nbytes(
        "layers", *mixers) + nbytes("dense_layers", *mixers)
    # every held expert touched (rows -> infinity): the whole stacks
    assert costs_latent.mlp_stage(m, "int8", 1e9)["bytes"] == pytest.approx(
        nbytes("dense_layers", "w_gate", "w_up", "w_down") + nbytes(
            "layers", "router", "router_bias", "w_gate", "w_up", "w_down",
            "ws_gate", "ws_up", "ws_down"))
    pool = jax.eval_shape(lambda: llama.init_paged_kv_cache(
        cfg, 5, 16, jnp.bfloat16, slots=3))
    assert 3 * ck.slot_bytes(m) == sum(
        pool[n].size * pool[n].dtype.itemsize for n in ("s", "conv"))
    assert 5 * 16 * ck.kv_bytes_per_token(m) == sum(
        pool[n].size * pool[n].dtype.itemsize for n in ("c", "r"))


# --------------------------------------------------------------- reader


@pytest.fixture
def scoped_ctx(monkeypatch):
    monkeypatch.setattr(trace, "find_xplane", lambda _dir: SCOPED)
    rounds = [types.SimpleNamespace(decode_steps=3, prefill_tokens=200)] * 3
    cell = types.SimpleNamespace(name="some.cell", config={})
    return Context(cell=cell, rows=[], t0=0.0, t_end=1.0, drain_limit_s=1.0,
                   trace=trace.reduce(trace.load(SCOPED)),
                   trace_rounds=rounds)


def test_kda_latent_roofline_on_the_recorded_trace(scoped_ctx):
    """The fixture's program is a toy without such layers, so the
    arithmetic is held over the scopes it has; nothing where there is
    nothing to read — a program without the ``kda_*`` scopes."""
    from benchmarks.harness.loadgen import Row
    from benchmarks.harness.traffic import Request
    from benchmarks.readers import (device_scope, device_trace,
                                    kda_latent_roofline)
    ctx = scoped_ctx
    ctx.peaks = costs.peaks("TPU v5 lite")
    for other in ({"num_layers": 2},
                  {**M, "linear_decay": "head"}, {**M, "kv_lora_rank": 0}):
        ctx.cell.config = {"model": other, "weight_quant": "int8",
                           "engine": {"prefill_buckets": [512]}}
        assert kda_latent_roofline.read(ctx, DECODE) is None
    ctx.cell.config = {"model": M, "weight_quant": "int8",
                       "engine": {"prefill_buckets": [512]}}
    assert kda_latent_roofline.read(ctx, DECODE) is None  # no rows stamped
    ctx.trace_t0, ctx.trace_t1 = 0.0, 1.0
    stream = types.SimpleNamespace(first_token_time=-1.0, finish_time=2.0,
                                   token_ids=[5] * 30, finish_reason="length")
    ctx.rows = [Row(Request(i, [3] * 100, 30, 1), 0.0, 0.0, stream=stream)
                for i in range(4)]
    rows, kv = ctx.mean_occupancy(sum)
    share = kda_latent_roofline.read(ctx, DECODE)
    ms = device_trace.read(ctx, "module_ms_per", modules=DECODE, per="step")
    least = costs.least_seconds(ck.decode_step(M, "int8", rows, kv),
                                ctx.peaks)
    assert share == pytest.approx(100 * least["seconds"] * 1e3 / ms)
    assert ctx.notes["kda_latent_roofline"]["step"]["bound"] == least["bound"]
    scope = "(^|/)attn(/|$)"        # a scope the toy has
    share = kda_latent_roofline.read(ctx, DECODE, scope=scope, of="step")
    ms = device_scope.read(ctx, scope, DECODE, per="step")
    least = costs.least_seconds(ck.state_step(M, rows), ctx.peaks)
    assert share == pytest.approx(100 * least["seconds"] * 1e3 / ms)
    for of in ("step", "chunks"):
        assert kda_latent_roofline.read(
            ctx, DECODE, scope="(^|/)(kda_step|kda_conv)(/|$)", of=of) is None
    assert kda_latent_roofline.read(ctx, "^no_such_module$") is None
    with pytest.raises(ValueError, match="does not know of"):
        kda_latent_roofline.read(ctx, DECODE, scope=scope, of="else")
    ctx.trace = None
    assert kda_latent_roofline.read(ctx, DECODE) is None


def test_the_counter_reads_nothing_from_a_program_without_it():
    from benchmarks.readers import decode_round_fields
    ctx = types.SimpleNamespace(rounds=[types.SimpleNamespace(
        decode_slots=4, decode_steps=8)])
    assert decode_round_fields.read(ctx, "route_groups_held_pct") is None
    ctx.rounds = [types.SimpleNamespace(decode_slots=4, decode_steps=8,
                                        route_groups_held_pct=x)
                  for x in (40.0, 60.0)]
    assert decode_round_fields.read(ctx, "route_groups_held_pct") == 50.0


# ------------------------------------------------------------ spec entry


@pytest.mark.parametrize("name,reader", [
    ("kda_latent_decode_step_roofline", "kda_latent_roofline"),
    ("decode_kda_roofline", "kda_latent_roofline"),
    ("prefill_kda_roofline", "kda_latent_roofline"),
    ("decode_kda_ms", "device_scope"),
    ("prefill_kda_ms_per_ktok", "device_scope"),
    ("route_groups_held_pct", "decode_round_fields")])
def test_new_metric_files_name_their_reader(name, reader):
    spec = Spec()
    entry = next(m for m in spec.doc["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "out_tok_per_s"
    assert spec.layer_metric(name)["reader"] == reader
    assert spec.layer_metric(name)["layer"] == entry["layer"]


def test_the_cell_reports_what_its_neighbours_under_the_same_traffic_do():
    """Every metric that BOTH other cells under this traffic file report
    the new cell reports too, and nothing of theirs that reads a
    mechanism it lacks; the six of its own are last in the list."""
    spec = Spec()
    kimi = "kimi-k2-instruct.long-context-decode-32"
    qwen = "qwen3-next-80b-a3b-instruct.long-context-decode-32"
    per = spec.doc["per_layer"]
    for m in per:
        ws = m.get("workloads", ())
        if kimi in ws and qwen in ws:
            assert CELL in ws, m["name"]
    joined = [m["name"] for m in per if CELL in m.get("workloads", ())]
    assert len(joined) == 19 + 6 and tuple(joined[-6:]) == NEW
    assert tuple(m["name"] for m in per[-6:]) == NEW
    assert not [n for n in joined if "gdn" in n or n.startswith(
        ("latent_decode_", "recurrent_", "sparse_", "hyper_"))]
    cell = spec.cell(CELL)
    assert cell.mix == spec.cell(kimi).mix == spec.cell(qwen).mix
    assert {m["name"] for m in cell.end_to_end} == {"out_tok_per_s",
                                                    "setup_s"}
    assert cell.workload["clients"] == 32 and cell.chips == 1
    assert len(spec.doc["workloads"]) == 10 and len(spec.doc["configs"]) == 9


def test_the_configuration_file_states_the_published_widths():
    assert LING["reference"] == "bailing_hybrid"
    assert LING["chips_sharing_a_layer"] == 8 and LING["weight_quant"] == \
        "int8"
    assert LING["reduced"] == ["num_hidden_layers", "num_experts"]
    want = dict(hidden_size=2560, num_heads=32, kv_lora_rank=512,
                qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                linear_conv_kernel_dim=4, moe_intermediate_size=768,
                intermediate_size=6144, num_experts=512,
                num_experts_per_tok=8, n_group=8, topk_group=4,
                vocab_size=157184, linear_key_head_dim=128,
                linear_value_head_dim=128, linear_num_value_heads=32,
                experts_held=64, num_layers=12, num_dense_layers=2,
                full_attention_interval=6)
    assert {k: M[k] for k in want} == want
    # every number of the published config under its own key, but the two
    # that were reduced
    pub = LING["published"]
    for key, value in pub.items():
        if key not in ("num_hidden_layers",):
            assert LING[key] == value or key == "num_experts", key
    assert (LING["num_experts"], pub["num_experts"]) == (64, 512)
    assert LING["num_hidden_layers"] == 12
    e = LING["engine"]
    assert (e["max_slots"], e["max_input_length"], e["max_output_length"],
            e["max_prefill_bucket"], e["prefill_buckets"],
            e["kv_pool_tokens"], e["sched_round_budget_tokens"]) == (
        32, 8192, 512, 512, [512], "auto", 32 * 512 + 8 * 32)
    lc = LING["logits_check"]
    assert (lc["prompts"], lc["prompt_pages"], lc["positions"],
            lc["decode_steps"]) == (4, 8, 64, 4)


def test_every_fault_of_the_faults_file_is_a_configuration_key():
    import dataclasses

    from generativeaiexamples_tpu.models.configs import LlamaConfig
    with open(os.path.join(REPO, "benchmarks", "faults",
                           "ling-3.0-flash.json")) as f:
        faults = json.load(f)
    cfg = LlamaConfig(**M)
    assert set(faults) == {"no_group_limit", "share_shifted_to_group_1",
                           "no_head_gate", "rope_pairs_as_halves",
                           "no_selection_bias"}
    for name, fields in faults.items():
        broken = dataclasses.replace(cfg, **fields)     # builds
        assert broken != cfg, name
