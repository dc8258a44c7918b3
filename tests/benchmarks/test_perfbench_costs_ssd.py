"""``harness/costs_ssd.py``: every term against a hand count at the
published widths of the configuration that uses it, and to the byte
against a stored tree at a small size; the reader over it on the
recorded scoped trace; the cell's entries in BENCHMARK.json."""

import json
import os

import pytest

from benchmarks.harness import costs, costs_ssd as cs

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
DECODE = "^jit_decode_round$"
CELL = "granite-4.0-h-micro.long-context-decode-32"
NEW = ("decode_ssd_ms", "decode_ssd_roofline", "prefill_ssd_ms_per_ktok",
       "prefill_ssd_roofline", "ssd_decode_step_roofline")

with open(os.path.join(REPO, "benchmarks", "configs",
                       "granite-4.0-h-micro.json")) as f:
    GRANITE = json.load(f)
M = GRANITE["model"]
SLOT = 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)


def test_the_layers_and_what_a_sequence_costs():
    assert cs.attention_layers(M) == [5, 15, 25, 35]
    assert cs.layer_counts(M) == (4, 36)
    # the attention layer at its period's end is another model
    assert cs.attention_layers({**M, "full_attention_place": -1}) == [
        9, 19, 29, 39]
    assert cs.conv_channels(M) == 4352 and cs.inner_width(M) == 4096
    assert cs.state_values(M) == 64 * 64 * 128
    # 2.10 MB of state and 26 KB of tail a layer: 76.4 MB a slot
    assert cs.slot_bytes(M) == SLOT == 76437504
    # K and V on four layers of forty: 8 KB a token
    assert cs.kv_bytes_per_token(M) == 4 * 2 * 8 * 64 * 2 == 8192


def test_the_matrices_as_stored():
    q, raw = cs.ssd_matrices(M)
    assert q == [(2048, 8448), (4096, 2048)]
    assert raw == [(2048, 64), (4352, 4), (4352, 1)]
    assert cs.attention_matrices(M) == [(2048, 2048), (2048, 512),
                                        (2048, 512), (2048, 2048)]
    assert cs.mlp_matrices(M) == [(2048, 8192), (2048, 8192), (8192, 2048)]
    ssd = sum(r * c + 4 * c for r, c in q) + 2 * sum(r * c for r, c in raw)
    attn = sum(r * c + 4 * c for r, c in cs.attention_matrices(M))
    mlp = sum(r * c + 4 * c for r, c in cs.mlp_matrices(M))
    # 26.0 MB, 10.5 MB and 50.4 MB: the issue's count
    assert ssd == pytest.approx(26.0e6, rel=0.01)
    assert attn == pytest.approx(10.5e6, rel=0.01)
    assert mlp == pytest.approx(50.4e6, rel=0.01)
    w = cs.layer_weights(M, "int8")
    assert w["bytes"] == 36 * ssd + 4 * attn + 40 * mlp
    assert w["bytes"] == pytest.approx(2.996e9, rel=0.002)
    # the tied head is the bf16 embedding, counted once: 3.41 GB
    assert cs.weight_bytes_resident(M, "int8") == w["bytes"] \
        + 2 * 100352 * 2048
    assert cs.weight_bytes_resident(M, "int8") == pytest.approx(3.407e9,
                                                                rel=0.002)


def test_the_recurrence_a_step_and_a_chunk():
    step = cs.state_step(M, 28)
    # read AND written: 4.25 MB a row a layer, 4.28 GB a step
    assert step["bytes"] == 28 * 2 * SLOT
    assert step["flops"] == 28 * 36 * 6 * 64 * 64 * 128
    least = costs.least_seconds(step, costs.peaks("TPU v5 lite"))
    assert least["bound"] == "bytes" and 5.0e-3 < least["seconds"] < 5.5e-3
    chunk = cs.state_chunks(M, tokens=2048, rows=4)
    token = (4352 + 4096) * 2 + 64 * 4
    assert chunk["bytes"] == 36 * 2048 * token + 4 * 2 * SLOT
    assert chunk["flops"] == 2048 * 36 * 6 * 64 * 64 * 128


def test_the_step_is_its_parts():
    rows, kv = 28.0, 28 * 4900.0
    step = cs.decode_step(M, "int8", rows, kv)
    w = cs.layer_weights(M, "int8")
    tail = costs.decode_stage(M, "int8", "tail", rows, kv)
    assert tail["bytes"] == 2 * 2048 * 100352          # the bf16 embedding
    assert step["kv_bytes"] == (kv + rows) * 8192
    assert step["state_bytes"] == rows * 2 * SLOT
    assert step["bytes"] == pytest.approx(
        w["bytes"] + tail["bytes"] + rows * 2 * 2048 + step["kv_bytes"]
        + step["state_bytes"])
    assert step["flops"] == pytest.approx(
        rows * w["flops"] + tail["flops"]
        + rows * 36 * 6 * 64 * 64 * 128 + 4 * 4 * 32 * 64 * kv)
    # about 8.8 GB a step: the state and the state-space layers'
    # projections ~59 % of it, 10.8 ms at 819 GB/s
    assert 8.6e9 < step["bytes"] < 9.0e9
    ssd = sum(r * c + 4 * c for r, c in cs.ssd_matrices(M)[0]) \
        + 2 * sum(r * c for r, c in cs.ssd_matrices(M)[1])
    assert 0.58 < (step["state_bytes"] + 36 * ssd) / step["bytes"] < 0.60
    least = costs.least_seconds(step, costs.peaks("TPU v5 lite"))
    assert least["bound"] == "bytes" and 10.4e-3 < least["seconds"] < 11.2e-3


def test_to_the_byte_against_a_stored_tree():
    """At a small size: the count is the bytes of the tree's leaves as
    ``ops/quant.py`` stores them, and of the pool's."""
    import jax
    import jax.numpy as jnp

    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.models.configs import LlamaConfig
    from generativeaiexamples_tpu.ops.quant import quantize_params
    m = dict(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=8,
        num_heads=4, num_kv_heads=2, head_dim=16, tie_word_embeddings=True,
        rope_layers=(0,), full_attention_interval=4, full_attention_place=1,
        linear_num_key_heads=1, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=8,
        linear_conv_kernel_dim=4, linear_decay="ssd")
    cfg = LlamaConfig(**m)
    tree = jax.eval_shape(lambda k: quantize_params(
        llama.init_params(cfg, k, jnp.bfloat16), "int8"), jax.random.key(0))

    def nbytes(*names):
        return sum(a.size * a.dtype.itemsize for n in names
                   for a in jax.tree.leaves(tree["layers"].get(n, ())))

    assert cs.layer_counts(m) == (2, 6)
    assert cs.layer_weights(m, "int8")["bytes"] == nbytes(
        "ssd_win", "ssd_wdt", "ssd_conv", "ssd_conv_b", "ssd_wout", "wq",
        "wk", "wv", "wo", "w_gate", "w_up", "w_down")
    embed = tree["embed"]
    assert "lm_head" not in tree
    assert cs.weight_bytes_resident(m, "int8") \
        - cs.layer_weights(m, "int8")["bytes"] \
        == embed.size * embed.dtype.itemsize
    pool = jax.eval_shape(lambda: llama.init_paged_kv_cache(
        cfg, 5, 16, jnp.bfloat16, slots=3))
    assert 3 * cs.slot_bytes(m) == sum(
        pool[n].size * pool[n].dtype.itemsize for n in ("s", "conv"))
    assert 5 * 16 * cs.kv_bytes_per_token(m) == sum(
        pool[n].size * pool[n].dtype.itemsize for n in ("k", "v"))


# --------------------------------------------------------------- reader


def test_ssd_roofline_on_the_recorded_trace(scoped_ctx, scoped_stream):
    """The fixture's program is a toy without such layers, so the
    arithmetic is held over the scopes it has; nothing where there is
    nothing to read — a program without the ``ssd_*`` scopes: the
    parent's."""
    from benchmarks.harness.loadgen import Row
    from benchmarks.harness.traffic import Request
    from benchmarks.readers import device_scope, device_trace, ssd_roofline
    ctx = scoped_ctx
    ctx.peaks = costs.peaks("TPU v5 lite")
    for other in ({"num_layers": 2}, {**M, "linear_decay": "head"},
                  {**M, "linear_decay": "channel"}):
        ctx.cell.config = {"model": other, "weight_quant": "int8",
                           "engine": {"prefill_buckets": [512]}}
        assert ssd_roofline.read(ctx, DECODE) is None
    ctx.cell.config = {"model": M, "weight_quant": "int8",
                       "engine": {"prefill_buckets": [512]}}
    assert ssd_roofline.read(ctx, DECODE) is None       # no rows stamped
    stream = scoped_stream()
    ctx.rows = [Row(Request(i, [3] * 100, 30, 1), 0.0, 0.0, stream=stream)
                for i in range(4)]
    rows, kv = ctx.mean_occupancy(sum)
    share = ssd_roofline.read(ctx, DECODE)
    ms = device_trace.read(ctx, "module_ms_per", modules=DECODE, per="step")
    least = costs.least_seconds(cs.decode_step(M, "int8", rows, kv),
                                ctx.peaks)
    assert share == pytest.approx(100 * least["seconds"] * 1e3 / ms)
    assert ctx.notes["ssd_roofline"]["step"]["bound"] == least["bound"]
    scope = "(^|/)attn(/|$)"        # a scope the toy has
    share = ssd_roofline.read(ctx, DECODE, scope=scope, of="step")
    ms = device_scope.read(ctx, scope, DECODE, per="step")
    least = costs.least_seconds(cs.state_step(M, rows), ctx.peaks)
    assert share == pytest.approx(100 * least["seconds"] * 1e3 / ms)
    for of in ("step", "chunks"):
        assert ssd_roofline.read(
            ctx, DECODE, scope="(^|/)(ssd_step|ssd_conv)(/|$)", of=of) is None
    assert ssd_roofline.read(ctx, "^no_such_module$") is None
    with pytest.raises(ValueError, match="does not know of"):
        ssd_roofline.read(ctx, DECODE, scope=scope, of="else")
    ctx.trace = None
    assert ssd_roofline.read(ctx, DECODE) is None


# ------------------------------------------------------------ spec entry


@pytest.mark.parametrize("name,reader", [
    ("ssd_decode_step_roofline", "ssd_roofline"),
    ("decode_ssd_roofline", "ssd_roofline"),
    ("prefill_ssd_roofline", "ssd_roofline"),
    ("decode_ssd_ms", "device_scope"),
    ("prefill_ssd_ms_per_ktok", "device_scope")])
def test_new_metric_files_name_their_reader(name, reader, spec):
    entry = next(m for m in spec.doc["per_layer"] if m["name"] == name)
    assert entry["workloads"][0] == CELL      # written for it; others may join
    assert entry["moves"] == "out_tok_per_s"
    assert spec.layer_metric(name)["reader"] == reader
    assert spec.layer_metric(name)["layer"] == entry["layer"]
    assert "ssd_" in json.dumps(spec.layer_metric(name)["args"]) \
        or name == "ssd_decode_step_roofline"


def in_order(names, among) -> bool:
    """``names`` appear in ``among`` in their order (a subsequence)."""
    rest = iter(among)
    return all(n in rest for n in names)


def test_the_cell_reports_what_its_neighbours_under_the_same_traffic_do(
        spec, general_at_pr_49):
    """Every general metric the three other cells under this traffic
    file report it reports too, and nothing of theirs that reads a
    mechanism it lacks (experts, a delta rule, a latent pool); the five
    of its own stand in the list in the order they were added in."""
    others = [f"{c}.long-context-decode-32" for c in (
        "kimi-k2-instruct", "qwen3-next-80b-a3b-instruct", "ling-3.0-flash")]
    per = spec.doc["per_layer"]
    by_name = {m["name"]: m for m in per}
    general = tuple(general_at_pr_49) + (
        "recurrent_state_bytes", "chunk_program_ms_per_ktok",
        "extend_program_ms", "tput.decode_program_ms_per_step")
    for name in general:
        assert CELL in by_name[name]["workloads"], name
    joined = [m["name"] for m in per if CELL in m.get("workloads", ())]
    assert set(general) | set(NEW) <= set(joined)
    assert in_order(NEW, joined) and in_order(NEW, [m["name"] for m in per])
    assert not [n for n in joined if "gdn" in n or "kda" in n or n.startswith(
        ("latent_", "recurrent_decode", "sparse_", "hyper_", "moe_",
         "route_"))]
    cell = spec.cell(CELL)
    assert all(cell.mix == spec.cell(o).mix for o in others)
    assert {m["name"] for m in cell.end_to_end} == {"out_tok_per_s",
                                                    "setup_s"}
    assert cell.workload["clients"] == 32 and cell.chips == 1
    assert (cell.workload["drain_limit_s"],
            cell.workload["trace_seconds"]) == (45.0, 2.5)


def test_the_configuration_file_states_the_published_model_whole():
    assert GRANITE["reference"] == "granitemoehybrid"
    assert GRANITE["chips_sharing_a_layer"] == 1
    assert GRANITE["weight_quant"] == "int8"
    assert GRANITE["reduced"] == [] and GRANITE["reduced_why"] == {}
    want = dict(hidden_size=2048, intermediate_size=8192, num_layers=40,
                num_heads=32, num_kv_heads=8, head_dim=64,
                vocab_size=100352, tie_word_embeddings=True,
                full_attention_interval=10, full_attention_place=5,
                linear_num_key_heads=1, linear_num_value_heads=64,
                linear_key_head_dim=128, linear_value_head_dim=64,
                linear_conv_kernel_dim=4, linear_decay="ssd",
                residual_multiplier=0.22, attention_multiplier=0.015625,
                logits_divisor=8.0, embed_scale=12.0, rope_layers=[0])
    assert {k: M[k] for k in want} == want
    # every key of the published config under its own name, unchanged
    pub = GRANITE["published"]
    assert pub["num_hidden_layers"] == 40 and len(pub["layer_types"]) == 40
    for key, value in pub.items():
        assert GRANITE[key] == value, key
    for said in ("in_proj_split", "gated_norm", "no_dt_clamp", "convolution",
                 "dense_block", "float32_state", "weights"):
        assert said in GRANITE["assumed"], said
    e = GRANITE["engine"]
    assert (e["max_slots"], e["max_input_length"], e["max_output_length"],
            e["max_prefill_bucket"], e["prefill_buckets"],
            e["kv_pool_tokens"], e["kv_quant"],
            e["sched_round_budget_tokens"]) == (
        32, 8192, 512, 512, [512], "auto", "", 32 * 512 + 8 * 32)
    assert set(e) == set(GRANITE["engine_why"])
    lc = GRANITE["logits_check"]
    assert (lc["prompts"], lc["prompt_pages"], lc["positions"],
            lc["decode_steps"]) == (4, 8, 64, 4)
    assert lc["max_share_over"] == 0.0      # a dense model: every position


def test_every_fault_of_the_faults_file_is_a_configuration_key():
    import dataclasses

    from generativeaiexamples_tpu.models.configs import LlamaConfig
    with open(os.path.join(REPO, "benchmarks", "faults",
                           "granite-4.0-h-micro.json")) as f:
        faults = json.load(f)
    cfg = LlamaConfig(**M)
    assert set(faults) == {
        "residual_multiplier_1", "score_scale_head_dim", "logits_divisor_1",
        "rotary_on", "attention_at_period_end", "embed_scale_1"}
    for name, fields in faults.items():
        broken = dataclasses.replace(cfg, **fields)     # builds
        assert broken != cfg, name
