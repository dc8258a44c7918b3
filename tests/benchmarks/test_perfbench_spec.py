"""Every data file of the benchmark loads and agrees with
BENCHMARK.json; the contract's own limits hold; the rehearsal's files
show that a configuration, a mix, a cell and a layer metric are each
added by new files alone."""

import importlib
import json
import os
import re

import pytest

from benchmarks.harness import spec as spec_mod

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmarks")
REHEARSAL = os.path.join(HERE, "rehearsal")


def names(kind, root=BENCH):
    return sorted(f[:-5] for f in os.listdir(os.path.join(root, kind))
                  if f.endswith(".json"))


def load(kind, name, root=BENCH):
    with open(os.path.join(root, kind, name + ".json")) as f:
        return json.load(f)


SPEC = spec_mod.Spec()
DOC = SPEC.doc
E2E = {m["name"]: m for m in DOC["end_to_end"]}
CELLS = [w["name"] for w in DOC["workloads"]]


def test_benchmark_json_has_exactly_the_contracts_keys():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10
    assert 1 <= DOC["run_seconds"] <= 51 and isinstance(
        DOC["run_seconds"], int)
    assert DOC["command"][:2] == ["python3", "benchmarks/run.py"]
    assert DOC["paths"] == ["benchmarks", "tests/benchmarks"]
    # a full check with all 24 cells fits the driver's time
    runs = 2 + 14 * 24
    assert runs * (DOC["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("entry", DOC["configs"], ids=lambda e: e["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert spec_mod.NAME_RE.match(entry["name"])
    assert entry["file"].startswith("benchmarks/configs/")
    cfg = spec_mod.load_json(os.path.join(REPO, entry["file"]))
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"] and len(entry["reduced"]) <= 16
    assert 1 <= len(entry["source"]) <= 200 and 1 <= len(entry["why"]) <= 200
    for key in entry["reduced"]:
        assert spec_mod.NAME_RE.match(key)
        # never a width
        assert not re.search(r"(_dim$|_rank$|_size$|experts_per_tok)", key)
    assert any(w["config"] == entry["name"] for w in DOC["workloads"])
    # the sizes as run build a LlamaConfig and agree with the source's
    from generativeaiexamples_tpu.models.configs import LlamaConfig
    m = LlamaConfig(**cfg["model"])
    pub = cfg["published"]
    assert m.hidden_size == pub["hidden_size"]
    assert m.num_heads == pub["num_attention_heads"]
    assert m.vocab_size == pub["vocab_size"]
    assert m.intermediate_size == pub.get("intermediate_size",
                                          pub.get("ffn_hidden_size"))
    assert m.num_layers == pub.get("num_hidden_layers",
                                   pub.get("num_layers"))
    for field in ("engine", "engine_why", "logits_check", "deployment",
                  "assumed", "weight_quant", "encoder_resident", "chips",
                  "reference"):
        assert field in cfg
    assert set(cfg["engine"]) <= set(cfg["engine_why"])
    # the plain reference is a file of its own, found by name
    assert os.path.exists(os.path.join(
        REPO, "benchmarks", "references", cfg["reference"] + ".py"))
    lc = cfg["logits_check"]
    assert set(lc) == {"prompts", "prompt_pages", "positions", "decode_steps",
                       "tolerance", "median_tolerance", "max_share_over",
                       "min_token_agreement", "why"}
    assert 0 < lc["median_tolerance"] <= lc["tolerance"] <= 0.05
    assert 0 <= lc["max_share_over"] <= 0.3
    if not cfg["model"].get("num_experts"):
        # dense: every position held, and most engine tokens
        assert lc["max_share_over"] == 0 and lc["min_token_agreement"] >= 0.8
    else:
        assert 0.25 <= lc["min_token_agreement"] <= 1 and lc["prompts"] >= 4
    assert cfg["platform"] == "tpu"


def test_the_two_published_configurations_keep_their_widths():
    from generativeaiexamples_tpu.models.configs import (MIXTRAL_8X7B,
                                                         NEMOTRON_8B,
                                                         LlamaConfig)
    import dataclasses
    nemo = LlamaConfig(**load("configs", "nemotron-8b-chat")["model"])
    assert nemo == NEMOTRON_8B                      # nothing cut
    mix = LlamaConfig(**load("configs", "mixtral-8x7b-instruct")["model"])
    assert mix == dataclasses.replace(MIXTRAL_8X7B, num_layers=mix.num_layers)
    assert mix.num_layers < MIXTRAL_8X7B.num_layers  # depth, listed
    assert mix.num_experts == 8 and mix.num_experts_per_tok == 2


@pytest.mark.parametrize("entry", DOC["workloads"], ids=lambda e: e["name"])
def test_cell_entry(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    for k in ("name", "config", "traffic"):
        assert spec_mod.NAME_RE.match(entry[k]), entry[k]
    assert entry["chips"] in (1, 4) and 1 <= len(entry["why"]) <= 200
    cell = SPEC.cell(entry["name"])
    assert cell.workload["chips"] == entry["chips"]
    reported = {m["name"] for m in cell.end_to_end}
    assert reported == set(cell.workload["reports"])
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    if cell.mix["loop"] == "open":
        assert cell.workload["rate_rps"] > 0
    else:
        assert 1 <= cell.workload["clients"] <= cell.config["engine"][
            "max_slots"]
    # the mix's longest request fits the engine's limits
    e = cell.config["engine"]
    assert cell.mix["prompt_tokens"]["max"] <= e["max_input_length"]
    assert cell.mix["output_tokens"]["max"] <= e["max_output_length"]


def test_cells_are_unique_and_few_take_four_chips():
    pairs = [(w["config"], w["traffic"]) for w in DOC["workloads"]]
    assert len(set(pairs)) == len(pairs) and len(set(CELLS)) == len(CELLS)
    four = sum(1 for w in DOC["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 4) and four == 0


@pytest.mark.parametrize("m", DOC["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(m):
    assert set(m) <= {"name", "unit", "better", "bound", "source",
                      "workloads"}
    assert spec_mod.NAME_RE.match(m["name"]) and spec_mod.UNIT_RE.match(
        m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.1
    for c in m.get("workloads", CELLS):
        assert c in CELLS


def test_setup_s_is_everywhere():
    assert "workloads" not in E2E["setup_s"] and E2E["setup_s"]["bound"] <= 0.1


@pytest.mark.parametrize("m", DOC["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_entry_and_file_agree(m):
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert spec_mod.NAME_RE.match(m["name"]) and spec_mod.UNIT_RE.match(
        m["unit"])
    assert m["source"] in spec_mod.SOURCES
    assert m["better"] in ("lower", "higher")
    assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    # the file says how the quantity is read; which cells report it and
    # what it moves there are BENCHMARK.json's
    f = SPEC.layer_metric(m["name"])
    assert set(f) - {"note"} == {"unit", "better", "source", "layer",
                                 "reader", "args"}
    for k in ("unit", "better", "source", "layer"):
        assert f[k] == m[k], k
    # moves names an end-to-end metric that each of its cells reports
    moved = E2E[m["moves"]]
    for c in m.get("workloads", CELLS):
        assert c in moved.get("workloads", CELLS), (m["name"], c)
    reader = importlib.import_module(f"benchmarks.readers.{f['reader']}")
    assert callable(reader.read)
    if m["name"].endswith("_roofline"):
        assert m["unit"] == "%"


def test_layer_names_are_those_of_perf_md():
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in DOC["per_layer"]}:
        assert layer in perf, layer


@pytest.mark.parametrize("kind", ["configs", "traffic", "workloads",
                                  "layer_metrics"])
def test_every_data_file_is_used_and_named_well(kind):
    used = {"configs": {c["name"] for c in DOC["configs"]},
            "traffic": {w["traffic"] for w in DOC["workloads"]},
            "workloads": set(CELLS),
            "layer_metrics": {SPEC.layer_metric_file(m["name"])
                              for m in DOC["per_layer"]}}[kind]
    have = set(names(kind))
    assert have == used
    for n in have:
        assert spec_mod.NAME_RE.match(n)
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", f"{kind}/{n}.json")


@pytest.mark.parametrize("name", names("traffic"))
def test_traffic_file(name):
    mix = load("traffic", name)
    assert mix["loop"] in ("open", "closed") and 1 <= len(mix["why"]) <= 200
    assert {"prompt_tokens", "output_tokens", "sampling"} <= set(mix)
    if mix["loop"] == "closed":
        assert mix["set_size"] >= 16


def test_unknown_cell_and_missing_file_raise(tmp_path):
    with pytest.raises(spec_mod.SpecError):
        SPEC.cell("no-such.cell")
    with pytest.raises(spec_mod.SpecError):
        spec_mod.load_json(str(tmp_path / "absent.json"))


# ---------------------------------------------- added by new files alone


def test_rehearsal_adds_config_mix_cell_and_metric_by_files_alone():
    """The rehearsal directory holds its own BENCHMARK.json and data
    files; the harness code is the same and nothing in it names them."""
    rs = spec_mod.Spec(os.path.join(REHEARSAL, "BENCHMARK.json"), REHEARSAL)
    assert set(rs.cell_names()) == {"tiny-dense.tiny-open",
                                    "tiny-dense.tiny-closed"}
    cell = rs.cell("tiny-dense.tiny-closed")
    assert cell.config["platform"] == "any"
    new = [m for m in cell.per_layer if m["name"] == "decode_steps_per_round"]
    assert new and new[0]["reader"] == "round_records"
    assert "decode_steps_per_round" not in names("layer_metrics")
    src = ""
    for root, _, files in os.walk(BENCH):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(root, fn)) as f:
                    src += f.read()
    for word in ("tiny-dense", "tiny-open", "decode_steps_per_round",
                 "nemotron-8b-chat.", "chat-steady", "decode-batch",
                 "rag-prefill"):
        assert word not in src, f"harness code names {word!r}"


def test_one_quantity_read_in_two_groups_of_cells_is_one_file():
    """``<group>.<quantity>`` with no file of its own is read by the
    quantity's file: one reader and arguments, and an entry in
    BENCHMARK.json for each end-to-end metric it moves."""
    grouped = [m for m in DOC["per_layer"] if "." in m["name"]]
    assert grouped
    by_name = {m["name"]: m for m in DOC["per_layer"]}
    for m in grouped:
        quantity = m["name"].split(".", 1)[1]
        assert SPEC.layer_metric_file(m["name"]) == quantity
        assert SPEC.layer_metric_file(quantity) == quantity
        twin = by_name[quantity]
        assert twin["moves"] != m["moves"]
        assert not set(twin["workloads"]) & set(m["workloads"])
        for cell in m["workloads"]:
            got = {x["name"]: x for x in SPEC.cell(cell).per_layer}[m["name"]]
            assert got["moves"] == m["moves"] and got["reader"]


@pytest.mark.parametrize("name", names("layer_metrics", REHEARSAL))
def test_rehearsal_layer_metric_files_load(name):
    f = load("layer_metrics", name, REHEARSAL)
    assert {"unit", "better", "source", "layer", "reader"} <= set(f)
    importlib.import_module(f"benchmarks.readers.{f['reader']}")
