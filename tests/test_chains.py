"""Chain-layer tests: splitter, readers, LLM clients, the developer_rag
example, and the 3-endpoint HTTP server (run with aiohttp test utils and a
fake LLM/embedder — the layer-test the reference never had, SURVEY.md §4)."""

import asyncio
import json
import os
import zlib

import pytest

import aiohttp
from aiohttp.test_utils import TestClient, TestServer

from generativeaiexamples_tpu.chains.base import BaseExample
from generativeaiexamples_tpu.chains.examples.developer_rag import QAChatbot
from generativeaiexamples_tpu.chains.llm import EchoLLM, OpenAICompatLLM, get_llm
from generativeaiexamples_tpu.chains.readers import read_document, read_pdf
from generativeaiexamples_tpu.chains.server import create_app, discover_example
from generativeaiexamples_tpu.chains.splitter import TokenTextSplitter, cap_context
from generativeaiexamples_tpu.embed.encoder import HashEmbedder
from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer
from generativeaiexamples_tpu.retrieval.docstore import DocumentIndex
from generativeaiexamples_tpu.utils.app_config import AppConfig
from generativeaiexamples_tpu.utils.configuration import from_dict
from generativeaiexamples_tpu.utils.errors import ChainError, ConfigError

TOK = ByteTokenizer()


# --------------------------------------------------------------- splitter

def test_splitter_respects_chunk_size():
    text = ". ".join(f"Sentence number {i} about TPUs" for i in range(100))
    sp = TokenTextSplitter(TOK, chunk_size=120, chunk_overlap=30)
    chunks = sp.split_text(text)
    assert len(chunks) > 3
    for c in chunks:
        assert len(TOK.encode(c, add_bos=False)) <= 120


def test_splitter_overlap_continuity():
    text = ". ".join(f"Alpha beta {i}" for i in range(60))
    sp = TokenTextSplitter(TOK, chunk_size=100, chunk_overlap=40)
    chunks = sp.split_text(text)
    # consecutive chunks share their boundary sentence(s)
    for a, b in zip(chunks, chunks[1:]):
        tail_sentence = a.split(". ")[-1].strip(". ")
        assert tail_sentence in b


def test_splitter_short_text_single_chunk():
    sp = TokenTextSplitter(TOK, chunk_size=510, chunk_overlap=200)
    assert sp.split_text("short text") == ["short text"]
    assert sp.split_text("   ") == []


def test_splitter_oversized_sentence_hard_split():
    sp = TokenTextSplitter(TOK, chunk_size=50, chunk_overlap=10)
    chunks = sp.split_text("x" * 400)  # one 'sentence' of 400 tokens
    assert len(chunks) >= 8
    assert "".join(chunks).count("x") == 400


def test_cap_context_budget():
    texts = ["a" * 100, "b" * 100, "c" * 100]  # 100 byte-tokens each
    kept = cap_context(texts, max_tokens=250, tokenizer=TOK)
    assert kept == texts[:2]


# ---------------------------------------------------------------- readers

def test_read_text_and_html(tmp_path):
    p = tmp_path / "doc.txt"
    p.write_text("hello world")
    assert read_document(str(p)) == "hello world"
    h = tmp_path / "doc.html"
    h.write_text("<html><body><script>x()</script><p>Visible text</p></body></html>")
    assert "Visible text" in read_document(str(h))
    assert "x()" not in read_document(str(h))


def _make_minimal_pdf(path: str, text: str) -> None:
    stream = f"BT /F1 12 Tf 72 720 Td ({text}) Tj ET".encode()
    compressed = zlib.compress(stream)
    body = (b"%PDF-1.4\n1 0 obj<</Length " + str(len(compressed)).encode()
            + b"/Filter/FlateDecode>>stream\n" + compressed
            + b"\nendstream endobj\ntrailer<<>>\n%%EOF")
    with open(path, "wb") as f:
        f.write(body)


def test_read_pdf_minimal(tmp_path):
    p = tmp_path / "doc.pdf"
    _make_minimal_pdf(str(p), "TPU systolic arrays rock")
    assert "TPU systolic arrays rock" in read_pdf(str(p))


def test_read_unsupported(tmp_path):
    p = tmp_path / "doc.xyz"
    p.write_text("x")
    with pytest.raises(ChainError):
        read_document(str(p))


# -------------------------------------------------------------------- llm

def test_echo_llm_streams_and_stops():
    llm = EchoLLM(prefix="", tail_chars=50)
    assert llm.complete("hello world", max_tokens=64) == "hello world"
    out = "".join(llm.stream("abc STOP def", max_tokens=64, stop=["STOP"]))
    assert "def" not in out


def test_get_llm_factory():
    cfg = from_dict(AppConfig, {"llm": {"model_engine": "echo"}})
    assert isinstance(get_llm(cfg), EchoLLM)
    cfg2 = from_dict(AppConfig, {"llm": {"model_engine": "openai-compat",
                                         "server_url": "http://x:1"}})
    assert isinstance(get_llm(cfg2), OpenAICompatLLM)
    with pytest.raises(ConfigError):
        get_llm(from_dict(AppConfig, {"llm": {"model_engine": "tpu-jax"}}))
    with pytest.raises(ConfigError):
        get_llm(from_dict(AppConfig, {"llm": {"model_engine": "nope"}}))


# ---------------------------------------------------------------- example

def _make_example() -> QAChatbot:
    cfg = from_dict(AppConfig, {
        "llm": {"model_engine": "echo"},
        "embeddings": {"model_engine": "hash", "dimensions": 64},
        "text_splitter": {"chunk_size": 100, "chunk_overlap": 20},
    })
    llm = EchoLLM(prefix="", tail_chars=4000)
    emb = HashEmbedder(dim=64)
    return QAChatbot(llm=llm, embedder=emb, config=cfg)


def test_developer_rag_ingest_and_chains(tmp_path):
    ex = _make_example()
    doc = tmp_path / "kb.txt"
    doc.write_text("The MXU is a 128x128 systolic array. "
                   "TPUs communicate over ICI links. "
                   "Paris is the capital of France.")
    ex.ingest_docs(str(doc), "kb.txt")
    assert len(ex.index) >= 1

    # rag_chain retrieves context and the prompt contains it
    out = "".join(ex.rag_chain("What is the MXU?", 4000))
    assert "systolic" in out  # retrieved context flowed into the prompt
    # llm_chain ignores the KB
    out2 = "".join(ex.llm_chain("", "What is the MXU?", 4000))
    assert "What is the MXU?" in out2

    hits = ex.document_search("systolic array", 2)
    assert hits and hits[0]["source"] == "kb.txt"
    assert {"score", "source", "content"} <= set(hits[0])


def test_discover_example():
    cls = discover_example("developer_rag")
    assert cls is QAChatbot
    with pytest.raises(ChainError):
        discover_example("generativeaiexamples_tpu.chains.base")


# ----------------------------------------------------------------- server

def _run(coro):
    return asyncio.get_event_loop_policy().new_event_loop().run_until_complete(coro)


async def _with_client(fn):
    ex = _make_example()
    app = create_app(ex, upload_dir=os.path.join(
        os.environ.get("TMPDIR", "/tmp"), "gaie-test-uploads"))
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        await fn(client, ex)
    finally:
        await client.close()


def test_server_health_and_metrics():
    async def fn(client, ex):
        resp = await client.get("/health")
        assert resp.status == 200
        assert (await resp.json())["status"] == "ok"
        resp = await client.get("/metrics")
        assert resp.status == 200
    _run(_with_client(fn))


def test_server_upload_generate_search(tmp_path):
    async def fn(client, ex):
        # upload (reference: server.py:89-118)
        form = aiohttp.FormData()
        form.add_field("file",
                       b"TPU pods scale with ICI. The MXU does matmuls.",
                       filename="notes.txt")
        resp = await client.post("/uploadDocument", data=form)
        assert resp.status == 200, await resp.text()
        assert (await resp.json())["filename"] == "notes.txt"

        # generate with KB → streamed chunks concatenate to the answer
        resp = await client.post("/generate", json={
            "question": "What does the MXU do?",
            "use_knowledge_base": True, "num_tokens": 4000})
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/event-stream")
        body = (await resp.read()).decode()
        assert "MXU" in body

        # generate without KB
        resp = await client.post("/generate", json={
            "question": "2+2?", "use_knowledge_base": False,
            "num_tokens": 4000})
        assert "2+2?" in (await resp.read()).decode()

        # documentSearch (reference: server.py:145-159)
        resp = await client.post("/documentSearch", json={
            "content": "matmul unit", "num_docs": 2})
        hits = await resp.json()
        assert isinstance(hits, list) and hits
        assert hits[0]["source"] == "notes.txt"

        # validation error
        resp = await client.post("/generate", json={})
        assert resp.status == 422
    _run(_with_client(fn))


def test_server_pre_stream_error_is_real_http_status():
    """A failure BEFORE the first generated chunk is a real 500 with a
    JSON body + X-Request-ID — not a 200 SSE carrying '[error]' text
    (docs/robustness.md error classes)."""
    class BrokenExample(BaseExample):
        def llm_chain(self, context, question, num_tokens):
            raise RuntimeError("boom")

        def rag_chain(self, prompt, num_tokens):
            raise RuntimeError("boom")

        def ingest_docs(self, data_dir, filename):
            raise RuntimeError("boom")

    async def fn():
        app = create_app(BrokenExample())
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.post("/generate", json={
                "question": "x", "num_tokens": 10})
            assert resp.status == 500
            assert resp.headers.get("X-Request-ID")
            body = await resp.json()
            assert "boom" in body["error"]["message"]
            assert body["request_id"] == resp.headers["X-Request-ID"]
        finally:
            await client.close()
    _run(fn())


def test_server_mid_stream_error_degrades_with_event():
    """After chunks have gone out on the 200, a failure keeps the
    in-stream degrade ('[error]' text) and appends a machine-readable
    final event frame."""
    class HalfBrokenExample(BaseExample):
        def llm_chain(self, context, question, num_tokens):
            yield "partial "
            yield "answer"
            raise RuntimeError("mid boom")

        def rag_chain(self, prompt, num_tokens):
            yield from self.llm_chain("", prompt, num_tokens)

        def ingest_docs(self, data_dir, filename):
            pass

    async def fn():
        app = create_app(HalfBrokenExample())
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.post("/generate", json={
                "question": "x", "num_tokens": 10})
            assert resp.status == 200
            body = (await resp.read()).decode()
            assert body.startswith("partial answer")
            assert "[error] mid boom" in body
            event = body.split("event: error\ndata:", 1)[1].strip()
            payload = json.loads(event.split("\n", 1)[0])
            assert payload["message"] == "mid boom"
            assert payload["request_id"] == resp.headers["X-Request-ID"]
        finally:
            await client.close()
    _run(fn())


# ------------------------------------------------------- fused RAG chatbot

def test_developer_rag_fused_path_end_to_end(tmp_path):
    """The chatbot auto-enables fused on-device RAG admission with an
    in-process engine + on-device embedder: fused answers carry source
    attribution, re-ingest does not recompile (stable spec), over-long
    questions fall back to the host path and CLEAR the attribution."""
    import jax
    import jax.numpy as jnp

    from generativeaiexamples_tpu.chains.llm import EngineLLM
    from generativeaiexamples_tpu.embed.encoder import EmbeddingService
    from generativeaiexamples_tpu.engine import Engine, EngineConfig
    from generativeaiexamples_tpu.models import encoder, llama
    from generativeaiexamples_tpu.models.configs import (ENCODER_TINY,
                                                         LLAMA_TINY)
    from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer

    emb = EmbeddingService(
        encoder.init_params(ENCODER_TINY, jax.random.key(1), jnp.float32),
        ENCODER_TINY, ByteTokenizer())
    eng = Engine(
        llama.init_params(LLAMA_TINY, jax.random.key(0), jnp.float32),
        LLAMA_TINY, ByteTokenizer(),
        EngineConfig(max_slots=2, max_input_length=1024,
                     max_output_length=32, prefill_buckets=(128, 512),
                     dtype="float32", page_size=64, kv_pool_tokens=None))
    cfg = from_dict(AppConfig, {
        "text_splitter": {"chunk_size": 100, "chunk_overlap": 20}})
    ex = QAChatbot(llm=EngineLLM(eng), embedder=emb, config=cfg)
    try:
        for i, text in enumerate(["The MXU is a systolic array.",
                                  "ICI links connect TPU chips."]):
            p = tmp_path / f"d{i}.txt"
            p.write_text(text)
            ex.ingest_docs(str(p), f"d{i}.txt")
        assert ex._fused_ready
        spec = ex._fused_spec

        out = "".join(ex.rag_chain("What is the MXU?", 8))
        assert isinstance(out, str)
        assert ex.last_sources, "fused answer lost attribution"

        # another ingest with identical config must keep the spec
        p = tmp_path / "extra.txt"
        p.write_text("Paged KV caching pools pages.")
        ex.ingest_docs(str(p), "extra.txt")
        assert ex._fused_spec == spec

        # over-long question -> host path; attribution cleared
        "".join(ex.rag_chain("why " * 40, 8))
        assert ex.last_sources == []
    finally:
        eng.stop()
